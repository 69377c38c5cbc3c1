"""Ahead-of-time compiles for a described TPU v5e, at smollm-135m widths.

The TPU compiler is installed wherever ``libtpu`` is, and it compiles for a
chip that is described rather than attached.  That catches what interpret
mode cannot: block shapes that break the (8, 128) tiling rule, kernels over
their scoped VMEM, and programs that do not fit the chip's HBM.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and every test worker imports this
file.  All such compiles stay in this one file, so one worker loads it.
"""
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.launch import roofline

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9

CFG = configs.get_config("smollm-135m")
H, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.hd
# one node row of the ring: a flattened (576, 1536) MLP leaf
ROW_F = CFG.d_model * CFG.d_ff


@pytest.fixture(scope="module")
def chip():
    """One described v5e device; the persistent compile cache is off, as
    programs compiled for an absent chip cannot be read back from it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> dict:
    return roofline.kernel_calls(compiled.as_text())


@pytest.mark.parametrize("mode", ["forward", "grad"])
@pytest.mark.parametrize("seq", [512, 100])
def test_flash_attention_compiles(chip, mode, seq):
    """Training attention at smollm heads (9 q, 3 kv, hd 64); 100 is a
    ragged sequence that the wrapper pads."""
    q = _sds(chip, (2, seq, H, HD))
    kv = _sds(chip, (2, seq, HKV, HD))

    def fwd(q, k, v):
        return ops.flash_attention(q, k, v, impl="pallas")

    # value_and_grad, as the trainer takes it: the loss keeps the Pallas
    # forward live (its backward is the oracle's VJP)
    fn = fwd if mode == "forward" else jax.value_and_grad(
        lambda q, k, v: fwd(q, k, v).sum(), argnums=(0, 1, 2))
    assert _kernels(_compile(fn, q, kv, kv)) == {"flash_attention": 1}


def test_paged_decode_compiles(chip):
    """One decode step at the serving shape of ``launch/serve.py``: 4 slots,
    128-token pages, 160-token context, the pool sized as serve.py sizes
    it."""
    slots, ps, m = 4, 128, 2
    args = (_sds(chip, (slots, H, HD)),
            _sds(chip, (2 * slots * m + 1, HKV, HD, ps)),
            _sds(chip, (2 * slots * m + 1, HKV, HD, ps)),
            _sds(chip, (slots, m), jnp.int32), _sds(chip, (slots,), jnp.int32))
    compiled = _compile(
        lambda *a: ops.paged_decode_attention(*a, impl="pallas"), *args)
    assert _kernels(compiled) == {"paged_decode": 1}


@pytest.mark.parametrize("b,hops", [(1, 1), (2, 3)])
def test_multi_hop_mix_compiles(chip, b, hops):
    """The fused ring mix on one device's halo panel of whole node rows."""
    rows = b + 2 * hops
    compiled = _compile(
        lambda p: ops.multi_hop_mix(p, hops=hops, out_rows=b, halo=hops,
                                    w_self=1 / 3, w_side=1 / 3,
                                    impl="pallas"),
        _sds(chip, (rows, ROW_F)))
    assert _kernels(compiled) == {"multi_hop_mix": 1}


@pytest.mark.parametrize("b,hops", [(1, 1), (2, 3)])
def test_multi_hop_mix_quant_compiles(chip, b, hops):
    """The all-hop int8 schedule: one launch per hop."""
    rows = b + 2 * hops
    compiled = _compile(
        lambda q, s: ops.multi_hop_mix_quant(
            q, s, hops=hops, out_rows=b, halo=hops, w_self=1 / 3,
            w_side=1 / 3, impl="pallas"),
        _sds(chip, (rows, ROW_F), jnp.int8), _sds(chip, (rows, 1)))
    assert _kernels(compiled) == {"multi_hop_mix_quant": hops}


def test_fused_retract_compiles_at_576x192(chip):
    x = _sds(chip, (576, 192))
    compiled = _compile(lambda x, g: ops.fused_retract(x, g, impl="pallas"),
                        x, x)
    assert _kernels(compiled) == {"fused_polar_retract": 1}


def test_fused_retract_refuses_576x576(chip):
    """smollm's wq / wo: (576, 576) pads to 640 lanes, whose (r, r) algebra
    needs more than the scoped VMEM; the wrapper says so before lowering."""
    x = _sds(chip, (576, 576))
    with pytest.raises(ValueError, match="VMEM"):
        _compile(lambda x, g: ops.fused_retract(x, g, impl="pallas"), x, x)


def test_drsgda_step_fits_one_chip(chip, monkeypatch):
    """The full-width trainer step of ``chip_smoke.py``: 4 node-stacked
    smollm-135m replicas, batch 2 x 512 per node, flash attention compiled
    as a Pallas kernel, within one v5e's HBM."""
    from repro.launch.steps import TrainSpec, abstract_train_state, \
        build_trainer

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    nodes, b, s = 4, 2, 512
    opt, _ = build_trainer(CFG, nodes, TrainSpec(optimizer="drsgda"))
    batch = {"tokens": jax.ShapeDtypeStruct((nodes, b, s), jnp.int32),
             "group_ids": jax.ShapeDtypeStruct((nodes, b), jnp.int32)}
    state = abstract_train_state(CFG, opt, nodes, batch)

    def place(tree):
        return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype), tree)

    compiled = opt.make_step(donate=True).lower(place(state),
                                                place(batch)).compile()
    assert _kernels(compiled).get("flash_attention", 0) > 0
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
