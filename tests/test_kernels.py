"""Pallas kernel sweeps: interpret-mode kernel vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import manifolds as M
from repro.kernels import ops, ref

SET = dict(deadline=None, max_examples=10)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # b, s, t, h, hkv, hd, hdv, causal, window, dtype
    (1, 128, 128, 4, 4, 32, 32, True, None, jnp.float32),
    (2, 64, 64, 8, 2, 64, 64, True, None, jnp.float32),
    (1, 128, 128, 4, 1, 32, 32, True, 48, jnp.float32),     # window + MQA
    (2, 1, 256, 8, 2, 64, 64, True, None, jnp.float32),     # decode
    (1, 96, 160, 4, 4, 16, 16, True, None, jnp.float32),    # ragged/padding
    (1, 64, 64, 4, 2, 32, 16, True, None, jnp.float32),     # hd_v != hd_k
    (1, 64, 64, 4, 4, 32, 32, False, None, jnp.float32),    # non-causal (cross)
    (1, 64, 64, 4, 4, 32, 32, True, None, jnp.bfloat16),    # bf16
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_oracle(case):
    b, s, t, h, hkv, hd, hdv, causal, window, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, hdv), dtype)
    qpos = jnp.broadcast_to(jnp.arange(t - s, t), (b, s)) if s < t else None
    want = ref.attention_naive(q, k, v, causal=causal, window=window,
                               q_positions=qpos)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_positions=qpos, impl="pallas_interpret",
                              block_q=32, block_kv=64)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    blk = ref.blockwise_attention(q, k, v, causal=causal, window=window,
                                  q_positions=qpos, chunk=48)
    np.testing.assert_allclose(np.asarray(blk, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_attention_ring_cache_positions():
    """Ring-buffer cache: unordered kv positions must still mask correctly."""
    b, t, h, hd = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    # cache holding positions 64..127 at slots (p % 64), query at pos 127
    kvpos = jnp.arange(64, 128)[None, :]
    kvpos = jnp.roll(kvpos, 7, axis=1)
    qpos = jnp.full((b, 1), 127)
    want = ref.attention_naive(q, k, v, causal=True, q_positions=qpos,
                               kv_positions=kvpos)
    got = ops.flash_attention(q, k, v, causal=True, q_positions=qpos,
                              kv_positions=kvpos, impl="pallas_interpret",
                              block_q=8, block_kv=32)
    np.testing.assert_allclose(got, want, atol=2e-5)


GRAD_CASES = [
    # b, s, h, hkv, hd, window, block
    (1, 64, 4, 2, 32, None, 32),        # GQA, block-aligned
    (2, 50, 4, 1, 16, None, 32),        # ragged: padded to 64 in the wrapper
    (1, 40, 2, 2, 16, 12, 16),          # sliding window, ragged
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_attention_grads_vs_ref(case):
    """``jax.value_and_grad`` through the Pallas path (its custom VJP) ==
    through the jnp oracle: loss and q/k/v gradients, padded tails
    included."""
    b, s, h, hkv, hd, window, block = case
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))
    w = jax.random.normal(ks[3], (b, s, h, hd))

    def run(impl):
        def loss(q, k, v):
            out = ops.flash_attention(q, k, v, window=window, impl=impl,
                                      block_q=block, block_kv=block)
            return jnp.sum(out * w)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    (l_got, g_got), (l_want, g_want) = run("pallas_interpret"), run("ref")
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    for got, want in zip(g_got, g_want):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_retract_refuses_over_vmem():
    """(576, 576) pads to 640 lanes: over the scoped VMEM budget, so the
    Pallas paths raise instead of failing in the chip's compiler."""
    x = jnp.zeros((576, 576))
    with pytest.raises(ValueError, match="VMEM"):
        ops.fused_retract(x, x, impl="pallas_interpret")


# ---------------------------------------------------------------------------
# stiefel projection
# ---------------------------------------------------------------------------


@st.composite
def proj_dims(draw):
    d = draw(st.integers(2, 300))
    r = draw(st.integers(1, min(d, 96)))
    seed = draw(st.integers(0, 2 ** 16))
    return d, r, seed


@given(proj_dims())
@settings(**SET)
def test_stiefel_project_kernel_sweep(drs):
    d, r, seed = drs
    x = M.random_stiefel(jax.random.PRNGKey(seed), d, r)
    g = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, r))
    want = ref.stiefel_project_ref(x, g)
    got = ops.stiefel_project(x, g, impl="pallas_interpret")
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [(), (3,), (2, 2)])
def test_stiefel_project_batched_dtypes(batch, dtype):
    x = M.random_stiefel(jax.random.PRNGKey(0), 64, 16, batch=batch).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (*batch, 64, 16), dtype)
    want = ref.stiefel_project_ref(x, g)
    got = ops.stiefel_project(x, g, impl="pallas_interpret")
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# ring mix
# ---------------------------------------------------------------------------


@given(st.integers(1, 4000), st.integers(0, 1000))
@settings(**SET)
def test_ring_mix_kernel_sweep(n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a, b, c = (jax.random.normal(k, (n,)) for k in ks)
    want = ref.ring_mix_ref(a, b, c, 0.4, 0.3)
    got = ops.ring_mix(a, b, c, w_self=0.4, w_side=0.3,
                       impl="pallas_interpret")
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("shape", [
    (1,),                # single element
    (9973,)              # prime: 10 panel rows, ragged both ways
    , (13, 1024),        # 13 rows — no old block-candidate divides it
    (5, 1024 + 1),       # lane tail + odd row count
    (3, 7, 191),         # multi-dim ragged leaf
    (30 * 1024 + 7,),    # row tail past the 8-sublane boundary
])
def test_ring_mix_ragged_shapes(shape):
    """Arbitrary leaf sizes tile cleanly: the dispatch pads ragged lane AND
    row tails (and slices back) instead of degenerating to 1-row blocks or
    tripping the kernel's tiling contract."""
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 3)
    a, b, c = (jax.random.normal(k, shape) for k in ks)
    want = ref.ring_mix_ref(a, b, c, 1 / 3, 1 / 3)
    got = ops.ring_mix(a, b, c, w_self=1 / 3, w_side=1 / 3,
                       impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
