"""The operation and byte counts against hand counts at two shapes."""
import pytest

from bench import flops, harness, weights

SMOLLM = weights.sizes_of(harness.load_json(
    harness.ROOT / "bench" / "configs" / "smollm-135m.json"))
GRANITE = weights.sizes_of(harness.load_json(
    harness.ROOT / "bench" / "configs" / "granite-3-2b.json"))
PEAKS = harness.peaks_for("TPU v5 lite")


def test_matmul_params_by_hand():
    # smollm: per layer q,o 576x576, k,v 576x192, MLP 3 x 576x1536;
    # head 576 x 49152 (tied: counted once)
    per_layer = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536
    assert flops.matmul_params(SMOLLM) == 30 * per_layer + 576 * 49152
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert flops.matmul_params(GRANITE) == 40 * per_layer + 2048 * 49155


@pytest.mark.parametrize("b,s,h,hkv,hd,item", [(4, 2047, 9, 3, 64, 4),
                                               (1, 8, 2, 1, 4, 2)])
def test_flash_attention_cost_by_hand(b, s, h, hkv, hd, item):
    pairs = sum(i + 1 for i in range(s))          # causal: keys 0..i
    f, n = flops.flash_attention_cost(b, s, h, hkv, hd, item)
    assert f == b * h * pairs * 2 * (2 * hd)      # QK^T and PV, 2 per MAC
    assert n == item * b * s * (h * hd + 2 * hkv * hd + h * hd)


@pytest.mark.parametrize("ctx,slots,h,hkv,hd,item", [(3000, 32, 32, 8, 64, 2),
                                                     (5, 2, 4, 2, 8, 4)])
def test_paged_decode_cost_by_hand(ctx, slots, h, hkv, hd, item):
    f, n = flops.paged_decode_cost(ctx, h, hkv, hd, item, slots)
    assert f == h * ctx * 2 * (2 * hd)
    assert n == item * (ctx * hkv * hd * 2 + slots * h * hd * 2)


def test_train_flops_by_hand():
    s = 2047
    pairs = s * (s + 1) // 2
    want = 6 * flops.matmul_params(SMOLLM) * s \
        + 3 * 30 * 9 * pairs * 4 * 64
    assert flops.train_flops_per_seq(SMOLLM, s) == want


def test_roofline_bound():
    t, which = flops.roofline_seconds(197e12, 1.0, PEAKS)
    assert (t, which) == (1.0, "compute")
    t, which = flops.roofline_seconds(1.0, 819e9, PEAKS)
    assert (t, which) == (1.0, "memory")


def test_decode_wave_flops():
    assert flops.decode_wave_flops(GRANITE, 3) == \
        2 * 3 * flops.matmul_params(GRANITE)
