"""Operations and bytes the algorithms need, from shapes alone.

These are the yardstick of every roofline and utilization share: what a
call must do, not what a kernel happens to do.  A causal attention needs
only the query-key pairs on or below the diagonal, and reads K and V once
per kv head; a decode step needs the keys and values of the positions a
slot holds, not the pages reserved for it.  Only matrix-unit work is
counted (two operations per multiply-add); softmax and norms are not.
"""
from __future__ import annotations


def matmul_params(sz: dict) -> int:
    """Weights one token multiplies through: every block matrix and the
    output head (the tied embedding counts once, as the head)."""
    d, hd = sz["d"], sz["hd"]
    attn = d * sz["h"] * hd * 2 + d * sz["hkv"] * hd * 2
    mlp = 3 * d * sz["ff"]
    return sz["layers"] * (attn + mlp) + d * sz["vocab"]


def causal_pairs(s: int) -> int:
    """Query-key pairs of one causal sequence of ``s`` positions."""
    return s * (s + 1) // 2


def train_flops_per_seq(sz: dict, positions: int) -> float:
    """Forward and backward of one sequence of ``positions`` positions
    (no recomputation counted): 6 per weight per position, plus the two
    attention matmuls (QK^T, PV) over the causal pairs, times three."""
    attn_fwd = 4.0 * sz["hd"] * sz["h"] * causal_pairs(positions) \
        * sz["layers"]
    return 6.0 * matmul_params(sz) * positions + 3.0 * attn_fwd


def flash_attention_cost(b: int, s: int, h: int, hkv: int, hd: int,
                         itemsize: int) -> tuple[float, float]:
    """One causal self-attention forward over (b, s) tokens: operations
    (QK^T and PV over the causal pairs) and bytes (q, k, v read once,
    the output written once; K/V once per kv head)."""
    flops = 4.0 * hd * h * causal_pairs(s) * b
    nbytes = float(itemsize) * b * s * hd * (2 * h + 2 * hkv)
    return flops, nbytes


def paged_decode_cost(context: int, h: int, hkv: int, hd: int,
                      itemsize: int, slots: int) -> tuple[float, float]:
    """One decode-attention call of one layer over ``slots`` live slots
    holding ``context`` positions between them (the new token included):
    operations over those positions, bytes of their K and V, plus each
    slot's query and output."""
    flops = 4.0 * hd * h * context
    nbytes = float(itemsize) * (2.0 * hkv * hd * context
                                + 2.0 * h * hd * slots)
    return flops, nbytes


def decode_wave_flops(sz: dict, live: int) -> float:
    """Weight operations of one decode wave: two per weight per live slot
    (attention over the cache left out)."""
    return 2.0 * matmul_params(sz) * live


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> \
        tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
