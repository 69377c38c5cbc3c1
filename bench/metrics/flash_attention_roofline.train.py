"""flash_attention_roofline.train: the flash attention kernel's share of
its roofline in the training step (layer: kernels; moves
train_tokens_per_s).

Each ``flash_attention`` call is one layer's causal self-attention over
every node's sequences (the node axis is batched into the call), so its
least time is the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth (``flops.flash_attention_cost``); the share
is that least time, summed over the calls of the traced window, over
their summed device time.  (On a v5e, 26 steps gave 1560 calls: 30
layers, forward and rematerialized forward.)"""
from bench import flops


def read(ctx):
    secs, n = ctx.trace.op_seconds(lambda name: "flash_attention" in name)
    sz, tr = ctx.sizes, ctx.traffic
    if n == 0:
        return None
    f, b = flops.flash_attention_cost(
        tr["nodes"] * tr["batch_per_node"], tr["seq_len"] - 1, sz["h"],
        sz["hkv"], sz["hd"], itemsize=4)
    least, _ = flops.roofline_seconds(f, b, ctx.peaks)
    return 100.0 * n * least / secs
