"""mfu.train: the whole DRSGDA step's share of the chips' bf16 peak (layer:
the decentralized step, launch/steps -> core/gda; moves
train_tokens_per_s).

Model operations only: forward and backward of every node's sequences
from shapes (``flops.train_flops_per_seq``), no recomputation and none of
the manifold algebra or gossip, times the steps of the traced window,
over the window and the chips' peak."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * ctx.steps * ctx.step_flops / (
        ctx.trace.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
