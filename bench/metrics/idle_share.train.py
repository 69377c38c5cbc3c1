"""idle_share.train: share of the traced window in which no operation ran
on the chip, averaged over the cell's chips (layer: device; moves
train_tokens_per_s)."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
