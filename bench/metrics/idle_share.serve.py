"""idle_share.serve: share of the traced window (open-loop arrivals and
the drain after them) in which no operation ran on the chip (layer:
device; moves serve_itl_p95_ms)."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
