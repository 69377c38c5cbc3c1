"""paged_decode_roofline.serve: the paged decode kernel's share of its
roofline (layer: kernels; moves serve_itl_p95_ms).

Every decode wave calls ``paged_decode`` once per layer.  A call's least
time is the larger of its operations over the bf16 peak and the bytes of
the keys and values its live slots hold (plus their queries and outputs)
over the HBM bandwidth (``flops.paged_decode_cost``): pages reserved but
not yet written are not needed.  The share is that least time, summed
over the waves of the traced window, over the kernel's summed device
time.  The least time of the calls the traced window holds is their
number times the mean least time of a call over the waves (host and
device clocks differ by about a millisecond at the window's edges)."""
from bench import flops


def read(ctx):
    secs, n = ctx.trace.op_seconds(lambda name: "paged_decode" in name)
    sz = ctx.sizes
    if n == 0 or not ctx.waves:
        return None
    least = 0.0
    for live, context in ctx.waves:
        f, b = flops.paged_decode_cost(context, sz["h"], sz["hkv"], sz["hd"],
                                       ctx.itemsize, live)
        least += flops.roofline_seconds(f, b, ctx.peaks)[0]
    return 100.0 * n * least / len(ctx.waves) / secs
