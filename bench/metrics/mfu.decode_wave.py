"""mfu.decode_wave: the decode wave's share of the chip's bf16 peak (layer:
the serve engine's step, serve/engine.ServeEngine.step; moves
serve_itl_p95_ms).

Two operations per weight per live slot, summed over the waves of the
traced window (``flops.decode_wave_flops``), over the summed time of the
benchmark's ``serve.step`` span (dispatch to tokens on the host) times
the peak."""
from bench import flops


def read(ctx):
    spans = ctx.trace.span_durations("serve.step")
    if not spans or not ctx.waves:
        return None
    work = sum(flops.decode_wave_flops(ctx.sizes, live)
               for live, _ in ctx.waves)
    return 100.0 * work / (sum(spans) * ctx.peaks["bf16_flops_per_s"])
