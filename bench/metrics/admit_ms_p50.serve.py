"""admit_ms_p50.serve: median time of one admission, prefill and page
scatter and the first token back on the host, from the benchmark's
``serve.admit`` span around ``ServeEngine.admit`` (layer: the serve
engine's admission; moves serve_itl_p95_ms: an admission's prefill
sits between two waves of every live slot)."""
import statistics


def read(ctx):
    spans = ctx.trace.span_durations("serve.admit")
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
