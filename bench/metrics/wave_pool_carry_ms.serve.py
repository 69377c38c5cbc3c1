"""wave_pool_carry_ms.serve: device milliseconds per decode wave outside
every ``block.*`` and ``model.head`` scope of the wave program (layer: the
serve engine's step, serve/engine.ServeEngine.step; moves
serve_itl_p95_ms).

The wave's layer scan carries the stacked K/V pools: each layer's pools
are sliced out of the carry and stacked into a new one, and XLA copies
them around the loop.  Those ops run in ``model.layers`` but in no block,
beside the token embedding and the loop's own control; their own device
time in the wave program over the traced window (``bench/scopes.py``),
over the waves of the window."""
from bench import scopes


def read(ctx):
    secs = scopes.cell_scopes(ctx, scopes.MODEL)
    if secs is None or not ctx.waves:
        return None
    return 1e3 * (secs.get("model.layers", 0.0) + secs.get("other", 0.0)) \
        / len(ctx.waves)
