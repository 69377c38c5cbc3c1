"""tracking_ms.train: device milliseconds per DRSGDA step in the scope
``gda.track``, the tracking phase: y's ascent and projection and the u / v tracker updates, less the mixes in them (layer: the decentralized step, launch/steps ->
core/gda; moves train_tokens_per_s).

The own time of the window's device ops whose innermost ``gda.`` scope is
``gda.track`` (``bench/scopes.py``), over the steps of the traced window."""
from bench import scopes


def read(ctx):
    secs = scopes.cell_scopes(ctx, scopes.GDA)
    if secs is None or not ctx.steps:
        return None
    return 1e3 * secs.get("gda.track", 0.0) / ctx.steps
