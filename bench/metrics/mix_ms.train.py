"""mix_ms.train: device milliseconds per DRSGDA step in the scope
``gda.mix``, the gossip mixes of x, y, u and v over the ring, innermost wherever they run (layer: the decentralized step, launch/steps ->
core/gda; moves train_tokens_per_s).

The own time of the window's device ops whose innermost ``gda.`` scope is
``gda.mix`` (``bench/scopes.py``), over the steps of the traced window."""
from bench import scopes


def read(ctx):
    secs = scopes.cell_scopes(ctx, scopes.GDA)
    if secs is None or not ctx.steps:
        return None
    return 1e3 * secs.get("gda.mix", 0.0) / ctx.steps
