"""retract_ms.train: device milliseconds per DRSGDA step in the scope
``gda.retract``, the retraction phase: each leaf's descent direction and its retraction onto the manifold (Newton-Schulz polar on the Stiefel leaves) (layer: the decentralized step, launch/steps ->
core/gda; moves train_tokens_per_s).

The own time of the window's device ops whose innermost ``gda.`` scope is
``gda.retract`` (``bench/scopes.py``), over the steps of the traced window."""
from bench import scopes


def read(ctx):
    secs = scopes.cell_scopes(ctx, scopes.GDA)
    if secs is None or not ctx.steps:
        return None
    return 1e3 * secs.get("gda.retract", 0.0) / ctx.steps
