"""grad_ms.train: device milliseconds per DRSGDA step in the scope
``gda.grad``, the gradient phase: every node's model forward and backward (with rematerialization and the flash attention kernel) and the tangent projection of the gradient (layer: the decentralized step, launch/steps ->
core/gda; moves train_tokens_per_s).

The own time of the window's device ops whose innermost ``gda.`` scope is
``gda.grad`` (``bench/scopes.py``), over the steps of the traced window."""
from bench import scopes


def read(ctx):
    secs = scopes.cell_scopes(ctx, scopes.GDA)
    if secs is None or not ctx.steps:
        return None
    return 1e3 * secs.get("gda.grad", 0.0) / ctx.steps
