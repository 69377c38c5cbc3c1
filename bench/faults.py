"""Faults planted in the program's timed path, to show that the check
catches them (``bench/tests`` on the CPU, ``bench/calibrate.py`` on the
chip).  Each is a context manager that patches the program while it is
built and run, and restores it afterwards.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """Every optimizer step returns the state it was given."""
    from repro.core.gda import DecentralizedGDA
    orig = DecentralizedGDA.step

    def step(self, state, batch):
        _, metrics = orig(self, state, batch)
        return state, metrics
    return _patched(DecentralizedGDA, "step", step)


def half_batch():
    """The loss leaves out the second half of every sequence's tokens: the
    mean is taken over the rest."""
    from repro.objectives import lm
    orig = lm.token_ce

    def token_ce(logits, targets, *a, **kw):
        h = targets.shape[1] // 2
        return orig(logits[:, :h], targets[:, :h], *a, **kw)
    return _patched(lm, "token_ce", token_ce)


def no_exchange():
    """Gossip leaves every node's rows as they are: nothing is exchanged
    between nodes (or chips)."""
    from repro.comms import backend
    stack = contextlib.ExitStack()
    for cls in (backend.StackedBackend, backend.ShardMapBackend):
        stack.enter_context(_patched(cls, "mix",
                                     lambda self, spec, tree, steps: tree))
    return stack


def token_altered():
    """Every sampled token is replaced by the next id in the vocabulary."""
    from repro.serve import engine
    orig = engine._sample

    def sample(logits, key, temperature):
        return (orig(logits, key, temperature) + 1) % logits.shape[-1]
    return _patched(engine, "_sample", sample)


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "no_exchange": no_exchange}
SERVE = {"token_altered": token_altered}
