"""Seeded traffic: token batches for training cells, open-loop request
mixes for serving cells.  Every parameter comes from the cell's traffic
file; the seed chooses contents and order, never the amount of work.

Token batches follow the program's synthetic stream (``TokenStream``):
each node over-samples a different mixture of ``n_groups`` groups, and
group g draws tokens Zipf-distributed over its own permutation of the
vocabulary, so per-group losses differ and the adversary's weights move.

A request mix holds ``round(rate * seconds)`` requests.  Their inter-arrival
gaps are the exponential quantiles at (i + 1/2)/n of a Poisson process at
``rate``, and their prompt and output lengths the lognormal quantiles at
the same points, clipped: the same multiset for every seed, which the
seed permutes (independently for gaps, prompts and outputs) and fills
with random prompt tokens.  Arrivals are due times on a fixed schedule
(open loop): the system's speed never moves them.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


class TokenFeed:
    """Node-stacked ``{tokens (n, b, s), group_ids (n, b)}`` batches; batch
    ``t`` of one seed is always the same, and no two batches share a row."""

    def __init__(self, *, nodes: int, batch_per_node: int, seq_len: int,
                 vocab: int, n_groups: int, hetero: float, seed: int):
        self.n, self.b, self.s = nodes, batch_per_node, seq_len
        self.vocab, self.groups, self.seed = vocab, n_groups, seed
        rng = _rng(seed, 0)
        pref = rng.dirichlet(np.full(n_groups, 0.3), size=nodes)
        self.mix = (1.0 - hetero) / n_groups + hetero * pref
        self.perm = np.stack([_rng(seed, 1, g).permutation(vocab)
                              for g in range(n_groups)]).astype(np.int32)
        cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
        self.cdf = cdf / cdf[-1]

    def batch(self, t: int) -> dict:
        rng = _rng(self.seed, 2, t)
        u = rng.random((self.n, self.b))
        gids = (u[..., None] > np.cumsum(self.mix, -1)[:, None, :]).sum(-1)
        gids = np.minimum(gids, self.groups - 1)
        ranks = np.searchsorted(self.cdf, rng.random((self.n, self.b, self.s)))
        ranks = np.minimum(ranks, self.vocab - 1)
        toks = self.perm[gids[..., None], ranks]
        return {"tokens": toks.astype(np.int32),
                "group_ids": gids.astype(np.int32)}


@dataclasses.dataclass
class Req:
    arrival: float          # due time, seconds after the window opens
    prompt: list
    max_new_tokens: int


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def request_mix(traffic: dict, seconds: float, seed: int,
                vocab: int) -> list[Req]:
    """The open-loop requests due in a window of ``seconds``."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / traffic["rate_per_s"]
    gaps *= seconds * (1.0 - 0.5 / n) / gaps.sum()     # last due inside
    p, o = traffic["prompt"], traffic["output"]
    plen = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    olen = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    rng = _rng(seed, 3)
    gaps, plen, olen = (rng.permutation(a) for a in (gaps, plen, olen))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    toks = _rng(seed, 4)
    return [Req(float(due[i]),
                toks.integers(0, vocab, int(plen[i])).tolist(), int(olen[i]))
            for i in range(n)]


def pages_for(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


def prompt_page_counts(traffic: dict, seconds: float) -> list[int]:
    """Every prefill page count the mix of this window uses (the same for
    every seed): the shapes set-up warms."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    p = traffic["prompt"]
    plen = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    return sorted({pages_for(int(x), traffic["page_size"]) for x in plen})


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sample."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))
    return float(v[k])
