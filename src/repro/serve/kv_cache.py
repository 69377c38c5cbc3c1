"""Paged KV cache: block-table-indexed pages from one fixed pool.

Contiguous per-request KV buffers waste memory on ragged workloads — a
4k-context slot and a 30-token slot cost the same.  Here every attention
layer owns one *pool* of ``(n_pages, Hkv, hd, page_size)`` pages,
token-minor; a decode slot references its pages through a row of the shared
block table ``(n_slots, max_pages_per_slot)`` int32.  Unallocated entries
are ``-1``; page 0 is the *dump page* — a write/read sink for inactive
slots, never handed out by the allocator — so the fused decode step needs no
host-side branching on slot liveness (``kernels/paged_decode.py`` clamps
``-1`` to 0 and fully masks those positions).

The pool pytree mirrors ``models.transformer.init_cache``'s stage/block
structure (a leading ``repeat`` axis for scanned stages) with only
``{"k_pages", "v_pages"}`` leaves.  In the decode wave a scanned stage's
pools ride the layer scan's carry whole, flattened to
``(repeat * n_pages, ...)``, and layer ``i`` addresses its pages through the
block table offset by ``i * n_pages`` (``models.transformer``), so a wave
rewrites only each slot's current page and copies no pool.
:class:`PagePool` is the host-side allocator (free list + admission
reservations) the scheduler draws from.

On the chip the page size should be a multiple of 128.  A page's tokens
then fill whole lanes: a head dim under 128 costs no padding, and the paged
kernel and the decode write use the pool in the layout it is stored in.
Under 128 the chip's compiler relays the pool out around every wave, whole
pool copies in and out, and a pool sized for 128-token pages may no longer
fit (PERF.md, open questions).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PagedKVSpec:
    """Static geometry of the paged cache."""
    page_size: int = 128         # tokens per page (see the module doc)
    n_pages: int = 64            # pool size per attention layer (incl. dump)
    max_pages_per_slot: int = 8  # block-table width M

    def __post_init__(self):
        assert self.page_size >= 1 and self.n_pages >= 2, self
        assert self.max_pages_per_slot >= 1, self

    @property
    def max_context(self) -> int:
        """Longest sequence one slot can hold (prompt + generated)."""
        return self.page_size * self.max_pages_per_slot

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)


class PagePool:
    """Host-side page allocator: free list over pages ``1..n_pages-1``.

    Admission *reserves* a request's worst-case page count up front (so a
    request never deadlocks mid-decode waiting for pages), then draws its
    actual pages from the reservation.  Page 0 (the dump page) is never
    allocated."""

    def __init__(self, spec: PagedKVSpec):
        self.spec = spec
        self._free = list(range(spec.n_pages - 1, 0, -1))  # pop() -> low ids

    @property
    def n_free(self) -> int:
        return len(self._free)

    def can_reserve(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` pages; raises if the pool is exhausted (callers gate
        on :meth:`can_reserve` at admission, so this is a logic error)."""
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        return [self._free.pop() for _ in range(n)]

    def release(self, pages: list[int]) -> None:
        for p in pages:
            assert 0 < p < self.spec.n_pages, p
            self._free.append(p)


# ---------------------------------------------------------------------------
# device-side pool pytree
# ---------------------------------------------------------------------------


def _is_paged_block(spec) -> bool:
    return spec.kind in ("attn", "moe_attn") and spec.attn.kind != "mla"


def validate_config(cfg: ModelConfig) -> None:
    """The paged path covers GQA attention blocks without sliding windows
    (full-context pages; the kernel's ``window`` masking is exercised at the
    kernel level).  Reject anything else up front."""
    for st in cfg.stages:
        for sp in st.blocks:
            if not _is_paged_block(sp):
                raise ValueError(
                    f"paged serving supports GQA attention blocks only, "
                    f"got kind={sp.kind!r}")
            if sp.attn.sliding_window is not None:
                raise ValueError(
                    "paged serving does not support sliding-window layers")
            if sp.attn.cross_attn:
                raise ValueError(
                    "paged serving does not support cross-attention layers")


def init_pools(cfg: ModelConfig, spec: PagedKVSpec,
               dtype=jnp.float32) -> dict:
    """Zero-filled per-layer page pools, shaped like ``init_cache``'s tree
    (scanned stages carry the leading ``repeat`` axis)."""
    pools = {}
    for i, st in enumerate(cfg.stages):
        cell = {}
        for j, sp in enumerate(st.blocks):
            shape = (spec.n_pages, cfg.n_kv_heads, cfg.hd, spec.page_size)
            cell[f"b{j}"] = {
                "k_pages": jnp.zeros(shape, dtype),
                "v_pages": jnp.zeros(shape, dtype),
            }
        if st.repeat > 1:
            cell = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (st.repeat, *x.shape)),
                cell)
        pools[f"s{i}"] = cell
    return pools


def scatter_prompt(pools: dict, caches: dict, pages: Array, *,
                   cfg: ModelConfig, page_size: int) -> dict:
    """Copy one prompt's contiguous prefill caches into its pages.

    ``caches`` is ``forward(mode="prefill")``'s output for a batch-of-one
    prompt with ``cache_len`` >= ``len(pages) * page_size`` (so the ring
    buffer is position-ordered); ``pages`` is the slot's page ids, (np,)
    int32.  Jit this with ``donate_argnums=(0,)`` so pool updates are
    in-place."""
    npg = pages.shape[0]
    span = npg * page_size

    def put(pool: Array, rows: Array) -> Array:
        # rows (cl, Hkv, hd) -> (np, Hkv, hd, ps) page-major, token-minor
        seq = rows[:span].reshape(npg, page_size, *rows.shape[1:])
        return pool.at[pages].set(jnp.moveaxis(seq, 1, -1))

    out = {}
    for i, st in enumerate(cfg.stages):
        cell = {}
        for j, _ in enumerate(st.blocks):
            c = caches[f"s{i}"][f"b{j}"]
            p = pools[f"s{i}"][f"b{j}"]
            if st.repeat > 1:       # (R, 1, cl, ...) caches / (R, P, ...) pool
                cell[f"b{j}"] = {
                    "k_pages": jax.vmap(put)(p["k_pages"], c["k"][:, 0]),
                    "v_pages": jax.vmap(put)(p["v_pages"], c["v"][:, 0]),
                }
            else:                   # (1, cl, ...) caches / (P, ...) pool
                cell[f"b{j}"] = {
                    "k_pages": put(p["k_pages"], c["k"][0]),
                    "v_pages": put(p["v_pages"], c["v"][0]),
                }
        out[f"s{i}"] = cell
    return out
