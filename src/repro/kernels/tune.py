"""Autotune-and-cache for the Pallas kernel launch configs.

tinygrad-style measure-or-load (SNIPPETS.md §search): every kernel's launch
config (block shapes, Newton--Schulz iteration count) is either hand-picked
(the ``DEFAULT`` below — what shipped before this module) or *tuned*: a
timed search over the kernel's candidate space, gated on accuracy where the
config changes math (``fused_retract``'s ``ns_iters``), cached as JSON under
``experiments/tune/<device_kind>.json`` keyed on
``kernel|shape|dtype[|extras]``.

``kernels/ops.py`` consults :func:`lookup` at trace time; the env knob is

  ``REPRO_TUNE=off``     — never consult the cache, always ship defaults.
  ``REPRO_TUNE=load``    — (default) use a cached config when one exists
                           for this exact key, defaults otherwise; never
                           measures anything.
  ``REPRO_TUNE=search``  — measure-or-load: a cache miss triggers the
                           search once and persists the result, so the
                           second invocation of the same program is pure
                           load.

Timing runs against whatever this process actually dispatches (the Pallas
kernel on TPU, the jnp oracle elsewhere) — on CPU the block-shape axes are
flat and the 5% hysteresis keeps the default, while ``ns_iters`` changes
real work on every backend, so the cache always demonstrates at least one
non-default tuned config.  Each record carries
``launch/roofline.place()``'s placement of the kernel's analytical
Estimates so the report can position tuned configs on the roofline.

Delete ``experiments/tune/`` (or point ``REPRO_TUNE_DIR`` elsewhere) to
retune from scratch.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

MODES = ("off", "load", "search")

#: hand-picked launch configs (what ops.py shipped before the tuner)
DEFAULTS = {
    "ring_mix": {"block_rows": 256},
    "quant_mix": {"block_cols": 32768},
    "multi_hop_mix": {"block_f": 1024},
    "multi_hop_mix_quant": {"block_f": 4096},
    "fused_retract": {"block_d": 256, "ns_iters": 20},
    "flash_attention": {"block_q": 128, "block_kv": 128},
    "paged_decode": {"pages_per_block": 1},
}

#: candidate spaces (the default is always included and is the fallback)
SPACES = {
    "ring_mix": [{"block_rows": v} for v in (512, 256, 128, 64, 32, 16, 8)],
    "quant_mix": [{"block_cols": v}
                  for v in (65536, 32768, 16384, 8192, 4096)],
    "multi_hop_mix": [{"block_f": v}
                      for v in (4096, 2048, 1024, 512, 256, 128)],
    "multi_hop_mix_quant": [{"block_f": v}
                            for v in (16384, 8192, 4096)],
    "fused_retract": [{"block_d": d, "ns_iters": n}
                      for n in (10, 12, 16, 20) for d in (128, 256, 512)],
    "flash_attention": [{"block_q": bq, "block_kv": bk}
                        for bq in (64, 128, 256)
                        for bk in (64, 128, 256, 512)],
    "paged_decode": [{"pages_per_block": g} for g in (1, 2, 4, 8)],
}

#: kernels whose every candidate (default included) is accuracy-gated
#: against an *independent* oracle rather than the default config's output
ORACLE_GATED = ("flash_attention", "paged_decode")

#: knobs that still change the dispatched computation on the oracle (ref)
#: path — candidates differing only in other knobs are deduped there.
#: ``fused_retract``'s ns_iters is real work everywhere; flash attention's
#: block_kv drives the streaming oracle's chunk size.
REF_KNOBS = {
    "fused_retract": ("ns_iters",),
    "flash_attention": ("block_kv",),
}

#: fixed head geometry for the paged-decode probe (the cache key carries
#: (slots, pages, page_size, hd); heads only rescale every candidate alike)
PAGED_PROBE_HEADS = (4, 2)      # (h, hkv) — exercises GQA grouping

#: relative tolerance for accuracy-gated configs (vs the default config's
#: output on the same probe inputs)
ACCURACY_RTOL = 1e-5

#: a non-default config must beat the default by this margin to win —
#: keeps flat (CPU) block-shape timings from churning the cache on noise
HYSTERESIS = 0.05

_MEM: dict[str, tuple[float, dict]] = {}   # path -> (mtime, parsed cache)


# ---------------------------------------------------------------------------
# cache plumbing
# ---------------------------------------------------------------------------


def mode() -> str:
    m = os.environ.get("REPRO_TUNE", "load").lower()
    if m not in MODES:
        raise ValueError(f"REPRO_TUNE={m!r}: choose from {MODES}")
    return m


def cache_dir() -> str:
    return os.environ.get("REPRO_TUNE_DIR",
                          os.path.join(ROOT, "experiments", "tune"))


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.replace(" ", "_")


def cache_path() -> str:
    return os.path.join(cache_dir(), f"{_device_kind()}.json")


def key(kernel: str, shape: tuple, dtype: Any,
        extra: Optional[dict] = None) -> str:
    k = f"{kernel}|{'x'.join(str(s) for s in shape)}|{dtype}"
    if extra:
        k += "|" + ",".join(f"{n}={v}" for n, v in sorted(extra.items()))
    return k


def _read_cache() -> dict:
    path = cache_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {"device_kind": _device_kind(), "entries": {}, "searches": 0}
    cached = _MEM.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    with open(path) as f:
        data = json.load(f)
    _MEM[path] = (mtime, data)
    return data


def _write_cache(data: dict) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    _MEM[path] = (os.path.getmtime(path), data)


def clear() -> None:
    """Drop the cache file for this device (tests / retuning)."""
    _MEM.clear()
    try:
        os.remove(cache_path())
    except OSError:
        pass


# ---------------------------------------------------------------------------
# the public trace-time hook
# ---------------------------------------------------------------------------


def lookup(kernel: str, shape: tuple, dtype: Any,
           extra: Optional[dict] = None) -> Optional[dict]:
    """Tuned config for this exact key, or None (→ caller ships defaults).

    ``load`` never measures; ``search`` runs :func:`autotune` once on a
    miss and serves the cache from then on."""
    m = mode()
    if m == "off" or kernel not in DEFAULTS:
        return None
    k = key(kernel, shape, dtype, extra)
    entry = _read_cache()["entries"].get(k)
    if entry is not None:
        return dict(entry["config"])
    if m == "search":
        return dict(autotune(kernel, shape, dtype, extra=extra)["config"])
    return None


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _dispatch_impl() -> str:
    from repro.kernels.ops import _default_impl
    return _default_impl()


def _probe_inputs(kernel: str, shape: tuple, dtype: Any, extra: dict):
    import jax
    import jax.numpy as jnp
    k0 = jax.random.PRNGKey(0)
    ks = jax.random.split(k0, 8)
    if kernel in ("ring_mix", "multi_hop_mix"):
        rows, f = shape
        mk = lambda i: jax.random.normal(ks[i], (rows, f), jnp.float32) \
            .astype(dtype)
        if kernel == "ring_mix":
            return (mk(0), mk(1), mk(2))
        return (mk(0),)
    if kernel in ("quant_mix", "multi_hop_mix_quant"):
        rows, f = shape
        q = lambda i: jax.random.randint(ks[i], (rows, f), -127, 128,
                                         jnp.int8)
        s = lambda i: jax.random.uniform(ks[4 + i], (rows, 1), jnp.float32,
                                         1e-3, 1e-1)
        if kernel == "quant_mix":
            return (q(0), q(1), q(2), s(0), s(1), s(2))
        return (q(0), s(0))
    if kernel == "fused_retract":
        d, r = shape
        x, _ = jnp.linalg.qr(jax.random.normal(ks[0], (d, r), jnp.float32))
        g = jax.random.normal(ks[1], (d, r), jnp.float32)
        return (x.astype(dtype), g.astype(dtype))
    if kernel == "flash_attention":
        b, s, t, h, hd = shape
        mk = lambda i, *sh: jax.random.normal(ks[i], sh, jnp.float32) \
            .astype(dtype)
        return (mk(0, b, s, h, hd), mk(1, b, t, h, hd), mk(2, b, t, h, hd))
    if kernel == "paged_decode":
        s, m, ps, hd = shape
        h, hkv = PAGED_PROBE_HEADS
        n_pages = s * m + 1                      # + the dump page
        q = jax.random.normal(ks[0], (s, h, hd), jnp.float32).astype(dtype)
        kp = jax.random.normal(ks[1], (n_pages, hkv, hd, ps),
                               jnp.float32).astype(dtype)
        vp = jax.random.normal(ks[2], (n_pages, hkv, hd, ps),
                               jnp.float32).astype(dtype)
        # ragged slots: slot i holds ~ (i+1)/s of the max context
        seq = jnp.asarray([max(1, ((i + 1) * m * ps) // s)
                           for i in range(s)], jnp.int32)
        bt = jnp.asarray(
            [[1 + i * m + j if j * ps < int(seq[i]) else -1
              for j in range(m)] for i in range(s)], jnp.int32)
        return (q, kp, vp, bt, seq)
    raise ValueError(f"no probe for kernel {kernel!r}")


def _probe_fn(kernel: str, shape: tuple, config: dict, extra: dict,
              impl: str):
    """A jittable callable honoring ``config`` under the current dispatch
    (Pallas on TPU, the jnp oracle elsewhere — where block shapes are
    no-ops but ``ns_iters`` is real work)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    interp = impl == "pallas_interpret"
    wc, ws = 1.0 / 3.0, 1.0 / 3.0
    if kernel == "ring_mix":
        if impl == "ref":
            return jax.jit(functools.partial(ref.ring_mix_ref,
                                             w_self=wc, w_side=ws))
        from repro.kernels import ring_mix as _rm
        return functools.partial(_rm.ring_mix_flat, w_self=wc, w_side=ws,
                                 block_rows=config["block_rows"],
                                 interpret=interp)
    if kernel == "quant_mix":
        if impl == "ref":
            return jax.jit(functools.partial(ref.quant_mix_ref,
                                             w_self=wc, w_side=ws))
        from repro.kernels import quant_mix as _qm
        return functools.partial(_qm.quant_mix_2d, w_self=wc, w_side=ws,
                                 block_cols=config["block_cols"],
                                 interpret=interp)
    if kernel == "multi_hop_mix":
        hops = int(extra.get("hops", 3))
        rows = shape[0]
        kw = dict(hops=hops, out_rows=max(rows - 2 * hops, 1), halo=hops,
                  w_self=wc, w_side=ws)
        if impl == "ref":
            return jax.jit(functools.partial(ref.multi_hop_mix_ref, **kw))
        from repro.kernels import multi_hop_mix as _mh
        return functools.partial(_mh.multi_hop_mix_flat, **kw,
                                 block_f=config["block_f"], interpret=interp)
    if kernel == "multi_hop_mix_quant":
        hops = int(extra.get("hops", 3))
        kw = dict(hops=hops, w_self=wc, w_side=ws)
        if impl == "ref":
            return jax.jit(functools.partial(ref.multi_hop_mix_quant_ref,
                                             **kw))
        from repro.kernels import multi_hop_mix as _mh
        return functools.partial(_mh.multi_hop_mix_quant_flat, **kw,
                                 block_f=config["block_f"], interpret=interp)
    if kernel == "fused_retract":
        if impl == "ref":
            return jax.jit(functools.partial(
                ref.fused_retract_ref, ns_iters=config["ns_iters"]))
        from repro.kernels import retract as _rt
        return functools.partial(_rt.fused_retract_2d,
                                 block_d=config["block_d"],
                                 ns_iters=config["ns_iters"],
                                 interpret=interp)
    # the attention kernels route through their ops.py wrappers — explicit
    # block args skip the tune lookup, so probing never recurses into the
    # cache being built
    from repro.kernels import ops as _ops
    if kernel == "flash_attention":
        return jax.jit(functools.partial(
            _ops.flash_attention, causal=True, impl=impl,
            block_q=config["block_q"], block_kv=config["block_kv"]))
    if kernel == "paged_decode":
        return jax.jit(functools.partial(
            _ops.paged_decode_attention, impl=impl,
            pages_per_block=config["pages_per_block"]))
    raise ValueError(f"no probe for kernel {kernel!r}")


def _oracle_fn(kernel: str):
    """The independent accuracy oracle for ORACLE_GATED kernels."""
    from repro.kernels import ref
    if kernel == "flash_attention":
        import functools
        return functools.partial(ref.attention_naive, causal=True)
    if kernel == "paged_decode":
        return ref.paged_decode_attention_ref
    raise ValueError(kernel)


def _default_for_shape(kernel: str, shape: tuple) -> dict:
    """The config ops.py would actually ship for this shape with no cache —
    the nominal DEFAULTS entry, stepped down the same fallback ladder ops.py
    uses when the nominal block doesn't tile the shape."""
    cfg = dict(DEFAULTS[kernel])
    rows, f = shape[0], shape[-1]
    if "block_rows" in cfg:
        for cand in (cfg["block_rows"], 128, 64, 32, 16, 8):
            if rows % cand == 0:
                cfg["block_rows"] = cand
                break
        else:
            cfg["block_rows"] = rows
    if "block_cols" in cfg:
        for cand in (cfg["block_cols"], 16384, 8192, 4096):
            if f % cand == 0:
                cfg["block_cols"] = cand
                break
        else:
            cfg["block_cols"] = f
    if "block_f" in cfg:
        for cand in (cfg["block_f"], 4096, 2048, 512, 256, 128):
            if f % cand == 0:
                cfg["block_f"] = cand
                break
        else:
            cfg["block_f"] = f
    if "block_d" in cfg and rows % cfg["block_d"]:
        cfg["block_d"] = 128 if rows % 128 == 0 else rows
    return cfg


def _feasible(kernel: str, shape: tuple, config: dict) -> bool:
    rows, f = shape[0], shape[-1]
    if "block_rows" in config:
        return rows % config["block_rows"] == 0
    if "block_cols" in config:
        return f % config["block_cols"] == 0
    if "block_f" in config:
        return f % config["block_f"] == 0
    if "block_d" in config:
        return rows % config["block_d"] == 0
    return True


def _time_us(fn, args, repeats: int = 5, inner: int = 3) -> float:
    import jax
    jax.block_until_ready(fn(*args))             # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1e6


def _estimate(kernel: str, shape: tuple, config: dict, extra: dict):
    from repro.obs import estimates as est
    if kernel == "ring_mix":
        return est.ring_mix_est(shape[0] * shape[1])
    if kernel == "quant_mix":
        return est.quant_mix_est(shape[0], shape[1])
    if kernel in ("multi_hop_mix", "multi_hop_mix_quant"):
        hops = int(extra.get("hops", 3))
        return est.multi_hop_mix_est(
            shape[0], shape[1], hops=hops,
            out_rows=max(shape[0] - 2 * hops, 1),
            quant=kernel.endswith("quant"))
    if kernel == "fused_retract":
        return est.fused_retract_est(shape[0], shape[1],
                                     ns_iters=config.get("ns_iters", 20))
    if kernel == "flash_attention":
        b, s, t, h, hd = shape
        return est.flash_attention_est(b, s, t, h, hd,
                                       block_q=config.get("block_q", 128))
    if kernel == "paged_decode":
        s, m, ps, hd = shape
        h, hkv = PAGED_PROBE_HEADS
        return est.paged_decode_est(s, h, hkv, hd, m, ps)
    raise ValueError(kernel)


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------


def autotune(kernel: str, shape: tuple, dtype: Any,
             extra: Optional[dict] = None, force: bool = False) -> dict:
    """Measure every feasible candidate, gate accuracy-sensitive ones, pick
    the winner (with hysteresis vs the default), persist, return the record.
    """
    import numpy as np

    from repro.launch import roofline

    extra = dict(extra or {})
    k = key(kernel, shape, dtype, extra)
    cache = _read_cache()
    if not force and k in cache["entries"]:
        return cache["entries"][k]

    impl = _dispatch_impl()
    default = _default_for_shape(kernel, shape)
    args = _probe_inputs(kernel, shape, dtype, extra)
    # two gating flavors: self-gated kernels (ns_iters changes the math, so
    # non-default candidates compare against the default config's output);
    # ORACLE_GATED kernels check *every* candidate — default included —
    # against an independent reference oracle
    oracle = kernel in ORACLE_GATED
    gated = oracle or "ns_iters" in default
    ref_out = None
    if oracle:
        ref_out = np.asarray(_oracle_fn(kernel)(*args))
    elif gated:
        ref_out = np.asarray(
            _probe_fn(kernel, shape, default, extra, impl)(*args))
    if gated:
        ref_scale = max(1.0, float(np.max(np.abs(ref_out))))

    candidates = []
    seen: set[tuple] = set()
    ref_knobs = REF_KNOBS.get(kernel, ())
    for cfg in [default] + SPACES[kernel]:
        # on the oracle path only math-bearing knobs differentiate
        # candidates (block shapes are no-ops there) — dedupe so the search
        # stays cheap; the default always survives as the first entry
        sig = tuple(sorted(cfg.items())) if impl != "ref" else \
            tuple(sorted((n, v) for n, v in cfg.items() if n in ref_knobs))
        if sig in seen or not _feasible(kernel, shape, cfg):
            continue
        seen.add(sig)
        fn = _probe_fn(kernel, shape, cfg, extra, impl)
        rec = {"config": cfg, "us": _time_us(fn, args)}
        if gated and (oracle or cfg != default):
            err = float(np.max(np.abs(np.asarray(fn(*args)) - ref_out)))
            rec["max_abs_err"] = err
            rec["accurate"] = bool(err <= ACCURACY_RTOL * ref_scale)
        candidates.append(rec)

    default_us = next(c["us"] for c in candidates
                      if c["config"] == default)
    ok = [c for c in candidates if c.get("accurate", True)]
    if not ok:
        raise RuntimeError(
            f"{kernel}: no candidate met the accuracy gate "
            f"(rtol={ACCURACY_RTOL}) — kernel/oracle mismatch")
    best = min(ok, key=lambda c: c["us"])
    if best["config"] != default and \
            best["us"] > default_us * (1.0 - HYSTERESIS):
        best = next((c for c in ok if c["config"] == default), best)

    est = _estimate(kernel, shape, best["config"], extra)
    entry = {
        "config": best["config"],
        "default_config": default,
        "best_us": best["us"],
        "default_us": default_us,
        "speedup_pct": 100.0 * (default_us / max(best["us"], 1e-9) - 1.0),
        "impl": impl,
        "candidates": candidates,
        "roofline": roofline.place(est),
        "searched_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    cache = _read_cache()
    cache["entries"][k] = entry
    cache["searches"] = cache.get("searches", 0) + 1
    cache["device_kind"] = _device_kind()
    _write_cache(cache)
    return entry


# ---------------------------------------------------------------------------
# CLI — the CI tune job's entry point
# ---------------------------------------------------------------------------

#: small default shape set: quick on CPU, representative of the bench sizes
DEMO_SHAPES = [
    ("ring_mix", (64, 1024), "float32", None),
    ("multi_hop_mix", (16, 1024), "float32", {"hops": 3}),
    ("fused_retract", (256, 64), "float32", None),
    ("flash_attention", (1, 128, 128, 4, 64), "float32", None),
    ("paged_decode", (4, 8, 16, 64), "float32", None),
]


def main(argv: Optional[list] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Autotune Pallas kernel launch configs "
                    "(cache: experiments/tune/<device>.json)")
    ap.add_argument("--kernel", action="append", default=None,
                    help="kernel[:RxC[:hops]] — repeatable; default: a "
                         "small demo set")
    ap.add_argument("--force", action="store_true",
                    help="re-search even on cache hits")
    args = ap.parse_args(argv)

    demo_shape = {n: s for n, s, _, _ in DEMO_SHAPES}
    todo = []
    for spec in args.kernel or []:
        parts = spec.split(":")
        name = parts[0]
        shape = tuple(int(v) for v in parts[1].split("x")) if len(parts) > 1 \
            else demo_shape.get(name, (64, 1024))
        extra = {"hops": int(parts[2])} if len(parts) > 2 else (
            {"hops": 3} if name.startswith("multi_hop_mix") else None)
        todo.append((name, shape, "float32", extra))
    if not todo:
        todo = DEMO_SHAPES

    for name, shape, dtype, extra in todo:
        entry = autotune(name, shape, dtype, extra=extra, force=args.force)
        print(f"{key(name, shape, dtype, extra)}: config={entry['config']} "
              f"default={entry['default_us']:.1f}us "
              f"best={entry['best_us']:.1f}us "
              f"({entry['speedup_pct']:+.1f}%)")
    print(f"cache: {cache_path()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
