"""Pallas kernel checker: VMEM budgets, tiling contracts, oracle coverage.

Three static passes over the kernel layer, no kernel execution required:

- **VMEM footprint** — every launch config's resident bytes per grid step
  (input + output blocks x dtypes, double-buffered for the pipelined DMA,
  plus scratch) estimated against the :class:`~repro.launch.roofline.
  HardwareModel` ``vmem_bytes`` budget (~16 MiB/core on every current TPU).
  The estimators mirror the real ``BlockSpec``s in ``kernels/*.py``.
- **Tiling contracts** — the ``ops.py`` dispatch wrappers promise
  "arbitrary leaf sizes in, padded panels out"; this pass re-derives each
  wrapper's pad-and-pick-block arithmetic over ragged (prime) shapes and
  fails if any shape escapes the kernel's ``dim % block == 0`` assert or
  loses tail elements.
- **Oracle coverage** — introspects ``kernels/ops.py`` (AST, not import
  side effects) and fails if any dispatched kernel lacks a ``ref.py``
  oracle call, an ``Estimates`` recorder registered in
  ``obs.estimates.KERNELS``, or — when it consults the autotuner — a
  ``tune.py`` registration (DEFAULTS + SPACES, which the search gates at
  ``ACCURACY_RTOL`` against the default config's output).
"""
from __future__ import annotations

import ast
from pathlib import Path

from repro.analysis.jaxpr_lint import Finding
from repro.launch import roofline

__all__ = ["vmem_footprint", "vmem_findings", "check_vmem",
           "check_tiling", "check_oracle_coverage", "run"]

_F32 = 4
_I32 = 4
_I8 = 1


# --------------------------------------------------------------------------
# VMEM footprint estimators (mirror the BlockSpecs in kernels/*.py)
# --------------------------------------------------------------------------

def _ring_mix_fp(dims: dict, cfg: dict) -> int:
    br = cfg.get("block_rows", 256)
    # 3 input panels + 1 output, (block_rows, 128) fp32
    return 4 * br * 128 * _F32


def _quant_mix_fp(dims: dict, cfg: dict) -> int:
    bc = cfg.get("block_cols", 32768)
    q = 3 * bc * _I8               # int8 payloads, one (bc/128, 128) row
    s = 3 * 8 * 128 * _F32         # (1, 128) scale rows pad to (8, 128)
    out = bc * dims.get("out_itemsize", _F32)
    return q + s + out


def _multi_hop_fp(dims: dict, cfg: dict) -> int:
    bf = cfg.get("block_f", 1024)
    rows, out_rows = dims["rows"], dims["out_rows"]
    return (rows + out_rows) * bf * _F32


def _multi_hop_quant_fp(dims: dict, cfg: dict) -> int:
    # one launch per hop: the input panel block (int8 at hop 0, the f32
    # state after), the f32 state out, and per-row (1, 128) scale and
    # row-max rows, each padded to an (8, 128) tile
    bf = cfg.get("block_f", 4096)
    rows = dims["rows"]
    return 2 * rows * bf * _F32 + 2 * rows * 8 * 128 * _F32


def _fused_retract_fp(dims: dict, cfg: dict) -> int:
    from repro.kernels import retract
    # the kernel's own model (r padded to the lane tile, scratch, live
    # (r, r) temporaries, double-buffered blocks) is already the whole
    # figure: halve it so vmem_footprint's uniform x2 leaves it as it is
    return _scratch_once(retract.vmem_bytes(dims["r"],
                                            cfg.get("block_d", 256)))


def _stiefel_project_fp(dims: dict, cfg: dict) -> int:
    bd, r = cfg.get("block_d", 128), dims["r"]
    blocks = 3 * bd * r * _F32 + r * r * _F32
    scratch = r * r * _F32
    return blocks + _scratch_once(scratch)


def _flash_attention_fp(dims: dict, cfg: dict) -> int:
    bq, bk = cfg.get("block_q", 128), cfg.get("block_kv", 128)
    hd, hdv = dims["hd"], dims.get("hdv", dims["hd"])
    # positions: a (bq, 1) column and a (1, bk) row, padded to the tile
    blocks = (bq * 128 * _I32 + 8 * bk * _I32
              + bq * hd * _F32 + bk * hd * _F32 + bk * hdv * _F32
              + bq * hdv * _F32)                 # q, k, v, out
    scratch = (bq * hdv + 2 * bq) * _F32         # acc + m + l
    return blocks + _scratch_once(scratch)


def _paged_decode_fp(dims: dict, cfg: dict) -> int:
    # every block spans all Hkv heads of the fetched pages
    ppb = cfg.get("pages_per_block", 1)
    ps, group, hkv = dims["ps"], dims["group"], dims["hkv"]
    hd, hdv = dims["hd"], dims.get("hdv", dims["hd"])
    blocks = hkv * (group * hd * _F32
                    + ppb * ps * hd * _F32 + ppb * ps * hdv * _F32
                    + group * hdv * _F32)
    scratch = hkv * (group * hdv + 2 * group) * _F32
    return blocks + _scratch_once(scratch)


def _scratch_once(nbytes: int) -> int:
    # scratch_shapes are allocated once, not double-buffered; halve here and
    # let vmem_footprint apply the uniform x2 to everything
    return nbytes // 2


_FOOTPRINTS = {
    "ring_mix": _ring_mix_fp,
    "quant_mix": _quant_mix_fp,
    "multi_hop_mix": _multi_hop_fp,
    "multi_hop_mix_quant": _multi_hop_quant_fp,
    "fused_retract": _fused_retract_fp,
    "stiefel_project": _stiefel_project_fp,
    "flash_attention": _flash_attention_fp,
    "paged_decode": _paged_decode_fp,
}

#: representative dims per kernel for config sweeps: the ROADMAP target
#: shapes (d=4096 r=128 retract; tiny_64k 8-node mix panel; 128-wide heads)
REPRESENTATIVE = {
    "ring_mix": {},
    "quant_mix": {"out_itemsize": 4},
    "multi_hop_mix": {"rows": 136, "out_rows": 128},
    "multi_hop_mix_quant": {"rows": 16},
    "fused_retract": {"r": 128},
    "stiefel_project": {"r": 128},
    "flash_attention": {"hd": 128, "hdv": 128},
    "paged_decode": {"ps": 64, "group": 8, "hkv": 8, "hd": 128,
                     "hdv": 128},
}


def vmem_footprint(kernel: str, dims: dict, cfg: dict) -> int:
    """Estimated resident VMEM bytes per grid step, double-buffered."""
    if kernel not in _FOOTPRINTS:
        raise KeyError(f"no footprint model for kernel {kernel!r}; add one "
                       "to _FOOTPRINTS mirroring its BlockSpecs")
    return 2 * _FOOTPRINTS[kernel](dims, cfg)


def vmem_findings(kernel: str, cfg: dict, *, dims: dict | None = None,
                  hw: roofline.HardwareModel | None = None) -> list[Finding]:
    """Check one launch config against the hardware VMEM budget."""
    hw = hw or roofline.get_hardware()
    dims = {**REPRESENTATIVE.get(kernel, {}), **(dims or {})}
    fp = vmem_footprint(kernel, dims, cfg)
    if fp > hw.vmem_bytes:
        return [Finding(
            "vmem-budget", f"{kernel} {cfg}",
            f"estimated footprint {fp / 2**20:.1f} MiB exceeds {hw.name} "
            f"VMEM budget {hw.vmem_bytes / 2**20:.0f} MiB")]
    return []


def check_vmem(hw: roofline.HardwareModel | None = None) -> list[Finding]:
    """Sweep every registered launch config (tune DEFAULTS + SPACES)."""
    from repro.kernels import tune
    hw = hw or roofline.get_hardware()
    findings = []
    for kernel in _FOOTPRINTS:
        configs = [tune.DEFAULTS.get(kernel, {})] + tune.SPACES.get(kernel, [])
        for cfg in configs:
            findings.extend(vmem_findings(kernel, cfg, hw=hw))
    return findings


# --------------------------------------------------------------------------
# tiling contracts: pad-and-pick-block arithmetic over ragged shapes
# --------------------------------------------------------------------------

#: ragged sizes the dispatch wrappers must cover without tripping a kernel's
#: divisibility assert: primes, one-off-tile, sub-tile, and aligned sizes
RAGGED_SIZES = (1, 7, 97, 127, 129, 1009, 4093, 8191, 8192, 65536, 99991)


def _pick(padded: int, cands: list[int]) -> int:
    for c in cands:
        if padded % c == 0:
            return c
    return padded


def check_tiling() -> list[Finding]:
    findings = []

    # ring_mix: flatten to (rows, 128), pad rows to 8, block from candidates
    for n in RAGGED_SIZES:
        rows = -(-n // 128)
        rows_p = rows + (-rows) % 8
        block = _pick(rows_p, [256, 128, 64, 32, 16, 8])
        if rows_p % block or rows_p * 128 < n:
            findings.append(Finding(
                "tiling", f"ring_mix n={n}",
                f"padded panel ({rows_p},128) not covered by "
                f"block_rows={block}"))

    # quant_mix: each row viewed as (cols/128, 128) int8 lane rows, padded
    # to whole (32, 128) tiles past one tile height; a block of
    # block_cols/128 lane rows must be whole tiles or span the row
    for rows in (1, 31, 32, 97):
        for cols in RAGGED_SIZES:
            n = -(-cols // 128)
            n += (-n) % 32 if n > 32 else 0
            cols_p = 128 * n
            block_c = _pick(cols_p, [32768, 16384, 8192, 4096])
            if cols_p % block_c or cols_p < cols or (
                    (block_c // 128) % 32 and block_c != cols_p):
                findings.append(Finding(
                    "tiling", f"quant_mix rows={rows} cols={cols}",
                    f"padded ({rows},{cols_p}) not tiled by "
                    f"({rows},{block_c // 128},128) blocks"))

    # multi_hop_mix(+quant): the feature tail pads to whole (8, 128) f32 /
    # (32, 128) int8 tiles; the block_f fallback chain must always divide
    for f in RAGGED_SIZES:
        for kernel, tile, cands in (
                ("multi_hop_mix", 8 * 128,
                 [1024, 4096, 2048, 1024, 512, 256, 128]),
                ("multi_hop_mix_quant", 32 * 128,
                 [4096, 4096, 2048, 1024, 512, 256, 128])):
            f_p = f + (-f) % tile
            block = _pick(f_p, cands)
            if f_p % block or f_p < f or block % tile:
                findings.append(Finding(
                    "tiling", f"{kernel} f={f}",
                    f"padded width {f_p} not divided by whole-tile "
                    f"block_f={block}"))

    # fused_retract / stiefel_project: d,r pad to 128; block_d falls back
    # to 128 whenever the tuned/explicit block does not divide
    for d in (3, 100, 127, 129, 4096):
        d_p = d + (-d) % 128
        for block_d in (256, 128, 512):
            eff = block_d if d_p % block_d == 0 else 128
            if d_p % eff:
                findings.append(Finding(
                    "tiling", f"fused_retract d={d} block_d={block_d}",
                    f"effective block {eff} does not divide padded d={d_p}"))

    # flash_attention: seq tails pad to min(block, seq); the kernel then
    # runs with block=min(block, padded) which must divide
    for s in (1, 5, 127, 128, 1000):
        for block in (64, 128, 256):
            eff = min(block, max(s, 1))
            s_p = s + (-s) % eff
            if s_p % min(block, s_p):
                findings.append(Finding(
                    "tiling", f"flash_attention seq={s} block={block}",
                    f"padded seq {s_p} not divided by {min(block, s_p)}"))

    # paged_decode: block table padded with -1 columns to pages_per_block
    for m_pages in (1, 3, 7, 16):
        for ppb in (1, 2, 4, 8):
            m_p = m_pages + (-m_pages) % max(ppb, 1)
            if m_p % max(ppb, 1) or m_p < m_pages:
                findings.append(Finding(
                    "tiling", f"paged_decode m_pages={m_pages} ppb={ppb}",
                    f"padded table width {m_p} not divided by {ppb}"))

    return findings


# --------------------------------------------------------------------------
# oracle-coverage gate: AST introspection of kernels/ops.py
# --------------------------------------------------------------------------

def _ops_path() -> Path:
    from repro import kernels
    return Path(kernels.__file__).parent / "ops.py"


def _scan_ops(path: Path | None = None) -> dict[str, dict]:
    """Per dispatched kernel (one ``_est.record("<name>", ...)`` call):
    whether its wrapper calls a ``ref.*`` oracle and which tune keys it
    consults (directly or through ``_pick_block_f``)."""
    tree = ast.parse((path or _ops_path()).read_text())
    out: dict[str, dict] = {}
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        recorded, tuned, has_ref = [], [], False
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            f = call.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                head, attr = f.value.id, f.attr
                lit = (call.args[0].value
                       if call.args and isinstance(call.args[0], ast.Constant)
                       and isinstance(call.args[0].value, str) else None)
                if head == "_est" and attr == "record" and lit:
                    recorded.append(lit)
                elif head == "_tune" and attr == "lookup" and lit:
                    tuned.append(lit)
                elif head == "ref":
                    has_ref = True
            elif isinstance(f, ast.Name) and f.id == "_pick_block_f":
                if call.args and isinstance(call.args[0], ast.Constant):
                    tuned.append(call.args[0].value)
        for name in recorded:
            out[name] = {"fn": node.name, "has_ref": has_ref,
                         "tune_keys": tuned}
    return out


def check_oracle_coverage(path: Path | None = None) -> list[Finding]:
    """Every dispatched kernel needs: a ref.py oracle, an Estimates
    recorder registered in ``obs.estimates.KERNELS``, and (when it consults
    the autotuner) ``tune.py`` DEFAULTS + SPACES entries so the accuracy
    gate (``ACCURACY_RTOL`` vs the default config) applies to it."""
    from repro.kernels import tune
    from repro.obs import estimates
    findings = []
    kernels = _scan_ops(path)
    if not kernels:
        findings.append(Finding("oracle-coverage", "ops.py",
                                "no dispatched kernels found — scan broken?"))
    for name, info in sorted(kernels.items()):
        where = f"ops.{info['fn']}"
        if not info["has_ref"]:
            findings.append(Finding(
                "oracle-coverage", where,
                f"kernel {name!r} dispatches with no ref.py oracle call — "
                "the interpret/CPU path and the accuracy gate have nothing "
                "to check against"))
        if name not in estimates.KERNELS:
            findings.append(Finding(
                "oracle-coverage", where,
                f"kernel {name!r} records estimates under a name missing "
                "from obs.estimates.KERNELS"))
        for key in info["tune_keys"]:
            if key not in tune.DEFAULTS:
                findings.append(Finding(
                    "oracle-coverage", where,
                    f"tunable kernel {key!r} has no tune.DEFAULTS entry"))
            if key not in tune.SPACES:
                findings.append(Finding(
                    "oracle-coverage", where,
                    f"tunable kernel {key!r} has no tune.SPACES entry — "
                    "the accuracy-gated search cannot cover it"))
    # stale registrations: every tune/estimates key must be dispatched
    for key in tune.DEFAULTS:
        if key not in kernels:
            findings.append(Finding(
                "oracle-coverage", f"tune.DEFAULTS[{key!r}]",
                "registered tune key is never dispatched from ops.py"))
    for key in estimates.KERNELS:
        if key not in kernels:
            findings.append(Finding(
                "oracle-coverage", f"estimates.KERNELS[{key!r}]",
                "registered estimator is never recorded from ops.py"))
    return findings


def run(hw: roofline.HardwareModel | None = None) -> list[Finding]:
    """All kernel-checker passes."""
    return check_vmem(hw) + check_tiling() + check_oracle_coverage()
