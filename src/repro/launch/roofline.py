"""Roofline term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), on a selectable hardware model
(default TPU v5e):

  compute_s    = FLOPs_per_device / hw.peak_flops    (bf16 MXU peak)
  memory_s     = bytes_per_device / hw.hbm_bw        (HBM bandwidth)
  collective_s = collective_bytes_per_device / hw.ici_bw  (ICI, per link)

FLOPs / bytes come from ``compiled.cost_analysis()`` of the SPMD-partitioned
per-device module.  Collective bytes are NOT in cost_analysis: we parse the
optimized HLO and sum the result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (counting the
per-device payload each op moves over the interconnect once — a deliberate
first-order model; ring reductions move ~2x, which we note rather than
model).

With a TPU attached the model is the chip's own (:func:`get_hardware`
reads its ``device_kind``).  For CPU-side analysis pick one with
``REPRO_HW=tpu_v4|tpu_v5e|tpu_v5p`` (or pass a :class:`HardwareModel` /
registry name explicitly to the entry points);
:func:`place` positions any :class:`repro.obs.Estimates` on that roofline.
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Peak numbers of one accelerator chip for roofline placement."""
    name: str
    peak_flops: float   # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link
    # VMEM per core: ~16 MiB on every current TPU generation — the hard
    # budget every pallas_call's resident blocks (inputs + outputs +
    # scratch, double-buffered) must fit inside
    vmem_bytes: int = 16 * 2**20

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte above which a kernel is compute-bound on this chip."""
        return self.peak_flops / self.hbm_bw


#: published per-chip peaks (bf16), keyed by the ``REPRO_HW`` names.
#: Source: Google Cloud TPU documentation, system architecture pages for
#: TPU v4, v5e and v5p.
HARDWARE = {
    "tpu_v4": HardwareModel("tpu_v4", peak_flops=275e12, hbm_bw=1.2e12,
                            ici_bw=50e9),
    "tpu_v5e": HardwareModel("tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                             ici_bw=50e9),
    "tpu_v5p": HardwareModel("tpu_v5p", peak_flops=459e12, hbm_bw=2.77e12,
                             ici_bw=100e9),
}

#: ``device_kind`` as JAX reports it -> the :data:`HARDWARE` entry
DEVICE_KINDS = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
}

DEFAULT_HW = "tpu_v5e"


def hardware_for_kind(device_kind: str) -> HardwareModel:
    """The model of an attached chip; an unknown kind is an error, never a
    default."""
    if device_kind not in DEVICE_KINDS:
        raise ValueError(f"no hardware model for device_kind "
                         f"{device_kind!r}; known: {sorted(DEVICE_KINDS)}")
    return HARDWARE[DEVICE_KINDS[device_kind]]


def get_hardware(name: Optional[str] = None) -> HardwareModel:
    """Resolve a hardware model.

    With a TPU attached, the chip's own model (from its ``device_kind``);
    an explicit ``name`` or ``REPRO_HW`` that names another chip is then an
    error.  Without one (CPU-side analysis): explicit name > ``REPRO_HW``
    env > v5e.
    """
    import jax

    name = name or os.environ.get("REPRO_HW")
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        hw = hardware_for_kind(dev.device_kind)
        if name and name != hw.name:
            raise ValueError(f"hardware model {name!r} asked for, but the "
                             f"attached chip is {hw.name}")
        return hw
    name = name or DEFAULT_HW
    if name not in HARDWARE:
        raise ValueError(f"unknown hardware model {name!r}; "
                         f"choose from {sorted(HARDWARE)}")
    return HARDWARE[name]


def place(est, hw: Optional[HardwareModel] = None) -> dict:
    """Place an analytical kernel estimate (``repro.obs.Estimates`` or any
    object with ``ops``/``mem``/``intensity``) on ``hw``'s roofline."""
    hw = hw or get_hardware()
    attainable = min(hw.peak_flops, hw.hbm_bw * max(est.intensity, 0.0))
    return {
        "hw": hw.name,
        "intensity": est.intensity,
        "ridge_intensity": hw.ridge_intensity,
        "bound": "compute" if est.intensity >= hw.ridge_intensity else "memory",
        "attainable_flops": attainable,
        "time_s": est.ops / attainable if attainable > 0 else 0.0,
    }


# legacy module-level v5e constants — RooflineTerms defaults route through
# get_hardware() now; these remain for external readers of the old API
PEAK_FLOPS = HARDWARE[DEFAULT_HW].peak_flops
HBM_BW = HARDWARE[DEFAULT_HW].hbm_bw
ICI_BW = HARDWARE[DEFAULT_HW].ici_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.3 = bf16[2,128,512]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result bytes per collective kind from optimized HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for m in _OP_RE.finditer(hlo_text):
        tuple_part, dtype, dims, kind = m.groups()
        if tuple_part is not None:
            b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tuple_part))
        else:
            b = _shape_bytes(dtype, dims)
        out[kind] += b
    return out


_OP_LINE_RE = re.compile(
    r"^\s*%?\S+\s*=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\((.*)$", re.M)

_META_RE = re.compile(r'op_name="([^"]+)"')


def top_collectives(hlo_text: str, n: int = 12) -> list[dict]:
    """The n largest collective ops with their result bytes and the source
    op_name metadata — the 'profile' a dry-run gives you for §Perf."""
    rows = []
    for m in _OP_LINE_RE.finditer(hlo_text):
        tuple_part, dtype, dims, kind, rest = m.groups()
        if tuple_part is not None:
            b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tuple_part))
            shape = tuple_part[:60]
        else:
            b = _shape_bytes(dtype, dims)
            shape = f"{dtype}[{dims}]"
        meta = _META_RE.search(rest)
        rows.append({"kind": kind, "shape": shape, "bytes": b,
                     "op_name": (meta.group(1)[-120:] if meta else "")})
    rows.sort(key=lambda r: -r["bytes"])
    # merge duplicates (same kind+shape+op_name) with a count
    merged: dict = {}
    for r in rows:
        key = (r["kind"], r["shape"], r["op_name"])
        if key in merged:
            merged[key]["count"] += 1
            merged[key]["total_bytes"] += r["bytes"]
        else:
            merged[key] = {**r, "count": 1, "total_bytes": r["bytes"]}
    out = sorted(merged.values(), key=lambda r: -r["total_bytes"])
    return out[:n]


_KERNEL_RE = re.compile(
    r"^\s*%?([A-Za-z_]\w*?)(?:\.\d+)?\s*=.*custom_call_target=\"tpu_custom_call\"",
    re.M)


def kernel_calls(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in compiled TPU HLO: ``tpu_custom_call`` instructions
    counted by the kernel's ``name`` (which names the instruction)."""
    out: dict[str, int] = {}
    for name in _KERNEL_RE.findall(hlo_text):
        out[name] = out.get(name, 0) + 1
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops_per_dev: float
    bytes_per_dev: float
    collective_bytes_per_dev: float
    collective_breakdown: dict
    chips: int
    hw: Optional[HardwareModel] = None   # None -> get_hardware() (env/default)

    @property
    def _hw(self) -> HardwareModel:
        return self.hw or get_hardware()

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / self._hw.peak_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / self._hw.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_dev / self._hw.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "collective_bytes_per_dev": self.collective_bytes_per_dev,
            "collective_breakdown": self.collective_breakdown,
            "chips": self.chips,
            "hw": self._hw.name,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def derive(compiled, chips: int,
           hw: Optional[HardwareModel] = None) -> RooflineTerms:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):             # some backends return [dict]
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = compiled.as_text()
    cb = collective_bytes(text)
    return RooflineTerms(
        flops_per_dev=flops,
        bytes_per_dev=byts,
        collective_bytes_per_dev=float(sum(cb.values())),
        collective_breakdown=cb,
        chips=chips,
        hw=hw,
    )


def model_flops(n_params_active: int, tokens: int) -> float:
    """MODEL_FLOPS = 6 * N_active * D (training); 2 * N * D for inference."""
    return 6.0 * n_params_active * tokens


def useful_fraction(model_fl: float, hlo_flops_global: float) -> Optional[float]:
    if hlo_flops_global <= 0:
        return None
    return model_fl / hlo_flops_global
