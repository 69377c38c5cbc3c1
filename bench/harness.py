"""Data-driven benchmark harness: finds a cell's files by name and runs it.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The traffic file names its job kind,
the driver ``bench/jobs/<job>.py`` that builds the program, warms it up,
measures the window and checks the result against the plain reference.
The limits of that check live in ``bench/limits/<cell>.json``, and each
per-layer metric is a reader ``bench/metrics/<metric>.py``.  Adding a
configuration, a cell or a metric is adding files; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Optional

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one run of a cell needs, read from its files."""
    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell
    root: Path

    @property
    def job(self) -> str:
        return self.traffic["job"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        known = sorted(w["name"] for w in spec["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {known}")
    w = entries[0]
    bench = root / "bench"
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a ``workloads`` key belongs to every
    # cell that reports the end-to-end metric it moves
    layer = [m for m in spec["per_layer"] if _applies(m, name)
             and ("workloads" in m or m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_json(bench / "configs" / f"{w['config']}.json"),
                traffic_name=w["traffic"],
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer, root=root)


def load_job(cell: Cell) -> ModuleType:
    return _load_module(cell.root / "bench" / "jobs" / f"{cell.job}.py",
                        f"bench_job_{cell.job}")


def load_reader(name: str, root: Path = ROOT) -> ModuleType:
    """The reader of one per-layer metric: a module with ``read(ctx)``
    returning a number, or None where the run has nothing to read."""
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_")
                        .replace("-", "_"))


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``; an
    unknown kind is an error, never a default."""
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU found (platform {d.platform!r}); the benchmark "
                     "measures the chip and has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips needed, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``, as the runtime
    reports it (None where it reports nothing)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where every value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks_from(readings: dict, limits: dict) -> list[Check]:
    """Pair each reading with its limit from the cell's limits file; a
    reading without a limit is an error (it would be compared with
    nothing)."""
    missing = sorted(set(readings) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return [Check(k, float(readings[k]), float(limits[k]["limit"]))
            for k in readings]


def result_line(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: Optional[dict] = None,
                extra_ok: bool = True) -> dict:
    """The last line of standard output.  ``checks`` goes last."""
    out = {"correct": bool(extra_ok and checks and all(c.ok for c in checks)),
           "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def print_checks(checks: list[Check], stream=sys.stderr) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=stream, flush=True)


#: a configuration file's published keys, and the ``ModelConfig`` field
#: each must equal in the program's configuration as it is run
PUBLISHED_FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
                    "num_key_value_heads": "n_kv_heads",
                    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                    "num_hidden_layers": "n_layers",
                    "tie_word_embeddings": "tie_embeddings",
                    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}


def program_config(cell: Cell, **extra):
    """The program's ``ModelConfig`` for the cell: the repo configuration
    the file names, with the file's ``program_changes`` and ``extra``
    applied, checked against every published size in the file."""
    import dataclasses as dc

    from repro import configs
    cfg = configs.get_config(cell.config["repo_config"],
                             smoke=cell.config.get("repo_variant") == "smoke")
    cfg = dc.replace(cfg, **cell.config.get("program_changes", {}), **extra)
    for key, field in PUBLISHED_FIELDS.items():
        want, got = cell.config[key], getattr(cfg, field)
        if want != got:
            raise ValueError(f"{cell.config_name}: {key} is {want!r} in the "
                             f"configuration file but {field} is {got!r} in "
                             "the program's")
    if cfg.hd != cell.config.get("head_dim", cfg.d_model // cfg.n_heads):
        raise ValueError(f"{cell.config_name}: head size {cfg.hd} differs")
    return cfg


def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool,
             device: dict, t_start: float, devices=None) -> dict:
    """Run the cell's job, then assemble the result line: end-to-end
    metrics (tracing off) or the per-layer readers' metrics (tracing on)."""
    import types

    import jax

    from bench import trace as trace_mod

    job = load_job(cell)
    rec = trace_mod.Recorder(trace_on)
    devices = devices if devices is not None else jax.devices()[:cell.chips]
    out = job.run(cell, seed, seconds, rec, t_start, devices)
    print("detail " + json.dumps(out.get("detail", {})), file=sys.stderr,
          flush=True)
    checks = checks_from(out["readings"], cell.limits)
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if trace_on:
        t = rec.trace
        ctx = types.SimpleNamespace(
            trace=t, cell=cell, chips=cell.chips,
            peaks=peaks_for(device["kind"], cell.root), **out["layer_ctx"])
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"], cell.root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        dev["busy_s"] = t.busy_s()
        dev["window_s"] = t.window_s
        breakdown = t.breakdown()
    else:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    line = result_line(checks=checks, attempted=out["attempted"],
                       failed=out["failed"], metrics=metrics, device=dev,
                       breakdown=breakdown, extra_ok=out.get("ok", True))
    return {"line": line, "checks_list": checks, "out": out}
