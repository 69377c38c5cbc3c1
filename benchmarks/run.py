"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, writes the full JSON payloads
to experiments/bench/, and appends one compact summary record per entry
(name, key metrics, git rev, timestamp) to the top-level BENCH_summary.json
so regressions are visible across revisions without diffing payloads.

  fair_det    — Fig. 1: DRGDA vs GT-GDA (deterministic fair classification)
  fair_stoch  — Fig. 2: DRSGDA vs GNSD-A / DM-HSGD / GT-SRVR
  dro         — supplementary: DRO with orthonormal weights (Eq. 21)
  consensus   — W^k contraction vs lambda_2^k theory; Stiefel consensus
  comms       — bits-per-parameter vs consensus error vs final M_t sweep
                (EF-int8 / top-k / low-rank / naive; channel fault rates)
  mix         — stacked vs shard_map (fused/unfused) backend: hops/sec +
                est bytes moved per gossip hop across model sizes and hop
                counts (8 virtual devices)
  tune        — autotuned vs default Pallas launch configs on the demo
                shapes (writes experiments/bench/tune.json; asserts the
                second lookup is a pure cache load)
  geometry    — retraction micro-bench: fused kernel vs unfused NS vs eigh
                (+ qr / cayley), node-stacked (d, r) sweep
  complexity  — Theorem-1 decay-rate sanity (log-log slope of M_t)
  roofline    — dry-run roofline table summary (reads experiments/dryrun)
  obs         — telemetry overhead + counter-vs-estimate agreement
  serve       — decode service: tokens/sec + p99 latency vs batch size,
                continuous vs static batching, paged-kernel accuracy,
                2-replica gossip drift (writes experiments/bench/serve.json)
  elastic     — elastic-gossip churn sweep: M_t / consensus vs churn rate
                and stale-hop tolerance tau on fair classification and
                robust PCA; checks the scripted leave-then-rejoin run stays
                within 2x of the static ring
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# make `python benchmarks/run.py ...` work from anywhere: the repo root (for
# the `benchmarks` package) and src/ (for `repro`) must be importable
for _p in (_REPO_ROOT, os.path.join(_REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCH_DIR = os.path.join(_REPO_ROOT, "experiments", "bench")
SUMMARY_PATH = os.path.join(_REPO_ROOT, "BENCH_summary.json")


def _save(name: str, payload: dict) -> None:
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1)


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10).stdout.strip() or "?"
    except Exception:
        return "?"


def append_summary(name: str, us_per_call: float, derived: str,
                   rev: str | None = None) -> dict:
    """Append one compact record to the top-level BENCH_summary.json.

    The file holds a flat list, newest last; ``derived`` is the same
    key=value string the CSV row prints, split into a dict for grepping.
    """
    metrics: dict = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                metrics[k] = float(v)
            except ValueError:
                metrics[k] = v
    rec = {"name": name, "us_per_call": round(us_per_call, 1),
           "metrics": metrics, "git_rev": rev or _git_rev(),
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}
    rows = []
    if os.path.exists(SUMMARY_PATH):
        try:
            with open(SUMMARY_PATH) as f:
                rows = json.load(f)
        except Exception:
            rows = []
    rows.append(rec)
    with open(SUMMARY_PATH, "w") as f:
        json.dump(rows, f, indent=1)
    return rec


def bench_fair_det():
    from benchmarks import fair_classification as fc
    res = {"figure1_deterministic": [fc.run_method("drgda", 100, True),
                                     fc.run_method("gt-gda", 100, True)]}
    _save("fair_det", res)
    runs = res["figure1_deterministic"]
    us = sum(r["us_per_step"] for r in runs) / len(runs)
    drgda = next(r for r in runs if r["method"] == "drgda")
    gtgda = next(r for r in runs if r["method"] == "gt-gda")
    derived = (f"drgda_final_Mt={drgda['final_M_t']:.4f};"
               f"gtgda_final_Mt={gtgda['final_M_t']:.4f};"
               f"drgda_wins={drgda['final_M_t'] <= gtgda['final_M_t']}")
    return us, derived


def bench_fair_stoch():
    from benchmarks import fair_classification as fc
    # equal SAMPLE budget (the paper's complexity metric): DM-HSGD and
    # GT-SRVR evaluate two gradients per step -> half the steps
    runs = [fc.run_method("drsgda", 120, False),
            fc.run_method("gnsd-a", 120, False),
            fc.run_method("dm-hsgd", 60, False),
            fc.run_method("gt-srvr", 60, False)]
    _save("fair_stoch", {"figure2_stochastic": runs})
    us = sum(r["us_per_step"] for r in runs) / len(runs)
    finals = {r["method"]: r["final_M_t"] for r in runs}
    best = min(finals, key=finals.get)
    derived = ";".join(f"{k}_Mt={v:.4f}" for k, v in finals.items()) + \
        f";best={best}"
    return us, derived


def bench_dro():
    from benchmarks import dro
    res = dro.run(steps=100)  # dro.run halves two-pass methods internally
    _save("dro", res)
    runs = res["dro"]
    us = sum(r["us_per_step"] for r in runs) / len(runs)
    finals = {r["method"]: r["final_M_t"] for r in runs}
    best = min(finals, key=finals.get)
    return us, ";".join(f"{k}_Mt={v:.4f}" for k, v in finals.items()) + \
        f";best={best}"


def bench_consensus():
    from benchmarks import consensus
    res = consensus.run()
    _save("consensus", res)
    ok = sum(r["bound_satisfied"] for r in res["contraction"])
    return res["us_total"] / max(len(res["contraction"]), 1), \
        (f"stiefel_consensus_converged={res['stiefel_consensus_converged']};"
         f"lambda2k_bound_holds={ok}/{len(res['contraction'])}")


def bench_comms():
    from benchmarks import comms
    res = comms.run()
    _save("comms", res)
    n_rows = len(res["gossip_sweep"]) + len(res["channel_rates"]) + \
        len(res["fair_classification"])
    fair = {r["variant"]: r["final_M_t"] for r in res["fair_classification"]}
    derived = (f"int8_ef_err_ratio={res['int8_ef_err_ratio']:.2f};"
               f"int8_ef_bits_ratio={res['int8_ef_bits_ratio']:.1f};"
               f"acceptance_2x_err_4x_bits={res['acceptance_2x_err_4x_bits']};"
               f"ef_beats_naive={res['ef_beats_naive']};"
               + ";".join(f"{k}_Mt={v:.4f}" for k, v in fair.items()))
    return res["us_total"] / max(n_rows, 1), derived


def bench_mix():
    from benchmarks import mix_backend
    res = mix_backend.run()
    _save("mix_backend", res)
    rows = res["rows"]
    ring = [r for r in rows if r["topology"] == "ring"]
    by = {r["backend"]: r for r in ring if r["size"] == "medium_2m"}
    sm, st = by["shard_map"], by["stacked"]
    tiny = {r["backend"]: r for r in ring if r["size"] == "tiny_64k"}
    fused, unfused = tiny["shard_map"], tiny["shard_map_unfused"]
    derived = (f"ring64k_fused_hps={fused['hops_per_sec']:.1f};"
               f"ring64k_unfused_hps={unfused['hops_per_sec']:.1f};"
               f"ring2m_shardmap_hps={sm['hops_per_sec']:.1f};"
               f"ring2m_stacked_hps={st['hops_per_sec']:.1f};"
               f"ring2m_bytes_ratio="
               f"{st['est_bytes_per_hop'] / max(sm['est_bytes_per_hop'], 1):.1f}")
    return res["us_total"] / max(len(rows), 1), derived


def bench_tune():
    """Autotuned vs default launch configs on the demo shapes — searches on
    a cache-miss, then proves the second lookup is a pure load."""
    from repro.kernels import tune as ktune
    os.environ["REPRO_TUNE"] = "search"
    t0 = time.time()
    rows = []
    for name, shape, dtype, extra in ktune.DEMO_SHAPES:
        entry = ktune.autotune(name, tuple(shape), dtype, extra=extra)
        rows.append({
            "kernel": name, "shape": list(shape), "dtype": dtype,
            "extra": extra, "config": entry["config"],
            "default_config": entry["default_config"],
            "best_us": entry["best_us"], "default_us": entry["default_us"],
            "speedup_pct": entry["speedup_pct"], "impl": entry["impl"],
        })
    searches = None
    try:
        with open(ktune.cache_path()) as f:
            searches = json.load(f).get("searches")
    except OSError:
        pass
    # round trip: every key must now serve from cache without re-searching
    for name, shape, dtype, extra in ktune.DEMO_SHAPES:
        assert ktune.lookup(name, tuple(shape), dtype, extra) is not None
    res = {"rows": rows, "cache_path": ktune.cache_path(),
           "searches": searches,
           "us_total": (time.time() - t0) * 1e6}
    _save("tune", res)
    tuned = [r for r in rows if r["config"] != r["default_config"]]
    derived = (f"n_kernels={len(rows)};n_nondefault={len(tuned)};"
               + ";".join(f"{r['kernel']}_speedup_pct={r['speedup_pct']:.1f}"
                          for r in rows))
    return res["us_total"] / max(len(rows), 1), derived


def bench_geometry():
    from benchmarks import geometry
    res = geometry.run()
    _save("geometry", res)
    rows = res["rows"]
    big = [r for r in rows if (r["d"], r["r"]) == (1024, 128)]
    by = {r["impl"]: r for r in big}
    fused, ns, eigh = by["polar_fused"], by["polar_ns"], by["polar_eigh"]
    worst_feas = max(r["feasibility"] for r in rows)
    derived = (f"fused1024_us={fused['us_per_call']:.0f};"
               f"ns1024_us={ns['us_per_call']:.0f};"
               f"eigh1024_us={eigh['us_per_call']:.0f};"
               f"fused_speedup_vs_eigh={fused['speedup_vs_eigh']:.2f};"
               f"max_feasibility_residual={worst_feas:.1e}")
    return res["us_total"] / max(len(rows), 1), derived


def bench_complexity():
    from benchmarks import complexity
    res = complexity.run(steps=300)
    _save("complexity", res)
    return res["us_total"] / 300, \
        (f"loglog_slope={res['loglog_slope']:.2f};"
         f"consistent_with_theorem1={res['consistent_with_theorem1']}")


def bench_roofline():
    from benchmarks import roofline_report
    t0 = time.time()
    res = roofline_report.run()
    _save("roofline", res)
    us = (time.time() - t0) * 1e6
    return us, (f"records={res['n_records']};"
                + ";".join(f"{k}={v}" for k, v in
                           sorted(res["dominant_histogram"].items())))


def bench_obs():
    from benchmarks import obs
    res = obs.run()
    _save("obs", res)
    derived = (f"overhead_pct={res['overhead_pct']:.2f};"
               f"bit_identical={res['bit_identical']};"
               f"bytes_per_hop_rel_err={res['bytes_per_hop_rel_err']:.2e};"
               f"n_flushes={res['n_flushes']};"
               f"n_events={res['n_events']}")
    return res["us_per_step_on"], derived


def bench_elastic():
    from benchmarks import elastic
    res = elastic.run()
    _save("elastic", res)
    rows = res["fair_classification"] + res["robust_pca"]
    fair = {r["schedule"]: r["final_M_t"] for r in res["fair_classification"]}
    derived = (f"leave_rejoin_ratio={res['leave_rejoin_Mt_ratio']:.2f};"
               f"within_2x={res['leave_rejoin_within_2x']};"
               f"all_finite={res['all_finite']};"
               + ";".join(f"{k}_Mt={v:.4f}" for k, v in fair.items()))
    return res["us_total"] / max(len(rows), 1), derived


def bench_serve():
    from benchmarks import serve
    res = serve.run()
    _save("serve", res)
    derived = (f"tok_per_s={res['continuous']['tok_per_s']:.1f};"
               f"p99_ms={res['continuous']['p99_ms']:.1f};"
               f"speedup_vs_static={res['speedup_vs_static']:.2f};"
               f"kernel_max_err={res['kernel_max_err']:.2e};"
               f"drift_final={res['replica']['drift_final']:.2e}")
    return res["us_per_token"], derived


ALL = {
    "fair_det": bench_fair_det,
    "fair_stoch": bench_fair_stoch,
    "dro": bench_dro,
    "consensus": bench_consensus,
    "comms": bench_comms,
    "mix": bench_mix,
    "tune": bench_tune,
    "geometry": bench_geometry,
    "complexity": bench_complexity,
    "roofline": bench_roofline,
    "obs": bench_obs,
    "serve": bench_serve,
    "elastic": bench_elastic,
}


def main() -> int:
    """Run the named entries (all by default); non-zero exit if any failed."""
    names = sys.argv[1:] or list(ALL)
    rev = _git_rev()
    failed = 0
    print("name,us_per_call,derived")
    for name in names:
        try:
            us, derived = ALL[name]()
            print(f"{name},{us:.1f},{derived}", flush=True)
            append_summary(name, us, derived, rev=rev)
        except Exception as e:  # report every entry, then fail the run
            failed += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
