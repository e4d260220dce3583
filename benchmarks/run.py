# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig2 fig7  # subset

Each module reproduces one paper artifact (see DESIGN.md §8) on synthetic
scale-matched datasets and emits machine-checkable claim lines.  The
roofline module aggregates the dry-run artifacts (deliverable g).

The ``bench_*`` modules additionally emit a JSON report; the harness pins
each one's ``--out`` to ``BENCH_<name>.json`` at the repo root (bench_engine
→ BENCH_engine.json, …) so the perf trajectory is tracked file-to-file
across PRs instead of only scrolling past on stdout.

``--smoke`` runs only the ``bench_*`` JSON modules at tiny sizes, writing
their reports to a temp directory (never clobbering the committed
``BENCH_*.json`` anchors) while still executing every module's claim
assertions — a fast CI gate that keeps the perf anchors from silently
rotting (tests/test_benchmarks_smoke.py wires it into the tier-1 suite)."""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback

MODULES = ["fig2_simulated_runtime", "fig3_wallclock", "fig4_hw_accel",
           "fig5_parallel", "fig6_test_acc", "fig7_inner_opt",
           "fig8_dsm_theta", "table1_time_model", "thm41_data_access",
           "ablation_schedule", "bench_engine", "bench_data", "bench_dist",
           "bench_elastic", "bench_serve", "bench_workloads", "bench_scale",
           "roofline"]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny-size flags for --smoke: small enough to finish in CI seconds, large
# enough that every module's claim set still exercises its real code paths
SMOKE_ARGS = {
    "bench_engine": ["--scale", "0.03"],
    # the overlap claim needs stage compute to dominate real shard I/O —
    # 0.125 is the smallest scale where the §3.3 overlap genuinely holds
    "bench_data": ["--scale", "0.125"],
    "bench_dist": ["--scale", "0.05", "--shard-size", "64",
                   "--delay-ms", "0.2"],
    "bench_elastic": ["--scale", "0.05", "--slow-s", "2.0"],
    # mirrors the smallest closed loop that still swaps >= 2 times
    "bench_serve": ["--capacity", "96", "--n0", "16", "--shard-size", "8",
                    "--rpt", "8", "--eval-rows", "16", "--batch-size", "4"],
    # the overlap claims need real shard I/O to hide behind compute, like
    # bench_data; shard 32 keeps the hot cap shard-alignable at this size
    "bench_scale": ["--scale", "0.125", "--compat-scale", "0.03125",
                    "--shard-size", "32", "--delay-ms", "0.5"],
}


def _bench_json_path(name: str, out_dir: str) -> str:
    return os.path.join(out_dir, f"BENCH_{name[len('bench_'):]}.json")


def _append_history(name: str, out_dir: str, smoke: bool) -> None:
    """One BENCH_history.jsonl record per bench module run: the JSON
    report's claims + guarded metrics, with the failure details
    check_claims logged.  Full runs append at the repo root (the
    committed trajectory), smoke runs inside the smoke temp dir."""
    from . import common
    from .history import HISTORY_NAME, append_history, history_record
    json_path = _bench_json_path(name, out_dir)
    if not os.path.exists(json_path):
        return
    with open(json_path) as fh:
        report = json.load(fh)
    record = history_record(name[len("bench_"):], report, smoke=smoke)
    for logged in common.CLAIMS_LOG:
        if logged["module"] == name and logged["failed"]:
            record["failed_details"] = logged["failed"]
    append_history(os.path.join(out_dir, HISTORY_NAME), record)


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv
    smoke = "--smoke" in argv[1:]
    selectors = [a for a in argv[1:] if a != "--smoke"]
    which = selectors or None
    modules = [m for m in MODULES if m.startswith("bench_")] if smoke \
        else MODULES
    out_dir = tempfile.mkdtemp(prefix="bench_smoke_") if smoke else REPO_ROOT
    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name in modules:
        if which and not any(name.startswith(w) for w in which):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        if name.startswith("bench_") and "--out" not in argv:
            # pin the JSON artifact path; user flags (and an explicit
            # --out) still flow through parse_known_args untouched
            extra = SMOKE_ARGS.get(name, []) if smoke else []
            sys.argv = [argv[0]] + selectors + extra + \
                ["--out", _bench_json_path(name, out_dir)]
        t0 = time.time()
        try:
            mod.main()
            print(f"{name}/__wall__,{(time.time()-t0)*1e6:.0f},ok",
                  flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name}/__wall__,{(time.time()-t0)*1e6:.0f},FAILED",
                  flush=True)
        finally:
            sys.argv = argv
        if name.startswith("bench_"):
            # the JSON report lands even when claims fail — record the
            # trajectory either way (a FAILED row with numbers beats a gap)
            try:
                _append_history(name, out_dir, smoke)
            except Exception:
                traceback.print_exc()
    if smoke:
        print(f"smoke reports under {out_dir}", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
