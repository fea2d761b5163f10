"""Benchmark harness — one module per paper table/figure (DESIGN.md §5).

    PYTHONPATH=src python -m benchmarks.run [--only fingerprint,...] \
        [--json bench.json]

Prints ``name,us_per_call,derived`` CSV rows.  With ``--json`` the same
rows plus per-module status/timing are written as a machine-readable
artifact (CI uploads it), and any executed trajectory-tracked modules
(``bench_fleet`` → ``BENCH_fleet.json``, ``bench_montecarlo`` →
``BENCH_montecarlo.json``) ALSO append their rows to the repo-root
trajectory files — an accumulating perf record across runs/PRs (CI
uploads those too).  Exits nonzero if any bench module fails.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from benchmarks import common
from repro.compile_cache import use_compile_cache

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
# module → repo-root trajectory artifact (appended per --json run)
TRAJECTORIES = {
    "bench_fleet": os.path.join(_ROOT, "BENCH_fleet.json"),
    "bench_fleet_distributed": os.path.join(_ROOT, "BENCH_fleet.json"),
    "bench_plant": os.path.join(_ROOT, "BENCH_fleet.json"),
    "bench_montecarlo": os.path.join(_ROOT, "BENCH_montecarlo.json"),
}

MODULES = [
    "bench_fingerprint",     # §4.1 fingerprint constants table
    "bench_throttling",      # §3.1 / Fig.2① Effect ①
    "bench_cpo",             # §3.2 / Fig.2② Effect ②
    "bench_hbm",             # §3.3 / Fig.2③ Effect ③
    "bench_guardband",       # §3.4 / Fig.2④ Effect ④
    "bench_preposition",     # §4.2 η
    "bench_multitile",       # §5 / Fig.4 V7.0
    "bench_serdes",          # §6
    "bench_competitive",     # §9 / Fig.5
    "bench_montecarlo",      # §10 / Fig.6
    "bench_dataset90k",      # Appendix B
    "bench_kernels",         # Pallas kernels vs refs
    "bench_roofline",        # deliverable g snapshot + §Perf deltas
    "bench_stragglers",      # beyond-paper: thermal straggler mitigation
    "bench_fleet",           # fleet-scale batched scheduler engine
    "bench_plant",           # thermal-plant fidelity ladder (pole/grid/rom)
    "bench_fleet_distributed",  # multi-host (emulated process-group) fleets
]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench suffixes to run")
    ap.add_argument("--json", default="",
                    help="write a machine-readable result artifact here")
    args = ap.parse_args()
    only = {f"bench_{s.strip()}" for s in args.only.split(",") if s.strip()}
    unknown = only - set(MODULES)
    if unknown:  # a typo'd --only must not silently pass CI
        ap.error(f"unknown bench modules: {sorted(unknown)}")

    print("name,us_per_call,derived")
    results, failures = [], []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        common.ROWS.clear()
        err = None
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run()
        except Exception as e:  # noqa: BLE001
            err = repr(e)
            failures.append((name, err))
            print(f"{name}.FAILED,0.0,{err}", file=sys.stderr)
        seconds = time.time() - t0
        results.append({"module": name,
                        "status": "failed" if err else "ok",
                        "seconds": round(seconds, 2),
                        "error": err,
                        "rows": list(common.ROWS)})
        print(f"# {name} took {seconds:.1f}s", file=sys.stderr)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"ok": not failures, "results": results}, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
        for result in results:
            path = TRAJECTORIES.get(result["module"])
            if path:
                _append_trajectory(path, result)

    if failures:
        print(f"benchmark failures: {failures}", file=sys.stderr)
        sys.exit(1)


def _append_trajectory(path: str, result: dict) -> None:
    """Append a module's rows to its repo-root trajectory artifact
    (a list of timestamped records — one per `--json` run)."""
    trajectory: list = []
    try:
        with open(path) as f:
            trajectory = json.load(f)
        if not isinstance(trajectory, list):
            trajectory = []
    except (FileNotFoundError, json.JSONDecodeError):
        pass
    trajectory.append({
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "status": result["status"],
        "seconds": result["seconds"],
        "rows": result["rows"],
    })
    with open(path, "w") as f:
        json.dump(trajectory, f, indent=2)
    print(f"# appended {result['module']} rows to {path} "
          f"({len(trajectory)} records)", file=sys.stderr)


if __name__ == "__main__":
    main()
