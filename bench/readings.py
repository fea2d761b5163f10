#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set, on the chip, in
one process: the program's compared numbers over many seeds (the lower
readings) and the control's over a few (the upper readings).

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...] [--control-units k]

The control is the plain reference computed in bfloat16 put in the
program's place, at the cell's own size, compared exactly as a run compares
the program.  ``--control-units`` is how many flushes or experiments the
control covers; by default the median count of the program runs.  One JSON
line per reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-units", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from bench import harness
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 1
    bm = harness.load_benchmark()
    cell, entry = harness.find_cell(bm, args.workload)
    config = harness.load_config(entry)
    traffic = harness.load_traffic(cell["traffic"])
    cls = harness.driver_class(traffic)
    units = []
    for seed in args.seeds:
        drv = cls(config, traffic, seed, devices[:cell["chips"]])
        t = time.perf_counter()
        drv.setup()
        drv.run(args.seconds)
        drv.release()
        units.append(drv.n_units)
        out = {"kind": "program", "seed": seed, "units": drv.n_units,
               "numbers": drv.check(),
               "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(out), flush=True)
    k = args.control_units or int(statistics.median(units or [1]))
    for seed in args.control_seeds:
        drv = cls(config, traffic, seed, devices[:cell["chips"]])
        t = time.perf_counter()
        out = {"kind": "control_bfloat16", "seed": seed, "units": k,
               "numbers": drv.control(k, jnp.bfloat16),
               "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
