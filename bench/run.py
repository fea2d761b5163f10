#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Cells, metrics and bounds are in
``BENCHMARK.json``; the layout of ``bench/`` is described in
``bench/harness.py``.  The last stdout line is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit).
The compared numbers are also the last lines on stderr.  With no TPU, or
fewer chips than the cell asks for, it exits nonzero and prints no result.

Before JAX starts, the process binds itself to the CPUs of the chip's NUMA
node where the host has several (`bench/placement.py`), so that every
thread JAX starts, and every host buffer they first touch, sits on the
chip's node whichever socket the OS started the process on.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro" / "fleet").is_dir():
        print("bench: no program under src/; run from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, placement
    try:
        bm = harness.load_benchmark()
        cell, entry = harness.find_cell(bm, args.workload)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    bound = placement.bind_to_chip_node(cell["chips"])

    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    try:
        config = harness.load_config(entry)
        harness.say(f"[setup] {len(devices)} x {devices[0].device_kind}; "
                    f"cell {cell['name']} ({cell['chips']} chip(s)); "
                    f"compile cache {cache}")
        harness.say(placement.placement_line(cell["chips"], bound))
        harness.run_cell(bm, cell, config, args.seed, args.seconds,
                         bool(args.trace), T_PROCESS, devices)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
