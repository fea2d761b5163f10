"""Helpers of the benchmark's tests: run a cell's driver on the CPU at a
tiny size through the harness, with an optional fault planted."""
from __future__ import annotations

import copy
import json
import time

TINY = {
    "serve_synth_4k": lambda c: (c.update(fleet_packages=16),
                                 c["service"].update(min_capacity=16)),
    "stream_pvc47_aurora": lambda c: c.update(fleet_packages=16),
    "mc_sec10_2k": lambda c: c["montecarlo"].update(
        n_trials=64, n_steps=1100, burn_in=100),
}


def tiny(cell_name: str):
    """(benchmark, cell, tiny config, traffic) of a cell."""
    from bench import harness
    bm = harness.load_benchmark()
    cell, entry = harness.find_cell(bm, cell_name)
    cfg = copy.deepcopy(harness.load_config(entry))
    TINY[cell_name](cfg)
    return bm, cell, cfg, harness.load_traffic(cell["traffic"])


def run(cell_name: str, seconds: float = 0.6, seed: int = 2 ** 31 + 7,
        emit: bool = False, trace: bool = False) -> dict:
    import jax

    from bench import harness
    bm, cell, cfg, _ = tiny(cell_name)
    return harness.run_cell(bm, cell, cfg, seed, seconds, trace,
                            time.perf_counter(), jax.devices(),
                            require_tpu=False, emit=emit)


def control(cell_name: str, units: int, seed: int = 2 ** 31 + 9) -> dict:
    """The control's compared numbers (the bfloat16 reference in the
    program's place) and the configuration's limits."""
    import jax

    from bench import harness
    _, _, cfg, traffic = tiny(cell_name)
    drv = harness.driver_class(traffic)(cfg, traffic, seed, jax.devices())
    return drv.control(units), cfg["limits"]


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
