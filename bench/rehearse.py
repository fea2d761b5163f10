#!/usr/bin/env python3
"""Compile each cell's flush programs at the cell's real sizes for a
described TPU v5e (no chip needed) and print the compiler's memory
analysis, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

The Pallas kernel is compiled for the chip (not interpret mode).  What the
compiler refuses here would fail on the chip; the sizes printed say
whether a cell's programs fit one chip's 16 GB.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _shapes(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _report(label: str, lowered) -> None:
    t = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gb = lambda b: f"{b / 1e9:.3f} GB"
    print(f"[{label}] compiled in {time.perf_counter() - t:.1f} s; "
          f"kernel {'present' if 'tpu_custom_call' in text else 'ABSENT'}; "
          f"args {gb(m.argument_size_in_bytes)} out "
          f"{gb(m.output_size_in_bytes)} temp {gb(m.temp_size_in_bytes)}",
          flush=True)


def _compiled_kernel(engine) -> None:
    """The fused backends pick interpret mode off the chip: compile the
    chip's kernel instead."""
    engine.backend_impl.interpret = False


def serve(cfg, one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core.scheduler import SchedulerConfig
    from repro.fleet.service import FleetService
    svc = FleetService(SchedulerConfig(**cfg["scheduler"]),
                       backend=cfg["backend"], **cfg["service"])
    _compiled_kernel(svc.engine)
    cap = svc.registry.capacity
    tiles = cfg["scheduler"]["n_tiles"]
    k = cfg["service"]["flush_every"]
    st = _shapes(svc.state, one_chip)
    chunk = jax.ShapeDtypeStruct((k, cap, tiles), jnp.float32,
                                 sharding=one_chip)
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)
    th = _shapes({k2: jnp.asarray(v) for k2, v in
                  svc.registry.threshold_arrays().items()}, one_chip)
    _report(f"serve flush cap={cap}",
            jax.jit(svc._flush_impl).lower(st, chunk, mask, ids, th))


def stream(cfg, one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core.scheduler import SchedulerConfig
    from repro.fleet import FleetEngine
    eng = FleetEngine(SchedulerConfig(**cfg["scheduler"]),
                      backend=cfg["backend"])
    _compiled_kernel(eng)
    n, tiles = cfg["fleet_packages"], cfg["scheduler"]["n_tiles"]
    st = _shapes(jax.eval_shape(lambda: eng.init(n)), one_chip)
    chunk = jax.ShapeDtypeStruct((cfg["flush_every"], n, tiles), jnp.float32,
                                 sharding=one_chip)
    _report(f"stream run_block n={n} tiles={tiles}",
            jax.jit(eng._run_block_impl).lower(st, chunk))


def montecarlo(cfg, one_chip):
    import inspect

    import jax
    import jax.numpy as jnp

    from repro.core import montecarlo as mc
    c = cfg["montecarlo"]
    for mode in ("reactive_poll", "v24"):
        eng = mc.engine(c["n_trials"], mode, backend=cfg["backend"])
        _compiled_kernel(eng)
        # the program's own call plan: trials packed onto the tile axis,
        # the survey advanced in blocks of run_survey's default length
        pack = eng.sched.cfg.n_tiles
        block = inspect.signature(eng.run_survey).parameters["chunk"].default
        n_pkg = c["n_trials"] // pack
        pkg = eng.sched.package_params(batch_shape=(n_pkg,))
        st = _shapes(jax.eval_shape(lambda: eng.init(n_pkg, pkg=pkg)),
                     one_chip)
        acc = tuple(jax.ShapeDtypeStruct(st.freq.shape, jnp.float32,
                                         sharding=one_chip)
                    for _ in range(4))
        for t in sorted({min(block, c["n_steps"] - s)
                         for s in range(0, c["n_steps"], block)}):
            chunk = jax.ShapeDtypeStruct((t, n_pkg, pack), jnp.float32,
                                         sharding=one_chip)
            counted = jax.ShapeDtypeStruct((t,), jnp.bool_, sharding=one_chip)
            _report(f"montecarlo {mode} survey block T={t}",
                    jax.jit(eng._survey_block_impl).lower(st, chunk, counted,
                                                          acc))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bm = harness.load_benchmark()
    wanted = set(argv if argv is not None else sys.argv[1:])
    for cell in bm["workloads"]:
        if wanted and cell["name"] not in wanted:
            continue
        if cell["chips"] != 1:
            print(f"[{cell['name']}] {cell['chips']} chips: not rehearsed "
                  f"here")
            continue
        _, entry = harness.find_cell(bm, cell["name"])
        cfg = harness.load_config(entry)
        driver = harness.load_traffic(cell["traffic"])["driver"]
        print(f"== {cell['name']} ({cfg['name']}, driver {driver})",
              flush=True)
        {"serve": serve, "stream": stream,
         "montecarlo": montecarlo}[driver](cfg, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
