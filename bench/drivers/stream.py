"""Driver of the streaming ingest path (`repro.fleet.stream`).

Set-up builds a `FleetEngine` from the configuration, makes a pool of
distinct density chunks on the device from the seed (package i runs the
workload kind ``KINDS[i % 4]``), fetches them to the host once, and warms
the flush program on a throw-away fleet state.  Each chunk stays in the
host buffer that the runtime's device-to-host copy filled: on a TPU v5e a
buffer the CPU wrote instead (a page-aligned mapping of its own) uploaded
at 8.4-9.3 GB/s and spread more, against 10 GB/s (PERF.md).  Last,
each chunk is uploaded once alone, blocking, for the notes.

The window is one `stream()` call over the pool, cycled, from a fresh
fleet state: every flush uploads a host chunk through the program's
`HintQueue`/`put_trace` ingest, runs one `run_block` and fetches the flush
telemetry in one host sync.  A flush's latency runs from the engine taking
its chunk (the `run_block` call) to its telemetry record on the host.  The
source stops handing out chunks once the window's seconds are over; every
flush handed out counts.

The notes: ``[flush]``, the flushes' latency p50, p95 and max, and p50/p95
by pool chunk (flush index mod ``pool_chunks``); ``[ingest]``, the time of
each chunk's lone upload in set-up and its GB/s; ``[pool]``, each chunk's
buffer: its offset in its page and its huge pages against its resident
size.  ``bench/run.py`` prints the ``[host]`` line:
the CPUs the process may run on, their nodes and the chip's.

The check replays the window from the same fresh state through the plain
reference and compares the fleet telemetry of sampled flushes (the first,
the last and one drawn from the seed): the continuous statistics, the
event counts and the fleet's membership.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench.harness import (SEED_SPAN, TELEMETRY_FIELDS, Phases, quantile,
                           rel_err, sampled)

EVENT_COUNTS = ("events_total", "events_step")


def upload_ms(chunk) -> float:
    """Host-clock time of one blocking upload of a host chunk, in ms."""
    import jax
    t = time.perf_counter()
    jax.device_put(chunk).block_until_ready()
    return (time.perf_counter() - t) * 1e3


class _TimedEngine:
    """The engine as `stream()` sees it, noting when each flush's chunk is
    handed to `run_block` and opening that flush's trace span."""

    def __init__(self, engine, log: list, span: str):
        self.engine, self.log, self.span = engine, log, span
        self.backend_impl = engine.backend_impl
        self.open = None

    def run_block(self, state, chunk, active=None):
        import jax
        self.log.append(time.perf_counter())
        self.open = jax.profiler.TraceAnnotation(self.span)
        self.open.__enter__()
        return self.engine.run_block(state, chunk, active=active)

    def close(self) -> None:
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


class Driver:
    unit_span = "bench.flush"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic = config, traffic
        self.seed = int(seed) % SEED_SPAN
        self.devices = devices
        self.n = int(config["fleet_packages"])
        self.steps = int(config["flush_every"])
        self.attempted = self.failed = self.n_units = 0

    def make_pool(self) -> None:
        """The traffic's distinct [steps, n, tiles] chunks, made on the
        device from the seed and fetched to the host once, each into the
        host buffer the runtime's device-to-host copy fills."""
        import jax

        from bench.reference.workload import fleet_chunk
        make = jax.jit(fleet_chunk, static_argnums=(1, 2, 3))
        key = jax.random.PRNGKey(self.seed)
        tiles = self.cfg["scheduler"]["n_tiles"]
        self.pool = [np.asarray(make(jax.random.fold_in(key, j), self.steps,
                                     self.n, tiles))
                     for j in range(self.traffic["pool_chunks"])]

    def setup(self) -> None:
        from repro.core.scheduler import SchedulerConfig
        from repro.fleet import FleetEngine, stream
        c = self.cfg
        ph = self.phases = Phases()
        self.engine = FleetEngine(SchedulerConfig(**c["scheduler"]),
                                  backend=c["backend"])
        self.make_pool()
        ph.mark("pool")
        warm = self.engine.init(self.n)
        warm, _, _ = stream(self.engine, warm, iter(self.pool[:2]))
        del warm
        ph.mark("warm")
        self.state = self.engine.init(self.n)
        self.state.freq.block_until_ready()
        ph.mark("init")
        self.upload_ms = [upload_ms(c) for c in self.pool]
        ph.mark("uploads")

    def run(self, seconds: float) -> None:
        from repro.fleet import stream
        pool = self.pool
        t0 = time.perf_counter()
        deadline = t0 + seconds
        handed, done = [], []
        eng = _TimedEngine(self.engine, handed, self.unit_span)

        def source():
            i = 0
            while time.perf_counter() < deadline:
                yield pool[i % len(pool)]
                i += 1

        def on_flush(i, d):
            done.append(time.perf_counter())
            eng.close()

        self.state, self.flushed, self.stats = stream(
            eng, self.state, source(), on_flush=on_flush)
        self.t0, self.t1 = t0, done[-1]
        self.lat_ms = [(b - a) * 1e3 for a, b in zip(handed, done)]
        self.attempted = len(self.flushed)
        self.n_units = len(self.flushed)

    def end_to_end(self) -> dict:
        steps = self.stats.steps * self.n
        return {"pkg_steps_per_s": steps / (self.t1 - self.t0),
                "flush_p95_ms": quantile(self.lat_ms, 95)}

    def notes(self) -> list[str]:
        from bench.placement import buffer_pages
        lat = self.lat_ms
        k = len(self.pool)
        per = "; ".join(
            f"{j}: {quantile(lat[j::k], 50):.2f}/{quantile(lat[j::k], 95):.2f}"
            for j in range(k))
        mb = self.pool[0].nbytes / 1e6
        ups = ", ".join(f"{t:.2f}" for t in self.upload_ms)
        pages = "; ".join(
            f"{j}: offset {p['offset']} huge {p['huge_mb']:.0f}/"
            f"{p['rss_mb']:.0f} MB"
            for j, p in enumerate(map(buffer_pages, self.pool)))
        return [
            self.phases.line(),
            f"[flush] {len(lat)} flushes of {self.n} packages x "
            f"{self.steps} steps x {self.cfg['scheduler']['n_tiles']} tiles; "
            f"latency p50 {quantile(lat, 50):.2f} ms p95 "
            f"{quantile(lat, 95):.2f} ms max {max(lat):.2f} ms; "
            f"by pool chunk p50/p95 ms {per}; "
            f"{self.stats.host_syncs} host syncs",
            f"[ingest] one {mb:.1f} MB chunk uploaded alone in set-up: "
            f"{ups} ms ({mb / quantile(self.upload_ms, 50):.3f} GB/s median)",
            f"[pool] {pages}",
        ]

    def trace_context(self) -> dict:
        from bench.kernel_bytes import flush_bytes
        return {"unit_span": self.unit_span, "unit_bytes": flush_bytes(
            self.cfg["scheduler"], self.steps, self.n),
            "chunk_bytes": 4 * self.steps * self.n
            * self.cfg["scheduler"]["n_tiles"]}

    def release(self) -> None:
        del self.state, self.engine

    # -------------------------------------------------------------- check
    def reference(self, n_flushes: int, sample: list[int], dtype=None):
        """Telemetry of the sampled flushes of the plain reference over the
        window's flushes."""
        import jax
        import jax.numpy as jnp

        from bench.reference.fleet import FleetRef
        ref = FleetRef(self.cfg["scheduler"], dtype=dtype or jnp.float32)
        st = ref.init(self.n)
        pool = [jnp.asarray(c) for c in self.pool]
        telem = {}
        for f in range(n_flushes):
            chunk = pool[f % len(pool)]
            if f in sample:
                st, t, _ = ref.window(st, chunk)
                telem[f] = {k: float(v)
                            for k, v in jax.device_get(t).items()}
            else:
                st = ref.advance(st, chunk)
        return telem

    def control(self, n_flushes: int, dtype=None) -> dict:
        """The compared numbers when the reference computed in ``dtype``
        (bfloat16 by default) stands in the program's place."""
        import jax.numpy as jnp
        if not hasattr(self, "pool"):
            self.make_pool()
        sample = sampled(self.seed, n_flushes)
        return compare(self.reference(n_flushes, sample,
                                      dtype=dtype or jnp.bfloat16),
                       self.reference(n_flushes, sample))

    def check(self) -> dict:
        n_flushes = len(self.flushed)
        sample = sampled(self.seed, n_flushes)
        return compare({f: self.flushed[f] for f in sample},
                       self.reference(n_flushes, sample))


def compare(prog: dict, ref: dict) -> dict:
    """Over the sampled flushes: ``telemetry_err``, the worst
    |prog - ref| / max(|ref|, 1) of the continuous fleet telemetry;
    ``events_err``, the same of the fleet's event counts (cumulative and
    within the flush); ``members_err``, the worst absolute gap of the
    fleet's package count, which is exact.

    Per-package states are not compared: under the 47-tile coupled law a
    rounding difference grows about tenfold every three steps, so two
    correct implementations part per package within a flush and agree only
    in the fleet's statistics, event totals among them."""
    if set(prog) != set(ref) or not ref:
        return {"telemetry_err": float("inf"), "events_err": float("inf"),
                "members_err": float("inf")}
    worst = lambda keys: max(rel_err(prog[f][k], ref[f][k])
                             for f in ref for k in keys)
    gaps = [abs(float(prog[f]["n_packages"]) - float(ref[f]["n_packages"]))
            for f in ref]
    return {"telemetry_err": worst(TELEMETRY_FIELDS),
            "events_err": worst(EVENT_COUNTS),
            "members_err": max(g if math.isfinite(g) else math.inf
                               for g in gaps)}
