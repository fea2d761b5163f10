"""The benchmark's harness: finds a cell's configuration, traffic mix,
driver and per-layer metric readers by the names in ``BENCHMARK.json``,
runs one measured window, checks the window's results against the plain
reference, and prints the result line.

Layout (a new cell, mix or metric is new files plus new entries):

  bench/configs/<config>.json     deployment: scheduler fields, fleet size,
                                  backend, limits of the correctness check
  bench/kernel_bytes.py           least bytes of a unit of work (a flush,
                                  an experiment), for the kernel roofline
  bench/traffic/<traffic>.json    a traffic mix: parameters read by the
                                  driver its ``driver`` key names
  bench/drivers/<driver>.py       a general generator and window driver
  bench/metrics/<metric>.py       a per-layer metric reader (``read``)
  bench/reference/                the plain references
  bench/peaks.json                device peaks, keyed by device_kind
  bench/testdata/                 reduced traces of traced chip runs, kept
                                  with BENCH_KEEP_TRACE and cut with
                                  `Trace.crop`, for the reducers' tests
"""
from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_out" / "trace"


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown device, ...)."""


# ------------------------------------------------------------ discovery
def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path=None) -> dict:
    return load_json(path or ROOT / "BENCHMARK.json")


def find_cell(bm: dict, name: str) -> tuple[dict, dict]:
    """(workload entry, configuration entry) for the cell ``name``."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; cells: "
                         f"{', '.join(sorted(cells))}")
    cell = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    return cell, configs[cell["config"]]


def load_config(entry: dict) -> dict:
    return load_json(ROOT / entry["file"])


def load_traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def driver_class(traffic: dict):
    return importlib.import_module(f"bench.drivers.{traffic['driver']}").Driver


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


def cell_metrics(bm: dict, cell: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({', '.join(sorted(table))})")
    return table[device_kind]


# -------------------------------------------------------------- numbers
def quantile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default definition)."""
    v = sorted(float(x) for x in values)
    if not v:
        return math.nan
    pos = q / 100.0 * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def rel_err(a: float, b: float) -> float:
    """|a - b| / max(|b|, 1): relative above 1, absolute below; infinite
    when either side is not finite, so that a NaN never passes a check."""
    e = abs(float(a) - float(b)) / max(abs(float(b)), 1.0)
    return e if math.isfinite(e) else math.inf


# The continuous fleet-telemetry fields of a flush record that the fleet
# cells compare with the plain reference.
TELEMETRY_FIELDS = ("temp_p50_c", "temp_p99_c", "temp_max_c", "temp_var_c2",
                    "freq_mean", "freq_min", "released_mtps",
                    "throttled_mtps", "at_risk_frac")

SEED_SPAN = 2 ** 31     # --seed is taken modulo this before it keys anything


def sampled(seed: int, n: int) -> list[int]:
    """The units (flushes, experiments) of a window of ``n`` that a check
    replays: the first, the last and one drawn from the seed."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return sorted({0, n - 1, int(rng.integers(n))})


class CompileCounter:
    """Counts XLA backend compiles while ``on`` is set."""

    def __init__(self):
        import jax
        self.count, self.on = 0, False

        def listener(event, duration, **kw):
            if self.on and "backend_compile" in event:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Phases:
    """Host-clock durations of named set-up phases, for the notes."""

    def __init__(self):
        self.t, self.done = time.perf_counter(), []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.done.append((name, now - self.t))
        self.t = now

    def line(self) -> str:
        return "[setup] phases: " + ", ".join(f"{n} {s:.2f} s"
                                             for n, s in self.done)


def say(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------------ run
def run_cell(bm: dict, cell: dict, config: dict, seed: int, seconds: float,
             trace: bool, t_process: float, devices, *,
             require_tpu: bool = True, emit: bool = True) -> dict:
    """Set up, measure one window, check, and return the result dict
    (printed as the last stdout line when ``emit``).  ``require_tpu=False``
    lets the tests drive the harness on the CPU."""
    import jax
    if require_tpu and (not devices or devices[0].platform != "tpu"):
        raise BenchError("no TPU found")
    if len(devices) < cell["chips"]:
        raise BenchError(f"cell {cell['name']} needs {cell['chips']} "
                         f"chips, JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    peak = peaks(kind) if require_tpu else None
    traffic = load_traffic(cell["traffic"])
    drv = driver_class(traffic)(config, traffic, seed, devices)

    drv.setup()
    counter = CompileCounter()
    tdir = str(TRACE_DIR / cell["name"])
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1       # annotations and dispatches only
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.run(seconds)
    t1 = time.perf_counter()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    say(f"[window] {t1 - t0:.3f} s measured; {counter.count} XLA compiles "
        f"inside the window")
    for line in drv.notes():
        say(line)
    dev = device_info(devices)

    breakdown = None
    if trace:
        tr = _read_trace(tdir)
        win = tr.window()
        if win is None:
            raise BenchError("the trace holds no bench.window span")
        busy = tr.mean_busy_ns(win.start, win.end)
        dev["busy_s"] = busy * 1e-9
        dev["window_s"] = win.dur * 1e-9
        ctx = drv.trace_context()
        ctx.update(peaks=peak, config=config, traffic=traffic)
        metrics = {}
        for m in cell_metrics(bm, cell, "per_layer"):
            v = metric_reader(m["name"])(tr, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = tr.breakdown(win.start, win.end)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bm, cell, "end_to_end")}
    say(f"[setup] setup_s {setup_s:.3f}")

    drv.release()
    checks = drv.check()
    limits = config["limits"]
    ok = True
    lines = []
    for name, value in checks.items():
        lim = limits[name]
        passed = value <= lim
        ok &= passed
        lines.append(f"[check] {name} {value:.6g} limit {lim:g} "
                     f"{'ok' if passed else 'FAIL'}")
    result = {"correct": bool(ok), "attempted": drv.attempted,
              "failed": drv.failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    if emit:
        for line in lines:
            print(line, file=sys.stderr, flush=True)
        say(json.dumps(result))
    return result


def _read_trace(tdir: str):
    """The window's trace; with ``BENCH_KEEP_TRACE=<dir>`` its reduced form
    (``trace.json.gz``) and a table of its device ops are kept there."""
    from bench.trace import Trace
    tr = Trace.from_dir(tdir)
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:
        os.makedirs(keep, exist_ok=True)
        tr.to_json(os.path.join(keep, "trace.json.gz"))
        per: dict = {}
        for o in tr.ops:
            n, t = per.get((o.name, o.category), (0, 0.0))
            per[(o.name, o.category)] = (n + 1, t + o.dur)
        with open(os.path.join(keep, "ops.txt"), "w") as f:
            for (name, cat), (n, t) in sorted(per.items(),
                                              key=lambda kv: -kv[1][1]):
                f.write(f"{t * 1e-6:12.3f} ms {n:7d}x {cat:28s} {name}\n")
    return tr
