#!/usr/bin/env python
"""Smoke run of the fleet's main path on a TPU, compiled, through its
public entry points.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: sharded_fused vs fused

One process, seeded, nothing but this checkout.  Phases on one chip:

  (a) the resident control plane in the ``serve.py --serve`` configuration
      (v24, one tile, ``mixed_mode``) with ``degraded_fallback`` on the
      ``fused`` backend: warm up, attach 4,096 packages, serve HTTP on an
      ephemeral port, tick 8 flushes of 50 steps while answering real
      requests (healthz, telemetry, fleet, attach, canary, detach), and
      count XLA compiles after warmup (there must be none);
  (b) the same seeded service and request schedule on the pure-JAX
      ``broadcast`` scan, on the same chip: the final flush telemetry must
      match (a);
  (c) the paper's §10 Monte Carlo (2,000 trials x 3,000 steps) on
      ``fused`` against ``broadcast``.

``--four-chips`` runs only ``sharded_fused`` at 16,384 packages over a
4-device mesh through `repro.fleet.stream`, against the single-device
``fused`` run of the same fleet and trace on device 0.

Comparisons use the repo's rules: error |a - b| / max(|a|, 1) at most 1e-5,
order and threshold statistics (``freq_min``, ``at_risk_frac``, the
uplift percentiles) within 1e-3, event and degraded counts exact.  Times
printed are set-up (compilation included) or informational, not benchmark
figures.  Any failure exits nonzero before the last line; on success the
last line is ``{"ok": true, "device": {...}}``.  With no TPU the script
exits nonzero before any phase.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
N_PACKAGES = 4096           # one datacenter row of packages
FLUSH_EVERY = 50            # 50 ms at the 1 kHz telemetry rate (look-ahead)
FLUSHES = 8
MC_TRIALS, MC_STEPS = 2000, 3000
FOUR_CHIP_PACKAGES = 16384
CANARY_FRAC = 0.25

TOL, KNIFE_TOL = 1e-5, 1e-3
EXACT = ("events_total", "events_step", "degraded_count", "n_packages")
KNIFE_EDGE = ("freq_min", "at_risk_frac", "uplift_p5", "uplift_p95")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def compare(ref: dict, got: dict, label: str) -> float:
    """Worst tolerance-scaled error of ``got`` against ``ref`` (≤ 1 passes);
    raises on any field out of tolerance or any inexact count."""
    worst, worst_field = 0.0, None
    for k, a in ref.items():
        b = got[k]
        if k in EXACT:
            check(a == b, f"{label}: {k} {b} != reference {a}")
            continue
        err = abs(float(a) - float(b)) / max(abs(float(a)), 1.0)
        tol = KNIFE_TOL if k in KNIFE_EDGE else TOL
        check(err <= tol, f"{label}: {k} {b} vs reference {a} "
                          f"(error {err:.3e} > {tol:g})")
        if err / tol > worst:
            worst, worst_field = err / tol, f"{k} err={err:.3e} (tol {tol:g})"
    print(f"[{label}] matches its reference; worst field: "
          f"{worst_field or 'all bitwise equal'}")
    return worst


def compile_counter():
    """Counts XLA backend compiles while ``counting[0]`` is set."""
    import jax
    compiles, counting = [], [False]

    def on_event(event, duration, **kw):
        if counting[0] and "backend_compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return compiles, counting


def assert_kernel(eng, state, steps: int, label: str) -> None:
    """The engine's backend resolved the Pallas kernel to compiled mode,
    and the program it runs for a ``steps``-long chunk holds the kernel."""
    import jax
    desc = eng.backend_impl.describe()
    check(desc.endswith(",compiled]"),
          f"{label}: kernel resolved to interpret mode ({desc})")
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    chunk = jax.ShapeDtypeStruct((steps,) + state.freq.shape, "float32")
    text = jax.jit(eng.block_traces).lower(shapes, chunk).as_text()
    check("tpu_custom_call" in text,
          f"{label}: no tpu_custom_call in the compiled engine program")
    print(f"[{label}] {desc}: Pallas kernel compiled (tpu_custom_call "
          f"in the engine program)")


# ------------------------------------------------------------- phase (a/b)
class Client:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"
        self.requests = 0

    def call(self, path: str, body: dict | None = None) -> dict:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(self.base + path, data=data,
                                     headers={"Content-Type":
                                              "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            check(r.status == 200, f"{path} answered {r.status}")
            out = json.loads(r.read())
        check(isinstance(out, dict), f"{path} answered non-object JSON")
        self.requests += 1
        return out


def serve_phase(backend: str, label: str, n_packages: int = N_PACKAGES,
                flushes: int = FLUSHES, flush_every: int = FLUSH_EVERY,
                counter=None) -> dict:
    """Drive the resident control plane; returns the final flush
    telemetry.  The request schedule is fixed, so two backends see the
    same fleet, the same membership changes and the same chunks."""
    import dataclasses

    from repro.core.scheduler import SchedulerConfig
    from repro.core.workload import KINDS
    from repro.fleet.service import FleetService, serve_http

    cfg = SchedulerConfig(n_tiles=1, mode="v24", step_ms=5.0)
    cfg = dataclasses.replace(cfg, mixed_mode=True, degraded_fallback=True)
    svc = FleetService(cfg, backend=backend, min_capacity=4,
                       flush_every=flush_every, seed=SEED)
    t0 = time.perf_counter()
    buckets = svc.warmup(max_packages=2 * n_packages)
    print(f"[{label}] {svc.engine.backend_impl.describe()}: warmed "
          f"{buckets} capacity buckets in {time.perf_counter() - t0:.1f} s "
          f"(set-up, compilation included)")
    if backend == "fused":
        assert_kernel(svc.engine, svc.state, flush_every, label)

    compiles, counting = counter
    compiles.clear()
    counting[0] = True
    server = None
    try:
        t0 = time.perf_counter()
        for i in range(n_packages):
            svc.attach(f"pkg{i}", tenant=f"tenant{i % 4}",
                       kind=KINDS[i % len(KINDS)])
        server, _ = serve_http(svc, port=0)
        http = Client(server.server_address[1])
        print(f"[{label}] attached {n_packages} packages in "
              f"{time.perf_counter() - t0:.1f} s (informational); HTTP on "
              f"port {server.server_address[1]}")
        schedule = {
            2: [("/healthz", None), ("/telemetry?last=3", None),
                ("/fleet", None)],
            3: [("/attach", {"package": "extra0", "tenant": "tenant1",
                             "kind": "vision"})],
            4: [("/canary", {"reactive_frac": CANARY_FRAC})],
            6: [("/detach", {"package": "pkg17"})],
        }
        rec = None
        for f in range(flushes):
            for path, body in schedule.get(f, []):
                out = http.call(path, body)
                if path == "/healthz":
                    check(out["ok"] and out["n_active"] == n_packages,
                          f"/healthz {out}")
                elif path.startswith("/telemetry"):
                    check(len(out["records"]) == min(3, f),
                          f"/telemetry returned {len(out['records'])} rows")
                elif path == "/fleet":
                    check(len(out["packages"]) == n_packages,
                          "/fleet lists the wrong membership")
                elif path == "/canary":
                    check(out["pinned_reactive"] > 0, f"/canary {out}")
            t1 = time.perf_counter()
            rec = svc.tick()
            d = rec["telemetry"]
            print(f"[{label}] flush {rec['flush']}: n={d['n_packages']} "
                  f"cap={rec['capacity']} p99 {d['temp_p99_c']:.3f}C "
                  f"f_mean {d['freq_mean']:.5f} events "
                  f"{int(d['events_total'])} degraded "
                  f"{d['degraded_count']} "
                  f"({(time.perf_counter() - t1) * 1e3:.0f} ms host, "
                  f"informational)")
        out = http.call("/telemetry?last=3")
        check([r["flush"] for r in out["records"]]
              == list(range(flushes - 3, flushes)),
              "/telemetry?last=3 returned the wrong flushes")
        check(out["n_active"] == n_packages, "membership drifted")
    finally:
        counting[0] = False
        if server is not None:
            server.shutdown()
            server.server_close()
    print(f"[{label}] {http.requests} HTTP requests answered 200 with JSON")
    check(not compiles, f"{label}: {len(compiles)} XLA compiles after "
                        f"warmup")
    print(f"[{label}] 0 XLA compiles after warmup (attaches, HTTP attach/"
          f"canary/detach, {flushes} flushes)")
    return rec["telemetry"]


# -------------------------------------------------------------- phase (c)
def montecarlo_phase(backend: str, label: str, n_trials: int = MC_TRIALS,
                     n_steps: int = MC_STEPS) -> dict:
    import jax

    from repro.core import montecarlo

    t0 = time.perf_counter()
    r = montecarlo.run(key=jax.random.PRNGKey(SEED), n_trials=n_trials,
                       n_steps=n_steps, backend=backend)
    s = r.stats()
    print(f"[{label}] {n_trials} trials x {n_steps} steps on {backend} in "
          f"{time.perf_counter() - t0:.1f} s (compilation included, "
          f"informational): baseline peak {s['baseline_mean_c']:.3f}C "
          f"sigma {s['baseline_std_c']:.3f}C, v24 peak "
          f"{s['v24_mean_c']:.3f}C sigma {s['v24_std_c']:.3f}C, uplift "
          f"{s['uplift_mean'] * 100:.2f}%")
    return s


def montecarlo_kernels(n_trials: int = MC_TRIALS) -> None:
    """Both fleets `montecarlo.run` drives on ``fused`` (the
    reactive-polling baseline and v24) run the compiled kernel."""
    from repro.core import montecarlo
    for mode in ("reactive_poll", "v24"):
        eng = montecarlo.engine(n_trials, mode, backend="fused")
        state = eng.init(n_trials // eng.sched.cfg.n_tiles)
        assert_kernel(eng, state, 1024, f"c: {mode}")


# ------------------------------------------------------------ four chips
def stream_phase(backend: str, label: str, trace, pins, devices=None,
                 flush_every: int = FLUSH_EVERY) -> tuple[dict, dict]:
    """Plain `FleetEngine` + `stream()` over ``trace``; returns (final
    flush telemetry, final per-lane counters)."""
    import jax
    import numpy as np

    from repro.core.scheduler import SchedulerConfig
    from repro.fleet import FleetEngine, chunk_source, stream

    cfg = SchedulerConfig(n_tiles=1, mode="v24", step_ms=5.0,
                          mixed_mode=True, degraded_fallback=True)
    n = trace.shape[1]
    with warnings.catch_warnings():
        # a mesh that shrinks below the requested devices is a failure here
        warnings.simplefilter("error", RuntimeWarning)
        eng = FleetEngine(cfg, backend=backend, devices=devices)
        state = eng.init(n)
    desc = eng.backend_impl.describe()
    placed = {s.device for s in state.freq.addressable_shards}
    if devices:
        check(f"[{devices}dev," in desc, f"{label}: mesh is {desc}")
        check(len(placed) == devices,
              f"{label}: state sits on {len(placed)} devices")
    else:
        check(placed == {jax.devices()[0]},
              f"{label}: state is not on device 0 ({placed})")
    state = state._replace(ctrl_mode=jax.device_put(
        pins, state.ctrl_mode.sharding))
    assert_kernel(eng, state, flush_every, label)
    times = [time.perf_counter()]
    state, flushed, stats = stream(
        eng, state, chunk_source(trace, flush_every),
        on_flush=lambda i, d: times.append(time.perf_counter()))
    check(stats.host_syncs == stats.flushes, f"{label}: extra host syncs")
    steady = np.diff(times[1:]) * 1e3
    print(f"[{label}] {desc} on {len(placed)} device(s): {stats.flushes} "
          f"flushes x {flush_every} steps x {n} packages; first flush "
          f"{(times[1] - times[0]):.1f} s (set-up, compilation included), "
          f"later flushes {np.median(steady):.1f} ms median host "
          f"(informational)")
    counters = {"events": np.asarray(state.events).tolist(),
                "degraded": np.asarray(state.degraded).tolist()}
    return flushed[-1], counters


def four_chip_trace(n: int, steps: int):
    """[steps, n, 1] seeded density trace, lanes cycling the workload
    kinds, synthesised on the device in one program per kind."""
    import jax
    import numpy as np

    from repro.core.workload import KINDS, make_trace

    keys = jax.random.split(jax.random.PRNGKey(SEED), n)
    out = np.empty((steps, n, 1), np.float32)
    for j, kind in enumerate(KINDS):
        gen = jax.jit(jax.vmap(lambda k: make_trace(k, steps, kind, 1)))
        out[:, j::len(KINDS), :] = np.moveaxis(
            np.asarray(gen(keys[j::len(KINDS)])), 0, 1)
    return out


# ------------------------------------------------------------------- main
def one_chip() -> None:
    counter = compile_counter()
    fused = serve_phase("fused", "a", counter=counter)
    ref = serve_phase("broadcast", "b", counter=counter)
    compare(ref, fused, "b: fused vs broadcast final flush")

    mc_fused = montecarlo_phase("fused", "c")
    montecarlo_kernels()
    mc_ref = montecarlo_phase("broadcast", "c")
    compare(mc_ref, mc_fused, "c: fused vs broadcast Monte Carlo stats")


def four_chips() -> None:
    import jax
    import numpy as np

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    n = FOUR_CHIP_PACKAGES
    t0 = time.perf_counter()
    trace = four_chip_trace(n, FLUSHES * FLUSH_EVERY)
    rng = np.random.default_rng(SEED)
    pins = rng.uniform(size=n) < CANARY_FRAC
    print(f"[4] trace [{trace.shape[0]}, {n}, 1] and {int(pins.sum())} "
          f"reactive pins made in {time.perf_counter() - t0:.1f} s "
          f"(set-up)")
    got, got_c = stream_phase("sharded_fused", "4: sharded_fused", trace,
                              pins, devices=4)
    ref, ref_c = stream_phase("fused", "4: fused on device 0", trace, pins)
    check(got_c == ref_c, "4: per-lane event/degraded counters differ")
    compare(ref, got, "4: sharded_fused vs fused final flush")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded_fused path and "
                         "its single-device fused reference")
    args = ap.parse_args(argv)
    if not (SRC / "repro" / "fleet").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    print(f"[setup] {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{cache}")
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
