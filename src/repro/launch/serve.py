"""Batched serving driver with thermal-aware admission control.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 8 --prompt-len 64 --gen 32

Serving loop = prefill (batch of prompts) → decode steps with a KV/state
cache.  The V24 scheduler runs host-side between decode batches: its
pre-positioning hint throttles ADMISSION (batch size of the next wave)
instead of frequency — the serving-side analogue of Effect ①, keeping the
P99 token latency envelope smooth (paper §3.1 / §8.1).

``--fleet N`` (N > 1) switches on fleet mode: this host serves package 0
while the `FleetEngine` advances all N packages' schedulers in one jitted,
batched step per wave (each package sees the base density plus per-package
load jitter).  Admission still follows package 0's frequency; fleet-wide
telemetry (events, p50/p99 junction temp, released MTPS) is printed per
wave — the single-host stand-in for a datacenter-scale control plane.

``--fleet-backend`` picks the fleet execution strategy (``vmap`` /
``broadcast`` / ``sharded`` / ``fused`` / ``sharded_fused``);
``--fleet-devices`` caps the device-mesh backends' package-axis mesh
(0 = every visible device).  The resolved backend (including the ACTUAL
device count after any mesh fallback) is logged up front.  ``--stream``
replaces the wave loop with a control-plane soak: the whole
``waves × gen``-step density trace is driven through the streaming ingest
loop (`repro.fleet.ingest`) — double-buffered host→device uploads, bounded
look-ahead hint queue, telemetry reduced in-graph over each ``gen``-step
flush window and fetched with ONE host sync per flush.

``--distributed`` makes a ``--stream`` soak ONE HOST of a
`jax.distributed` group: launch the same command on every host with
``--coordinator host0:port --num-processes N --process-id 0..N-1`` and a
``--fleet`` that is the GLOBAL package count.  Each process feeds only its
own lane span through its own hint queue
(`repro.fleet.distributed_ingest`); telemetry is all-reduced in-graph and
printed by rank 0 (see docs/serving.md "Multi-host streaming").

``--montecarlo N`` runs the §10 process-variation population instead: N
heterogeneous trials (per-trial Rth/τ/η/polling draws in the fleet state)
paired baseline/V24 through the selected ``--fleet-backend``, reporting the
peak-temperature distributions, σ tightening and the §3.4 guard-band
margins derived from them.

``--serve`` starts the RESIDENT control plane (`repro.fleet.service`)
instead of the wave loop: a `FleetService` with ``--fleet`` packages
attached, warmed up across its capacity buckets, ticking one flush per
``--flush-every`` steps while the HTTP operator API (attach/detach/
thresholds/telemetry — see docs/serving.md) listens on ``--port``.  Runs
until POST /shutdown (or ``--serve-flushes`` flushes in scripted runs).

The wave loop itself always runs on a `FleetEngine` (n = ``--fleet``,
minimum 1): one batched jitted step advances every package's scheduler
between decode waves, and this host serves package 0.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_arch, reduced
from repro.configs.base import ShapeConfig
from repro.core.density import rho_v24
from repro.core.scheduler import SchedulerConfig
from repro.fleet import (FleetEngine, available_backends, chunk_source,
                         stream)
from repro.launch import steps as S
from repro.models import transformer as tf


def _node_pkg(eng, node: str, n: int):
    """Per-lane `PackageParams` rows for a non-base ``--node`` fleet (None
    keeps the homogeneous fast path)."""
    if node == "base":
        return None
    from repro.core.nodebank import fleet_package_params
    return fleet_package_params(eng.sched, [node] * n)


def _montecarlo(args):
    """--montecarlo N: §10 process-variation population through the fleet.

    Each trial is one lane of a heterogeneous fleet (per-trial Rth/τ/η/poll
    draws riding in the scheduler state) driven through the selected fleet
    backend; prints the §10 distribution statistics and the §3.4 guard-band
    margins derived from the measured σ ratio.
    """
    from repro.core import guardband, montecarlo
    t0 = time.time()
    r = montecarlo.run(n_trials=args.montecarlo, n_steps=args.mc_steps,
                       key=jax.random.PRNGKey(args.seed),
                       backend=args.fleet_backend,
                       devices=args.fleet_devices or None,
                       filtration_impl=args.filtration,
                       plant=args.plant)
    s = r.stats()
    dt = time.time() - t0
    print(f"[mc] {args.montecarlo} trials x {args.mc_steps} steps "
          f"(paired baseline+v24) on '{args.fleet_backend}' "
          f"plant '{args.plant}' in {dt:.1f} s "
          f"({args.montecarlo / dt:.0f} trials/s)")
    print(f"[mc] baseline peak-T {s['baseline_mean_c']:.1f}C "
          f"sigma {s['baseline_std_c']:.2f}C, exceedance "
          f"{s['baseline_time_above_frac'] * 100:.1f}%")
    print(f"[mc] v24      peak-T {s['v24_mean_c']:.1f}C "
          f"sigma {s['v24_std_c']:.2f}C, exceedance "
          f"{s['v24_time_above_frac'] * 100:.2f}%")
    print(f"[mc] sigma tightening {s['sigma_tighter_x']:.1f}x, uplift "
          f"{s['uplift_mean'] * 100:.1f}% "
          f"[p5 {s['uplift_p5'] * 100:.1f}%, p95 {s['uplift_p95'] * 100:.1f}%]")
    for g in guardband.from_montecarlo(s):
        print(f"[mc] guard-band {g.category}: {g.margin_before * 100:.0f}% "
              f"-> {g.margin_after * 100:.1f}% (-{g.reduction_pct:.1f}%)")
    return {"montecarlo": s, "trials_per_s": args.montecarlo / dt}


def _stream_soak(args, sched_cfg: SchedulerConfig, rho: float, key):
    """--stream: fleet control-plane soak through the streaming ingest loop.

    With ``--distributed`` this is ONE PROCESS of a `jax.distributed`
    group (the caller already ran `multihost.initialize`): the fleet size
    is GLOBAL, the full density trace is generated deterministically on
    every host (same seed → same trace) and sliced to this process's lane
    span, and each process streams only its own slab — telemetry comes
    back all-reduced and identical on every rank, so only rank 0 prints
    per-flush lines.
    """
    n = max(args.fleet, 1)
    eng = FleetEngine(sched_cfg, backend=args.fleet_backend,
                      devices=args.fleet_devices or None)
    steps = args.waves * args.gen
    t = np.linspace(0.0, np.pi, steps, dtype=np.float32)
    swell = rho * (0.85 + 0.3 * np.sin(t) ** 2)                # [T]
    jitter = 0.15 * np.asarray(jax.random.normal(
        jax.random.fold_in(key, 7777), (n, sched_cfg.n_tiles)))
    trace = np.clip(swell[:, None, None] + jitter, 0.9, 2.7
                    ).astype(np.float32)                       # [T, n, tiles]

    rank0 = jax.process_index() == 0

    def on_flush(i, d):
        if rank0:
            print(f"[stream] flush {i}: p50 {d['temp_p50_c']:.1f}C "
                  f"p99 {d['temp_p99_c']:.1f}C f_mean {d['freq_mean']:.3f} "
                  f"released {d['released_mtps']:.1f} MTPS "
                  f"events {int(d['events_total'])}")

    state = eng.init(n, pkg=_node_pkg(eng, args.node, n))
    # the mesh is resolved at init: log the ACTUAL device count so a soak
    # degraded by an indivisible fleet size can't masquerade as multi-device
    tag = (f"[stream p{jax.process_index()}/{jax.process_count()}]"
           if args.distributed else "[stream]")
    print(f"{tag} backend {eng.backend_impl.describe()} "
          f"({eng.backend_impl.n_devices()} device(s)), fleet {n}")
    t0 = time.time()
    if args.distributed:
        from repro.fleet import distributed_stream
        state, flushed, stats = distributed_stream(
            eng, state, chunk_source(trace, args.gen),
            global_chunks=True, on_flush=on_flush)
    else:
        state, flushed, stats = stream(eng, state,
                                       chunk_source(trace, args.gen),
                                       on_flush=on_flush)
    dt = time.time() - t0
    rate = stats.steps * n / max(dt, 1e-9)
    print(f"{tag} done: {stats.steps} steps x {n} pkgs "
          f"({eng.backend_impl.describe()}) in {dt*1e3:.0f} ms "
          f"({rate:.0f} pkg-steps/s), {stats.host_syncs} host syncs / "
          f"{stats.flushes} flushes (contract: 1/flush)")
    return {"stream": flushed, "host_syncs": stats.host_syncs,
            "flushes": stats.flushes, "pkg_steps_per_s": rate}


def _serve_resident(args, sched_cfg: SchedulerConfig):
    """--serve: the resident multi-tenant control plane (docs/serving.md).

    With ``--snapshot-dir`` the service journals every membership op and
    snapshots every ``--snapshot-every`` flushes; a SIGTERM (preemption)
    takes one final BLOCKING snapshot before exiting, so
    `FleetService.restore()` resumes the stream losslessly."""
    import dataclasses

    from repro.distributed.fault_tolerance import PreemptionGuard
    from repro.fleet.service import FleetService, serve_http
    # the resident plane always carries the per-lane controller pins so
    # operators can canary (`POST /canary` / `/mode`) without a restart;
    # unpinned lanes are bit-identical to a plain v24 fleet
    sched_cfg = dataclasses.replace(sched_cfg, mixed_mode=True)
    svc = FleetService(sched_cfg, backend=args.fleet_backend,
                       min_capacity=4, flush_every=args.flush_every,
                       seed=args.seed,
                       snapshot_dir=args.snapshot_dir or None,
                       snapshot_every=args.snapshot_every,
                       heartbeat_timeout_s=args.heartbeat_timeout)
    n0 = max(args.fleet, 1)
    buckets = svc.warmup(max_packages=max(2 * n0, 8))
    print(f"[serve] warmed {buckets} capacity buckets "
          f"(zero recompiles from here)")
    for i in range(n0):
        svc.attach(f"pkg{i}", tenant="default", kind="inference",
                   node=args.node)
    guard = PreemptionGuard()
    server, _ = serve_http(svc, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    print(f"[serve] control plane on http://{host}:{port} — "
          f"GET /healthz /telemetry /fleet /alerts /dashboard, "
          f"POST /attach /detach /thresholds /ingest /replay /shutdown "
          f"/canary /mode")
    flushes = 0
    try:
        while (not svc.shutting_down and not guard.should_exit
               and (args.serve_flushes == 0
                    or flushes < args.serve_flushes)):
            rec = svc.tick()
            flushes += 1
            if rec is None:
                time.sleep(0.05)       # empty fleet — idle until an attach
                continue
            d = rec["telemetry"]
            print(f"[serve] flush {rec['flush']}: n={d['n_packages']} "
                  f"cap={rec['capacity']} p99 {d['temp_p99_c']:.1f}C "
                  f"f_mean {d['freq_mean']:.3f} "
                  f"alerts {len(rec['alerts'])}")
    finally:
        if guard.should_exit and svc.snapshot_dir is not None:
            step = svc.save_snapshot(blocking=True)
            print(f"[serve] preempted: final snapshot at step {step} "
                  f"-> {svc.snapshot_dir}")
        guard.restore()
        server.shutdown()
    return {"flushes": flushes, "port": port,
            "capacity": svc.registry.capacity,
            "n_active": svc.registry.n_active,
            "preempted": guard.should_exit}


def _chaos_soak(args):
    """--chaos: the fault-injection soak (docs/serving.md, CI `chaos` job).

    Four phases, each gated — any failure exits nonzero:
      1. fleet-wide hint starvation: every lane falls back to reactive
         polling in-graph, then recovers with hysteresis;
      2. per-lane sensor faults (dropout + NaN/Inf corruption): contained
         in-band on all five backends, unaffected lanes bit-match a
         fault-free run, telemetry equivalent across backends;
      3. the service surface: `degraded` alert fires on the rising edge and
         clears on the falling edge, /healthz-visible degraded counts;
      4. mid-run SIGTERM → final snapshot → `FleetService.restore()`
         resumes ≤1e-5-equivalent to an uninterrupted oracle with zero
         XLA recompiles after restore's warmup.
    """
    import os
    import signal
    import tempfile

    from repro.distributed.fault_tolerance import PreemptionGuard
    from repro.fleet import FaultPlan, FleetEngine, available_backends
    from repro.fleet.faults import HintOutage, SensorFault
    from repro.fleet.service import FleetService

    failures: list[str] = []

    def check(ok, msg):
        print(f"[chaos] {'ok  ' if ok else 'FAIL'} {msg}")
        if not ok:
            failures.append(msg)

    cfg = SchedulerConfig(n_tiles=2, mode="v24", filtration_window=16,
                          degraded_fallback=True, stale_limit_steps=4,
                          recover_steps=8)
    n, T, K = 8, 384, 64
    rng = np.random.default_rng(args.seed)
    trace = rng.uniform(0.9, 2.7, (T, n, cfg.n_tiles)).astype(np.float32)

    # -- phase 1: hint starvation — engage + hysteresis recovery ----------
    starve = FaultPlan(seed=args.seed, hint_outages=(HintOutage(96, 24),))
    eng = FleetEngine(cfg, backend="broadcast", debug_nan=True)
    st = eng.init(n)
    st, tel = eng.run_chunked(st, jnp.asarray(starve.apply(trace, 0)), K)
    dc = np.asarray(tel.degraded_count)            # [F] window peaks
    check(int(dc[96 // K]) == n,
          f"starvation flush degrades all {n} lanes (peaks {dc.tolist()})")
    check(int(dc[-1]) == 0, "fleet recovered by the final flush")
    check(int(np.asarray(st.degraded).sum()) == 0, "no lane left degraded")

    # -- phase 2: sensor faults — containment on all five backends --------
    plan = FaultPlan(seed=args.seed,
                     sensor_faults=(SensorFault(2, "dropout", 120, 48),
                                    SensorFault(5, "corrupt", 180, 32)))
    faulted = plan.apply(trace, 0)
    ok_lanes = [i for i in range(n) if i not in plan.faulted_lanes()]
    exact = ("events_total", "events_step", "degraded_count", "n_packages")
    knife = ("freq_min", "at_risk_frac")
    ref = None
    for be in available_backends():
        e1 = FleetEngine(cfg, backend=be, debug_nan=True)
        s1 = e1.init(n)
        s1, t1 = e1.run_chunked(s1, jnp.asarray(faulted), K)
        e0 = FleetEngine(cfg, backend=be)
        s0 = e0.init(n)
        s0, _ = e0.run_chunked(s0, jnp.asarray(trace), K)
        bit = all(np.array_equal(np.asarray(getattr(s1, f))[ok_lanes],
                                 np.asarray(getattr(s0, f))[ok_lanes])
                  for f in ("freq", "thermal", "events", "rho_last"))
        check(bit, f"{be}: unaffected lanes bit-match the fault-free run")
        d1 = {k: np.asarray(v)
              for k, v in jax.device_get(t1)._asdict().items()}
        check(int(d1["degraded_count"].max()) >= 1
              and int(d1["degraded_count"][-1]) == 0,
              f"{be}: faulted lanes degrade and recover "
              f"(peaks {d1['degraded_count'].tolist()})")
        if ref is None:
            ref = d1
            continue
        for k, v in d1.items():
            if k in exact:
                same = np.array_equal(ref[k], v)
            elif k in knife:
                same = np.allclose(ref[k], v, rtol=1e-3, atol=1e-3)
            else:
                same = np.allclose(ref[k], v, rtol=1e-4, atol=5e-5)
            check(same, f"{be}: telemetry[{k}] matches broadcast")

    # -- phase 3: degraded alert rises and clears at the service ----------
    svc = FleetService(cfg, flush_every=50, seed=args.seed, debug_nan=True)
    for i in range(4):
        svc.attach(f"pkg{i}", tenant="acme")
    svc.set_thresholds("acme", degraded_limit=0)
    cap = svc.registry.capacity
    chunk = rng.uniform(0.9, 2.7, (50, cap, cfg.n_tiles)).astype(np.float32)
    bad_chunk = chunk.copy()
    bad_chunk[25:, 0, :] = np.nan       # lane 0 dark through the flush edge
    svc.tick(chunk=chunk)
    rec_bad = svc.tick(chunk=bad_chunk)
    rec_ok = svc.tick(chunk=chunk)      # sensor back — recover + clear
    rec_clean = svc.tick(chunk=chunk)   # fully recovered window
    fired = [a for a in rec_bad["alerts"] if a["kind"] == "degraded"]
    cleared = [a for a in rec_ok["alerts"] if a["kind"] == "degraded"]
    check(len(fired) == 1 and fired[0]["event"] == "fired",
          f"degraded alert fired once ({fired})")
    check(len(cleared) == 1 and cleared[0]["event"] == "cleared",
          f"degraded alert cleared once ({cleared})")
    check(not [a for a in rec_clean["alerts"] if a["kind"] == "degraded"],
          "no duplicate degraded events once steady")
    check(rec_bad["telemetry"]["degraded_count"] >= 1
          and rec_clean["telemetry"]["degraded_count"] == 0,
          "flush records carry the degraded counts")

    # -- phase 4: SIGTERM mid-run → snapshot → restore → equivalence ------
    def drive(svc, until, grow_at):
        while svc.flushes < until:
            if svc.flushes == grow_at:       # capacity transition mid-run
                for i in range(4, 9):
                    svc.attach(f"pkg{i}", tenant="acme")
            svc.tick()
        return svc.log.rows()[-1]["telemetry"]

    f_total, f_kill, f_grow = 16, 10, 6
    oracle = FleetService(cfg, flush_every=50, seed=args.seed)
    for i in range(4):
        oracle.attach(f"pkg{i}", tenant="acme")
    final_oracle = drive(oracle, f_total, f_grow)

    with tempfile.TemporaryDirectory() as tmp:
        victim = FleetService(cfg, flush_every=50, seed=args.seed,
                              snapshot_dir=tmp, snapshot_every=4)
        victim.warmup(16)
        for i in range(4):
            victim.attach(f"pkg{i}", tenant="acme")
        guard = PreemptionGuard()
        drive(victim, f_kill, f_grow)
        os.kill(os.getpid(), signal.SIGTERM)     # preemption notice
        time.sleep(0)                            # let the handler run
        check(guard.should_exit, "SIGTERM reached the PreemptionGuard")
        victim.save_snapshot(blocking=True)      # the --serve exit path
        guard.restore()
        del victim

        restored = FleetService.restore(tmp, debug_nan=True)
        check(restored.flushes == f_kill and restored.registry.n_active == 9,
              f"restored at flush {restored.flushes} with "
              f"{restored.registry.n_active} packages")
        compiles: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: compiles.append(name)
            if "compile" in name else None)
        final_restored = drive(restored, f_total, f_grow)
        comp = [c for c in compiles if "backend_compile" in c]
        check(not comp, f"zero recompiles after restore ({len(comp)} seen)")
        worst = max(abs(final_restored[k] - final_oracle[k])
                    / max(abs(final_oracle[k]), 1e-9)
                    for k in final_oracle)
        check(worst <= 1e-5,
              f"restore ≤1e-5-equivalent to uninterrupted "
              f"(worst rel diff {worst:.2e})")

    if failures:
        print(f"[chaos] {len(failures)} failure(s):")
        for f in failures:
            print(f"[chaos]   - {f}")
        raise SystemExit(1)
    print("[chaos] all gates passed")
    return {"chaos": "ok"}


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", type=int, default=1,
                    help="simulate N packages; >1 enables batched fleet mode")
    ap.add_argument("--fleet-backend", default="broadcast",
                    choices=available_backends(),
                    help="fleet execution strategy")
    ap.add_argument("--fleet-devices", type=int, default=0,
                    help="sharded/sharded_fused backend device budget "
                         "(0 = all visible)")
    ap.add_argument("--filtration", default="incremental",
                    choices=["incremental", "ring"],
                    help="filtration fast path (O(1) sliding stats) or the "
                         "ring-buffer oracle")
    from repro.core.plant import available_plants
    ap.add_argument("--plant", default="pole", choices=available_plants(),
                    help="thermal-plant fidelity rung (docs/architecture.md "
                         "'Thermal-plant fidelity ladder'): the paper's "
                         "pole bank, the spatial RC grid, or the ROM "
                         "fitted from it")
    from repro.core.nodebank import available_nodes
    ap.add_argument("--node", default="base", choices=available_nodes(),
                    help="technology-node parameter bank "
                         "(repro.core.nodebank): every fleet lane gets "
                         "that node's thermal/DVFS rows; non-base nodes "
                         "run a heterogeneous pole fleet")
    ap.add_argument("--stream", action="store_true",
                    help="streaming control-plane soak instead of serving "
                         "(async ingest, 1 host sync per gen-step flush)")
    ap.add_argument("--distributed", action="store_true",
                    help="join a jax.distributed process group: this "
                         "invocation is ONE host of a multi-host --stream "
                         "soak (launch one per host with --process-id "
                         "0..N-1; --fleet is the GLOBAL fleet size)")
    ap.add_argument("--coordinator", default="127.0.0.1:8476",
                    help="--distributed coordinator address (host:port of "
                         "process 0)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="--distributed total process count")
    ap.add_argument("--process-id", type=int, default=0,
                    help="--distributed this process's rank")
    ap.add_argument("--serve", action="store_true",
                    help="resident control plane: FleetService + HTTP "
                         "operator API instead of the wave loop "
                         "(docs/serving.md)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--serve bind address")
    ap.add_argument("--port", type=int, default=8787,
                    help="--serve port (0 = ephemeral)")
    ap.add_argument("--flush-every", type=int, default=50,
                    help="--serve steps per flush window")
    ap.add_argument("--serve-flushes", type=int, default=0,
                    help="--serve: stop after N flushes (0 = run until "
                         "POST /shutdown)")
    ap.add_argument("--snapshot-dir", default="",
                    help="--serve: journal + snapshot directory; enables "
                         "crash-consistent recovery via "
                         "FleetService.restore()")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="--serve: async snapshot every N flushes "
                         "(needs --snapshot-dir)")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.0,
                    help="--serve: mark /healthz stalled when no flush "
                         "lands for this many seconds (0 = off)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection soak: starvation fallback + "
                         "recovery, sensor-fault containment on every "
                         "backend, degraded alert edges, SIGTERM -> "
                         "snapshot -> restore equivalence; exits nonzero "
                         "on any gate failure (CI `chaos` job)")
    ap.add_argument("--montecarlo", type=int, default=0,
                    help="run the §10 process-variation Monte-Carlo with N "
                         "trials through the fleet backend instead of "
                         "serving")
    ap.add_argument("--mc-steps", type=int, default=3_000,
                    help="steps per Monte-Carlo trial (>= 3000 reproduces "
                         "the paper's §10 distributions)")
    args = ap.parse_args(argv)

    if args.distributed:
        # bootstrap FIRST — the process group must exist before any jax
        # computation pins the backend topology
        if not args.stream:
            ap.error("--distributed requires --stream (the multi-host "
                     "path is the streaming fleet soak)")
        if args.fleet_backend not in ("sharded", "sharded_fused"):
            ap.error(f"--distributed needs a device-mesh backend "
                     f"(sharded/sharded_fused), got "
                     f"--fleet-backend {args.fleet_backend}")
        from repro.distributed import multihost
        topo = multihost.initialize(args.coordinator, args.num_processes,
                                    args.process_id)
        print(f"[distributed] {topo.describe()}")

    if args.chaos:
        return _chaos_soak(args)
    if args.montecarlo:
        return _montecarlo(args)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    key = jax.random.PRNGKey(args.seed)
    max_seq = args.prompt_len + args.gen
    sched_cfg = SchedulerConfig(n_tiles=1, mode="v24", step_ms=5.0,
                                filtration_impl=args.filtration,
                                plant=args.plant,
                                heterogeneous=args.node != "base")
    shape = ShapeConfig("serve", max_seq, args.batch, "decode")
    rho = rho_v24(cfg, shape)

    if args.serve:                   # resident control plane, no wave loop
        return _serve_resident(args, sched_cfg)
    if args.stream:                  # control-plane soak, no model serving
        return _stream_soak(args, sched_cfg, float(rho), key)

    params = tf.init_params(key, cfg)
    prefill_fn = jax.jit(S.make_prefill_step(cfg, max_seq))
    decode_fn = jax.jit(S.make_decode_step(cfg))

    # the wave loop always rides the fleet engine (n = 1 is just a fleet of
    # one): one batched step advances every package; this host serves pkg 0
    n_pkgs = max(args.fleet, 1)
    fleet = FleetEngine(sched_cfg, backend=args.fleet_backend,
                        devices=args.fleet_devices or None)
    fst = fleet.init(n_pkgs, pkg=_node_pkg(fleet, args.node, n_pkgs))
    if args.fleet > 1:
        print(f"[fleet] backend {fleet.backend_impl.describe()} "
              f"({fleet.backend_impl.n_devices()} device(s))")
        # deterministic per-package load jitter around the base density
        jitter = 0.15 * jax.random.normal(jax.random.fold_in(key, 7777),
                                          (n_pkgs,))
    else:
        jitter = jnp.zeros((1,))     # a fleet of one serves the base density

    lat, admitted_hist, fleet_telem = [], [], []
    for wave in range(args.waves):
        # --- thermal admission control -----------------------------------
        rho_fleet = jnp.clip(rho + jitter * (1 + wave % 3), 0.9, 2.7)
        fst, out, telem = fleet.step(fst, rho_fleet)
        freq0 = float(out.freq[0, 0])
        if args.fleet > 1:
            d = telem.as_dict()
            fleet_telem.append(d)
            print(f"[fleet] wave {wave}: n={args.fleet} "
                  f"p50 {d['temp_p50_c']:.1f}C p99 {d['temp_p99_c']:.1f}C "
                  f"events {int(d['events_total'])} "
                  f"released {d['released_mtps']:.1f} MTPS")
        admit = max(1, int(args.batch * freq0))
        admitted_hist.append(admit)

        prompts = jax.random.randint(jax.random.fold_in(key, wave),
                                     (admit, args.prompt_len), 2,
                                     cfg.vocab_size)
        if cfg.frontend != "token":
            prompts = 0.02 * jax.random.normal(
                jax.random.fold_in(key, wave),
                (admit, args.prompt_len, cfg.d_model))
        t0 = time.time()
        last, cache = prefill_fn(params, prompts)
        tok = jnp.argmax(last, -1)
        if cfg.frontend != "token":
            tok = 0.02 * jax.random.normal(jax.random.fold_in(key, 99),
                                           (admit, cfg.d_model))
        jax.block_until_ready(last)
        t_prefill = time.time() - t0

        toks = []
        for i in range(args.gen):
            t1 = time.time()
            logits, cache = decode_fn(params, cache,
                                      tok, jnp.asarray(args.prompt_len + i))
            nxt = jnp.argmax(logits, -1)
            jax.block_until_ready(nxt)
            if wave or i:               # first call = jit compile, not latency
                lat.append(time.time() - t1)
            toks.append(np.asarray(nxt))
            tok = (nxt if cfg.frontend == "token" else tok)
        print(f"[serve] wave {wave}: admitted {admit}/{args.batch}, "
              f"prefill {t_prefill*1e3:.1f} ms, "
              f"decode p50 {np.percentile(lat, 50)*1e3:.2f} ms "
              f"p99 {np.percentile(lat, 99)*1e3:.2f} ms, "
              f"T {float(out.temp_c.ravel()[0]):.1f}C")
    p50, p99 = np.percentile(lat, 50), np.percentile(lat, 99)
    print(f"[serve] done: p50 {p50*1e3:.2f} ms, p99 {p99*1e3:.2f} ms, "
          f"p99/p50 {p99/max(p50,1e-9):.2f}, admissions {admitted_hist}")
    result = {"p50": p50, "p99": p99, "admitted": admitted_hist}
    if fleet_telem:
        result["fleet"] = fleet_telem
        last = fleet_telem[-1]
        print(f"[fleet] final: events {int(last['events_total'])}, "
              f"p99 {last['temp_p99_c']:.1f}C, "
              f"released {last['released_mtps']:.1f} MTPS "
              f"(throttled {last['throttled_mtps']:.1f})")
    return result


if __name__ == "__main__":
    main()
