"""Fleet engine throughput: backends, device scaling, streaming ingest,
and the PR-3 fused fast path.

Acceptance bars:
  * at 256 packages the batched `FleetEngine.step` must be ≥5× the
    throughput of looping a jitted `ThermalScheduler.update` per package
    (the loop pays 256 dispatches + per-package host sync; the fleet pays
    one);
  * the sharded backend on a single device must be within 5% of (or faster
    than) vmap — on a 1-mesh, shard_map must cost nothing;
  * released-MTPS capacity scales with emulated device count (weak scaling:
    128 packages per device, subprocesses with
    XLA_FLAGS=--xla_force_host_platform_device_count);
  * the streaming ingest loop sustains a 90 000-step trace end-to-end with
    EXACTLY one host sync per telemetry flush interval;
  * incremental filtration (O(1) sliding sufficient statistics) must be
    ≥2× the PR-2 ring-buffer baseline's pkg_steps_per_s at 4096 packages
    with filtration_window=64;
  * incremental filtration AND the fused Pallas whole-step backend AND its
    sharded_fused composition (one kernel per device partition) must match
    the PR-2 pure-JAX vmap/ring reference to ≤1e-5 over a 90k-step trace
    (fused off-TPU runs in interpret mode: correctness-gated only, its
    wall-clock is reported, not gated);
  * sharded_fused weak-scales like sharded: released-MTPS capacity tracks
    the emulated mesh size at 128 packages/device;
  * the control plane's masked capacity pools are near-free: run_block at
    50% occupancy (512-lane pool, [capacity] active mask, masked telemetry
    reductions) stays within 1.10× of the dense same-capacity fleet;
  * the PR-8 degraded-mode machinery (staleness counters, sanitised
    density latch, per-lane mode mask) is near-free on the fault-free hot
    path: a fault-free `degraded_fallback=True` run_block stays within
    1.10× of the same fleet with the fallback compiled out;
  * the ISSUE-10 mixed-profile fleet (pole+rom plant groups, two node
    banks, 50% canary-pinned reactive lanes) stays within 1.15× of a
    homogeneous pole/v24 fleet at the same capacity, and decomposes into
    per-group homogeneous oracles to ≤1e-5 per lane over the 90k-step
    trace;
  * the plant fidelity ladder (`run_plants`, surfaced as
    ``benchmarks.bench_plant``): the default pole bank served THROUGH the
    plant interface stays within 1.05× of scanning `core.thermal` directly
    (the refactor must be free), MTPS is reported per rung
    (pole / rom / grid), and the fitted ROM's peak ΔT tracks the RC grid
    within `repro.core.plant.ROM_PEAK_TOL`.

`benchmarks.run` appends this module's rows to ``BENCH_fleet.json`` at the
repo root, so the fleet fast path accumulates a perf trajectory across PRs.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.core.scheduler import SchedulerConfig, ThermalScheduler
from repro.fleet import FleetEngine, stream

N_PACKAGES = 256
N_TILES = 4
STEPS = 8

STREAM_STEPS = 90_000          # the paper's Appendix-B trace length
STREAM_PACKAGES = 32
STREAM_FLUSH = 1_000

FAST_PACKAGES = 4_096          # incremental-filtration gate operating point
FAST_WINDOW = 64
FAST_STEPS = 128               # long enough to amortise host-load jitter


def _rho_trace(key) -> jnp.ndarray:
    return 0.9 + 1.8 * jax.random.uniform(key, (STEPS, N_PACKAGES, N_TILES))


def _backend_steps(eng, trace):
    def go():
        st = eng.init(N_PACKAGES)
        for i in range(STEPS):
            st, out, _ = eng.step(st, trace[i])
        return out.freq
    return go


_SCALE_CODE = """
    import numpy as np, jax, jax.numpy as jnp, time
    from repro.core.scheduler import SchedulerConfig
    from repro.fleet import FleetEngine

    NDEV, PER_DEV, STEPS = {ndev}, 128, 64
    n = NDEV * PER_DEV
    eng = FleetEngine(SchedulerConfig(n_tiles=4, mode="v24"),
                      backend={backend!r}, devices=NDEV)
    assert eng.backend_impl.n_devices() == NDEV
    trace = 0.9 + 1.8 * jax.random.uniform(jax.random.PRNGKey(0),
                                           (STEPS, n, 4))
    st = eng.init(n)
    # the fleet really is partitioned: one package shard per device
    assert len(st.freq.sharding.device_set) == NDEV
    st, telem = eng.run_block(st, trace)          # warm (compile)
    jax.block_until_ready(telem)
    t0 = time.perf_counter()
    st, telem = eng.run_block(st, trace)
    d = telem.as_dict()
    dt = time.perf_counter() - t0
    print(f"RESULT {{d['released_mtps']:.1f}} {{STEPS * n / dt:.0f}}")
"""


def _sharded_scaling(backend: str = "sharded") -> None:
    """Weak scaling over emulated devices: 128 packages per device, so fleet
    capacity (released MTPS) must track the mesh size — PROVIDED the state
    really partitions (asserted inside the subprocess via the sharding's
    device_set; without that check the MTPS growth would hold by
    construction).  Wall-clock pkg_steps_per_s is reported but not gated:
    emulated devices share the host's cores, so timing scaling is too noisy
    for CI.  Subprocesses keep the parent single-device, and run on the CPU
    (``JAX_PLATFORMS=cpu``): a parent that has touched JAX holds any
    accelerator, so these rows are CPU emulation on every machine.  Runs for
    both the pure-JAX ``sharded`` backend and the ``sharded_fused``
    composition (one Pallas whole-step kernel per device partition)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    released = {}
    for ndev in (1, 2, 4):
        env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(
                _SCALE_CODE.format(ndev=ndev, backend=backend))],
            capture_output=True, text=True, env=env, timeout=540)
        assert out.returncode == 0, out.stderr[-2000:]
        mtps, rate = out.stdout.strip().split()[-2:]
        released[ndev] = float(mtps)
        row(f"fleet.{backend}_scale_dev{ndev}", 0.0,
            f"released_mtps={float(mtps):.0f};pkg_steps_per_s={rate};"
            f"devices=cpu-emulated")
    assert released[2] > 1.5 * released[1], (backend, released)
    assert released[4] > 1.5 * released[2], (backend, released)


def _filtration_fast_path() -> None:
    """Incremental (O(1) sliding stats) vs PR-2 ring-buffer filtration:
    pkg_steps_per_s of the raw jitted scheduler scan (no telemetry plane —
    this isolates the filtration math) at 4096 packages, W=64.  Gated ≥2×."""
    trace = 0.9 + 1.8 * jax.random.uniform(
        jax.random.PRNGKey(0), (FAST_STEPS, FAST_PACKAGES, N_TILES))
    trace = jax.block_until_ready(trace)
    pkg_steps = FAST_PACKAGES * FAST_STEPS

    def scan_for(impl):
        sched = ThermalScheduler(SchedulerConfig(
            n_tiles=N_TILES, mode="v24", filtration_window=FAST_WINDOW,
            filtration_impl=impl))
        state = sched.init(batch_shape=(FAST_PACKAGES,))

        @jax.jit
        def run(st, tr):
            def tick(s, rho):
                s, out = sched.update(s, rho)
                return s, out.freq[0, 0]
            return jax.lax.scan(tick, st, tr)

        return lambda: run(state, trace)[1]

    us = {}
    for impl in ("ring", "incremental"):
        _, us[impl] = timed(scan_for(impl), iters=5, best=True)
        row(f"fleet.filtration_{impl}_{FAST_PACKAGES}", us[impl] / FAST_STEPS,
            f"pkg_steps_per_s={pkg_steps / (us[impl] / 1e6):.0f};"
            f"window={FAST_WINDOW}")
    speedup = us["ring"] / us["incremental"]
    row("fleet.filtration_speedup", 0.0,
        f"incremental_vs_ring={speedup:.2f}x(need>=2)")
    assert speedup >= 2.0, \
        f"incremental filtration {speedup:.2f}x below the 2x bar"


def _fused_backend(cfg) -> None:
    """Fused Pallas whole-step backend — and its sharded_fused composition
    on the trivial 1-mesh — vs vmap over `run_block`.  Off-TPU the kernel
    runs in interpret mode, so the wall-clock rows are informative only;
    correctness (≤1e-5 vs the pure-JAX reference) IS gated for both."""
    n, steps = 256, 64
    trace = jax.block_until_ready(0.9 + 1.8 * jax.random.uniform(
        jax.random.PRNGKey(1), (steps, n, N_TILES)))
    us, telem = {}, {}
    for backend in ("vmap", "fused", "sharded_fused"):
        # donate_state=False: the timing closure feeds the SAME state every
        # iteration, which a donating engine would have deleted after call 1
        eng = FleetEngine(cfg, backend=backend, donate_state=False)
        state = eng.init(n)

        def go(eng=eng, state=state):
            st, t = eng.run_block(state, trace)
            return t
        # timed() returns the last call's result — reuse it as the
        # equivalence record instead of running the block again
        telem[backend], us[backend] = timed(go, iters=3, best=True)
        row(f"fleet.fused_{backend}_{n}", us[backend] / steps,
            f"pkg_steps_per_s={n * steps / (us[backend] / 1e6):.0f}")

    on_tpu = jax.default_backend() == "tpu"
    for backend in ("fused", "sharded_fused"):
        def rel(f, backend=backend):
            return (abs(float(getattr(telem[backend], f))
                        - float(getattr(telem["vmap"], f)))
                    / max(abs(float(getattr(telem["vmap"], f))), 1.0))
        # freq_min / at_risk_frac are order/threshold statistics — one
        # ulp-level flag flip moves them past 1e-5 (see _equivalence_90k)
        # — discrete bound
        err = max(rel(f) for f in telem["vmap"]._fields
                  if f not in ("freq_min", "at_risk_frac"))
        knife = max(rel("freq_min"), rel("at_risk_frac"))
        row(f"fleet.{backend}_vs_vmap", 0.0,
            f"ratio={us[backend] / us['vmap']:.2f}x;rel_err={err:.2e}"
            f"(need<=1e-5);knife_edge_err={knife:.2e};interpret={not on_tpu}")
        assert err <= 1e-5, f"{backend} diverges from vmap: {err:.2e}"
        assert knife <= 1e-3, f"{backend} knife-edge stats: {knife:.2e}"


def _equivalence_90k() -> None:
    """Acceptance bar: over the full Appendix-B-scale 90k-step trace, the
    incremental filtration AND the fused kernel backend must track the PR-2
    pure-JAX vmap/ring reference to ≤1e-5 (reduced telemetry per flush
    window + final event counters compared)."""
    n = 8
    rng = np.random.default_rng(2)
    trace = jnp.asarray((0.9 + 1.8 * rng.random(
        (STREAM_STEPS, n, N_TILES))).astype(np.float32))

    def soak(impl, backend):
        eng = FleetEngine(SchedulerConfig(n_tiles=N_TILES, mode="v24",
                                          filtration_impl=impl),
                          backend=backend)
        t0 = time.perf_counter()
        state, red = eng.run_chunked(eng.init(n), trace, STREAM_FLUSH)
        red = jax.device_get(red)
        dt = time.perf_counter() - t0
        return state, red, dt

    # freq_min and at_risk_frac are ORDER/THRESHOLD statistics: a 1-ulp
    # state difference can pick a different minimiser or flip one
    # straggler flag (1 flip in a 1000-step window of 32 tiles = 3.1e-5),
    # so they get a looser discrete bound; every continuous aggregate and
    # the integer event counters carry the 1e-5 contract.
    knife_edge = {"freq_min": 1e-3, "at_risk_frac": 1e-3}
    _, ref, dt_ref = soak("ring", "vmap")            # the PR-2 baseline
    for name, impl, backend in (
            ("incremental", "incremental", "broadcast"),
            ("fused", "incremental", "fused"),
            # the composition on the trivial 1-mesh (multi-device meshes are
            # gated by tests/test_fleet_sharded_fused.py subprocesses)
            ("sharded_fused", "incremental", "sharded_fused")):
        state, got, dt = soak(impl, backend)
        errs = {f: np.max(np.abs(np.asarray(gf, np.float64)
                                 - np.asarray(rf, np.float64))
                          / np.maximum(np.abs(np.asarray(rf, np.float64)),
                                       1.0))
                for f, gf, rf in zip(ref._fields, got, ref)}
        err = max(e for f, e in errs.items() if f not in knife_edge)
        row(f"fleet.equiv90k_{name}", dt / STREAM_STEPS * 1e6,
            f"rel_err={err:.2e}(need<=1e-5);"
            f"knife_edge_err={max(errs[f] for f in knife_edge):.2e};"
            f"pkg_steps_per_s={STREAM_STEPS * n / dt:.0f};"
            f"ref_pkg_steps_per_s={STREAM_STEPS * n / dt_ref:.0f}")
        assert err <= 1e-5, f"{name} 90k drift {err:.2e} exceeds 1e-5"
        for f, bound in knife_edge.items():
            assert errs[f] <= bound, (name, f, errs[f])
        assert int(np.asarray(state.events).sum()) == \
            int(np.asarray(ref.events_total[-1]))


MASK_CAPACITY = 512
MASK_STEPS = 64


def _masked_occupancy(cfg) -> None:
    """Control-plane mask overhead bound (ISSUE-6 gate): a capacity pool at
    50% occupancy — run_block with a [capacity] active mask — must stay
    within 1.10× of the dense same-capacity fleet.  The padded lanes step
    either way (lockstep execution is the zero-recompile design); what the
    gate bounds is the PRICE of masking itself: the where-sums, inf-padded
    masked quantiles and traced-count telemetry reductions
    `repro.fleet.service` adds to every flush."""
    eng = FleetEngine(cfg, backend="broadcast")
    rng = np.random.default_rng(7)
    trace = jnp.asarray((0.9 + 1.8 * rng.random(
        (MASK_STEPS, MASK_CAPACITY, N_TILES))).astype(np.float32))
    mask = np.zeros(MASK_CAPACITY, bool)
    mask[::2] = True                          # 50% occupancy
    mask = jnp.asarray(mask)
    st0 = eng.init(MASK_CAPACITY)

    def dense():
        _, telem = eng.run_block(st0, trace)
        return telem

    def masked():
        _, telem = eng.run_block(st0, trace, active=mask)
        return telem

    # best-of: the masked/dense RATIO is gated (see timed's docstring)
    _, us_dense = timed(dense, iters=10, best=True)
    telem, us_masked = timed(masked, iters=10, best=True)
    assert int(telem.as_dict()["n_packages"]) == MASK_CAPACITY // 2
    ratio = us_masked / us_dense
    rate = MASK_STEPS * MASK_CAPACITY / (us_masked / 1e6)
    row("fleet.masked_occupancy_512", us_masked / MASK_STEPS,
        f"pkg_steps_per_s={rate:.0f};masked_vs_dense={ratio:.3f}"
        f"(need<=1.10)")
    assert ratio <= 1.10, \
        f"masked 50%-occupancy fleet {ratio:.3f}x of dense (>1.10)"


def _degraded_overhead(cfg) -> None:
    """PR-8 gate: the degraded-mode fallback machinery — per-step isfinite
    scan, rho_last latch, staleness counter with hysteresis, per-lane mode
    select — must cost ≤1.10× on a FAULT-FREE trace (the hot path every
    healthy fleet pays forever).  Same 512-lane operating point as the
    mask-overhead gate; faulted-path pricing is not gated (faults are
    rare), only measured by the chaos soak."""
    fb_cfg = SchedulerConfig(n_tiles=N_TILES, mode="v24",
                             degraded_fallback=True, stale_limit_steps=5,
                             recover_steps=10)
    rng = np.random.default_rng(8)
    trace = jnp.asarray((0.9 + 1.8 * rng.random(
        (MASK_STEPS, MASK_CAPACITY, N_TILES))).astype(np.float32))
    us = {}
    for name, c in (("plain", cfg), ("fallback", fb_cfg)):
        eng = FleetEngine(c, backend="broadcast")
        st0 = eng.init(MASK_CAPACITY)

        def go(eng=eng, st0=st0):
            _, telem = eng.run_block(st0, trace)
            return telem
        telem, us[name] = timed(go, iters=10, best=True)
    assert int(telem.as_dict()["degraded_count"]) == 0   # fault-free run
    ratio = us["fallback"] / us["plain"]
    rate = MASK_STEPS * MASK_CAPACITY / (us["fallback"] / 1e6)
    row("fleet.degraded_overhead_512", us["fallback"] / MASK_STEPS,
        f"pkg_steps_per_s={rate:.0f};fallback_vs_plain={ratio:.3f}"
        f"(need<=1.10)")
    assert ratio <= 1.10, \
        f"fault-free degraded-mode machinery {ratio:.3f}x of plain (>1.10)"


MIX_CAPACITY = 256
MIX_STEPS = 64


def _mixed_profile_overhead() -> None:
    """ISSUE-10 gate: a mixed-profile fleet — pole+rom plant groups under
    `GroupedFleetEngine`, two node banks on the pole group, 50% of lanes
    canary-pinned to the reactive controller — must stay within 1.15× of
    a homogeneous pole/v24 fleet at the SAME total capacity.  What the
    gate bounds: the per-group dispatch (two scans instead of one), the
    merged telemetry flush, the traced ctrl_mode select and the
    per-lane PackageParams rows.  Grid is deliberately NOT in this gate
    (a grid rung costs what the fidelity ladder says it costs —
    ``fleet.plant_grid_256``); mixed pole+grid correctness is gated by
    tests/test_fleet_groups.py instead."""
    from repro.core import nodebank
    from repro.fleet import GroupedFleetEngine

    half = MIX_CAPACITY // 2
    rng = np.random.default_rng(9)
    trace = jnp.asarray((0.9 + 1.8 * rng.random(
        (MIX_STEPS, MIX_CAPACITY, N_TILES))).astype(np.float32))

    base = FleetEngine(SchedulerConfig(n_tiles=N_TILES, mode="v24"),
                       backend="broadcast")
    st_base = base.init(MIX_CAPACITY)

    def homogeneous():
        _, telem = base.run_block(st_base, trace)
        return telem

    mcfg = SchedulerConfig(n_tiles=N_TILES, mode="v24", mixed_mode=True,
                           heterogeneous=True)
    ge = GroupedFleetEngine(mcfg, backend="broadcast",
                            groups=("pole", "rom"))
    nodes = ["base" if i % 2 else "n5" for i in range(half)]
    pkg = {"pole": nodebank.fleet_package_params(ge.engines["pole"].sched,
                                                 nodes)}
    states = ge.init({"pole": half, "rom": half}, pkg=pkg)
    pin = jnp.asarray(np.arange(half) < half // 2)     # 50% canary
    for g in ge.groups:
        states[g] = states[g]._replace(ctrl_mode=pin)

    def mixed():
        _, telem = ge.run_block(states, trace)
        return telem

    _, us_homog = timed(homogeneous, iters=10, best=True)
    telem, us_mixed = timed(mixed, iters=10, best=True)
    assert int(telem.as_dict()["n_packages"]) == MIX_CAPACITY
    ratio = us_mixed / us_homog
    rate = MIX_STEPS * MIX_CAPACITY / (us_mixed / 1e6)
    row("fleet.mixed_profile_overhead", us_mixed / MIX_STEPS,
        f"pkg_steps_per_s={rate:.0f};mixed_vs_homogeneous={ratio:.3f}"
        f"(need<=1.15);groups=pole+rom;nodes=base+n5;canary=0.5")
    assert ratio <= 1.15, \
        f"mixed-profile fleet {ratio:.3f}x of homogeneous (>1.15)"


def _mixed_equivalence_90k() -> None:
    """ISSUE-10 acceptance bar at Appendix-B scale: the mixed-profile
    fleet decomposes into per-group homogeneous oracles over the full
    90k-step trace to ≤1e-5 per lane (bitwise in practice — the grouped
    engine runs the SAME per-group programs).  All five backends carry
    this contract at block scale in tests/test_fleet_groups.py; the 90k
    soak runs the serving default (broadcast)."""
    from repro.core import nodebank
    from repro.fleet import GroupedFleetEngine

    pole_n, rom_n = 4, 4
    n = pole_n + rom_n
    rng = np.random.default_rng(12)
    trace = jnp.asarray((0.9 + 1.8 * rng.random(
        (STREAM_STEPS, n, N_TILES))).astype(np.float32))

    mcfg = SchedulerConfig(n_tiles=N_TILES, mode="v24", mixed_mode=True,
                           heterogeneous=True)
    ge = GroupedFleetEngine(mcfg, backend="broadcast",
                            groups=("pole", "rom"))
    nodes = ["base", "n5", "n3", "base"]
    pkg = {"pole": nodebank.fleet_package_params(ge.engines["pole"].sched,
                                                 nodes)}
    states = ge.init({"pole": pole_n, "rom": rom_n}, pkg=pkg)
    pins = {"pole": np.array([1, 0, 1, 0], bool),
            "rom": np.array([0, 1, 0, 0], bool)}
    for g in ge.groups:
        states[g] = states[g]._replace(ctrl_mode=jnp.asarray(pins[g]))

    t0 = time.perf_counter()
    _, temps, freqs = ge.block_traces(states, trace)
    temps = np.asarray(temps, np.float64)
    freqs = np.asarray(freqs, np.float64)
    dt = time.perf_counter() - t0

    sl = {"pole": slice(0, pole_n), "rom": slice(pole_n, n)}
    err = 0.0
    for g in ge.groups:
        eng = FleetEngine(ge.engines[g].cfg, backend="broadcast")
        st = eng.init(sl[g].stop - sl[g].start, pkg=pkg.get(g))
        st = st._replace(ctrl_mode=jnp.asarray(pins[g]))
        _, tg, fg = eng.block_traces(st, trace[:, sl[g]])
        for got, want in ((temps[:, sl[g]], np.asarray(tg, np.float64)),
                          (freqs[:, sl[g]], np.asarray(fg, np.float64))):
            err = max(err, float(np.max(np.abs(got - want)
                                        / np.maximum(np.abs(want), 1.0))))
    row("fleet.mixed_equiv90k", dt / STREAM_STEPS * 1e6,
        f"rel_err={err:.2e}(need<=1e-5);"
        f"pkg_steps_per_s={STREAM_STEPS * n / dt:.0f};"
        f"groups=pole+rom;nodes=base+n5+n3;pins=mixed")
    assert err <= 1e-5, f"mixed-profile 90k drift {err:.2e} exceeds 1e-5"


def _streaming_90k(cfg) -> None:
    """Streaming ingest over the Appendix-B-scale 90k-step trace: the sync
    contract (1 host sync per flush window) must hold end-to-end."""
    eng = FleetEngine(cfg, backend="broadcast")
    rng = np.random.default_rng(0)

    def source():
        for _ in range(STREAM_STEPS // STREAM_FLUSH):
            yield (0.9 + 1.8 * rng.random(
                (STREAM_FLUSH, STREAM_PACKAGES, N_TILES))).astype(np.float32)

    st = eng.init(STREAM_PACKAGES)
    # warm the run_block compile outside the timed region
    st_w, _ = eng.run_block(eng.init(STREAM_PACKAGES),
                            jnp.zeros((STREAM_FLUSH, STREAM_PACKAGES,
                                       N_TILES)) + 1.5)
    jax.block_until_ready(st_w.freq)
    # enforce (don't just self-attest) the sync contract: count the actual
    # device→host fetches issued through jax.device_get — the channel
    # `FleetTelemetry.as_dict` uses — during the streamed run
    real_get, gets = jax.device_get, 0

    def counting_get(x):
        nonlocal gets
        gets += 1
        return real_get(x)

    jax.device_get = counting_get
    try:
        t0 = time.perf_counter()
        st, flushed, stats = stream(eng, st, source(), keep_telemetry=False)
        dt = time.perf_counter() - t0
    finally:
        jax.device_get = real_get
    assert stats.steps == STREAM_STEPS, stats
    assert stats.host_syncs == stats.flushes == STREAM_STEPS // STREAM_FLUSH, \
        stats
    assert gets == stats.flushes, \
        f"{gets} device_get calls for {stats.flushes} flushes"
    rate = stats.steps * STREAM_PACKAGES / dt
    row("fleet.stream_90k", dt / stats.steps * 1e6,
        f"pkg_steps_per_s={rate:.0f};host_syncs={stats.host_syncs};"
        f"flushes={stats.flushes};syncs_per_flush={stats.syncs_per_flush:.1f}")


PLANT_STEPS = 64
PLANT_PACKAGES = 256
IFACE_STEPS = 2_048
ROM_PEAK_STEPS = 9_000


def run_plants() -> None:
    """Fidelity-ladder rows (surfaced as ``benchmarks.bench_plant`` so the
    smoke can run them without the full fleet sweep; NOT called from
    `run()` — the two modules share this file but never duplicate rows).

      * ``fleet.plant_{pole,rom,grid}_256`` — run_block MTPS per rung on
        the broadcast backend: what one fidelity upgrade costs at serving
        time;
      * ``fleet.plant_iface_overhead`` — GATED ≤1.05×: scanning the pole
        bank THROUGH the plant interface vs calling `core.thermal`
        directly (the pre-refactor form).  Both jit to the same XLA
        program — the gate proves the indirection stays free;
      * ``fleet.plant_rom_fidelity`` — GATED: the fitted ROM's peak ΔT
        over a varied-load trace within `ROM_PEAK_TOL` of the grid it was
        fit from (the 90k-step version of this gate is
        tests/test_plant.py::test_rom_tracks_grid_peak_90k).
    """
    from repro.core import thermal
    from repro.core.density import power_from_rho
    from repro.core.plant import ROM_PEAK_TOL, make_plant

    # --- MTPS per rung ----------------------------------------------------
    n, steps = PLANT_PACKAGES, PLANT_STEPS
    trace = jax.block_until_ready(0.9 + 1.8 * jax.random.uniform(
        jax.random.PRNGKey(3), (steps, n, N_TILES)))
    pkg_steps = n * steps
    for plant in ("pole", "rom", "grid"):
        cfg = SchedulerConfig(n_tiles=N_TILES, mode="v24", plant=plant)
        eng = FleetEngine(cfg, backend="broadcast", donate_state=False)
        state = eng.init(n)

        def go(eng=eng, state=state):
            _, telem = eng.run_block(state, trace)
            return telem
        telem, us = timed(go, iters=10, best=True)
        row(f"fleet.plant_{plant}_{n}", us / steps,
            f"pkg_steps_per_s={pkg_steps / (us / 1e6):.0f};"
            f"released_mtps={telem.as_dict()['released_mtps']:.0f};"
            f"plant={eng.sched.plant.describe()}")

    # --- interface overhead: pole via interface vs direct thermal.* ------
    cfg = SchedulerConfig(n_tiles=N_TILES, mode="v24")
    plant_obj = make_plant(cfg)
    poles = plant_obj.poles
    power = jax.block_until_ready(power_from_rho(
        0.9 + 1.8 * jax.random.uniform(jax.random.PRNGKey(4),
                                       (IFACE_STEPS, n, N_TILES))))
    st0 = jax.block_until_ready(plant_obj.init_state((n,)))

    @jax.jit
    def via_iface(st, pw):
        def tick(s, p):
            s = plant_obj.step(s, p)
            return s, plant_obj.delta_t(s)
        return jax.lax.scan(tick, st, pw)

    @jax.jit
    def direct(st, pw):
        def tick(s, p):
            s = thermal.step(poles, s, p)
            return s, thermal.delta_t(s)
        return jax.lax.scan(tick, st, pw)

    _, us_iface = timed(lambda: via_iface(st0, power)[1], iters=10,
                        best=True)
    _, us_direct = timed(lambda: direct(st0, power)[1], iters=10, best=True)
    ratio = us_iface / us_direct
    row("fleet.plant_iface_overhead", us_iface / IFACE_STEPS,
        f"iface_vs_direct={ratio:.3f}(need<=1.05);"
        f"pkg_steps_per_s={n * IFACE_STEPS / (us_iface / 1e6):.0f}")
    assert ratio <= 1.05, \
        f"plant interface {ratio:.3f}x of the direct pole path (>1.05)"

    # --- ROM honesty: peak ΔT vs the grid it was fit from ----------------
    cfg = SchedulerConfig(n_tiles=N_TILES, mode="v24", plant="grid")
    power = power_from_rho(0.9 + 1.8 * jax.random.uniform(
        jax.random.PRNGKey(5), (ROM_PEAK_STEPS, N_TILES)))
    peaks = {}
    for name in ("grid", "rom"):
        p = make_plant(SchedulerConfig(n_tiles=N_TILES, mode="v24",
                                       plant=name))

        def tick(c, pw, p=p):
            s, pk = c
            s = p.step(s, pw)
            return (s, jnp.maximum(pk, p.delta_t(s).max())), None
        (_, pk), _ = jax.jit(
            lambda c, tr, tick=tick: jax.lax.scan(tick, c, tr))(
            (p.init_state(()), jnp.float32(0.0)), power)
        peaks[name] = float(pk)
    rel = abs(peaks["rom"] - peaks["grid"]) / peaks["grid"]
    row("fleet.plant_rom_fidelity", 0.0,
        f"rom_vs_grid_peak={rel:.4f}(need<={ROM_PEAK_TOL});"
        f"peak_grid_c={peaks['grid']:.2f};peak_rom_c={peaks['rom']:.2f}")
    assert rel <= ROM_PEAK_TOL, \
        f"ROM peak ΔT {rel:.4f} off the grid (> {ROM_PEAK_TOL})"


def run() -> None:
    cfg = SchedulerConfig(n_tiles=N_TILES, mode="v24")
    key = jax.random.PRNGKey(0)
    trace = jax.block_until_ready(_rho_trace(key))

    # --- every registered single-host backend over the same trace ---------
    pkg_steps = N_PACKAGES * STEPS
    us = {}
    for backend in ("vmap", "broadcast", "sharded"):
        eng = FleetEngine(cfg, backend=backend)
        # best-of-10: the sharded/vmap ratio below is GATED, and mean-of-5
        # on a noisy shared host swings it by 2x
        _, us[backend] = timed(_backend_steps(eng, trace), iters=10,
                               best=True)
        # window-mean released MTPS for the backend (telemetry plane)
        _, telem = eng.run_block(eng.init(N_PACKAGES), trace)
        row(f"fleet.{backend}_{N_PACKAGES}", us[backend] / STEPS,
            f"pkg_steps_per_s={pkg_steps / (us[backend] / 1e6):.0f};"
            f"released_mtps={telem.as_dict()['released_mtps']:.0f}")

    # --- sequential per-package loop (jitted update, one call per pkg) ----
    sched = ThermalScheduler(cfg)
    upd = jax.jit(sched.update)

    def seq_steps():
        states = [sched.init() for _ in range(N_PACKAGES)]
        for i in range(STEPS):
            for p in range(N_PACKAGES):
                states[p], out = upd(states[p], trace[i, p])
        jax.block_until_ready(out.freq)
        return out.freq

    _, us_seq = timed(seq_steps, warmup=1, iters=1)
    row("fleet.sequential_256", us_seq / STEPS,
        f"pkg_steps_per_s={pkg_steps / (us_seq / 1e6):.0f}")

    speedup = us_seq / us["vmap"]
    row("fleet.speedup", 0.0, f"vmap_vs_seq={speedup:.1f}x(need>=5)")
    assert speedup >= 5.0, f"fleet speedup {speedup:.1f}x below 5x bar"

    # sharded on a trivial 1-mesh must not cost anything vs vmap (≤5% slower,
    # or faster); measured over the same 5-iter timed windows above
    ratio = us["sharded"] / us["vmap"]
    row("fleet.sharded_vs_vmap_1dev", 0.0,
        f"ratio={ratio:.3f}(need<=1.05)")
    assert ratio <= 1.05, f"sharded 1-dev {ratio:.3f}x of vmap (>1.05)"

    _masked_occupancy(cfg)
    _degraded_overhead(cfg)
    _mixed_profile_overhead()
    _filtration_fast_path()
    _fused_backend(cfg)
    _sharded_scaling("sharded")
    _sharded_scaling("sharded_fused")
    _streaming_90k(cfg)
    _equivalence_90k()
    _mixed_equivalence_90k()


if __name__ == "__main__":
    run()
