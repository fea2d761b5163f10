"""§10 — Monte-Carlo thermal simulation under parameter uncertainty.

N = 2 000 trials varying thermal resistance (Rth ± 8 % Gaussian — Intel 18A
process variation), time constant (τ ± 12 % — assembly/TIM1 tolerance) and
workload density (ρ ± 15 % — production workload diversity), per §10.1.  Each
trial additionally redraws its workload trace and its OEM temperature-polling
period (the §9 baseline is "reactive DVFS + temperature polling"; polling
heterogeneity across deployed governors is what spreads the baseline
peak-temperature tail).

`run` drives the whole population through the FLEET ENGINE: one trial = one
(package, tile) lane of a heterogeneous fleet whose per-trial Rth/τ pole
banks, preposition fractions and polling periods ride in the state
(`repro.core.scheduler.PackageParams`), so every fleet fast path — O(1)
incremental filtration, the fused Pallas whole-step kernel, sharded device
meshes — applies to the paper's flagship population workload.  Trials are
packed onto the tile axis in groups of `_TILE_PACK` (the f32 sublane width):
with Γ disabled, tiles are physically independent lanes, so a [N/8, 8] fleet
is the same population as [N, 1] but fills the kernel's sublane tile with
real work.  The per-trial peak-T / exceedance / delivered-perf statistics
reduce in-graph via `FleetEngine.run_survey` (O(N) accumulators — no [T, N]
trace is ever materialised).

`run_reference` keeps the original per-trial `jax.vmap` over the
`repro.core.dvfs` simulators — the oracle `benchmarks/bench_montecarlo.py`
gates the fleet path against (≤1e-5 on the aggregate statistics, every
backend).

Published findings reproduced by `benchmarks/bench_montecarlo.py`:

  * baseline peak-T: mean ≈ 91 °C, σ ≈ 6 °C; time above the 85 °C safe
    limit ≈ 23 %   (we report the exceedance as a time fraction — a *peak*
    mean of 91 °C with only 23 % exceedance is only mutually consistent
    under the time-fraction reading)
  * V24 peak-T: mean ≈ 82.5 °C, σ ≈ 2.1 °C (3.5× tighter); exceedance < 1 %
  * performance uplift +19–31 % across all four workload types
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dvfs, thermal, workload
from repro.core.fingerprint import FINGERPRINT, Fingerprint
from repro.core.scheduler import SchedulerConfig

_TILE_PACK = 8      # f32 sublane width — trials packed per fleet package


class MCResult(NamedTuple):
    peak_t_baseline: jnp.ndarray    # [N] per-trial peak junction temp [°C]
    peak_t_v24: jnp.ndarray         # [N]
    time_above_baseline: jnp.ndarray  # [N] fraction of time T > 85 °C
    time_above_v24: jnp.ndarray       # [N]
    perf_baseline: jnp.ndarray      # [N] mean delivered perf
    perf_v24: jnp.ndarray           # [N]

    def stats(self) -> dict:
        b, v = self.peak_t_baseline, self.peak_t_v24
        return {
            "baseline_mean_c": float(b.mean()),
            "baseline_std_c": float(b.std()),
            "baseline_time_above_frac": float(self.time_above_baseline.mean()),
            "v24_mean_c": float(v.mean()),
            "v24_std_c": float(v.std()),
            "v24_time_above_frac": float(self.time_above_v24.mean()),
            "sigma_ratio": float(v.std() / b.std()),
            "sigma_tighter_x": float(b.std() / v.std()),
            "uplift_mean": float((self.perf_v24 / self.perf_baseline).mean() - 1),
            "uplift_p5": float(jnp.percentile(
                self.perf_v24 / self.perf_baseline - 1, 5)),
            "uplift_p95": float(jnp.percentile(
                self.perf_v24 / self.perf_baseline - 1, 95)),
        }


def _ar1(z: jnp.ndarray, corr: float) -> jnp.ndarray:
    """AR(1) chain over i.i.d. standard normals, unit marginal variance.

    z_i' = corr·z'_{i−1} + √(1−corr²)·z_i — neighbouring trials end up
    with correlation ``corr`` while each marginal stays N(0, 1), so the
    downstream scale/clip pipeline sees the same per-trial distribution
    as the i.i.d. draw."""
    c = jnp.asarray(corr, z.dtype)
    root = jnp.sqrt(1.0 - c * c)

    def step(prev, e):
        cur = c * prev + root * e
        return cur, cur

    _, rest = jax.lax.scan(step, z[0], z[1:])
    return jnp.concatenate([z[:1], rest])


def sample_params(key, n_trials: int, fp: Fingerprint = FINGERPRINT, *,
                  corr: float = 0.0):
    """(rth, tau, util, poll_ticks) draws per §10.1 (+ OEM polling spread).

    ``corr`` > 0 makes the Rth/τ draws RETICLE-NEIGHBOUR correlated:
    adjacent trial indices model adjacent reticle sites, whose process
    variation is spatially correlated rather than i.i.d., via an AR(1)
    chain over the underlying normals (corr = the neighbour correlation
    coefficient; marginals stay N(0,1), so per-trial distributions are
    unchanged).  Workload utilisation and OEM polling stay i.i.d. — they
    are not process-linked.  ``corr=0.0`` (default) is BIT-IDENTICAL to
    the historical i.i.d. sampler (regression-gated in
    tests/test_montecarlo_corr.py)."""
    if not -1.0 < corr < 1.0:
        raise ValueError(f"corr must be in (-1, 1), got {corr}")
    k1, k2, k3, k4 = jax.random.split(key, 4)
    z_rth = jax.random.normal(k1, (n_trials,))
    z_tau = jax.random.normal(k2, (n_trials,))
    if corr:
        z_rth = _ar1(z_rth, corr)
        z_tau = _ar1(z_tau, corr)
    rth = fp.rth_c_per_w * (1 + 0.08 * z_rth)
    tau = fp.tau_ms * (1 + 0.12 * z_tau)
    util = 1.02 + 0.15 * jax.random.normal(k3, (n_trials,))
    poll = jax.random.randint(k4, (n_trials,), 15, 76)   # ms, OEM diversity
    return (jnp.clip(rth, 0.25, 0.70), jnp.clip(tau, 30.0, 160.0),
            jnp.clip(util, 0.5, 1.35), poll)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _trial_traces(trial_keys, util, n_steps: int, kind: str,
                  fp: Fingerprint) -> jnp.ndarray:
    """[N, T] per-trial density traces, exactly the oracle's draws.

    Jitted with static shape/kind/fingerprint so repeated experiments reuse
    the compiled generator (trace synthesis at N=2000 otherwise re-traces
    2000 vmapped OU/burst programs per call and dominates the wall-clock).
    """
    def one(key_i, util_i):
        tr = workload.make_trace(key_i, n_steps, kind) * util_i
        return jnp.clip(tr, 0.4 * fp.rho_min, 1.3 * fp.rho_max)[:, 0]
    return jax.vmap(one)(trial_keys, util)


def _pack(n_trials: int) -> int:
    """Trials per package: the largest divisor of N up to the sublane tile."""
    return max(d for d in range(1, _TILE_PACK + 1) if n_trials % d == 0)


def _scheduler_cfg(cfg: dvfs.DVFSConfig, lanes: int, mode: str,
                   filtration_impl: str,
                   plant: str = "pole") -> SchedulerConfig:
    """Map the DVFS simulator's knobs onto an equivalent fleet scheduler.

    Per-trial Rth/τ/poll draws ride `PackageParams`, which requires the
    pole-bank plant; higher-fidelity rungs (grid / rom) run the fleet
    HOMOGENEOUS — trial diversity then comes from the workload draws alone
    (documented restriction, see `run`).
    """
    return SchedulerConfig(
        n_tiles=lanes, mode=mode, two_pole=False, use_coupling=False,
        step_ms=cfg.dt_ms,
        lookahead_steps=cfg.lookahead_ms / cfg.dt_ms,
        filtration_window=cfg.filtration_window,
        filtration_impl=filtration_impl,
        t_safe_margin_c=cfg.t_safe_margin_c,
        power_exponent=cfg.power_exponent,
        heterogeneous=plant == "pole",
        plant=plant,
        throttle_level=cfg.throttle_level,
        resume_below_c=cfg.resume_below_c,
        recover_ms=cfg.recover_ms,
        poll_interval_ms=cfg.poll_interval_ms)


@functools.lru_cache(maxsize=16)
def _engine(scfg: SchedulerConfig, fp: Fingerprint, backend: str,
            devices: int | None):
    """One engine (and its compiled jits) per distinct configuration —
    repeated Monte-Carlo calls reuse the compiled fleet programs instead of
    paying a fresh trace/compile per experiment.  Both config dataclasses
    are frozen, so the cache keys by value; the LRU bound keeps a process
    sweeping trial counts / backends / configs from accumulating compiled
    XLA programs without limit."""
    from repro.fleet import FleetEngine
    return FleetEngine(scfg, fp=fp, backend=backend, devices=devices)


def engine(n_trials: int, mode: str, *, backend: str = "broadcast",
           devices: int | None = None, cfg: dvfs.DVFSConfig | None = None,
           fp: Fingerprint = FINGERPRINT,
           filtration_impl: str = "incremental", plant: str = "pole"):
    """The fleet engine `run` drives for one ``mode`` ("reactive_poll" for
    the baseline, "v24") of an ``n_trials`` population: the same cached
    object, so its backend and compiled programs can be inspected."""
    cfg = dvfs.DVFSConfig() if cfg is None else cfg
    return _engine(_scheduler_cfg(cfg, _pack(n_trials), mode,
                                  filtration_impl, plant),
                   fp, backend, devices)


def run(key=None, n_trials: int = 2_000, n_steps: int = 3_000,
        kind: str = "inference", burn_in: int = 400,
        cfg: dvfs.DVFSConfig | None = None,
        fp: Fingerprint = FINGERPRINT, *,
        backend: str = "broadcast", devices: int | None = None,
        filtration_impl: str = "incremental",
        plant: str = "pole", corr: float = 0.0) -> MCResult:
    """Run the paired (baseline, V24) Monte-Carlo experiment at fleet scale.

    One trial = one lane of a heterogeneous `FleetEngine` fleet (per-trial
    Rth/τ/η/poll draws in the state, trials packed onto the tile axis);
    baseline and V24 run as two fleets over the same traces and draws.
    ``backend`` picks any registered fleet backend (vmap / broadcast /
    sharded / fused / sharded_fused), ``devices`` caps the device-mesh
    backends, ``filtration_impl`` picks the Ft fast path ("incremental",
    the O(1) serving default) or the ring oracle.  Statistically identical
    to `run_reference` — gated ≤1e-5 on the aggregate §10 statistics by
    `benchmarks/bench_montecarlo.py`.

    ``plant`` picks the thermal-plant fidelity rung (`repro.core.plant`):
    the default pole bank carries the full §10.1 per-trial Rth/τ/poll
    heterogeneity; under ``"grid"`` / ``"rom"`` those draws have no
    per-package override (the fleet runs the fitted/spatial physics
    HOMOGENEOUSLY) so trial diversity comes from the workload draws alone —
    compare the two stats dicts to see how much of the §3.4 guard-band
    reduction survives the higher-fidelity plant
    (`repro.core.guardband.from_montecarlo`).

    ``corr`` threads through to `sample_params`: > 0 makes the per-trial
    Rth/τ draws reticle-neighbour correlated (0.0 keeps the historical
    i.i.d. population bit-identically).
    """
    # construct-per-call: a dataclass default argument would be built once
    # at import and shared by every caller (the FleetEngine bug class)
    cfg = dvfs.DVFSConfig() if cfg is None else cfg
    key = jax.random.PRNGKey(2_000) if key is None else key
    k_par, k_tr = jax.random.split(key)
    rth, tau, util, poll = sample_params(k_par, n_trials, fp, corr=corr)
    trial_keys = jax.random.split(k_tr, n_trials)

    lanes = _pack(n_trials)
    n_pkg = n_trials // lanes
    traces = _trial_traces(trial_keys, util, n_steps, kind, fp)   # [N, T]
    fleet_trace = traces.T.reshape(n_steps, n_pkg, lanes)

    lane_shape = (n_pkg, lanes)
    banks = thermal.pole_bank(rth.reshape(lane_shape),
                              tau.reshape(lane_shape), cfg.dt_ms)

    def survey(mode: str):
        eng = engine(n_trials, mode, backend=backend, devices=devices,
                     cfg=cfg, fp=fp, filtration_impl=filtration_impl,
                     plant=plant)
        pkg = None
        if plant == "pole":
            pkg = eng.sched.package_params(
                banks, poll_ticks=poll.reshape(lane_shape),
                batch_shape=(n_pkg,))
        # the oracle seeds each trial's ring with its opening density
        state = eng.init(n_pkg, pkg=pkg, filtration_fill=fleet_trace[0])
        _, sv = eng.run_survey(state, fleet_trace, burn_in=burn_in)
        return sv

    sb = survey("reactive_poll")
    sv = survey("v24")
    flat = lambda x: x.reshape(n_trials)
    return MCResult(peak_t_baseline=flat(sb.peak_t_c),
                    peak_t_v24=flat(sv.peak_t_c),
                    time_above_baseline=flat(sb.exceed_frac),
                    time_above_v24=flat(sv.exceed_frac),
                    perf_baseline=flat(sb.freq_mean),
                    perf_v24=flat(sv.freq_mean))


def run_reference(key=None, n_trials: int = 2_000, n_steps: int = 3_000,
                  kind: str = "inference", burn_in: int = 400,
                  cfg: dvfs.DVFSConfig | None = None,
                  fp: Fingerprint = FINGERPRINT) -> MCResult:
    """The original per-trial vmap oracle (one `dvfs` scan pair per trial).

    Kept as the ground truth the fleet-backed `run` is gated against; it
    bypasses the fleet engine entirely, so none of the fleet fast paths
    apply — O(W) ring refits every step, [T]-long per-trial traces, and a
    per-trial percentile sort.
    """
    cfg = dvfs.DVFSConfig() if cfg is None else cfg
    key = jax.random.PRNGKey(2_000) if key is None else key
    k_par, k_tr = jax.random.split(key)
    rth, tau, util, poll = sample_params(k_par, n_trials, fp)
    trial_keys = jax.random.split(k_tr, n_trials)

    def one_trial(rth_i, tau_i, util_i, poll_i, key_i):
        poles = thermal.PoleParams(
            decay=jnp.exp(-cfg.dt_ms / tau_i)[None], gain=rth_i[None])
        tr = workload.make_trace(key_i, n_steps, kind) * util_i
        tr = jnp.clip(tr, 0.4 * fp.rho_min, 1.3 * fp.rho_max)
        base = dvfs.simulate_reactive(tr, cfg, fp, poles=poles,
                                      poll_ticks=poll_i)
        v24 = dvfs.simulate_v24(tr, cfg, fp, poles=poles)
        tb, tv = base.temp[burn_in:], v24.temp[burn_in:]
        return (tb.max(), tv.max(),
                (tb > fp.t_crit_c).mean(), (tv > fp.t_crit_c).mean(),
                base.perf, v24.perf)

    pb, pv, ab, av, fb, fv = jax.vmap(one_trial)(rth, tau, util, poll,
                                                 trial_keys)
    return MCResult(peak_t_baseline=pb, peak_t_v24=pv,
                    time_above_baseline=ab, time_above_v24=av,
                    perf_baseline=fb, perf_v24=fv)


def uplift_by_workload(key=None, n_steps: int = 4_000,
                       cfg: dvfs.DVFSConfig | None = None,
                       fp: Fingerprint = FINGERPRINT) -> dict[str, float]:
    """Fig. 6 (right): V24 performance uplift per workload type."""
    cfg = dvfs.DVFSConfig() if cfg is None else cfg
    key = jax.random.PRNGKey(6) if key is None else key
    out = {}
    for i, kind in enumerate(workload.KINDS):
        # fold in the kind's INDEX — `hash(kind)` is salted per process
        # (PYTHONHASHSEED), which made the Fig. 6 numbers irreproducible
        # across runs
        tr = workload.make_trace(jax.random.fold_in(key, i), n_steps, kind)
        base = dvfs.simulate_reactive(tr, cfg, fp)
        v24 = dvfs.simulate_v24(tr, cfg, fp)
        out[kind] = float(dvfs.released_compute(base, v24))
    return out
