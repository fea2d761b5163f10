"""Plain reference of the paper's §10 Monte Carlo: one independent
simulation per trial (``jax.vmap`` over trials of two ``lax.scan`` loops),
copied from the program's per-trial oracle (`run_reference` over the
`dvfs` simulators) so that the benchmark's yardstick does not move with
the program.  Nothing here touches the fleet engine.

Per trial: Rth ~ 0.45 (1 + 0.08 z), tau ~ 80 (1 + 0.12 z) ms, workload
utilisation 1.02 + 0.15 z, OEM polling period uniform in [15, 76) ms; an
inference trace scaled by the utilisation; the reactive governor
(polled, with hysteresis) and the V24 law each run the trial's one-pole
plant over it.  Statistics are taken past a burn-in.

``dtype`` exists for the control run (the same reference in bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.fleet import RHO_MAX, RHO_MIN, T_AMB, T_CRIT, power
from bench.reference.workload import make_trace

# DVFS configuration of the §10 experiment (1 kHz steps)
DT_MS = 1.0
LOOKAHEAD_MS = 35.0
WINDOW = 64
MARGIN_C = 0.5
THROTTLE = 0.55
RESUME_C = 66.0
RECOVER_MS = 100.0
POWER_EXP = 3.0
RTH, TAU_MS = 0.45, 80.0


def sample_params(key, n_trials: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    rth = RTH * (1 + 0.08 * jax.random.normal(k1, (n_trials,)))
    tau = TAU_MS * (1 + 0.12 * jax.random.normal(k2, (n_trials,)))
    util = 1.02 + 0.15 * jax.random.normal(k3, (n_trials,))
    poll = jax.random.randint(k4, (n_trials,), 15, 76)
    return (jnp.clip(rth, 0.25, 0.70), jnp.clip(tau, 30.0, 160.0),
            jnp.clip(util, 0.5, 1.35), poll)


def _reactive(rho, decay, gain, poll, dtype):
    ramp = (1.0 - THROTTLE) / max(int(RECOVER_MS / DT_MS), 1)

    def tick(carry, inp):
        x, f, thr = carry
        r, k = inp
        x = decay * x + (1.0 - decay) * gain * (power(r) * f ** POWER_EXP)
        t = T_AMB + x
        polled = (k % poll) == 0
        trig = (t >= T_CRIT) & polled
        cool = (t <= RESUME_C) & polled
        thr = (thr | trig) & ~cool
        f = jnp.where(thr, jnp.asarray(THROTTLE, dtype),
                      jnp.minimum(f + ramp, 1.0))
        return (x, f, thr), (f, t)

    init = (jnp.zeros((), dtype), jnp.ones((), dtype), jnp.zeros((), bool))
    _, (fs, ts) = jax.lax.scan(tick, init, (rho, jnp.arange(rho.shape[0])))
    return fs, ts


def _v24(rho, decay, gain, dtype):
    eta = 1.0 - decay ** (LOOKAHEAD_MS / DT_MS)
    t_allow = T_CRIT - MARGIN_C - T_AMB
    ahead = LOOKAHEAD_MS / DT_MS
    tc = jnp.arange(WINDOW, dtype=dtype) - (WINDOW - 1) / 2.0
    denom = (WINDOW * (WINDOW * WINDOW - 1)) / 12.0
    q = max(WINDOW // 4, 1)

    def tick(carry, r):
        x, hist = carry
        hist = jnp.concatenate([hist[1:], r[None]])
        slope = (tc * hist).sum() / denom
        pred = jnp.clip(hist[-q:].mean() + slope * ahead, 0.0, 1.5 * RHO_MAX)
        p_now = power(r)
        h = jnp.maximum(power(pred), p_now)
        budget = (t_allow - (1.0 - eta) * x) / (eta * gain)
        f = jnp.clip((budget / jnp.maximum(h, 1e-3)) ** (1.0 / POWER_EXP),
                     0.05, 1.0)
        x = decay * x + (1.0 - decay) * gain * (p_now * f ** POWER_EXP)
        return (x, hist), (f, T_AMB + x)

    init = (jnp.zeros((), dtype), jnp.full((WINDOW,), rho[0], dtype))
    _, (fs, ts) = jax.lax.scan(tick, init, rho)
    return fs, ts


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def experiment(key, n_trials: int, n_steps: int, burn_in: int = 400,
               dtype=jnp.float32) -> dict:
    """Per-trial arrays of one paired experiment: peak temperature and time
    above T_crit past the burn-in, and mean delivered frequency, for the
    reactive baseline and V24."""
    k_par, k_tr = jax.random.split(key)
    rth, tau, util, poll = sample_params(k_par, n_trials)
    keys = jax.random.split(k_tr, n_trials)

    def one(rth_i, tau_i, util_i, poll_i, key_i):
        tr = make_trace(key_i, n_steps, "inference")[:, 0] * util_i
        tr = jnp.clip(tr, 0.4 * RHO_MIN, 1.3 * RHO_MAX).astype(dtype)
        decay = jnp.exp(-DT_MS / tau_i).astype(dtype)
        gain = rth_i.astype(dtype)
        fb, tb = _reactive(tr, decay, gain, poll_i, dtype)
        fv, tv = _v24(tr, decay, gain, dtype)
        tb, tv = tb[burn_in:], tv[burn_in:]
        f32 = lambda x: x.astype(jnp.float32)
        return (f32(tb.max()), f32(tv.max()),
                f32((tb > T_CRIT).mean()), f32((tv > T_CRIT).mean()),
                f32(fb).mean(), f32(fv).mean())

    out = jax.vmap(one)(rth, tau, util, poll, keys)
    names = ("peak_t_baseline", "peak_t_v24", "time_above_baseline",
             "time_above_v24", "perf_baseline", "perf_v24")
    return dict(zip(names, out))


def stats(r: dict) -> dict:
    """The §10 summary statistics of one experiment's per-trial arrays."""
    b, v = r["peak_t_baseline"], r["peak_t_v24"]
    up = r["perf_v24"] / r["perf_baseline"] - 1
    return {
        "baseline_mean_c": float(b.mean()),
        "baseline_std_c": float(b.std()),
        "baseline_time_above_frac": float(r["time_above_baseline"].mean()),
        "v24_mean_c": float(v.mean()),
        "v24_std_c": float(v.std()),
        "v24_time_above_frac": float(r["time_above_v24"].mean()),
        "uplift_mean": float(up.mean()),
        "uplift_p5": float(jnp.percentile(up, 5)),
        "uplift_p95": float(jnp.percentile(up, 95)),
    }
