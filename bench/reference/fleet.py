"""Plain reference of the fleet scheduler's V24 / V7.0 step and of the flush
telemetry, written from the paper's equations and the program's documented
semantics, with no import from the program.

One step per package (batched over a leading package axis by plain
broadcasting): the density enters a ring of the last W steps; the PDU-gate
hint is the least-squares extrapolation of that ring ``lookahead`` steps
ahead (level = mean of the newest quarter), mapped to power and, on a
multi-tile package, coupled through the row-normalised N x N Gamma; the
DVFS law picks the largest frequency whose predicted junction rise stays in
the thermal budget (uniform law, and under Gamma also the coupled law and a
+0.05 slew limit); the pole bank then advances at the chosen power.
Lanes pinned to ``reactive_poll`` (or degraded by stale hints) run the
polled reactive governor with hysteresis instead.

The ring is kept in age order and refit every step (the O(W) definition),
where the program carries O(1) sliding sums; the two agree to rounding.

``dtype`` exists for the control run: the same reference computed in
bfloat16.  The Gamma products run at ``highest`` precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# fingerprint constants (paper §4.1; Appendix B density/throughput domain)
RTH = 0.45
TAU1_MS, TAU2_MS, A1_FRAC = 5.0, 80.0, 0.35
TAU_MS = 80.0
T_CRIT, T_AMB = 85.0, 45.0
RHO_MIN, RHO_MAX = 0.9, 2.7
ALPHA, BETA = 63.0, -1256.6
RTOK_MIN, RTOK_MAX = 20.20, 20.85
RTOK_SLOPE = (RTOK_MAX - RTOK_MIN) / (RHO_MAX - RHO_MIN)
RTOK_ICEPT = RTOK_MIN - RTOK_SLOPE * RHO_MIN
KAPPA_NM_PER_C = 0.0852

GAMMA_SELF, GAMMA_VERTICAL, GAMMA_LATERAL, GAMMA_DISTANT = 1.0, 0.80, 0.275, 0.07


def rtok(rho):
    return RTOK_ICEPT + RTOK_SLOPE * rho


def power(rho):
    """Tile power: steady-state inversion P = (alpha * R_tok(rho) + beta) / Rth."""
    return (ALPHA * rtok(rho) + BETA) / RTH


def coupling(n_tiles: int) -> np.ndarray:
    """Row-normalised Gamma [n, n] on a near-square grid (paper §5.1):
    1 on the diagonal, 0.80 at Manhattan distance 1, 0.275 at the diagonal
    neighbour, 0.07 elsewhere at distance 2-3, 0 beyond."""
    cols = int(np.ceil(np.sqrt(n_tiles)))
    idx = np.arange(n_tiles)
    xy = np.stack([idx // cols, idx % cols], axis=1)
    d = np.abs(xy[:, None, :] - xy[None, :, :])
    man, cheb = d.sum(-1), d.max(-1)
    g = np.zeros((n_tiles, n_tiles))
    g[(man >= 2) & (man <= 3)] = GAMMA_DISTANT
    g[(cheb == 1) & (man == 2)] = GAMMA_LATERAL
    g[man == 1] = GAMMA_VERTICAL
    g[man == 0] = GAMMA_SELF
    g = g.astype(np.float32)
    return g / g.sum(axis=1, keepdims=True)


class FleetRef:
    """Reference stepper for one scheduler configuration (a dict of the
    configuration file's ``scheduler`` fields)."""

    def __init__(self, sched: dict, dtype=jnp.float32):
        c = self.c = dict(sched)
        if c["mode"] != "v24":
            raise ValueError("the fleet reference covers mode 'v24'")
        self.dtype = dtype
        n = c["n_tiles"]
        dt = c["step_ms"]
        if c["two_pole"]:
            self.decay = np.exp(np.asarray([-dt / TAU1_MS, -dt / TAU2_MS],
                                           np.float32))
            self.gain = np.asarray([A1_FRAC * RTH, (1 - A1_FRAC) * RTH],
                                   np.float32)
        else:
            self.decay = np.asarray([np.exp(np.float32(-dt / TAU_MS))],
                                    np.float32)
            self.gain = np.asarray([RTH], np.float32)
        self.ahead = c["lookahead_steps"]
        self.eta = float(np.float32(1) - self.decay[-1]
                         ** np.float32(self.ahead))
        self.gain_sum = float(self.gain.sum())
        self.gamma = (coupling(n) if c["use_coupling"] and n > 1 else None)
        self.t_allow = T_CRIT - c["t_safe_margin_c"] - T_AMB
        self.ramp = (1.0 - c["throttle_level"]) / max(
            int(c["recover_ms"] / dt), 1)
        self.poll = max(int(c["poll_interval_ms"] / dt), 1)
        self.reactive_plane = c["mixed_mode"] or c["degraded_fallback"]

    # ------------------------------------------------------------ state
    def init(self, n: int) -> dict:
        c, f = self.c, self.dtype
        t = c["n_tiles"]
        st = {"th": jnp.zeros((n, t, len(self.decay)), f),
              "hist": jnp.full((n, c["filtration_window"], t), RHO_MIN, f),
              "freq": jnp.ones((n, t), f),
              "step": jnp.zeros((), jnp.int32),
              "events": jnp.zeros((n,), jnp.int32)}
        if self.reactive_plane:
            st["thr"] = jnp.zeros((n, t), bool)
        if c["degraded_fallback"]:
            st["rho_last"] = jnp.full((n, t), RHO_MIN, f)
            st["stale"] = jnp.zeros((n,), jnp.int32)
            st["degraded"] = jnp.zeros((n,), bool)
        return st

    def _couple(self, p):
        if self.gamma is None:
            return p
        g = jnp.asarray(self.gamma, self.dtype)
        return jnp.einsum("ij,nj->ni", g, p,
                          precision=jax.lax.Precision.HIGHEST)

    def _predict(self, hist):
        w = hist.shape[1]
        t = jnp.arange(w, dtype=self.dtype)
        tc = (t - (w - 1) / 2.0)[None, :, None]
        slope = (tc * hist).sum(1) / ((w * (w * w - 1)) / 12.0)
        recent = hist[:, w - max(w // 4, 1):, :].mean(1)
        return jnp.clip(recent + slope * self.ahead, 0.0, 1.5 * RHO_MAX)

    def step(self, st: dict, rho, pins=None):
        """One scheduler step; returns (state', temp [n, t], freq [n, t],
        sanitised rho [n, t])."""
        c = self.c
        pe = c["power_exponent"]
        rho = rho.astype(self.dtype)
        st = dict(st)
        reactive = pins
        if c["degraded_fallback"]:
            finite = jnp.isfinite(rho)
            valid = jnp.all(finite, axis=-1)
            rho = jnp.where(finite, rho, st["rho_last"])
            lim, rec = c["stale_limit_steps"], c["recover_steps"]
            stale = jnp.where(valid, jnp.maximum(st["stale"] - 1, 0),
                              jnp.minimum(st["stale"] + 1, lim + rec))
            degraded = (st["degraded"] & (stale > 0)) | (stale >= lim)
            st.update(rho_last=rho, stale=stale, degraded=degraded)
            reactive = degraded if pins is None else (degraded | pins)
        hist = jnp.concatenate([st["hist"][:, 1:], rho[:, None, :]], axis=1)
        p_now = power(rho)
        dt_now = st["th"].sum(-1)
        hint = jnp.maximum(self._couple(power(self._predict(hist))),
                           self._couple(p_now))
        budget = (self.t_allow - (1.0 - self.eta) * dt_now) / (
            self.eta * self.gain_sum)
        f = jnp.clip((budget / jnp.maximum(hint, 1e-3)) ** (1.0 / pe),
                     0.05, 1.0)
        if self.gamma is not None:
            gd = jnp.asarray(np.diagonal(self.gamma), self.dtype)
            p_prev = p_now * st["freq"] ** pe
            neigh = self._couple(p_prev) - gd * p_prev
            f_cpl = jnp.clip((jnp.maximum(budget - neigh, 1e-6)
                              / jnp.maximum(gd * p_now, 1e-3)) ** (1.0 / pe),
                             0.05, 1.0)
            f = jnp.minimum(jnp.minimum(f, f_cpl), st["freq"] + 0.05)
        decay = jnp.asarray(self.decay, self.dtype)
        gain = jnp.asarray(self.gain, self.dtype)
        if reactive is None:
            p_eff = self._couple(p_now * f ** pe)
            th = decay * st["th"] + (1.0 - decay) * gain * p_eff[..., None]
            temp = T_AMB + th.sum(-1)
            events = st["events"] + jnp.any(temp > T_CRIT, -1)
        else:
            r = reactive[:, None]
            p_eff = self._couple(p_now * jnp.where(r, st["freq"], f) ** pe)
            th = decay * st["th"] + (1.0 - decay) * gain * p_eff[..., None]
            temp = T_AMB + th.sum(-1)
            polled = (st["step"] % self.poll) == 0
            trig = (temp >= T_CRIT) & polled
            cool = (temp <= c["resume_below_c"]) & polled
            thr = jnp.where(r, (st["thr"] | trig) & ~cool, False)
            f = jnp.where(r, jnp.where(thr, c["throttle_level"],
                                       jnp.minimum(st["freq"] + self.ramp,
                                                   1.0)), f)
            events = st["events"] + jnp.where(
                reactive, jnp.any(trig & ~st["thr"], -1),
                jnp.any(temp > T_CRIT, -1))
            st["thr"] = thr
        st.update(th=th, hist=hist, freq=f, step=st["step"] + 1,
                  events=events.astype(jnp.int32))
        return st, temp, f, rho

    # ------------------------------------------------------------ windows
    @functools.partial(jax.jit, static_argnums=(0,))
    def advance(self, st, chunk, pins=None):
        """Step a [T, n, t] chunk; no telemetry."""
        def body(s, rho):
            s, *_ = self.step(s, rho, pins)
            return s, None
        st, _ = jax.lax.scan(body, st, chunk)
        return st

    @functools.partial(jax.jit, static_argnums=(0,))
    def window(self, st, chunk, pins=None, active=None):
        """Step a [T, n, t] chunk and reduce the window's fleet telemetry
        over the active lanes.  Returns (state', telemetry dict, lane dict
        for per-tenant statistics)."""
        n = chunk.shape[1]
        active = jnp.ones((n,), bool) if active is None else active
        ev0 = st["events"]

        def body(s, rho):
            prev = jnp.where(active, s["events"], 0).sum()
            s, temp, f, rho_s = self.step(s, rho, pins)
            rec = _step_record(temp.astype(jnp.float32),
                               f.astype(jnp.float32),
                               rho_s.astype(jnp.float32), active,
                               s["events"], prev,
                               s.get("degraded"),
                               self.c["straggler_threshold"])
            return s, (rec, temp.astype(jnp.float32), f.astype(jnp.float32))

        st, (recs, temps, freqs) = jax.lax.scan(body, st, chunk)
        telem = {
            "n_packages": recs["n_packages"][-1],
            "events_total": recs["events_total"][-1],
            "events_step": recs["events_step"].sum(),
            "temp_p50_c": recs["temp_p50_c"].mean(),
            "temp_p99_c": recs["temp_p99_c"].max(),
            "temp_max_c": recs["temp_max_c"].max(),
            "temp_var_c2": recs["temp_var_c2"].mean(),
            "freq_mean": recs["freq_mean"].mean(),
            "freq_min": recs["freq_min"].min(),
            "released_mtps": recs["released_mtps"].mean(),
            "throttled_mtps": recs["throttled_mtps"].mean(),
            "at_risk_frac": recs["at_risk_frac"].mean(),
            "degraded_count": recs["degraded_count"].max(),
        }
        thr = self.c["straggler_threshold"]
        lanes = {
            "peak": temps.max(axis=(0, 2)),
            "fmin": freqs.min(axis=(0, 2)),
            "fsum": freqs.sum(axis=(0, 2)),
            "risk": (freqs < thr).sum(axis=(0, 2)),
            "swing": (temps.max(0) - temps.min(0)).max(-1),
            "events": st["events"] - ev0,
            "degraded": (st["degraded"] if "degraded" in st
                         else jnp.zeros((n,), bool)),
        }
        return st, telem, lanes


def _step_record(temp, f, rho, active, events, prev_events, degraded,
                 straggler):
    """One step's fleet telemetry over the active lanes ([n, t] arrays)."""
    m = jnp.broadcast_to(active[:, None], temp.shape).reshape(-1)
    cnt = jnp.maximum(m.sum(), 1)
    tv, fv = temp.reshape(-1), f.reshape(-1)
    srt = jnp.sort(jnp.where(m, tv, jnp.inf))
    mu = jnp.where(m, tv, 0.0).sum() / cnt
    rt = jnp.broadcast_to(rtok(rho), temp.shape).reshape(-1)
    ev = jnp.where(active, events, 0).sum()
    deg = (jnp.zeros((), jnp.int32) if degraded is None
           else (degraded & active).sum().astype(jnp.int32))
    return {
        "n_packages": active.sum().astype(jnp.int32),
        "events_total": ev,
        "events_step": ev - prev_events,
        "temp_p50_c": _quantile(srt, cnt, 50.0),
        "temp_p99_c": _quantile(srt, cnt, 99.0),
        "temp_max_c": jnp.where(m, tv, -jnp.inf).max(),
        "temp_var_c2": jnp.where(m, (tv - mu) ** 2, 0.0).sum() / cnt,
        "freq_mean": jnp.where(m, fv, 0.0).sum() / cnt,
        "freq_min": jnp.where(m, fv, jnp.inf).min(),
        "released_mtps": jnp.where(m, rt * fv, 0.0).sum(),
        "throttled_mtps": jnp.where(m, rt * (1.0 - fv), 0.0).sum(),
        "at_risk_frac": jnp.where(m, fv < straggler, False).sum() / cnt,
        "degraded_count": deg,
    }


def _quantile(sorted_v, cnt, q):
    """Linear-interpolated percentile of the first ``cnt`` sorted entries
    (numpy's default definition)."""
    pos = q / 100.0 * (cnt - 1).astype(jnp.float32)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    frac = pos - lo
    return sorted_v[lo] * (1.0 - frac) + sorted_v[hi] * frac


def tenant_stats(lanes: dict, groups: dict[str, np.ndarray], n_steps: int,
                 n_tiles: int) -> dict:
    """Per-tenant window statistics from the reference's per-lane
    reductions; ``groups`` maps tenant name to its lane indices."""
    h = {k: np.asarray(v) for k, v in lanes.items()}
    out = {}
    for name, idx in groups.items():
        denom = max(len(idx), 1) * n_steps * n_tiles
        out[name] = {
            "n_lanes": len(idx),
            "temp_peak_c": float(h["peak"][idx].max()),
            "freq_min": float(h["fmin"][idx].min()),
            "freq_mean": float(h["fsum"][idx].astype(np.float64).sum()
                               / denom),
            "at_risk_frac": float(h["risk"][idx].sum() / denom),
            "events": int(h["events"][idx].sum()),
            "drift_nm": float(h["swing"][idx].max() * KAPPA_NM_PER_C),
            "degraded_lanes": int(h["degraded"][idx].sum()),
        }
    return out
