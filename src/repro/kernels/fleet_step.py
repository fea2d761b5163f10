"""Pallas TPU kernel: the WHOLE fleet scheduler step, fused over a K-step chunk.

`thermal_conv.py` fuses only the thermal plant; at fleet scale the paper's
headline loop (density → filtration → PDU-gate hint → v24 control law →
two-pole plant → event count, 90 000 steps at the 1 kHz telemetry rate for
thousands of packages) still crosses HBM once per step per stage.  This
kernel advances a [packages × tiles] block over a K-step density chunk
entirely in VMEM:

  * layout: packages on the 128-lane axis, tiles (padded to the 8-sublane
    f32 tile) on the sublane axis — every per-tile op is a VPU op over the
    package lanes, and the Γ coupling is a tiny [tp, tp] × [tp, blk] MXU
    matmul;
  * grid: 2-D (package-block, time-chunk), extending `thermal_conv.py`'s
    sequential-grid VMEM-scratch accumulator: the ring buffer, sliding
    filtration statistics (same closed form as `pdu_gate.FiltrationStats`),
    two-pole state, frequency and event counters persist in scratch across
    the time chunks of one package block;
  * the filtration is the O(1) incremental form: two dynamic sublane reads
    (evictions) + three FMAs per step — the window is never gathered;
  * outputs stream the per-step junction temperatures and frequencies (the
    telemetry plane reduces them outside, in the same jitted program) plus
    the final ring/thermal state.

Caller contract (`repro.fleet.backends.fused` / `sharded_fused`):

  * the ring is normalised to age-order (ptr = 0) before the call and the
    scheduler-state pytree is rebuilt from the kernel outputs after — the
    kernel's flat VMEM state never leaks upward, so `update()`-level code
    (and the control plane's lane surgery) sees one state layout across
    all five backends;
  * heterogeneous per-package physics (`het` rows: pole constants, η,
    t_crit, poll periods drawn per package) enter as [packages]-wide
    planes broadcast over the sublane axis — resident in VMEM for the
    whole block, so per-package variation costs no extra HBM traffic;
  * outputs are fresh buffers: with donation enabled the inputs are
    consumed, and callers must rebind the returned state (the engine
    enforces this — see `core/scheduler.py`'s state contract);
  * a non-divisible trace tail is the CALLER's problem: `run_chunked` /
    `stream()` hand the tail in as its own shorter chunk (separate flush
    window), never padded into this kernel's time grid.

Interpret mode is the off-TPU fallback, verified against the pure-JAX
engine to ≤1e-5 (tests/test_fleet_fused.py).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128      # package-lane block
SUBLANE = 8     # f32 sublane tile — n_tiles padded up to a multiple


@dataclasses.dataclass(frozen=True)
class FleetStepParams:
    """Static (python-level) scheduler constants baked into the kernel."""

    window: int            # filtration depth W
    recent: int            # newest-quarter depth Q
    n_poles: int
    mode: str              # v24 | reactive | off
    use_gamma: bool
    power_exponent: float
    eta: float
    t_allow: float         # t_crit − margin − t_ambient
    gain_sum: float        # Σ pole gains
    ahead: float           # lookahead_ms / step_ms
    # power_from_rho's affine chain, kept as the SAME op sequence as
    # repro.core.density (ρ → R_tok → ΔT → P) so the kernel's floats track
    # the pure path op-for-op: P = (α·(r_icept + r_slope·ρ) + β) / Rth
    rtok_slope: float
    rtok_icept: float
    alpha: float
    beta: float
    rth: float
    rho_hi: float          # predict_rho clip ceiling (1.5·ρ_max)
    t_crit_c: float
    t_ambient_c: float
    throttle_floor: float
    decay: tuple           # per-pole a_i = exp(−dt/τ_i), python floats
    gain: tuple            # per-pole G_i [°C/W]
    # reactive_poll baseline constants (mode == "reactive_poll"); per-package
    # polling periods override ``poll_ticks`` via the heterogeneous rows
    throttle_level: float = 0.55
    resume_below_c: float = 66.0
    ramp: float = 0.045    # per-step frequency ramp-back
    poll_ticks: int = 25   # homogeneous sensor polling period [steps]
    # degraded fallback (mode == "v24" + SchedulerConfig.degraded_fallback):
    # packages with stale hints run the reactive_poll law in-kernel; the
    # per-package staleness/mode rows ride in VMEM beside the het rows
    fallback: bool = False
    stale_limit: int = 5   # consecutive stale steps before fallback
    recover: int = 10      # hysteresis: fresh steps before recovery
    # operator-pinned controller mode (mode == "v24" +
    # SchedulerConfig.mixed_mode): a [n]-wide 0/1 input plane pins lanes to
    # reactive_poll semantics through the SAME merged branch the fallback
    # uses — the plane is chunk-constant (a VALUE, so canary shifts reuse
    # the compiled kernel) and ORs with the staleness latch when both ride
    mixed: bool = False


def _pad_axis(x, n, axis, value=0.0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def _kernel(rho_ref, gamma_ref, buf0_ref, th0_ref, stats0_ref, freq0_ref,
            ev0_ref, het_ref, thr0_ref, step0_ref, fb0_ref, mode0_ref,
            temp_ref, freqs_ref, buf_ref, th_ref, ev_ref, thr_ref, fb_ref,
            ring_scr, th_scr, stat_scr, f_scr, e_scr, thr_scr, fb_scr, *,
            ck: int, tp: int, n_tiles: int, het: bool, p: FleetStepParams):
    c = pl.program_id(1)
    w, q, np_ = p.window, p.recent, p.n_poles
    tm = (p.window - 1) / 2.0
    denom = p.window * (p.window * p.window - 1) / 12.0
    inv_exp = 1.0 / p.power_exponent

    @pl.when(c == 0)
    def _load_state():
        ring_scr[...] = buf0_ref[...]
        th_scr[...] = th0_ref[...]
        stat_scr[...] = stats0_ref[...]
        f_scr[...] = freq0_ref[...]
        e_scr[...] = ev0_ref[...]
        thr_scr[...] = thr0_ref[...]
        fb_scr[...] = fb0_ref[...]

    gamma = gamma_ref[...]                                   # [tp, tp]
    if p.use_gamma:
        rows = jax.lax.broadcasted_iota(jnp.int32, (tp, tp), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (tp, tp), 1)
        gdiag = jnp.sum(jnp.where(rows == cols, gamma, 0.0), axis=1,
                        keepdims=True)                       # [tp, 1]

    # ask the MXU for full f32 precision: a single bf16 pass would round
    # the density ring and Γ products to ~3 significant digits
    hi = jax.lax.Precision.HIGHEST

    def couple(x):                                           # Γ @ x over tiles
        return jnp.dot(gamma, x, precision=hi,
                       preferred_element_type=jnp.float32)

    # per-package physics: with the heterogeneous rows resident in VMEM,
    # every pole/η/ΣG/poll constant becomes a [tp, blk] plane read; the
    # homogeneous path keeps the baked python-float constants (bit-identical
    # products — the floats are the same f32 values)
    if het:
        hrow = lambda r: het_ref[pl.ds(r * tp, tp), :]
        decay = [hrow(j) for j in range(np_)]
        gain = [hrow(np_ + j) for j in range(np_)]
        eta_l = hrow(2 * np_)
        gsum_l = hrow(2 * np_ + 1)
        poll_l = hrow(2 * np_ + 2).astype(jnp.int32)
    else:
        decay, gain, poll_l = p.decay, p.gain, p.poll_ticks

    def tick(i, _):
        step = c * ck + i
        ptr = step % w                   # caller rolled the ring to ptr0 = 0
        rho = rho_ref[i]                                     # [tp, blk]

        if p.fallback:
            # staleness plane (mirrors the pure path in core/scheduler.py):
            # non-finite density entries mark a stale hint stream — hold
            # the last finite value so the filtration stays warm, count
            # staleness per PACKAGE lane, latch the degraded flag with
            # hysteresis.  Padded tile rows and padded lanes carry the
            # benign finite fill, so the min-over-tiles validity test can
            # never degrade a phantom.  f32 counters are exact at these
            # magnitudes (abs(x) < inf is False for both NaN and ±inf).
            finite = jnp.abs(rho) < jnp.inf                  # [tp, blk]
            rho = jnp.where(finite, rho, fb_scr[0:tp, :])
            valid = jnp.min(jnp.where(finite, 1.0, 0.0), axis=0,
                            keepdims=True)                   # [1, blk]
            stale = fb_scr[tp:tp + 1, :]
            stale_n = jnp.where(
                valid > 0.5, jnp.maximum(stale - 1.0, 0.0),
                jnp.minimum(stale + 1.0, float(p.stale_limit + p.recover)))
            deg = jnp.maximum(
                jnp.where((fb_scr[tp + 1:tp + 2, :] > 0.5)
                          & (stale_n > 0.5), 1.0, 0.0),
                jnp.where(stale_n >= float(p.stale_limit), 1.0, 0.0))
            fb_scr[0:tp, :] = rho
            fb_scr[tp:tp + 1, :] = stale_n
            fb_scr[tp + 1:tp + 2, :] = deg
        if p.mixed:
            # operator pin rides the same merged branch: a pinned lane is
            # reactive whether or not the staleness latch fired — the row
            # is input-only (chunk-constant), never latched into fb state
            mrow = mode0_ref[...]                            # [1, blk]
            deg = jnp.maximum(deg, mrow) if p.fallback else mrow

        # -- incremental filtration: O(1) evict-reads + FMAs ---------------
        x_old = ring_scr[pl.ds(ptr * tp, tp), :]
        x_rec = ring_scr[pl.ds(((ptr + w - q) % w) * tp, tp), :]
        wsum = stat_scr[0:tp, :]
        csum = stat_scr[tp:2 * tp, :]
        rsum = stat_scr[2 * tp:3 * tp, :]
        wsum_n = wsum - x_old + rho
        csum_n = csum - wsum + (tm + 1.0) * x_old + tm * rho
        rsum_n = rsum - x_rec + rho
        ring_scr[pl.ds(ptr * tp, tp), :] = rho

        # exact refresh at wraparound (same contract as the pure-JAX
        # `pdu_gate._observe_stats`): recompute the three sums from the
        # whole ring — at ptr 0 the ring is age-ordered, so each sum is a
        # constant [tp, W·tp] selection/weight matrix applied on the MXU.
        # Runs once every W steps, bounding drift over arbitrary chunks.
        def _refresh():
            rows = jax.lax.broadcasted_iota(jnp.int32, (tp, w * tp), 1)
            tiles = jax.lax.broadcasted_iota(jnp.int32, (tp, w * tp), 0)
            sel = (rows % tp == tiles).astype(jnp.float32)
            age = (rows // tp).astype(jnp.float32)
            ring = ring_scr[...]
            mm = lambda m: jnp.dot(m, ring, precision=hi,
                                   preferred_element_type=jnp.float32)
            return (mm(sel), mm(sel * (age - tm)),
                    mm(sel * (age >= w - q).astype(jnp.float32)))

        wsum_n, csum_n, rsum_n = jax.lax.cond(
            (step + 1) % w == 0, _refresh,
            lambda: (wsum_n, csum_n, rsum_n))
        stat_scr[0:tp, :] = wsum_n
        stat_scr[tp:2 * tp, :] = csum_n
        stat_scr[2 * tp:3 * tp, :] = rsum_n

        power_from = lambda r: (p.alpha * (p.rtok_icept + p.rtok_slope * r)
                                + p.beta) / p.rth
        p_now = power_from(rho)
        f_prev = f_scr[...]
        real = (jax.lax.broadcasted_iota(jnp.int32, (tp, 1), 0) < n_tiles)

        def plant(freq_used):
            """Advance the pole bank at ``freq_used``; returns the new
            junction temperature (scratch updated in place)."""
            power = p_now * freq_used ** p.power_exponent
            p_eff = couple(power) if p.use_gamma else power
            dt_next = jnp.zeros((tp, p_now.shape[-1]), jnp.float32)
            for j in range(np_):
                st_j = decay[j] * th_scr[j * tp:(j + 1) * tp, :] \
                    + (1.0 - decay[j]) * gain[j] * p_eff
                th_scr[j * tp:(j + 1) * tp, :] = st_j
                dt_next = dt_next + st_j
            return p.t_ambient_c + dt_next

        if p.mode == "reactive_poll":
            # §9 baseline: the plant runs at LAST step's frequency, the
            # sensor only observes every poll interval, and the throttle
            # latch (scratch, f32 0/1) carries the hysteresis.  ``events``
            # counts fresh trigger engagements, not crossings.  Polling
            # phase follows the GLOBAL scheduler step (step0 + local) so
            # chunk boundaries never reset a package's sensor cadence.
            temp = plant(f_prev)
            step_g = step0_ref[0, 0].astype(jnp.int32) + step
            polled = (step_g % poll_l) == 0
            trig = (temp >= p.t_crit_c) & polled
            cool = (temp <= p.resume_below_c) & polled
            thr = thr_scr[...] > 0.5
            fresh = jnp.max(
                jnp.where(real, (trig & ~thr).astype(jnp.float32), 0.0),
                axis=0, keepdims=True)                       # any real tile
            e_scr[...] = e_scr[...] + fresh
            thr_n = (thr | trig) & ~cool
            freq = jnp.where(thr_n, p.throttle_level,
                             jnp.minimum(f_prev + p.ramp, 1.0))
            thr_scr[...] = thr_n.astype(jnp.float32)
            f_scr[...] = freq
            temp_ref[pl.ds(i, 1)] = temp[None]
            freqs_ref[pl.ds(i, 1)] = freq[None]
            return 0

        dt_now = th_scr[0:tp, :]
        for j in range(1, np_):
            dt_now = dt_now + th_scr[j * tp:(j + 1) * tp, :]

        # -- PDU-gate hint + v24 control law -------------------------------
        if p.mode == "v24":
            pred = jnp.clip(rsum_n / q + (csum_n / denom) * p.ahead,
                            0.0, p.rho_hi)
            p_ahead = power_from(pred)
            if p.use_gamma:
                hint = jnp.maximum(couple(p_ahead), couple(p_now))
            else:
                hint = jnp.maximum(p_ahead, p_now)
            if het:
                # per-package η/ΣG planes, same op order as the pure path
                # (explicit reciprocal-multiply, matching the pure budget)
                budget = (p.t_allow - (1.0 - eta_l) * dt_now) \
                    * (1.0 / (eta_l * gsum_l))
            else:
                # η·gain_sum multiplied in f32 like the pure path (gain_sum
                # is a traced f32 scalar there) — keeps budget bit-aligned
                budget = (p.t_allow - (1.0 - p.eta) * dt_now) \
                    * (1.0 / (jnp.float32(p.eta) * jnp.float32(p.gain_sum)))
            f_uni = jnp.clip((budget / jnp.maximum(hint, 1e-3)) ** inv_exp,
                             0.05, 1.0)
            if p.use_gamma:
                p_prev = p_now * f_prev ** p.power_exponent
                neigh = couple(p_prev) - gdiag * p_prev
                f_cpl = jnp.clip(
                    (jnp.maximum(budget - neigh, 1e-6)
                     / jnp.maximum(gdiag * p_now, 1e-3)) ** inv_exp,
                    0.05, 1.0)
                freq = jnp.minimum(jnp.minimum(f_uni, f_cpl), f_prev + 0.05)
            else:
                freq = f_uni
        elif p.mode == "reactive":
            hot = (p.t_ambient_c + dt_now) >= p.t_crit_c
            freq = jnp.where(hot, p.throttle_floor,
                             jnp.minimum(f_prev + 0.1, 1.0))
        else:                                                # off
            freq = jnp.ones_like(f_prev)

        # -- plant + events -----------------------------------------------
        if (p.fallback or p.mixed) and p.mode == "v24":
            # merged plant: degraded lanes run reactive_poll semantics
            # (plant at LAST step's frequency, polled sensor, throttle
            # hysteresis in thr_scr), healthy lanes take the v24 law — the
            # plant steps ONCE at the per-lane blended frequency.  With
            # deg all-zero every `where` takes the v24 branch bitwise.
            # Mosaic cannot select between boolean planes, nor broadcast a
            # [1, blk] mask inside a select: the mode mask is broadcast to
            # the tile plane once, and the latch is masked with `&`.
            deg_b = jnp.broadcast_to(deg, (tp, deg.shape[-1])) > 0.5
            temp = plant(jnp.where(deg_b, f_prev, freq))
            step_g = step0_ref[0, 0].astype(jnp.int32) + step
            polled = (step_g % poll_l) == 0
            trig = (temp >= p.t_crit_c) & polled
            cool = (temp <= p.resume_below_c) & polled
            thr = thr_scr[...] > 0.5
            thr_n = deg_b & (thr | trig) & ~cool
            freq = jnp.where(
                deg_b,
                jnp.where(thr_n, p.throttle_level,
                          jnp.minimum(f_prev + p.ramp, 1.0)),
                freq)
            fresh = jnp.max(
                jnp.where(real, (trig & ~thr).astype(jnp.float32), 0.0),
                axis=0, keepdims=True)
            crossed = jnp.max(
                jnp.where(real, (temp > p.t_crit_c).astype(jnp.float32),
                          0.0),
                axis=0, keepdims=True)
            e_scr[...] = e_scr[...] + jnp.where(deg > 0.5, fresh, crossed)
            thr_scr[...] = thr_n.astype(jnp.float32)
            f_scr[...] = freq
            temp_ref[pl.ds(i, 1)] = temp[None]
            freqs_ref[pl.ds(i, 1)] = freq[None]
            return 0

        temp = plant(freq)
        # event = any REAL tile over t_crit: mask the padded phantom tile
        # rows so they can never inflate a package's counter (they sit at a
        # benign fill temperature, but t_crit is caller-configurable)
        crossed = jnp.max(
            jnp.where(real, (temp > p.t_crit_c).astype(jnp.float32), 0.0),
            axis=0, keepdims=True)                           # any over tiles
        e_scr[...] = e_scr[...] + crossed
        f_scr[...] = freq

        temp_ref[pl.ds(i, 1)] = temp[None]
        freqs_ref[pl.ds(i, 1)] = freq[None]
        return 0

    jax.lax.fori_loop(0, ck, tick, 0)

    # final-state outputs are rewritten every chunk (same pattern as
    # thermal_conv.py): the last chunk's write is the one that lands
    buf_ref[...] = ring_scr[...]
    th_ref[...] = th_scr[...]
    ev_ref[...] = e_scr[...]
    thr_ref[...] = thr_scr[...]
    fb_ref[...] = fb_scr[...]


def _divisor_chunk(t: int, target: int) -> int:
    """Largest divisor of t that is ≤ target (grid chunks must tile T)."""
    best = 1
    for d in range(1, min(target, t) + 1):
        if t % d == 0:
            best = d
    return best


def fleet_step(rho, buf0, th0, stats0, freq0, ev0, gamma,
               params: FleetStepParams, *, het=None, thr0=None, step0=0,
               fb0=None, mode0=None, block_packages: int = LANE,
               time_chunk: int = 256, interpret: bool | None = None):
    """Fused K-step fleet advance.

    Args (tiles-on-sublanes layout, packages last):
      rho:    [T, n_tiles, n] density chunk
      buf0:   [W, n_tiles, n] age-ordered ring (oldest first — ptr = 0)
      th0:    [n_poles, n_tiles, n] pole states
      stats0: [3, n_tiles, n] (wsum, csum, rsum)
      freq0:  [n_tiles, n];  ev0: [1, n] float32 cumulative event counts
      gamma:  [n_tiles, n_tiles] or None (pole constants ride in ``params``)
      het:    optional [2·n_poles + 3, n_tiles | 1, n] per-package physics
              (decay per pole, gain per pole, η, ΣG, poll — see
              `repro.fleet.backends.fused.FusedBackend._het_rows`); loaded
              into VMEM alongside the ring, overriding the baked constants
      thr0:   optional [n_tiles, n] f32 0/1 reactive_poll hysteresis latch
      step0:  global scheduler step at chunk entry (traced or python int) —
              keeps the reactive_poll sensor cadence continuous across
              chunk boundaries
      fb0:    optional degraded-fallback plane (required iff
              ``params.fallback``): a (rho_last [n_tiles, n], stale [n],
              degraded [n]) triple of f32-coercible arrays — resident in
              VMEM as `n_tiles + 2` mode rows beside the het rows
      mode0:  optional [n] 0/1 operator controller-mode plane (required
              iff ``params.mixed``): 1 pins the lane to reactive_poll for
              the whole chunk — input-only (the caller's `ctrl_mode` state
              leaf passes through unchanged), so canary shifts are value
              changes against the same compiled kernel

    Returns (temps [T, n_tiles, n], freqs [T, n_tiles, n],
             buf [W, n_tiles, n] (ring, ptr = T mod W),
             th [n_poles, n_tiles, n], ev [1, n],
             thr [n_tiles, n] f32 latch, or None when ``thr0`` is None,
             fb (rho_last, stale, degraded) f32 triple, or None when
             ``fb0`` is None).
    """
    if params.fallback and (fb0 is None or thr0 is None):
        raise ValueError("FleetStepParams.fallback requires the fb0 "
                         "(rho_last, stale, degraded) plane and the thr0 "
                         "latch")
    if params.mixed and (mode0 is None or thr0 is None):
        raise ValueError("FleetStepParams.mixed requires the mode0 "
                         "controller-mode plane and the thr0 latch")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, n_tiles, n = rho.shape
    w, np_ = params.window, params.n_poles
    tp = ((n_tiles + SUBLANE - 1) // SUBLANE) * SUBLANE
    # per-shard grid sizing: on TPU the package block must fill 128 lanes,
    # but in interpret mode (plain XLA on the block shapes) any width works —
    # pad small partitions (e.g. one device's slice of a sharded fleet) to
    # the sublane tile only, instead of 128, so a 2-package shard doesn't pay
    # for 126 phantom lanes.  No step mixes package lanes, so the block
    # width cannot change any real lane's numerics.
    align = LANE if not interpret else SUBLANE
    blk = min(block_packages, align * ((n + align - 1) // align))
    n_pad = ((n + blk - 1) // blk) * blk
    ck = _divisor_chunk(t, time_chunk)
    grid = (n_pad // blk, t // ck)

    f32 = jnp.float32
    # pad tiles (neutral values) then packages; padded tile rows have zero
    # Γ rows/cols, so they never contaminate real tiles
    def prep(x, tile_axis, fill):
        x = _pad_axis(x.astype(f32), tp, tile_axis, fill)
        return _pad_axis(x, n_pad, x.ndim - 1, fill)

    rho_p = prep(rho, 1, params.rho_hi / 1.5 / 3.0)   # benign in-domain fill
    buf_p = prep(buf0, 1, 0.0)
    th_p = prep(th0, 1, 0.0)
    stats_p = prep(stats0, 1, 0.0)
    freq_p = prep(freq0, 0, 1.0)
    ev_p = _pad_axis(ev0.astype(f32), n_pad, 1, 0.0)
    g = jnp.zeros((tp, tp), f32) if gamma is None else \
        _pad_axis(_pad_axis(gamma.astype(f32), tp, 0), tp, 1)

    # heterogeneous rows: broadcast a per-package (tile-axis-1) plane over
    # the real tiles, then pad with 1.0 — decay 1 freezes phantom-tile pole
    # state at 0, ΣG 1 keeps the budget division finite, poll 1 is a legal
    # period; phantom tiles are masked out of event counting regardless
    has_het = het is not None
    n_het = (2 * np_ + 3) if has_het else 1
    if has_het:
        het_p = jnp.broadcast_to(het.astype(f32),
                                 (n_het, n_tiles, het.shape[-1]))
        het_p = prep(het_p, 1, 1.0).reshape(n_het * tp, n_pad)
        h_rows = n_het * tp
    else:
        het_p = jnp.zeros((1, n_pad), f32)
        h_rows = 1
    has_thr = thr0 is not None
    if has_thr:
        thr_p = prep(thr0.astype(f32), 0, 0.0)
        t_rows = tp
    else:
        thr_p = jnp.zeros((1, n_pad), f32)
        t_rows = 1
    # degraded-fallback plane: rho_last padded with the same benign finite
    # fill as rho (phantom tiles/lanes must stay "fresh" forever), stale
    # and degraded rows padded with 0
    has_fb = fb0 is not None
    if has_fb:
        rl0, stl0, dg0 = fb0
        fb_p = jnp.concatenate([
            prep(jnp.asarray(rl0, f32), 0, params.rho_hi / 1.5 / 3.0),
            _pad_axis(jnp.asarray(stl0, f32)[None, :], n_pad, 1, 0.0),
            _pad_axis(jnp.asarray(dg0, f32)[None, :], n_pad, 1, 0.0),
        ], axis=0)
        fb_rows = tp + 2
    else:
        fb_p = jnp.zeros((1, n_pad), f32)
        fb_rows = 1
    # operator mode plane: padded lanes get 0.0 (v24 — benign: phantom
    # lanes never take the reactive branch, matching the fb padding)
    has_mode = mode0 is not None
    if has_mode:
        mode_p = _pad_axis(jnp.asarray(mode0, f32)[None, :], n_pad, 1, 0.0)
    else:
        mode_p = jnp.zeros((1, n_pad), f32)
    # global-step offset: f32 is exact for the 90k-scale step counts
    step0_p = jnp.broadcast_to(jnp.asarray(step0, f32), (1, 1))

    # fold the [W|poles|stats, tiles] leading dims into the sublane axis
    buf_p = buf_p.reshape(w * tp, n_pad)
    th_p = th_p.reshape(np_ * tp, n_pad)
    stats_p = stats_p.reshape(3 * tp, n_pad)

    state_spec = lambda r: pl.BlockSpec((r, blk), lambda b, c: (0, b))
    trace_spec = pl.BlockSpec((ck, tp, blk), lambda b, c: (c, 0, b))
    temps, freqs, buf, th, ev, thr, fb = pl.pallas_call(
        functools.partial(_kernel, ck=ck, tp=tp, n_tiles=n_tiles,
                          het=has_het, p=params),
        grid=grid,
        in_specs=[
            trace_spec,                                        # rho
            pl.BlockSpec((tp, tp), lambda b, c: (0, 0)),       # gamma
            state_spec(w * tp),                                # buf0
            state_spec(np_ * tp),                              # th0
            state_spec(3 * tp),                                # stats0
            state_spec(tp),                                    # freq0
            state_spec(1),                                     # ev0
            state_spec(h_rows),                                # het
            state_spec(t_rows),                                # thr0
            pl.BlockSpec((1, 1), lambda b, c: (0, 0)),         # step0
            state_spec(fb_rows),                               # fb0
            state_spec(1),                                     # mode0
        ],
        out_specs=[
            trace_spec,                                        # temps
            trace_spec,                                        # freqs
            state_spec(w * tp),                                # buf
            state_spec(np_ * tp),                              # th
            state_spec(1),                                     # ev
            state_spec(t_rows),                                # thr
            state_spec(fb_rows),                               # fb
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, tp, n_pad), f32),
            jax.ShapeDtypeStruct((t, tp, n_pad), f32),
            jax.ShapeDtypeStruct((w * tp, n_pad), f32),
            jax.ShapeDtypeStruct((np_ * tp, n_pad), f32),
            jax.ShapeDtypeStruct((1, n_pad), f32),
            jax.ShapeDtypeStruct((t_rows, n_pad), f32),
            jax.ShapeDtypeStruct((fb_rows, n_pad), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((w * tp, blk), f32),                    # ring
            pltpu.VMEM((np_ * tp, blk), f32),                  # poles
            pltpu.VMEM((3 * tp, blk), f32),                    # stats
            pltpu.VMEM((tp, blk), f32),                        # freq
            pltpu.VMEM((1, blk), f32),                         # events
            pltpu.VMEM((t_rows, blk), f32),                    # thr latch
            pltpu.VMEM((fb_rows, blk), f32),                   # fb plane
        ],
        interpret=interpret,
    )(rho_p, g, buf_p, th_p, stats_p, freq_p, ev_p, het_p, thr_p, step0_p,
      fb_p, mode_p)

    return (temps[:, :n_tiles, :n], freqs[:, :n_tiles, :n],
            buf.reshape(w, tp, n_pad)[:, :n_tiles, :n],
            th.reshape(np_, tp, n_pad)[:, :n_tiles, :n],
            ev[:, :n],
            thr[:n_tiles, :n] if has_thr else None,
            ((fb[0:tp, :][:n_tiles, :n], fb[tp, :n], fb[tp + 1, :n])
             if has_fb else None))
