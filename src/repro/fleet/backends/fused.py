"""fused backend — the Pallas whole-step kernel as a fleet execution strategy.

Per-step `update` falls back to the pure-JAX broadcast layout (so `step()`
and the streaming ingest loop work unchanged), but `run_block` — the unit
of work of `FleetEngine.run_block/run_chunked` and the streaming loop —
advances the whole [T, n_packages, n_tiles] chunk inside ONE Pallas kernel
(`repro.kernels.fleet_step`): ring buffer, sliding filtration statistics,
v24 control law, two-pole plant and event counters all stay VMEM-resident
across the chunk instead of round-tripping HBM every step.

State layout is the broadcast layout (scalar lockstep counters).  The ring
buffer is normalised to age-order (ptr = 0) on kernel entry and the sliding
statistics are re-derived exactly from the ring at every chunk boundary, so
float drift cannot accumulate across a 90k-step soak; both filtration
representations (`FiltrationStats` fast path and ring-buffer `Filtration`
oracle) are accepted.  Verified against the pure-JAX engine to ≤1e-5
(tests/test_fleet_fused.py).  Off-TPU the kernel runs in interpret mode;
the choice is made once, when the backend is built, and `describe()`
names it (``fused[blk=128,compiled]`` / ``...,interpret]``).

Active-lane masks never enter the kernel: padded capacity-pool lanes ride
the 128-lane axis like any other package (the kernel already masks its OWN
grid-padding phantom lanes out of event counting), and the engine applies
the membership mask in the telemetry reductions over the streamed
temp/freq traces — so dynamic attach/detach reuses the compiled kernel
unchanged.  The mask keeps the default replicated placement
(`FleetBackend.put_mask`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pdu_gate
from repro.core.scheduler import (SchedulerOutput, SchedulerState,
                                  ThermalScheduler)
from repro.fleet.backends.base import FleetBackend, register
from repro.kernels.fleet_step import FleetStepParams, fleet_step


@register
class FusedBackend(FleetBackend):
    name = "fused"

    def __init__(self, sched: ThermalScheduler, block_packages: int = 128,
                 time_chunk: int = 256, interpret: bool | None = None):
        super().__init__(sched)
        self.block_packages = block_packages
        self.time_chunk = time_chunk
        self.interpret = (jax.default_backend() != "tpu" if interpret is None
                          else interpret)
        self._rom_plant = None
        plant = sched.plant
        if plant.family != "pole":
            # grid states can't live in the kernel's pole-bank VMEM plane:
            # shadow the method with None so the engine's
            # `backend_impl.run_block is not None` dispatch routes this
            # backend through its pure-JAX scan path (same state layout,
            # ≤1e-5-gated against the other backends) — a fleet can still
            # run a fidelity mix by mapping plants to engines per package
            # group
            self.run_block = None
            self.params = None
            return
        if plant.name != "pole":
            # fitted ROM banks ride the kernel's heterogeneous-row path:
            # the per-tile bank broadcasts as VMEM planes (`_rom_rows`)
            self._rom_plant = plant
        import numpy as np
        from repro.core.density import _RTOK_INTERCEPT, _RTOK_SLOPE
        from repro.core.fingerprint import FINGERPRINT
        c, fp = sched.cfg, sched.fp
        gain = np.asarray(sched.poles.gain, np.float32)
        if gain.ndim == 1:          # the paper's bank — exact scalars
            gain_tuple = tuple(float(g) for g in gain)
            gain_sum = float(gain.sum())
        else:                       # per-tile fitted bank: the kernel reads
            gain_tuple = tuple(float(g) for g in gain.mean(0))  # het rows —
            gain_sum = float(np.asarray(plant.gain_sum,         # placeholders
                                        np.float32).mean())
        self.params = FleetStepParams(
            window=c.filtration_window,
            recent=pdu_gate.recent_len(c.filtration_window),
            n_poles=int(sched.poles.decay.shape[0]),
            mode=c.mode,
            use_gamma=sched.gamma is not None,
            power_exponent=float(c.power_exponent),
            eta=float(sched.eta),
            t_allow=float(fp.t_crit_c - c.t_safe_margin_c - fp.t_ambient_c),
            gain_sum=gain_sum,
            ahead=float(c.lookahead_ms / c.step_ms),
            # density.power_from_rho reads the module FINGERPRINT (not the
            # scheduler's fp) — mirror that so the kernel's power chain
            # tracks the pure path exactly
            rtok_slope=float(_RTOK_SLOPE),
            rtok_icept=float(_RTOK_INTERCEPT),
            alpha=float(FINGERPRINT.alpha_c_per_mtps),
            beta=float(FINGERPRINT.beta_c),
            rth=float(FINGERPRINT.rth_c_per_w),
            rho_hi=float(1.5 * FINGERPRINT.rho_max),   # predict_rho's clip
            t_crit_c=float(fp.t_crit_c),
            t_ambient_c=float(fp.t_ambient_c),
            throttle_floor=float(fp.throttle_floor),
            decay=tuple(float(d) for d in sched.poles.decay),
            gain=gain_tuple,
            # reactive_poll baseline constants (homogeneous defaults; a
            # heterogeneous fleet overrides poll per package via het rows)
            throttle_level=float(c.throttle_level),
            resume_below_c=float(c.resume_below_c),
            ramp=float(sched.ramp),
            poll_ticks=int(sched.poll_ticks),
            # degraded fallback: per-package mode rows ride in VMEM
            fallback=bool(c.degraded_fallback),
            stale_limit=int(c.stale_limit_steps),
            recover=int(c.recover_steps),
            # operator-pinned per-lane controller mode (canary rollouts):
            # the ctrl_mode state leaf enters as a chunk-constant plane
            mixed=bool(c.mixed_mode),
        )

    # -- state ------------------------------------------------------------
    def init(self, n_packages: int, pkg=None,
             filtration_fill=None) -> SchedulerState:
        return self.sched.init(batch_shape=(n_packages,), pkg=pkg,
                               filtration_fill=filtration_fill)

    def update(self, state: SchedulerState, rho: jnp.ndarray
               ) -> tuple[SchedulerState, SchedulerOutput]:
        """Single-step fallback: identical to the broadcast backend."""
        return self.sched.update(state, rho)

    # -- fused fast path ---------------------------------------------------
    def _het_rows(self, pkg) -> jnp.ndarray:
        """Stack per-package draws for the kernel's VMEM-resident het input.

        Layout [2·n_poles + 3, n_tiles | 1, n]: decay per pole, gain per
        pole, then η, ΣG and the polling period — each a tiles-on-sublanes /
        packages-on-lanes plane, padded (benignly) and folded into the
        sublane axis by `fleet_step` exactly like the thermal state.
        """
        f32 = jnp.float32
        tr = lambda x: jnp.transpose(x.astype(f32), (2, 1, 0))  # → [np, t, n]
        one = lambda x: x.astype(f32).T[None]                   # → [1, t, n]
        return jnp.concatenate([
            tr(pkg.decay), tr(pkg.gain),
            one(pkg.eta), one(pkg.gain_sum), one(pkg.poll_ticks),
        ], axis=0)

    def _rom_rows(self, n: int) -> jnp.ndarray:
        """Fitted ROM bank as broadcast heterogeneous planes [2·np+3, t, n].

        The kernel's het path already supports per-tile-varying decay/gain/
        ΣG planes, so a `FittedROMPlant` fleet (homogeneous across packages,
        per-tile gains from the grid fit) is just the same rows broadcast
        over the package lanes — constants folded at trace time.
        """
        import numpy as np
        p = self._rom_plant
        n_poles, nt = p.poles.decay.shape[0], p.n_tiles
        rows = np.empty((2 * n_poles + 3, nt, 1), np.float32)
        rows[:n_poles] = np.asarray(p.poles.decay,
                                    np.float32)[:, None, None]
        rows[n_poles:2 * n_poles] = np.asarray(p.poles.gain,
                                               np.float32).T[:, :, None]
        rows[2 * n_poles] = np.float32(p.eta)
        rows[2 * n_poles + 1] = np.asarray(p.gain_sum,
                                           np.float32)[:, None]
        rows[2 * n_poles + 2] = np.float32(self.sched.poll_ticks)
        return jnp.broadcast_to(jnp.asarray(rows),
                                (2 * n_poles + 3, nt, n))

    def run_block(self, state: SchedulerState, rho_trace: jnp.ndarray):
        """Advance T steps in one kernel.  rho_trace: [T, n, tiles].

        Returns (state', temps [T, n, tiles], freqs [T, n, tiles]).
        Heterogeneous fleets feed their per-package decay/gain/η/ΣG/poll
        draws into the kernel alongside the ring (`_het_rows`) — fitted ROM
        plants reuse the same path with broadcast rows (`_rom_rows`) — and
        the ``reactive_poll`` baseline threads its hysteresis latch through
        kernel scratch.
        """
        t = rho_trace.shape[0]
        ft = state.filtration
        w = ft.buf.shape[-2]
        # age-order the ring (ptr = 0) so the kernel's write pointer is just
        # step mod W; one gather per T-step chunk, amortised to nothing
        buf0 = jnp.roll(ft.buf, -ft.ptr, axis=-2)
        wsum, csum, rsum = pdu_gate.exact_stats(buf0, 0)

        if state.pkg is not None:
            het = self._het_rows(state.pkg)
        elif self._rom_plant is not None:
            het = self._rom_rows(state.freq.shape[0])
        else:
            het = None
        thr0 = (None if state.throttled is None
                else state.throttled.astype(jnp.float32).T)
        fb0 = (None if state.degraded is None
               else (state.rho_last.astype(jnp.float32).T,
                     state.stale.astype(jnp.float32),
                     state.degraded.astype(jnp.float32)))
        mode0 = (None if state.ctrl_mode is None
                 else state.ctrl_mode.astype(jnp.float32))

        # tiles-on-sublanes, packages-on-lanes layout
        tnl = lambda x: jnp.moveaxis(x, -1, -2)            # [.., n, t]->[.., t, n]
        temps, freqs, buf, th, ev, thr, fb = fleet_step(
            tnl(rho_trace),
            jnp.transpose(buf0, (1, 2, 0)),                # [W, tiles, n]
            jnp.transpose(state.thermal, (2, 1, 0)),       # [poles, tiles, n]
            jnp.stack([wsum.T, csum.T, rsum.T]),
            state.freq.T,
            state.events.astype(jnp.float32)[None, :],
            self.sched.gamma,
            self.params,
            het=het,
            thr0=thr0,
            step0=state.step,
            fb0=fb0,
            mode0=mode0,
            block_packages=self.block_packages,
            time_chunk=self.time_chunk,
            interpret=self.interpret,
        )
        buf = jnp.transpose(buf, (2, 0, 1))                # [n, W, tiles]
        ptr = jnp.asarray(t % w, jnp.int32)
        if isinstance(ft, pdu_gate.FiltrationStats):
            nwsum, ncsum, nrsum = pdu_gate.exact_stats(buf, ptr)
            ft_out = pdu_gate.FiltrationStats(buf=buf, ptr=ptr, wsum=nwsum,
                                              csum=ncsum, rsum=nrsum)
        else:
            ft_out = pdu_gate.Filtration(buf=buf, ptr=ptr)
        state = SchedulerState(
            thermal=jnp.transpose(th, (2, 1, 0)),
            filtration=ft_out,
            freq=freqs[-1].T,
            step=state.step + t,
            events=ev[0].astype(state.events.dtype),
            pkg=state.pkg,
            throttled=None if thr is None else (thr.T > 0.5),
            rho_last=None if fb is None else fb[0].T,
            stale=None if fb is None else fb[1].astype(jnp.int32),
            degraded=None if fb is None else (fb[2] > 0.5),
            ctrl_mode=state.ctrl_mode,
        )
        return state, tnl(temps), tnl(freqs)

    def kernel_mode(self) -> str:
        """``compiled`` / ``interpret`` for the Pallas kernel, or ``scan``
        when the plant keeps this backend on the pure-JAX scan."""
        if self.run_block is None:
            return "scan"
        return "interpret" if self.interpret else "compiled"

    def describe(self) -> str:
        return f"{self.name}[blk={self.block_packages},{self.kernel_mode()}]"
