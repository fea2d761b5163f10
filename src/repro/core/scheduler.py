"""ThermalScheduler — the paper's firmware layer as a first-class training/serving
component.

This is the integration point between the V24/V7.0 physics (density → filtration
→ PDU-gate hint → pre-positioning) and the JAX training loop: the scheduler
state rides in the train state, `update()` is pure JAX (jit/scan-safe), and its
outputs drive (a) the simulated per-chip frequency envelope, (b) straggler
mitigation weights for the data pipeline, and (c) host telemetry.

One call to `update()` == one training/serving step; the thermal plant is
advanced by the step's wall-time in closed form (exact ZOH over n ticks:
state' = aⁿ·state + (1−aⁿ)·G·P).

State contract (what every caller above this layer relies on):

  * `SchedulerState` is an immutable NamedTuple pytree; `update()` is pure
    and returns a NEW state — **rebind the returned state**, always.  Under
    `FleetEngine(donate_state=True)` the input state's buffers are donated
    to XLA, so reusing a pre-call state is a bug; the engine turns it into
    a readable ValueError instead of a crash (donation is disabled on CPU,
    where XLA ignores it — code written against the rebind rule runs
    unchanged either way).
  * Batching is by LEADING axes: `init(batch_shape=(n,))` broadcasts every
    per-tile leaf to [n, ...]; scalar leaves (step counter, poll phase)
    stay shared — they are fleet-wide clocks, not per-package state.  The
    fleet control plane discriminates per-lane vs shared leaves by exactly
    this rule (`ndim >= 1 and shape[0] == capacity`).
  * `state_pspecs(batch_axes)` mirrors the state pytree with
    `PartitionSpec`s for the same leading axes (per `filtration_impl`, whose
    two variants carry different filtration leaves) — the sharded backends
    consume it so states are BORN sharded rather than resharded.
  * `PackageParams` rows (per-package process variation) batch the same
    way and ride beside the state; `_eta_f32` keeps the homogeneous and
    heterogeneous η derivations bitwise identical.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pdu_gate, thermal
from repro.core import plant as plant_mod
from repro.core.coupling import apply_coupling, coupling_matrix
from repro.core.density import power_from_rho
from repro.core.fingerprint import FINGERPRINT, Fingerprint
# shared η derivation lives with the plant ladder now; re-exported here for
# existing importers (homogeneous constant, PackageParams draws, tests)
from repro.core.plant import _eta_f32


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    n_tiles: int = 1
    # v24 | reactive | reactive_poll | off.  ``reactive_poll`` is the §9/§10
    # baseline row ("reactive DVFS + temperature polling"): the sensor loop
    # only observes the junction every poll interval, with throttle
    # hysteresis — op-for-op the fleet form of `dvfs.simulate_reactive`.
    mode: str = "v24"
    two_pole: bool = True          # V7.0 kernel (V24 single-pole if False)
    use_coupling: bool = True      # V7.0 N×N Γ (identity if False)
    step_ms: float = 10.0          # wall-time of one training step
    lookahead_steps: int = 3       # hint horizon in steps (≈ 20–50 ms)
    filtration_window: int = 16    # Ft depth in steps
    # "incremental" (O(1)/step sliding sufficient statistics — the serving
    # fast path) or "ring" (O(W)/step gather + refit — the oracle the
    # incremental path is verified against, tests/test_filtration.py)
    filtration_impl: str = "incremental"
    t_safe_margin_c: float = 1.0
    power_exponent: float = 3.0
    straggler_threshold: float = 0.9   # f below this ⇒ tile flagged at-risk
    # per-package process variation: the state carries a `PackageParams`
    # pytree (pole decay/gain, preposition fraction, polling period) and
    # every batch lane runs ITS OWN physics — the §10 Monte-Carlo object
    heterogeneous: bool = False
    # ``reactive_poll`` baseline knobs (mirror repro.core.dvfs.DVFSConfig)
    throttle_level: float = 0.55   # emergency floor while throttled
    resume_below_c: float = 66.0   # hysteresis: throttled until T ≤ this
    recover_ms: float = 100.0      # ramp-back time constant
    poll_interval_ms: float = 25.0 # homogeneous polling period
    # in-graph graceful degradation (v24 only): packages whose hint stream
    # goes stale (non-finite density entries — a late/dropped/corrupted
    # chunk) fall back to the reactive_poll safety floor PER PACKAGE, and
    # recover with hysteresis once fresh hints resume.  The predictive
    # layer is advisory; reactive control is the floor (§9).
    degraded_fallback: bool = False
    stale_limit_steps: int = 5     # consecutive stale steps before fallback
    recover_steps: int = 10        # consecutive fresh steps before recovery
    # operator-settable per-lane controller mode (v24 only): the state
    # carries a `ctrl_mode` [*batch] bool plane — True pins that lane to
    # reactive_poll semantics, False keeps v24 — shifted LIVE by the
    # control plane (canary rollouts: POST /canary pins fleet fractions
    # per mode with zero recompiles).  Composes with degraded_fallback:
    # a lane runs reactive when EITHER the staleness latch or the
    # operator pin says so.
    mixed_mode: bool = False
    # thermal-plant fidelity rung (`repro.core.plant`): "pole" is the
    # paper's bank (bit-matching the pre-refactor path), "grid" the spatial
    # RC-grid ground truth, "rom" the reduced-order bank fit from it.  The
    # grid_*/rom_* knobs are scalars so the config stays hashable (engine
    # caches) and JSON-round-trips (service snapshot manifests).
    plant: str = "pole"
    grid_cells: int = 8            # cells per tile edge (gy = gx patches)
    grid_kappa: float = 0.35       # lateral / vertical conductance ratio
    grid_contrast: float = 0.5     # bridge-shadow g_v reduction (§5.2 EMIB)
    grid_substeps: int = 1         # Euler substeps per scheduler step
    rom_poles: int = 3             # fitted ROM bank size
    rom_fit_steps: int = 2048      # step-response window the fit regresses

    @property
    def lookahead_ms(self) -> float:
        return self.lookahead_steps * self.step_ms


class PackageParams(NamedTuple):
    """Per-package process/deployment draws riding IN the state (§10.1).

    Leaves broadcast against the state's [*batch, n_tiles, ...] layout: the
    tile axis may be 1 (one draw per package) or n_tiles (one draw per
    tile — how the Monte-Carlo harness packs independent trials onto the
    tile lanes).  ``eta``/``gain_sum`` are derived EAGERLY from decay/gain
    at construction (`ThermalScheduler.package_params`) so the pure-JAX,
    vmap and Pallas paths all consume the exact same float32 constants.
    """

    decay: jnp.ndarray      # [*batch, n_tiles | 1, n_poles]  a = exp(−dt/τ)
    gain: jnp.ndarray       # [*batch, n_tiles | 1, n_poles]  G [°C/W]
    eta: jnp.ndarray        # [*batch, n_tiles | 1]  1 − a_slow^(Δt_la/dt)
    gain_sum: jnp.ndarray   # [*batch, n_tiles | 1]  Σ G (= Rth)
    poll_ticks: jnp.ndarray # [*batch, n_tiles | 1] int32 — OEM poll period


class SchedulerState(NamedTuple):
    """All array leaves tolerate leading batch dims ([*batch, ...]) so one
    state can carry an entire fleet of packages stepped in lockstep."""

    # plant state, two trailing model dims: [..., n_tiles, n_poles] for
    # pole-family plants, [..., gy, n_tiles·gx] for the RC grid — every
    # rung keeps exactly two trailing dims so pspecs / lane surgery are
    # plant-agnostic (see repro.core.plant)
    thermal: jnp.ndarray
    # FiltrationStats (filtration_impl="incremental", the default) or
    # Filtration (the "ring" oracle) — structure follows the config
    filtration: "pdu_gate.FiltrationStats | pdu_gate.Filtration"
    freq: jnp.ndarray               # [..., n_tiles]
    step: jnp.ndarray               # scalar int32
    events: jnp.ndarray             # [...] int32 — T_crit crossings (want 0)
    # per-package physics (config.heterogeneous) — None ⇒ homogeneous fleet,
    # every package on the scheduler's shared fingerprint poles
    pkg: "PackageParams | None" = None
    # reactive_poll hysteresis latch [..., n_tiles] bool — None unless the
    # mode is reactive_poll or degraded_fallback is on (the fallback runs
    # the same latch on degraded lanes)
    throttled: "jnp.ndarray | None" = None
    # degraded-fallback plane (config.degraded_fallback) — None otherwise.
    # Per-PACKAGE (not per-tile): one hint stream serves a package, so the
    # whole package degrades or recovers together.
    rho_last: "jnp.ndarray | None" = None   # [..., n_tiles] last finite ρ
    stale: "jnp.ndarray | None" = None      # [...] int32 staleness counter
    degraded: "jnp.ndarray | None" = None   # [...] bool — on reactive floor
    # operator controller-mode plane (config.mixed_mode) — None otherwise.
    # True pins the lane to reactive_poll; a VALUE, never a trace constant,
    # so canary shifts reuse the compiled step (no recompiles).
    ctrl_mode: "jnp.ndarray | None" = None  # [...] bool — pinned reactive


class SchedulerOutput(NamedTuple):
    freq: jnp.ndarray               # [..., n_tiles] frequency multiplier this step
    temp_c: jnp.ndarray             # [..., n_tiles] junction temperature
    hint_w: jnp.ndarray             # [..., n_tiles] H(t) pre-position hint [W]
    eta: jnp.ndarray                # scalar preposition fraction
    at_risk: jnp.ndarray            # [..., n_tiles] bool straggler-risk flags
    balance: jnp.ndarray            # [..., n_tiles] work-rebalance weights (sum=1)


class ThermalScheduler:
    """Pure-functional scheduler: `state = init(); state, out = update(state, ρ)`."""

    def __init__(self, cfg: SchedulerConfig | None = None,
                 fp: Fingerprint = FINGERPRINT):
        # default constructed per instance — a shared default-argument
        # object would alias every default-constructed scheduler's config
        cfg = SchedulerConfig() if cfg is None else cfg
        if cfg.filtration_impl not in ("incremental", "ring"):
            raise ValueError(f"unknown filtration_impl "
                             f"{cfg.filtration_impl!r} (incremental|ring)")
        if cfg.mode not in ("v24", "reactive", "reactive_poll", "off"):
            raise ValueError(f"unknown mode {cfg.mode!r} "
                             f"(v24|reactive|reactive_poll|off)")
        if cfg.degraded_fallback and cfg.mode != "v24":
            raise ValueError(
                f"degraded_fallback=True requires mode='v24' (the fallback "
                f"IS reactive_poll — mode {cfg.mode!r} has no predictive "
                f"layer to degrade from)")
        if cfg.degraded_fallback and (cfg.stale_limit_steps < 1
                                      or cfg.recover_steps < 1):
            raise ValueError("stale_limit_steps and recover_steps must be "
                             ">= 1")
        if cfg.mixed_mode and cfg.mode != "v24":
            raise ValueError(
                f"mixed_mode=True requires mode='v24' (per-lane pins shift "
                f"lanes v24 <-> reactive_poll — mode {cfg.mode!r} has no "
                f"predictive layer to pin away from)")
        if cfg.plant not in plant_mod.available_plants():
            raise ValueError(
                f"unknown plant {cfg.plant!r} (available: "
                f"{', '.join(plant_mod.available_plants())})")
        if cfg.heterogeneous and cfg.plant != "pole":
            raise ValueError(
                "heterogeneous=True requires plant='pole' — per-package "
                "PackageParams draws override the fingerprint pole bank; "
                f"plant {cfg.plant!r} has no per-package override")
        self.cfg = cfg
        self.fp = fp
        # the thermal plant is a pluggable fidelity rung (repro.core.plant):
        # PoleBankPlant constructs the identical bank the scheduler used to
        # build inline, so plant="pole" (the default) is op-for-op the
        # pre-refactor path
        self.plant = plant_mod.make_plant(cfg, fp)
        # pole-family plants expose their bank (fused kernel, hetero draws,
        # oracle comparisons); None for grid — package_params guards on it
        self.poles = self.plant.poles
        self.gamma = (coupling_matrix(cfg.n_tiles) if cfg.use_coupling
                      and cfg.n_tiles > 1 else None)
        # per-tile Γ row-sum normalisation keeps multi-tile steady-state in the
        # same °C/W fingerprint frame as the single-tile validation
        if self.gamma is not None:
            self.gamma = self.gamma / self.gamma.sum(axis=1, keepdims=True)
        # η = 1 − a_slow^(Δt_la/dt), derived by the plant from its OWN slow
        # mode with the SAME f32 ops per-package heterogeneous draws use
        # (`plant._eta_f32`, shared with PackageParams) — so a heterogeneous
        # fleet whose draws all equal the fingerprint bit-matches the
        # homogeneous path.  A concrete python float even under jit trace.
        self.eta = self.plant.eta
        # reactive_poll ramp-back per step (mirrors dvfs.simulate_reactive)
        self.ramp = (1.0 - cfg.throttle_level) / max(
            int(cfg.recover_ms / cfg.step_ms), 1)
        self.poll_ticks = max(int(cfg.poll_interval_ms / cfg.step_ms), 1)
        self._init_cache: dict = {}   # compiled sharded-init per layout

    # ------------------------------------------------------------------ api
    def package_params(self, poles: thermal.PoleParams | None = None,
                       poll_ticks=None,
                       batch_shape: tuple[int, ...] = ()) -> PackageParams:
        """Build per-package draws for a heterogeneous fleet.

        ``poles``: batched `thermal.PoleParams` with decay/gain shaped
        [*batch, n_tiles | 1, n_poles] (see `thermal.pole_bank`; an
        [*batch, n_poles] bank gains a broadcast tile axis).  ``None``
        replicates the scheduler's fingerprint poles — a heterogeneous fleet
        with all-identical draws, bit-matching the homogeneous path.
        ``poll_ticks``: [*batch, n_tiles | 1]-broadcastable int polling
        periods for the ``reactive_poll`` baseline (default: the config's
        homogeneous interval).  η and ΣG are derived here, eagerly, in f32.
        """
        c = self.cfg
        if self.poles is None:
            raise ValueError(
                f"package_params requires a pole-family plant "
                f"(plant={c.plant!r} carries no pole bank)")
        if poles is None:
            poles = thermal.PoleParams(
                decay=jnp.broadcast_to(self.poles.decay,
                                       batch_shape + (1,) + self.poles.decay.shape),
                gain=jnp.broadcast_to(self.poles.gain,
                                      batch_shape + (1,) + self.poles.gain.shape))
        decay, gain = jnp.asarray(poles.decay), jnp.asarray(poles.gain)
        if decay.ndim == len(batch_shape) + 1:     # [*batch, n_poles]
            decay, gain = decay[..., None, :], gain[..., None, :]
        n_poles = self.poles.decay.shape[0]
        if decay.shape[-1] != n_poles or gain.shape != decay.shape:
            raise ValueError(
                f"per-package poles must carry decay/gain "
                f"[*batch, n_tiles|1, {n_poles}], got {decay.shape} / "
                f"{gain.shape}")
        if poll_ticks is None:
            poll_ticks = jnp.full(decay.shape[:-1], self.poll_ticks,
                                  jnp.int32)
        # η eagerly, via the SAME numpy f32 derivation as the homogeneous
        # self.eta — identical draws therefore carry bitwise identical η
        # (draws must be concrete; they are experiment inputs, not traces)
        return PackageParams(
            decay=decay, gain=gain,
            eta=jnp.asarray(_eta_f32(decay[..., -1],
                                     c.lookahead_ms / c.step_ms)),
            gain_sum=gain.sum(-1),
            poll_ticks=jnp.asarray(poll_ticks, jnp.int32))

    def init(self, batch_shape: tuple[int, ...] = (),
             shardings=None, pkg: PackageParams | None = None,
             filtration_fill=None) -> SchedulerState:
        """Fresh state; ``batch_shape`` prepends fleet/package dimensions.

        Batched states share the scalar step/ptr counters (packages step in
        lockstep) while thermal, filtration and frequency are per-package.
        ``shardings`` (a pytree of `jax.sharding.Sharding` congruent with the
        state — see `state_pspecs`) places each leaf at creation, so sharded
        fleet backends never materialise the full state on one device.
        With ``config.heterogeneous`` the state additionally carries ``pkg``
        per-package draws (default: fingerprint replicas — see
        `package_params`); ``filtration_fill`` overrides the ring's seed
        value (scalar or [*batch, n_tiles]-broadcastable, the Monte-Carlo
        harness seeds each trial with its trace's opening density).
        """
        c = self.cfg
        if pkg is not None and not c.heterogeneous:
            raise ValueError("per-package draws require "
                             "SchedulerConfig(heterogeneous=True)")
        if c.heterogeneous and pkg is None:
            pkg = self.package_params(batch_shape=batch_shape)
        if pkg is not None:
            # loud shape contract: a [*batch, n_poles] bank passed without
            # its tile axis would otherwise broadcast into a wrong-rank
            # state deep inside the first update
            if (pkg.decay.ndim != len(batch_shape) + 2
                    or pkg.decay.shape[:len(batch_shape)] != batch_shape
                    or pkg.decay.shape[-2] not in (1, c.n_tiles)):
                raise ValueError(
                    f"PackageParams.decay must be "
                    f"[*{batch_shape}, {c.n_tiles}|1, n_poles], got "
                    f"{pkg.decay.shape} (build it with "
                    f"package_params(..., batch_shape=...))")
            # the state owns its buffers: a donating engine call deletes
            # them, and the caller's draws must survive it (the Monte-Carlo
            # harness reuses one pole bank for its baseline and v24 fleets)
            pkg = jax.tree_util.tree_map(jnp.copy, pkg)
        fill = self.fp.rho_min if filtration_fill is None else filtration_fill

        init_ft = (pdu_gate.init_filtration_stats
                   if c.filtration_impl == "incremental"
                   else pdu_gate.init_filtration)

        def make(pkg_in, fill_in) -> SchedulerState:
            fb = c.degraded_fallback
            return SchedulerState(
                thermal=self.plant.init_state(batch_shape),
                filtration=init_ft(
                    c.filtration_window, c.n_tiles, fill=fill_in,
                    batch_shape=batch_shape),
                freq=jnp.ones(batch_shape + (c.n_tiles,)),
                step=jnp.zeros((), jnp.int32),
                events=jnp.zeros(batch_shape, jnp.int32),
                pkg=pkg_in,
                throttled=(jnp.zeros(batch_shape + (c.n_tiles,), bool)
                           if c.mode == "reactive_poll" or fb
                           or c.mixed_mode else None),
                # hold-last-value seed = the filtration seed: if the very
                # first chunk is already faulted the lane holds the same
                # benign density the ring was primed with
                rho_last=(jnp.broadcast_to(
                    jnp.asarray(fill_in, jnp.float32),
                    batch_shape + (c.n_tiles,)) if fb else None),
                stale=(jnp.zeros(batch_shape, jnp.int32) if fb else None),
                degraded=(jnp.zeros(batch_shape, bool) if fb else None),
                ctrl_mode=(jnp.zeros(batch_shape, bool)
                           if c.mixed_mode else None),
            )

        if shardings is None:
            return make(pkg, fill)
        # born sharded: jit with out_shardings materialises each leaf
        # directly on its owning device(s) — the full fleet state never
        # lands on one device.  The compiled initializer is cached per
        # layout (a fresh jit per call would recompile every init); the
        # per-package draws and fill ride in as (small) jit arguments.
        key = (batch_shape, tuple(jax.tree_util.tree_leaves(shardings)))
        fn = self._init_cache.get(key)
        if fn is None:
            fn = self._init_cache[key] = jax.jit(make,
                                                 out_shardings=shardings)
        return fn(pkg, fill)

    def state_pspecs(self, batch_axes: tuple = (None,)) -> SchedulerState:
        """PartitionSpec pytree congruent with ``init(batch_shape)`` output.

        Per-package leaves get ``batch_axes`` (one mesh-axis name or None per
        batch dim) on their leading dims; the shared scalar step/ptr counters
        stay replicated.  This is the init hook the sharded fleet backend
        feeds to `shard_map` / `NamedSharding` placement.
        """
        from jax.sharding import PartitionSpec as P
        ba = tuple(batch_axes)
        if self.cfg.filtration_impl == "incremental":
            ft = pdu_gate.FiltrationStats(
                buf=P(*ba, None, None), ptr=P(), wsum=P(*ba, None),
                csum=P(*ba, None), rsum=P(*ba, None))
        else:
            ft = pdu_gate.Filtration(buf=P(*ba, None, None), ptr=P())
        pkg = None
        if self.cfg.heterogeneous:
            # per-package draws partition with the packages they describe
            pkg = PackageParams(decay=P(*ba, None, None),
                                gain=P(*ba, None, None),
                                eta=P(*ba, None), gain_sum=P(*ba, None),
                                poll_ticks=P(*ba, None))
        fb = self.cfg.degraded_fallback
        return SchedulerState(
            thermal=self.plant.state_pspec(ba),
            filtration=ft,
            freq=P(*ba, None),
            step=P(),
            events=P(*ba),
            pkg=pkg,
            throttled=(P(*ba, None)
                       if self.cfg.mode == "reactive_poll" or fb
                       or self.cfg.mixed_mode else None),
            rho_last=(P(*ba, None) if fb else None),
            stale=(P(*ba) if fb else None),
            degraded=(P(*ba) if fb else None),
            ctrl_mode=(P(*ba) if self.cfg.mixed_mode else None),
        )

    def output_pspecs(self, batch_axes: tuple = (None,)) -> SchedulerOutput:
        """PartitionSpec pytree congruent with `update`'s SchedulerOutput
        (scalar η replicated, everything else per-package)."""
        from jax.sharding import PartitionSpec as P
        ba = tuple(batch_axes)
        tile = P(*ba, None)
        return SchedulerOutput(freq=tile, temp_c=tile, hint_w=tile,
                               eta=P(), at_risk=tile, balance=tile)

    def _physics(self, st: SchedulerState):
        """(poles, eta, gain_sum) — the plant's constants (``poles=None`` ⇒
        the plant steps its own physics), or the state's per-package draws
        when the fleet is heterogeneous.  Both sources carry the same
        eagerly-derived f32 values, so identical draws reproduce the
        homogeneous trajectory bit-for-bit."""
        if st.pkg is None:
            return None, self.plant.eta, self.plant.gain_sum
        return (thermal.PoleParams(decay=st.pkg.decay, gain=st.pkg.gain),
                st.pkg.eta, st.pkg.gain_sum)

    def update(self, st: SchedulerState,
               rho: jnp.ndarray) -> tuple[SchedulerState, SchedulerOutput]:
        """Advance one step.  rho: [..., n_tiles] density of the work just
        scheduled; leading dims (if any) must match the state's batch shape."""
        c, fp = self.cfg, self.fp
        rho = jnp.broadcast_to(jnp.asarray(rho), st.freq.shape)

        degraded = stale = None
        if c.degraded_fallback:
            # staleness plane: non-finite density entries mark a package
            # whose hint stream is late/dropped/corrupted.  Hold the last
            # finite value (the filtration stays warm, so recovery is
            # immediate once fresh hints resume) and run the per-package
            # staleness counter with hysteresis.  Fault-free lanes take the
            # `where` else-branches everywhere, so a clean run bit-matches
            # a fallback-disabled run.
            finite = jnp.isfinite(rho)
            valid = jnp.all(finite, axis=-1)
            rho = jnp.where(finite, rho, st.rho_last)
            stale = jnp.where(
                valid, jnp.maximum(st.stale - 1, 0),
                jnp.minimum(st.stale + 1,
                            c.stale_limit_steps + c.recover_steps))
            degraded = ((st.degraded & (stale > 0))
                        | (stale >= c.stale_limit_steps))

        # effective per-lane reactive mask: the staleness latch OR the
        # operator's controller pin — either routes the lane through the
        # reactive_poll semantics of the merged branch below
        reactive = degraded
        if st.ctrl_mode is not None:
            reactive = (st.ctrl_mode if reactive is None
                        else reactive | st.ctrl_mode)

        ft = pdu_gate.observe(st.filtration, rho)

        # instantaneous tile power, computed ONCE: it floors the hint below
        # and (scaled by the chosen frequency) drives the plant at the end
        p_now = power_from_rho(rho)
        poles, eta, gain_sum = self._physics(st)

        if c.mode == "reactive_poll":
            return self._update_reactive_poll(st, ft, p_now, poles)

        dt_now = self.plant.delta_t(st.thermal)
        t_allow = fp.t_crit_c - c.t_safe_margin_c - fp.t_ambient_c

        if c.mode == "v24":
            hint = pdu_gate.hint(ft, self.gamma, c.lookahead_ms, c.step_ms)
            # instantaneous load floors the hint: prediction buys lead time,
            # never permission to exceed budget on a mispredicted onset
            hint = jnp.maximum(hint, p_now if self.gamma is None
                               else apply_coupling(self.gamma, p_now))
            # explicit reciprocal-multiply: XLA rewrites division by a
            # SCALAR constant (the homogeneous η·ΣG) to `* (1/c)` anyway,
            # but keeps true division for the per-package ARRAY denominator
            # — writing the reciprocal out makes the heterogeneous path
            # bit-identical to the homogeneous one for identical draws
            budget = (t_allow - (1.0 - eta) * dt_now) * (1.0 / (eta * gain_sum))
            f_uni = jnp.clip((budget / jnp.maximum(hint, 1e-3))
                             ** (1.0 / c.power_exponent), 0.05, 1.0)
            if self.gamma is None:
                freq = f_uni
            else:
                # coupled control, two bounding laws (both must hold):
                #  · uniform law  — all tiles scale together (f_uni caps the
                #    "everyone jumps at once" overshoot);
                #  · coupled law  — only the self term is controllable, the
                #    neighbour heat (at last step's f) is subtracted.
                # Upward moves are rate-limited (voltage ramps are physically
                # slew-limited), which damps the simultaneous-move
                # oscillation of the per-tile fixed point.
                gd = jnp.diagonal(self.gamma)
                p_prev = p_now * st.freq ** c.power_exponent
                neigh = apply_coupling(self.gamma, p_prev) - gd * p_prev
                f_cpl = jnp.clip(
                    (jnp.maximum(budget - neigh, 1e-6)
                     / jnp.maximum(gd * p_now, 1e-3))
                    ** (1.0 / c.power_exponent), 0.05, 1.0)
                freq = jnp.minimum(f_uni, f_cpl)
                freq = jnp.minimum(freq, st.freq + 0.05)   # slew limit up
        elif c.mode == "reactive":
            hot = (fp.t_ambient_c + dt_now) >= fp.t_crit_c
            freq = jnp.where(hot, fp.throttle_floor,
                             jnp.minimum(st.freq + 0.1, 1.0))
        else:  # off — uncontrolled
            freq = jnp.ones_like(st.freq)

        if c.mode != "v24":
            # prediction only drives the v24 gate; the reported hint falls
            # back to the instantaneous (Γ-coupled) load floor
            hint = (p_now if self.gamma is None
                    else apply_coupling(self.gamma, p_now))

        throttled = st.throttled
        if reactive is None:
            p = p_now * freq ** c.power_exponent
            p_eff = p if self.gamma is None else apply_coupling(self.gamma, p)
            thermal_next = self.plant.step(st.thermal, p_eff, poles=poles)
            temp = fp.t_ambient_c + self.plant.delta_t(thermal_next)
            events = st.events + jnp.any(temp > fp.t_crit_c,
                                         axis=-1).astype(jnp.int32)
        else:
            # merged plant: reactive lanes (staleness-degraded OR operator-
            # pinned) run reactive_poll semantics — the plant advances at
            # LAST step's frequency, the sensor polls the post-step
            # junction, and the throttle latch carries the hysteresis —
            # v24 lanes take the predictive law untouched.  The plant
            # steps ONCE, at the per-lane blended frequency.
            deg_t = reactive[..., None]
            f_used = jnp.where(deg_t, st.freq, freq)
            p = p_now * f_used ** c.power_exponent
            p_eff = p if self.gamma is None else apply_coupling(self.gamma, p)
            thermal_next = self.plant.step(st.thermal, p_eff, poles=poles)
            temp = fp.t_ambient_c + self.plant.delta_t(thermal_next)

            poll = self.poll_ticks if st.pkg is None else st.pkg.poll_ticks
            polled = (st.step % poll) == 0
            trig = (temp >= fp.t_crit_c) & polled
            cool = (temp <= c.resume_below_c) & polled
            throttled = jnp.where(deg_t, (st.throttled | trig) & ~cool,
                                  False)
            freq = jnp.where(
                deg_t,
                jnp.where(throttled, c.throttle_level,
                          jnp.minimum(st.freq + self.ramp, 1.0)),
                freq)
            # reactive lanes count fresh throttle engagements (the §10
            # baseline statistic); v24 lanes count T_crit crossings
            events = st.events + jnp.where(
                reactive, jnp.any(trig & ~st.throttled, axis=-1),
                jnp.any(temp > fp.t_crit_c, axis=-1)).astype(jnp.int32)
            hint = jnp.where(deg_t, p_eff, hint)

        at_risk = freq < c.straggler_threshold
        balance = freq / jnp.maximum(freq.sum(axis=-1, keepdims=True), 1e-6)

        out = SchedulerOutput(freq=freq, temp_c=temp, hint_w=hint,
                              eta=jnp.asarray(self.eta), at_risk=at_risk,
                              balance=balance)
        return SchedulerState(thermal=thermal_next, filtration=ft, freq=freq,
                              step=st.step + 1, events=events,
                              pkg=st.pkg, throttled=throttled,
                              rho_last=(rho if degraded is not None
                                        else st.rho_last),
                              stale=stale if stale is not None else st.stale,
                              degraded=(degraded if degraded is not None
                                        else st.degraded),
                              ctrl_mode=st.ctrl_mode), out

    def _update_reactive_poll(self, st: SchedulerState, ft, p_now,
                              poles) -> tuple[SchedulerState, SchedulerOutput]:
        """§9 baseline: reactive DVFS + temperature polling with hysteresis.

        Op-for-op the fleet form of `dvfs.simulate_reactive`'s tick: the
        plant runs at the frequency DECIDED LAST STEP (`st.freq`), the
        sensor loop only observes the post-step junction every
        ``poll_ticks`` (per-package under heterogeneity), and the throttle
        latch releases only once the junction cools below ``resume_below_c``.
        ``events`` counts trigger events (fresh throttle engagements), not
        T_crit crossings — the §10 baseline statistic.  The emitted ``freq``
        is next step's decision, matching the oracle's reported trace.
        """
        c, fp = self.cfg, self.fp
        p = p_now * st.freq ** c.power_exponent
        p_eff = p if self.gamma is None else apply_coupling(self.gamma, p)
        thermal_next = self.plant.step(st.thermal, p_eff, poles=poles)
        temp = fp.t_ambient_c + self.plant.delta_t(thermal_next)

        poll = self.poll_ticks if st.pkg is None else st.pkg.poll_ticks
        polled = (st.step % poll) == 0
        trig = (temp >= fp.t_crit_c) & polled
        cool = (temp <= c.resume_below_c) & polled
        events = st.events + jnp.any(trig & ~st.throttled,
                                     axis=-1).astype(jnp.int32)
        throttled = (st.throttled | trig) & ~cool
        freq = jnp.where(throttled, c.throttle_level,
                         jnp.minimum(st.freq + self.ramp, 1.0))

        at_risk = freq < c.straggler_threshold
        balance = freq / jnp.maximum(freq.sum(axis=-1, keepdims=True), 1e-6)
        out = SchedulerOutput(freq=freq, temp_c=temp, hint_w=p_eff,
                              eta=jnp.asarray(self.eta), at_risk=at_risk,
                              balance=balance)
        return SchedulerState(thermal=thermal_next, filtration=ft, freq=freq,
                              step=st.step + 1, events=events,
                              pkg=st.pkg, throttled=throttled), out
