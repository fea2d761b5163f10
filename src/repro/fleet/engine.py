"""FleetEngine — batched thermal scheduling for fleets of 3.5D packages.

The paper's V7.0 framework controls ONE N×N-coupled multi-tile package; a
production deployment schedules thousands of independent packages at once.
Because `ThermalScheduler.update` is pure JAX and (after the batch-dim
refactor) tolerant of leading batch dimensions, a whole fleet advances in a
single jitted step.  HOW the package axis is executed is a pluggable
backend (`repro.fleet.backends`):

  * ``vmap``      — `jax.vmap` over a per-package state axis (reference),
  * ``broadcast`` — batch-shaped state arrays, no vmap (lockstep counters),
  * ``sharded``   — package axis partitioned over a device mesh via
                    `shard_map` (degrades to broadcast on one device),
  * ``fused``     — `run_block`/`run_chunked` chunks advance inside ONE
                    Pallas whole-step kernel (`repro.kernels.fleet_step`),
                    state VMEM-resident across the chunk,
  * ``sharded_fused`` — fused × sharded: each mesh device runs the
                    whole-step kernel on its package partition; telemetry
                    is all-reduced in-graph before the single host sync.

All are numerically identical to a Python loop of per-package `update`
calls — see ``tests/test_fleet.py`` / ``tests/test_fleet_sharded.py`` — but
amortise dispatch/compile over the fleet (``benchmarks/bench_fleet.py``).

    eng = FleetEngine(SchedulerConfig(n_tiles=4, mode="v24"),
                      backend="sharded")
    state = eng.init(n_packages=1024)
    state, out, telem = eng.step(state, rho)     # rho: [1024, 4]
    print(telem.as_dict())   # events, p50/p99 junction temp, released MTPS

For serving loops, per-step `as_dict()` costs one host sync per step; use
`run_chunked` (or the streaming loop in `repro.fleet.ingest`) to reduce
telemetry over K steps in-graph and sync once per flush interval.

State contract (the rules the control plane in `repro.fleet.service`
is built on; see also docs/architecture.md):

  * **Rebind the returned state.**  With ``donate_state=True`` (the
    default off-CPU) every jitted entry point donates its state argument
    — the buffers you passed in are dead the moment the call dispatches.
    Always write ``state, ... = eng.step(state, ...)``; reuse of a donated
    state is caught at the engine boundary with a readable ValueError.
  * **Lane independence.**  Per-package physics is elementwise over the
    package axis (only the telemetry reductions cross lanes), so a lane's
    trajectory depends solely on its own rho sequence since init — the
    property that lets `repro.fleet.registry` pad fleets to power-of-two
    capacities and scatter fresh lane states in and out without touching
    the neighbours.  (One caveat: under ``mode="reactive_poll"`` the
    polling phase follows the fleet's shared step clock, so a lane
    attached mid-flight polls in the fleet's phase, not its own.)
  * **Active masks.**  ``step``/``run``/``run_block``/``run_chunked``
    accept ``active`` — a [n_packages] bool mask, threaded as a TRACED jit
    argument — and reduce telemetry over the active lanes only: padded
    lanes still compute (lockstep execution never re-specialises), but
    they cannot pollute `freq_min`, `at_risk_frac`, the percentiles or the
    event counters.  Flipping mask bits therefore never recompiles; only a
    capacity (shape) change does.
  * **Tail flushes.**  `run_chunked` (like `ingest.chunk_source`/`stream`)
    treats a trace length that does not divide ``flush_every`` as legal:
    the remainder becomes its own SHORTER flush window — ceil(T/K) records
    total, every step counted, no padding entering the telemetry.
  * **Multi-host.**  Under `jax.distributed` the mesh backends span every
    process (`repro.distributed.multihost`): state lives sharded across
    hosts (NOT fully addressable on any one), `put_trace` accepts either a
    global chunk or this host's lane slab, telemetry reductions all-reduce
    in-graph, and the fully-replicated `FleetTelemetry` scalars fetch with
    the usual single `device_get` per flush ON EACH process.  On a
    process-spanning mesh the flush record is always derived from the
    block's streamed temp/freq traces, so the package-axis reductions
    all-reduce ONCE per flush — never inside the step scan, where each
    one would be a cross-host gloo round trip (~10^2x the step math;
    see `_run_block_impl`).  Every entry
    point is then a collective program — all processes must make the same
    sequence of calls (see `repro.fleet.distributed_ingest`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.density import rtok_from_rho
from repro.core.fingerprint import FINGERPRINT, Fingerprint
from repro.core.scheduler import (SchedulerConfig, SchedulerOutput,
                                  SchedulerState, ThermalScheduler)
from repro.fleet.backends import FleetBackend, backend_class
from repro.tracing import span


class FleetTelemetry(NamedTuple):
    """Aggregate fleet health for one step (all leaves are jnp scalars)."""

    n_packages: jnp.ndarray      # int32
    events_total: jnp.ndarray    # cumulative T_crit crossings, fleet-wide
    events_step: jnp.ndarray     # crossings added this step (window: summed)
    temp_p50_c: jnp.ndarray      # fleet junction-temperature percentiles
    temp_p99_c: jnp.ndarray
    temp_max_c: jnp.ndarray
    temp_var_c2: jnp.ndarray     # fleet junction-temperature variance [°C²]
    freq_mean: jnp.ndarray       # mean frequency multiplier
    freq_min: jnp.ndarray
    released_mtps: jnp.ndarray   # Σ R_tok(ρ)·f — compute actually released
    throttled_mtps: jnp.ndarray  # Σ R_tok(ρ)·(1−f) — compute held back
    at_risk_frac: jnp.ndarray    # fraction of tiles under straggler threshold
    # active lanes running the reactive fallback (degraded_fallback mode;
    # 0 whenever the fallback is off) — window reduce keeps the peak
    degraded_count: jnp.ndarray = jnp.zeros((), jnp.int32)  # int32

    def as_dict(self) -> dict[str, float]:
        """Host-side scalar dict — ONE device sync for the whole record
        (a single `jax.device_get` of the pytree), not one per field."""
        host = jax.device_get(self)._asdict()
        host["n_packages"] = int(host["n_packages"])
        host["degraded_count"] = int(host["degraded_count"])
        return {k: (v if isinstance(v, int) else float(v))
                for k, v in host.items()}

    def reduce(self) -> "FleetTelemetry":
        """Reduce a [K]-leaved (stacked per-step) record to one telemetry
        record for the whole K-step window, entirely in-graph.

        Semantics per field: counters take the window's last cumulative value
        (`events_total`, `n_packages`) or sum (`events_step` = crossings in
        the window); temperatures keep the worst tail (`p99`/`max` = max over
        steps, `p50` = mean); frequency keeps mean/min; the MTPS split and
        at-risk fraction are window means (units stay MTPS).  The per-step
        invariant released+throttled == ΣR_tok therefore also holds for the
        reduced record against the window-mean offered throughput.
        """
        return FleetTelemetry(
            n_packages=self.n_packages[-1],
            events_total=self.events_total[-1],
            events_step=self.events_step.sum(),
            temp_p50_c=self.temp_p50_c.mean(),
            temp_p99_c=self.temp_p99_c.max(),
            temp_max_c=self.temp_max_c.max(),
            temp_var_c2=self.temp_var_c2.mean(),   # mean per-step spread
            freq_mean=self.freq_mean.mean(),
            freq_min=self.freq_min.min(),
            released_mtps=self.released_mtps.mean(),
            throttled_mtps=self.throttled_mtps.mean(),
            at_risk_frac=self.at_risk_frac.mean(),
            degraded_count=self.degraded_count.max(),   # window peak
        )


class FleetSurvey(NamedTuple):
    """Per-(package, tile) lane reductions over a trace (the §10 Monte-Carlo
    plane): one record per lane, accumulated in-graph — see
    `FleetEngine.run_survey`."""

    peak_t_c: jnp.ndarray      # [n, tiles] max junction temp past burn-in
    exceed_frac: jnp.ndarray   # [n, tiles] fraction of counted steps > T_crit
    freq_mean: jnp.ndarray     # [n, tiles] mean delivered frequency (all steps)
    steps: jnp.ndarray         # int32 — trace length
    counted_steps: jnp.ndarray # int32 — steps past burn-in


class FleetEngine:
    """Pure-functional fleet stepper around one `ThermalScheduler` config.

    ``backend`` is a registered backend name (``vmap``/``broadcast``/
    ``sharded``/``fused``/``sharded_fused``) or a ready `FleetBackend`
    instance; ``devices`` is forwarded to the device-mesh backends
    (None = all visible devices).
    ``broadcast`` is the default: its lockstep scalar counters are what the
    O(1) incremental-filtration refresh needs to stay a real `lax.cond`
    (under vmap's per-lane counters it degrades to a both-branches select);
    ``vmap`` remains the per-package reference layout.

    ``donate_state``: the jitted `step`/`run`/`run_block`/`run_chunked`
    entry points donate the state pytree (`jax.jit(donate_argnums=0)`), so
    a 90k-step soak updates its ring buffers and pole states in place
    instead of copying the whole fleet state every call.  The engine
    therefore OWNS the state you pass in — rebind the returned state
    (``state, ... = eng.step(state, ...)``) and never reuse the old
    reference.  Defaults to on everywhere donation is implemented (XLA
    ignores it on CPU, so it is skipped there to avoid warning spam).
    """

    def __init__(self, cfg: SchedulerConfig | None = None,
                 fp: Fingerprint = FINGERPRINT,
                 backend: str | FleetBackend = "broadcast",
                 devices: int | None = None,
                 donate_state: bool | None = None,
                 debug_nan: bool = False):
        # construct-per-instance: a shared default-argument instance would
        # alias every default-constructed engine onto ONE config object
        self.cfg = cfg = SchedulerConfig() if cfg is None else cfg
        self.fp = fp
        self.sched = ThermalScheduler(cfg, fp)
        if isinstance(backend, FleetBackend):
            self.backend_impl = backend
        else:
            cls = backend_class(backend)
            if devices is not None and not cls.accepts_devices:
                raise ValueError(
                    f"devices={devices} only applies to device-mesh "
                    f"backends (sharded/sharded_fused), got "
                    f"backend={backend!r}")
            kw = {"devices": devices} if cls.accepts_devices else {}
            self.backend_impl = cls(self.sched, **kw)
        self.backend = self.backend_impl.name
        if donate_state is None:
            donate_state = jax.default_backend() != "cpu"
        self.donate_state = donate_state
        # debug-mode NaN/Inf guard (tests/chaos): every public entry point
        # host-checks the returned state + telemetry and raises with the
        # offending lane indices instead of letting NaNs propagate silently
        # into BENCH_*.json or the alert reductions.  Off by default — it
        # forces a host sync per call.
        self.debug_nan = debug_nan
        dn = (0,) if donate_state else ()
        self._step = jax.jit(self._step_impl, donate_argnums=dn)
        self._run = jax.jit(self._run_impl, donate_argnums=dn)
        self._run_block = jax.jit(self._run_block_impl, donate_argnums=dn)
        self._run_chunked = jax.jit(self._run_chunked_impl, donate_argnums=dn)
        # survey entry points donate the state AND the accumulator pytree
        # (argument 3) — the chunk loop rebinds both every call
        dns = (0, 3) if donate_state else ()
        self._survey = jax.jit(self._survey_impl, donate_argnums=dns)
        self._survey_block = jax.jit(self._survey_block_impl,
                                     donate_argnums=dns)
        # survey normalisation for process-spanning meshes: eager ops on
        # non-fully-addressable arrays are rejected outside jit, so the
        # final divisions run as one tiny jitted program (counts traced —
        # no respecialisation across trace lengths)
        self._survey_finalize = jax.jit(
            lambda exceed, fsum, counted, total: (exceed / counted,
                                                  fsum / total))

    # ------------------------------------------------------------------ api
    def init(self, n_packages: int, pkg=None,
             filtration_fill=None) -> SchedulerState:
        """Fleet state with a leading [n_packages] axis on every per-package
        leaf; layout (and device placement) is the backend's choice.

        ``pkg`` (`repro.core.scheduler.PackageParams`, requires
        ``SchedulerConfig(heterogeneous=True)``) gives every package its own
        process-variation draws — Rth/τ pole banks, preposition fraction,
        polling period; ``filtration_fill`` seeds each package's ring (the
        Monte-Carlo harness uses its trace's opening density)."""
        return self.backend_impl.init(n_packages, pkg=pkg,
                                      filtration_fill=filtration_fill)

    def step(self, state: SchedulerState, rho, active=None) -> tuple[
            SchedulerState, SchedulerOutput, FleetTelemetry]:
        """Advance the whole fleet one step in a single jitted call.

        rho: scalar, [n_packages], or [n_packages, n_tiles] workload density.
        ``active``: optional [n_packages] bool mask — telemetry reduces over
        the active lanes only (padded lanes still compute; see the module
        docstring's mask contract).
        """
        self._guard_donated(state)
        state, out, telem = self._step(state, self._rho_fleet(state, rho),
                                       self._active(state, active))
        self._debug_check_finite(state, telem)
        return state, out, telem

    def run(self, state: SchedulerState, rho_trace, active=None) -> tuple[
            SchedulerState, FleetTelemetry]:
        """`lax.scan` the fleet over a [T, n_packages, n_tiles] density trace;
        returns final state + stacked per-step telemetry ([T]-leaved).
        The trace is placed via the backend's `put_trace`, so device-mesh
        backends receive each package partition pre-sharded (and
        multi-process meshes accept a process-local lane slab)."""
        self._guard_donated(state)
        self._check_trace(rho_trace)
        return self._run(state, self.backend_impl.put_trace(rho_trace),
                         self._active(state, active))

    def run_chunked(self, state: SchedulerState, rho_trace,
                    flush_every: int,
                    active=None) -> tuple[SchedulerState, FleetTelemetry]:
        """Scan a [T, n, tiles] trace in K-step chunks, reducing telemetry
        over each chunk IN-GRAPH: the result carries one record per flush
        interval, so fetching it costs one host sync per flush instead of
        one per step.

        A trace length that is NOT a multiple of ``flush_every`` is legal:
        the final partial chunk becomes its own (shorter) flush window,
        exactly as `repro.fleet.ingest.chunk_source`/`stream` deliver it —
        the result is ceil(T/K)-leaved and every step of the trace is
        counted (nothing is silently dropped, no padding enters the
        telemetry).  Chunks are placed via the backend's `put_trace`, so
        device-mesh backends receive each package partition pre-sharded."""
        self._guard_donated(state)
        self._check_trace(rho_trace)
        active = self._active(state, active)
        t = rho_trace.shape[0]
        n_full, rem = divmod(t, flush_every)
        telems = None
        if n_full:
            chunked = rho_trace[:n_full * flush_every].reshape(
                (n_full, flush_every) + rho_trace.shape[1:])
            state, telems = self._run_chunked(
                state, self.backend_impl.put_trace(chunked), active)
        if rem:
            state, tail = self._run_block(
                state, self.backend_impl.put_trace(
                    rho_trace[n_full * flush_every:]), active)
            telems = (jax.tree_util.tree_map(lambda b: b[None], tail)
                      if telems is None else
                      jax.tree_util.tree_map(
                          lambda a, b: jnp.concatenate([a, b[None]]),
                          telems, tail))
        self._debug_check_finite(state, telems)
        return state, telems

    def run_block(self, state: SchedulerState, rho_trace, active=None
                  ) -> tuple[SchedulerState, FleetTelemetry]:
        """One jitted call: scan a [K, n, tiles] chunk and return the state
        plus the chunk's SINGLE reduced telemetry record (the streaming
        ingest loop's unit of work — one host sync per block)."""
        with span("engine.run_block"):
            self._guard_donated(state)
            self._check_trace(rho_trace)
            state, telem = self._run_block(state, rho_trace,
                                           self._active(state, active))
            self._debug_check_finite(state, telem)
        return state, telem

    def run_survey(self, state: SchedulerState, rho_trace, burn_in: int = 0,
                   chunk: int = 1024) -> tuple[SchedulerState, "FleetSurvey"]:
        """Scan a [T, n, tiles] trace accumulating PER-PACKAGE (per-tile)
        reductions in-graph — the Monte-Carlo plane.

        Unlike `run`/`run_chunked` (fleet-aggregate telemetry), the survey
        keeps one record per (package, tile) lane: running peak junction
        temperature and T_crit exceedance fraction over the steps past
        ``burn_in``, plus the mean delivered frequency over the whole trace
        — exactly the §10 per-trial statistics, with O(n) accumulator state
        instead of an O(T·n) trace.  Backends with a fused `run_block`
        advance ``chunk``-step blocks through the kernel and reduce its
        streamed temp/freq traces; pure backends accumulate inside one scan.
        One host transfer total (when the caller fetches the result).
        """
        self._guard_donated(state)
        self._check_trace(rho_trace)
        t = rho_trace.shape[0]
        if not 0 <= burn_in < t:
            raise ValueError(f"burn_in={burn_in} outside the trace [0, {t})")
        acc = (jnp.full(state.freq.shape, -jnp.inf),     # running peak T
               jnp.zeros(state.freq.shape),              # exceedance count
               jnp.zeros(state.freq.shape),              # Σ freq (Kahan)
               jnp.zeros(state.freq.shape))              # Kahan compensation
        if isinstance(state.freq, jax.Array) and \
                not state.freq.is_fully_addressable:
            # process-spanning mesh: the accumulators must shard exactly
            # like the state's package axis (a host-local [n_global, tiles]
            # array is not even constructible per process at fleet scale)
            import numpy as np
            sh = state.freq.sharding
            acc = tuple(jax.device_put(
                np.full(state.freq.shape,
                        -np.inf if i == 0 else 0.0, np.float32), sh)
                for i in range(4))
        counted = jnp.arange(t) >= burn_in
        put = self.backend_impl.put_trace
        if self.backend_impl.run_block is None:
            state, acc = self._survey(state, put(rho_trace), counted, acc)
        else:
            for i in range(0, t, chunk):
                state, acc = self._survey_block(
                    state, put(rho_trace[i:i + chunk]), counted[i:i + chunk],
                    acc)
        peak, exceed, fsum, _ = acc
        if isinstance(peak, jax.Array) and not peak.is_fully_addressable:
            exceed, fsum = self._survey_finalize(
                exceed, fsum, jnp.float32(t - burn_in), jnp.float32(t))
        else:
            exceed, fsum = exceed / (t - burn_in), fsum / t
        return state, FleetSurvey(
            peak_t_c=peak,
            exceed_frac=exceed,
            freq_mean=fsum,
            steps=jnp.asarray(t, jnp.int32),
            counted_steps=jnp.asarray(t - burn_in, jnp.int32))

    # ------------------------------------------------------------- internals
    @staticmethod
    def _check_trace(rho_trace) -> None:
        """One guard for every trace entry point (run/run_block/run_chunked/
        run_survey): a zero-length trace would otherwise fall through to a
        zero-length scan or kernel call with an opaque failure mode."""
        if rho_trace.shape[0] == 0:
            raise ValueError("empty density trace")

    def _guard_donated(self, state: SchedulerState) -> None:
        """Fail readably when a donated state pytree is passed back in.

        With ``donate_state=True`` every jitted entry point donates its
        state argument, so on accelerators the buffers are invalidated the
        moment the call is dispatched; reusing the old reference would
        otherwise surface as an opaque XLA "buffer has been deleted" crash
        deep inside the next call."""
        if not self.donate_state:
            return
        for leaf in jax.tree_util.tree_leaves(state):
            if isinstance(leaf, jax.Array) and leaf.is_deleted():
                raise ValueError(
                    "this SchedulerState was already donated to a previous "
                    "FleetEngine call (donate_state=True invalidates the "
                    "input buffers): rebind the returned state — "
                    "`state, ... = eng.step(state, ...)` — instead of "
                    "reusing the old reference, or construct the engine "
                    "with donate_state=False")

    def _debug_check_finite(self, state: SchedulerState, telem) -> None:
        """``debug_nan`` guard: host-check the returned state + telemetry
        for NaN/Inf and raise with the offending lane indices.

        Degraded-fallback fleets sanitise faulty sensor words in-graph, so
        with the fallback on this should NEVER fire — a trip means a fault
        escaped the in-band containment.  Skipped on process-spanning
        meshes (the state is not fully addressable on any one host)."""
        if not self.debug_nan or telem is None:
            return
        import numpy as np
        for name in ("freq", "thermal"):
            arr = getattr(state, name)
            if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
                break
            a = np.asarray(jax.device_get(arr))
            if not np.isfinite(a).all():
                lanes = np.unique(np.argwhere(~np.isfinite(a))[:, :1])
                raise ValueError(
                    f"debug_nan: non-finite state.{name} on lane(s) "
                    f"{lanes.tolist()} — a sensor fault escaped in-band "
                    f"containment (is degraded_fallback on?)")
        host = jax.device_get(telem)._asdict()
        bad = [k for k, v in host.items()
               if not np.isfinite(np.asarray(v)).all()]
        if bad:
            raise ValueError(
                f"debug_nan: non-finite telemetry field(s) {bad} — "
                f"NaN/Inf would have propagated into flush records")

    def _active(self, state: SchedulerState, active):
        """Validate/place an optional [n_packages] bool lane mask.

        ``None`` (a dense fleet) keeps the historical telemetry code paths
        untouched; a mask is placed via the backend (`put_mask`, so sharded
        backends land each partition on its owning device) and threaded as
        a TRACED jit argument — mask-bit flips never recompile."""
        if active is None:
            return None
        n = state.freq.shape[0]
        arr = jnp.asarray(active)
        if arr.shape != (n,) or arr.dtype != jnp.bool_:
            raise ValueError(
                f"active mask must be a [{n}] bool array (one flag per "
                f"package lane), got shape {arr.shape} dtype {arr.dtype}")
        return self.backend_impl.put_mask(arr)

    def _rho_fleet(self, state: SchedulerState, rho) -> jnp.ndarray:
        n = state.freq.shape[0]
        rho = jnp.asarray(rho, state.freq.dtype)
        if rho.ndim == 1:            # per-package scalar density
            rho = rho[:, None]
        return jnp.broadcast_to(rho, (n, self.cfg.n_tiles))

    @staticmethod
    def _masked_quantile(sorted_v: jnp.ndarray, cnt, q: float) -> jnp.ndarray:
        """Linear-interpolated percentile over the first ``cnt`` entries of an
        ascending-sorted last axis (inactive lanes sort to +inf past them) —
        numpy's default interpolation, computed with a traced count so mask
        flips never re-specialise the program."""
        pos = q / 100.0 * (cnt - 1).astype(sorted_v.dtype)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        frac = pos - lo
        take = lambda i: jnp.take_along_axis(
            sorted_v, jnp.broadcast_to(i, sorted_v.shape[:-1])[..., None],
            axis=-1)[..., 0]
        return take(lo) * (1.0 - frac) + take(hi) * frac

    def _degraded_count(self, state: SchedulerState, active=None):
        """Active lanes currently on the reactive fallback (int32 scalar;
        0 whenever degraded_fallback is off)."""
        if state.degraded is None:
            return jnp.zeros((), jnp.int32)
        deg = state.degraded if active is None else (state.degraded & active)
        return deg.sum().astype(jnp.int32)

    def _masked_step_telemetry(self, rho, out, prev_events, events, active,
                               degraded_count) -> FleetTelemetry:
        """One step's fleet telemetry reduced over the active lanes only —
        padded lanes cannot touch the percentiles, `freq_min`,
        `at_risk_frac` or the event counters."""
        m = jnp.broadcast_to(active[:, None], out.temp_c.shape)   # [n, tiles]
        mf = m.reshape(-1)
        cnt = jnp.maximum(mf.sum(), 1)                 # guard the empty fleet
        fcnt = cnt.astype(out.temp_c.dtype)
        temp = out.temp_c.reshape(-1)
        freq = out.freq.reshape(-1)
        sorted_t = jnp.sort(jnp.where(mf, temp, jnp.inf))
        mu = jnp.where(mf, temp, 0.0).sum() / fcnt
        rtok = jnp.broadcast_to(rtok_from_rho(rho),
                                out.temp_c.shape).reshape(-1)
        ev_total = jnp.where(active, events, 0).sum()
        return FleetTelemetry(
            degraded_count=degraded_count,
            n_packages=active.sum().astype(jnp.int32),
            events_total=ev_total,
            events_step=ev_total - prev_events,
            temp_p50_c=self._masked_quantile(sorted_t, cnt, 50.0),
            temp_p99_c=self._masked_quantile(sorted_t, cnt, 99.0),
            temp_max_c=jnp.where(mf, temp, -jnp.inf).max(),
            temp_var_c2=(jnp.where(mf, (temp - mu) ** 2, 0.0).sum() / fcnt),
            freq_mean=jnp.where(mf, freq, 0.0).sum() / fcnt,
            freq_min=jnp.where(mf, freq, jnp.inf).min(),
            released_mtps=jnp.where(mf, rtok * freq, 0.0).sum(),
            throttled_mtps=jnp.where(mf, rtok * (1.0 - freq), 0.0).sum(),
            at_risk_frac=jnp.where(
                mf, (freq < self.cfg.straggler_threshold), 0.0).sum() / fcnt,
        )

    def _step_impl(self, state: SchedulerState, rho: jnp.ndarray,
                   active=None):
        prev_events = (state.events.sum() if active is None
                       else jnp.where(active, state.events, 0).sum())
        state, out = self.backend_impl.update(state, rho)
        if self.cfg.degraded_fallback:
            # telemetry must reduce over the SANITISED density the controller
            # actually acted on (post-update rho_last == this step's
            # hold-last-value fill), never raw NaN/Inf sensor words
            rho = state.rho_last
        if active is not None:
            return state, out, self._masked_step_telemetry(
                rho, out, prev_events, state.events, active,
                self._degraded_count(state, active))
        rtok = rtok_from_rho(rho)                    # [n_packages, n_tiles]
        telem = FleetTelemetry(
            degraded_count=self._degraded_count(state),
            n_packages=jnp.asarray(state.freq.shape[0], jnp.int32),
            events_total=state.events.sum(),
            events_step=state.events.sum() - prev_events,
            temp_p50_c=jnp.percentile(out.temp_c, 50.0),
            temp_p99_c=jnp.percentile(out.temp_c, 99.0),
            temp_max_c=out.temp_c.max(),
            temp_var_c2=out.temp_c.var(),
            freq_mean=out.freq.mean(),
            freq_min=out.freq.min(),
            released_mtps=(rtok * out.freq).sum(),
            throttled_mtps=(rtok * (1.0 - out.freq)).sum(),
            at_risk_frac=out.at_risk.mean(),
        )
        return state, out, telem

    def _run_impl(self, state: SchedulerState, rho_trace: jnp.ndarray,
                  active=None):
        def tick(st, rho):
            st, _, telem = self._step_impl(st, rho, active)
            return st, telem
        return jax.lax.scan(tick, state, rho_trace)

    @staticmethod
    def _kahan(fsum, comp, x):
        """Compensated add: a 3000-step sequential f32 Σfreq otherwise
        drifts ~1e-5 relative (the dominant fleet-vs-oracle survey error;
        peak is a max and the exceedance count is exact small integers, so
        only this accumulator needs compensation)."""
        y = x - comp
        tot = fsum + y
        return tot, (tot - fsum) - y

    def _survey_impl(self, state: SchedulerState, rho_trace, counted, acc):
        """Pure-backend survey: one scan carrying O(n) accumulators."""
        t_crit = self.fp.t_crit_c

        def tick(carry, x):
            st, peak, exceed, fsum, comp = carry
            rho, m = x
            st, out = self.backend_impl.update(st, rho)
            peak = jnp.maximum(peak, jnp.where(m, out.temp_c, -jnp.inf))
            exceed = exceed + jnp.where(m & (out.temp_c > t_crit), 1.0, 0.0)
            fsum, comp = self._kahan(fsum, comp, out.freq)
            return (st, peak, exceed, fsum, comp), None

        (state, *acc), _ = jax.lax.scan(tick, (state, *acc),
                                        (rho_trace, counted))
        return state, tuple(acc)

    def _survey_block_impl(self, state: SchedulerState, rho_trace, counted,
                           acc):
        """Fused-backend survey: whole-chunk kernel, then lane reductions
        over its streamed temp/freq traces — same jitted program."""
        peak, exceed, fsum, comp = acc
        state, temps, freqs = self.backend_impl.run_block(state, rho_trace)
        m = counted[:, None, None]
        peak = jnp.maximum(peak, jnp.where(m, temps, -jnp.inf).max(0))
        exceed = exceed + jnp.where(
            m & (temps > self.fp.t_crit_c), 1.0, 0.0).sum(0)
        fsum, comp = self._kahan(fsum, comp, freqs.sum(0))
        return state, (peak, exceed, fsum, comp)

    @staticmethod
    def _step0(state0: SchedulerState):
        """Fleet-global scheduler step at block entry.  The vmap layout
        carries a per-lane [n] step counter, but lanes advance in lockstep
        (attached lanes poll in the fleet's phase — see the module
        docstring), so any lane's value IS the fleet step; the broadcast
        layouts carry the scalar directly."""
        s = state0.step
        return s if jnp.ndim(s) == 0 else s.reshape(-1)[0]

    def _reactive_poll_events(self, state0: SchedulerState,
                              temps: jnp.ndarray,
                              active=None) -> jnp.ndarray:
        """[T] per-step fresh throttle engagements reconstructed from a
        temperature trace — the reactive_poll event statistic.

        Replays the sensor/hysteresis recurrence of
        `ThermalScheduler._update_reactive_poll` (polled → trig/cool →
        latch) over the streamed temps, starting from the pre-block latch
        and global step, so the trace-derived telemetry counts the SAME
        events as the state counter the kernel advances (the comparisons
        are exact on identical f32 temperatures)."""
        c, fp = self.cfg, self.fp
        poll = (self.sched.poll_ticks if state0.pkg is None
                else state0.pkg.poll_ticks)
        t = temps.shape[0]
        steps = self._step0(state0) + jnp.arange(t)

        def tick(latch, x):
            temp, k = x
            polled = (k % poll) == 0
            trig = (temp >= fp.t_crit_c) & polled
            cool = (temp <= c.resume_below_c) & polled
            fresh = jnp.any(trig & ~latch, axis=-1)          # [n]
            if active is not None:
                fresh = fresh & active
            return (latch | trig) & ~cool, fresh.sum().astype(jnp.int32)

        _, ev_step = jax.lax.scan(tick, state0.throttled, (temps, steps))
        return ev_step

    def _fallback_replay(self, state0: SchedulerState, rho_trace, temps,
                         active=None):
        """Replay the degraded-fallback recurrence of
        `ThermalScheduler.update` over a chunk's raw density trace and
        streamed temps: ([T] event counts, [T] degraded-lane counts,
        [T, n, tiles] sanitised rho).

        Mirrors the staleness counter / hysteresis latch / per-mode event
        plane the kernel advances in VMEM, starting from the pre-block
        state, so trace-derived telemetry counts the SAME events (fresh
        throttle engagements on degraded lanes, T_crit crossings on healthy
        ones) and the downstream MTPS reductions never see a non-finite
        density word."""
        c, fp = self.cfg, self.fp
        poll = (self.sched.poll_ticks if state0.pkg is None
                else state0.pkg.poll_ticks)
        t = temps.shape[0]
        steps = self._step0(state0) + jnp.arange(t)
        lim, rec = c.stale_limit_steps, c.recover_steps

        ctrl = state0.ctrl_mode

        def tick(carry, x):
            rho_last, stale, deg, thr = carry
            rho, temp, k = x
            finite = jnp.isfinite(rho)
            valid = jnp.all(finite, axis=-1)
            rho_safe = jnp.where(finite, rho, rho_last)
            stale_n = jnp.where(valid, jnp.maximum(stale - 1, 0),
                                jnp.minimum(stale + 1, lim + rec))
            deg_n = (deg & (stale_n > 0)) | (stale_n >= lim)
            # effective reactive mask: the staleness latch OR the operator's
            # controller pin (mixed_mode) — either routes the lane through
            # the reactive_poll semantics, mirroring the merged branch in
            # `ThermalScheduler.update` and the kernel
            reactive = deg_n if ctrl is None else (deg_n | ctrl)
            polled = (k % poll) == 0
            trig = (temp >= fp.t_crit_c) & polled
            cool = (temp <= c.resume_below_c) & polled
            deg_t = reactive[..., None]
            thr_n = jnp.where(deg_t, (thr | trig) & ~cool, False)
            ev = jnp.where(reactive, jnp.any(trig & ~thr, axis=-1),
                           jnp.any(temp > fp.t_crit_c, axis=-1))
            deg_vis = deg_n
            if active is not None:
                ev = ev & active
                deg_vis = deg_n & active
            return (rho_safe, stale_n, deg_n, thr_n), (
                ev.sum().astype(jnp.int32),
                deg_vis.sum().astype(jnp.int32), rho_safe)

        carry0 = (state0.rho_last, state0.stale, state0.degraded,
                  state0.throttled)
        _, (ev_step, deg_count, rho_safe) = jax.lax.scan(
            tick, carry0, (rho_trace, temps, steps))
        return ev_step, deg_count, rho_safe

    def _mixed_mode_events(self, state0: SchedulerState, temps,
                           active=None) -> jnp.ndarray:
        """[T] event plane for operator-pinned mixed fleets WITHOUT the
        degraded fallback (config.mixed_mode, degraded_fallback off):
        pinned lanes count fresh throttle engagements (the reactive_poll
        statistic, latch replayed from the pre-block state), v24 lanes
        count T_crit crossings — mirroring the merged branch the scheduler
        and kernel step."""
        c, fp = self.cfg, self.fp
        poll = (self.sched.poll_ticks if state0.pkg is None
                else state0.pkg.poll_ticks)
        t = temps.shape[0]
        steps = self._step0(state0) + jnp.arange(t)
        ctrl = state0.ctrl_mode

        def tick(latch, x):
            temp, k = x
            polled = (k % poll) == 0
            trig = (temp >= fp.t_crit_c) & polled
            cool = (temp <= c.resume_below_c) & polled
            latch_n = jnp.where(ctrl[..., None], (latch | trig) & ~cool,
                                False)
            ev = jnp.where(ctrl, jnp.any(trig & ~latch, axis=-1),
                           jnp.any(temp > fp.t_crit_c, axis=-1))
            if active is not None:
                ev = ev & active
            return latch_n, ev.sum().astype(jnp.int32)

        _, ev_step = jax.lax.scan(tick, state0.throttled, (temps, steps))
        return ev_step

    def _event_plane(self, rho_trace, temps, state0: SchedulerState,
                     active=None):
        """Per-step event/degraded planes for one chunk's streamed traces:
        ([T] event counts, [T] degraded-lane counts, rho_trace — sanitised
        under the degraded fallback, passed through otherwise).  Split out
        from `_telemetry_from_traces` so profile-group dispatch
        (`repro.fleet.groups`) can derive each group's plane under its own
        config before merging one fleet-wide record."""
        t = temps.shape[0]
        deg_count = jnp.zeros((t,), jnp.int32)
        if self.cfg.mode == "reactive_poll":
            ev_step = self._reactive_poll_events(state0, temps, active)
        elif self.cfg.degraded_fallback:
            # one recurrence pass yields the mixed-mode event plane, the
            # degraded-lane counts AND the sanitised density the MTPS
            # reductions below must see instead of raw NaN/Inf words
            ev_step, deg_count, rho_trace = self._fallback_replay(
                state0, rho_trace, temps, active)
        elif self.cfg.mixed_mode:
            ev_step = self._mixed_mode_events(state0, temps, active)
        else:
            crossed = jnp.any(temps > self.fp.t_crit_c, axis=-1)  # [T, n]
            if active is not None:
                crossed = crossed & active[None, :]
            ev_step = crossed.sum(axis=-1).astype(jnp.int32)
        return ev_step, deg_count, rho_trace

    def _telemetry_from_traces(self, rho_trace, temps, freqs, prev_events,
                               state0: SchedulerState,
                               active=None) -> FleetTelemetry:
        """[T]-leaved telemetry derived from per-step temperature/frequency
        traces — the telemetry plane of the fused whole-chunk backends.
        Field-for-field identical to stacking `_step_impl`'s records: under
        ``mode="reactive_poll"`` the event plane replays the sensor
        recurrence from ``state0`` (throttle engagements, the §10 baseline
        statistic); every other mode counts T_crit crossings.  With an
        ``active`` lane mask every reduction covers the active lanes only
        (padded capacity-pool lanes are invisible to the operator)."""
        ev_step, deg_count, rho_trace = self._event_plane(
            rho_trace, temps, state0, active)
        return self._traces_record(rho_trace, temps, freqs, prev_events,
                                   ev_step, deg_count, active)

    def _traces_record(self, rho_trace, temps, freqs, prev_events,
                       ev_step, deg_count, active=None) -> FleetTelemetry:
        """The masked/unmasked trace reductions behind
        `_telemetry_from_traces`, taking pre-computed event/degraded
        planes — profile-group dispatch concatenates per-group traces and
        sums per-group planes before calling this once fleet-wide."""
        t, n = temps.shape[0], temps.shape[1]
        flat = lambda x: x.reshape(t, -1)
        rtok = rtok_from_rho(rho_trace)
        if active is None:
            return FleetTelemetry(
                degraded_count=deg_count,
                n_packages=jnp.full((t,), n, jnp.int32),
                events_total=prev_events + jnp.cumsum(ev_step),
                events_step=ev_step,
                temp_p50_c=jnp.percentile(flat(temps), 50.0, axis=1),
                temp_p99_c=jnp.percentile(flat(temps), 99.0, axis=1),
                temp_max_c=flat(temps).max(axis=1),
                temp_var_c2=flat(temps).var(axis=1),
                freq_mean=flat(freqs).mean(axis=1),
                freq_min=flat(freqs).min(axis=1),
                released_mtps=flat(rtok * freqs).sum(axis=1),
                throttled_mtps=flat(rtok * (1.0 - freqs)).sum(axis=1),
                at_risk_frac=flat(freqs < self.cfg.straggler_threshold
                                  ).mean(axis=1),
            )
        mf = jnp.broadcast_to(active[:, None], temps.shape[1:]).reshape(-1)
        cnt = jnp.maximum(mf.sum(), 1)
        fcnt = cnt.astype(temps.dtype)
        tf, ff = flat(temps), flat(freqs)
        sorted_t = jnp.sort(jnp.where(mf[None, :], tf, jnp.inf), axis=1)
        mu = jnp.where(mf, tf, 0.0).sum(axis=1) / fcnt
        msum = lambda x: jnp.where(mf, x, 0.0).sum(axis=1)
        return FleetTelemetry(
            degraded_count=deg_count,
            n_packages=jnp.full((t,), 1, jnp.int32)
            * active.sum().astype(jnp.int32),
            events_total=prev_events + jnp.cumsum(ev_step),
            events_step=ev_step,
            temp_p50_c=self._masked_quantile(sorted_t, cnt, 50.0),
            temp_p99_c=self._masked_quantile(sorted_t, cnt, 99.0),
            temp_max_c=jnp.where(mf, tf, -jnp.inf).max(axis=1),
            temp_var_c2=msum((tf - mu[:, None]) ** 2) / fcnt,
            freq_mean=msum(ff) / fcnt,
            freq_min=jnp.where(mf, ff, jnp.inf).min(axis=1),
            released_mtps=msum(flat(rtok * freqs)),
            throttled_mtps=msum(flat(rtok * (1.0 - freqs))),
            at_risk_frac=msum(ff < self.cfg.straggler_threshold) / fcnt,
        )

    def block_traces(self, state: SchedulerState, rho_trace):
        """(state', temps [T, n, tiles], freqs [T, n, tiles]) for one chunk —
        via the backend's fused whole-chunk kernel when it has one, else a
        scan of `update`.  Trace-safe (NOT jitted here): the control plane
        (`repro.fleet.service`) composes it with the per-tenant alert
        reductions inside ITS one jitted flush."""
        if self.backend_impl.run_block is not None:
            return self.backend_impl.run_block(state, rho_trace)

        def tick(st, rho):
            st, out = self.backend_impl.update(st, rho)
            return st, (out.temp_c, out.freq)

        state, (temps, freqs) = jax.lax.scan(tick, state, rho_trace)
        return state, temps, freqs

    def window_telemetry(self, rho_trace, temps, freqs, prev_events,
                         state0: SchedulerState,
                         active=None) -> FleetTelemetry:
        """Public trace-safe wrapper over the traces→telemetry reduction
        (see `_telemetry_from_traces`) for callers that already hold the
        streamed temp/freq traces of a window — returns the [T]-leaved
        record; `.reduce()` collapses it to one flush record."""
        return self._telemetry_from_traces(rho_trace, temps, freqs,
                                           prev_events, state0, active)

    def _run_block_impl(self, state: SchedulerState, rho_trace: jnp.ndarray,
                        active=None):
        if active is not None:
            # masked flush window: one traces pass (kernel or scan) feeds
            # the active-lane-only reductions
            prev_events = jnp.where(active, state.events, 0).sum()
            state0 = state
            state, temps, freqs = self.block_traces(state, rho_trace)
            telems = self._telemetry_from_traces(rho_trace, temps, freqs,
                                                 prev_events, state0, active)
        elif (self.backend_impl.run_block is not None
              or self._spans_processes()):
            # whole-chunk traces path: advance the block (fused kernel when
            # the backend has one, else a collective-free scan of update),
            # then reduce telemetry from the streamed temp/freq traces.
            # Process-spanning meshes MUST take this path even without a
            # kernel: the per-step telemetry scan below puts ~a dozen
            # package-axis reductions inside every scan iteration — free
            # intra-host, but each one is a cross-HOST gloo round trip on a
            # multi-process mesh (~10^2-10^3x the step math).  Here the
            # reductions run ONCE per flush, in-graph, right before the
            # single host sync.
            prev_events = state.events.sum()
            state0 = state
            state, temps, freqs = self.block_traces(state, rho_trace)
            telems = self._telemetry_from_traces(rho_trace, temps, freqs,
                                                 prev_events, state0)
        else:
            state, telems = self._run_impl(state, rho_trace)
        return state, telems.reduce()

    def _spans_processes(self) -> bool:
        """True when the backend's mesh spans a multi-process group (the
        host-side fact is identical on every process, so branching on it
        keeps the program SPMD)."""
        spans = getattr(self.backend_impl, "_spans_processes", None)
        return bool(spans and spans())

    def _run_chunked_impl(self, state: SchedulerState, chunked: jnp.ndarray,
                          active=None):
        return jax.lax.scan(
            lambda st, ch: self._run_block_impl(st, ch, active),
            state, chunked)


def sequential_step(sched: ThermalScheduler, states: list[SchedulerState],
                    rho: jnp.ndarray) -> tuple[list[SchedulerState],
                                               list[SchedulerOutput]]:
    """Per-package Python-loop reference: one `update` call per package.

    This is the baseline the fleet engine is benchmarked and verified
    against.  rho: [n_packages, n_tiles].
    """
    nxt, outs = [], []
    for i, st in enumerate(states):
        st, out = sched.update(st, rho[i])
        nxt.append(st)
        outs.append(out)
    return nxt, outs
