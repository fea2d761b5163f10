"""Fleet control plane — a resident multi-tenant serving service.

`FleetService` keeps a `FleetEngine` resident and serves a DYNAMIC fleet:
packages attach and detach at runtime (OEM fleets come and go), every
tenant gets its own alert thresholds, and an operator can watch and steer
the whole thing over a plain HTTP/JSON API — with ZERO recompilations of
the jitted step after warmup.

How the zero-recompile guarantee is put together (the whole design keys
off what does and does not retrace a `jax.jit` program):

  * **Capacity pools** (`repro.fleet.registry.FleetRegistry`): fleet state
    is padded to power-of-two capacity buckets, so the engine only ever
    sees O(log max_fleet) distinct shapes, all compiled during `warmup`.
  * **Membership is a traced mask**: attach/detach flips bits in a
    `[capacity]` bool mask (`FleetEngine`'s ``active`` argument) — a VALUE
    change, never a shape change.  Padded lanes still step (lockstep
    execution is what keeps one program) but the engine's masked telemetry
    and the per-tenant segment reductions cannot see them.
  * **State surgery is jitted too**: scattering a fresh lane in
    (`_attach_op`, traced lane index), growing to the next bucket
    (`_grow_op`, copy-to-front of a cached fresh template) and compacting
    into a smaller bucket (`_shrink_op`, traced gather permutation) are
    ordinary jitted programs, one per capacity (pair), warmed like the
    rest.
  * **Thresholds are traced operands**: per-tenant t_crit / at-risk /
    CPO-drift budgets live in dense `[max_tenants]` arrays
    (`FleetRegistry.threshold_arrays`) consumed in-graph by
    `repro.fleet.alerts.tenant_window_stats` — editing a tenant's
    threshold over POST /thresholds changes array VALUES only.

Each `tick()` is ONE flush: assemble the next `[K, capacity, tiles]`
density chunk (per-package synthetic workloads via
`repro.core.workload.make_trace`, padded lanes idle at ``pad_rho``), run
one jitted flush program (engine `block_traces` → masked window telemetry
→ per-tenant stats/alarms), fetch everything in a SINGLE host sync, append
a replayable record to the `TelemetryLog`, and push alarm edges through
the `AlertEngine` sinks.  `replay()` re-drives a recorded JSONL stream
through the existing `HintQueue` ingest path — including any recorded
capacity transitions, via each flush record's surgery-op journal — and
returns the reproduced telemetry.

Robustness (docs/serving.md "Fault tolerance & recovery"):
``snapshot_dir=...`` + ``snapshot_every=N`` takes crash-consistent async
snapshots (engine state through `repro.checkpoint.CheckpointManager`,
host bookkeeping — including still-queued `/ingest` chunks — in the
manifest) and journals every membership/threshold/ingest op to
``journal.jsonl``; `FleetService.restore()` resumes a killed
service ≤1e-5-equivalent to an uninterrupted run.  ``heartbeat_timeout_s``
arms a stalled-flush watchdog surfaced at GET /healthz, and a fleet run
with `SchedulerConfig(degraded_fallback=True)` reports degraded-lane
counts per flush plus a per-tenant ``degraded`` alert kind.

Workloads are synthesised per attached package by default; a tenant can
instead POST real density chunks to `/ingest` — they queue in a bounded
per-tenant `HintQueue` (back-pressure via HTTP 429 when full) and `tick()`
routes the head chunk onto the tenant's lanes through `merge_sources`,
while unfed lanes keep their synthetic workloads.

The HTTP surface (stdlib `http.server`, no new dependencies) is documented
operator-facing in docs/serving.md:

    GET  /healthz /telemetry /fleet /alerts /dashboard
    POST /attach /detach /thresholds /ingest /replay /shutdown
    POST /canary /mode           (per-lane controller-mode rollout)

Per-lane profiles (`repro.fleet.registry.LaneProfile`) ride membership:
`POST /attach` accepts optional ``node`` (a `repro.core.nodebank` bank,
resolved to that lane's heterogeneous `PackageParams` row), ``mode``
(``v24`` | ``reactive_poll`` — pinned into the traced ctrl_mode plane of
a `SchedulerConfig(mixed_mode=True)` fleet) and ``plant`` keys, and
`POST /canary {"reactive_frac": f}` shifts the fleet's mode mix live —
pure value changes, ZERO recompiles after warmup (the §9/§10 canary
rollout path; see docs/serving.md).

`GET /dashboard` is the same surface rendered for humans: a stdlib-built
HTML page (sparkline flush history, per-tenant table, alert feed) with a
2-second meta-refresh — point a browser at it and it is a live operator
view with zero extra dependencies.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fingerprint import FINGERPRINT, Fingerprint
from repro.core.scheduler import SchedulerConfig, SchedulerState
from repro.core.telemetry import TelemetryLog
from repro.core.workload import KINDS, make_trace
from repro.fleet.alerts import AlertEngine, tenant_window_stats
from repro.fleet.engine import FleetEngine
from repro.fleet.ingest import HintQueue, merge_sources
from repro.fleet.registry import FleetRegistry, LaneProfile
from repro.tracing import span

__all__ = ["FleetService", "serve_http"]


class FleetService:
    """Resident control plane over one `FleetEngine`.

    All public methods are thread-safe (one re-entrant lock serialises
    membership surgery, threshold edits and flushes against the HTTP
    handler threads).  The engine state is owned by the service — callers
    never touch it directly.
    """

    def __init__(self, cfg: SchedulerConfig | None = None,
                 fp: Fingerprint = FINGERPRINT,
                 backend: str = "broadcast", *,
                 min_capacity: int = 4, max_tenants: int = 8,
                 flush_every: int = 50, pad_rho: float = 1.0,
                 sinks=(), log_capacity: int = 4096, seed: int = 0,
                 feed_capacity: int = 4,
                 snapshot_dir: str | None = None, snapshot_every: int = 0,
                 heartbeat_timeout_s: float = 0.0, debug_nan: bool = False):
        self.engine = FleetEngine(cfg, fp, backend=backend,
                                  debug_nan=debug_nan)
        self.cfg, self.fp = self.engine.cfg, fp
        self.backend_name = backend
        self.registry = FleetRegistry(min_capacity=min_capacity,
                                      max_tenants=max_tenants)
        self.alerts = AlertEngine(sinks=sinks)
        self.log = TelemetryLog(capacity=log_capacity)
        self.flush_every = int(flush_every)
        self.pad_rho = float(pad_rho)
        self.feed_capacity = int(feed_capacity)
        self._feeds: dict[str, HintQueue] = {}  # tenant -> queued chunks
        self.lock = threading.RLock()
        self.flushes = 0
        self.steps = 0            # host mirror of the fleet clock — keeps
        #                           tick() at exactly one device sync
        self._seed = seed
        self._kind_of: dict[str, str] = {}      # package -> workload kind
        self._pkg_key: dict[str, int] = {}      # package -> key counter base
        self._next_key = 0
        self._attached_since_flush: list[int] = []
        self._surgery_since_flush: list[dict] = []   # ordered per-flush ops
        self._templates: dict[int, SchedulerState] = {}
        self._shutdown = threading.Event()
        # crash-consistent recovery: periodic async snapshots of the whole
        # service (engine state + registry/counters in the manifest) plus a
        # JSONL journal of every membership/threshold op since boot —
        # `FleetService.restore()` replays journal entries past the snapshot
        # to resume ≤1e-5-equivalent to an uninterrupted run
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = int(snapshot_every)
        self._ckpt = None
        self._journal_seq = 0
        self._restoring = False    # suppresses journaling during replay
        self._warmed_max = 0
        self.last_degraded = 0     # degraded-lane count of the last flush
        if snapshot_dir is not None:
            from repro.checkpoint.manager import CheckpointManager
            self._ckpt = CheckpointManager(snapshot_dir)
            self._journal_path = os.path.join(snapshot_dir, "journal.jsonl")
        # stalled-flush watchdog (GET /healthz surfaces `stalled`); 0 = off
        self.heartbeat = None
        if heartbeat_timeout_s > 0:
            from repro.distributed.fault_tolerance import Heartbeat
            self.heartbeat = Heartbeat(timeout_s=heartbeat_timeout_s)
        dn = (0,) if self.engine.donate_state else ()
        self._flush_jit = jax.jit(self._flush_impl, donate_argnums=dn)
        self._attach_jit = jax.jit(self._attach_op, donate_argnums=dn)
        self._grow_jit = jax.jit(self._grow_op, donate_argnums=dn)
        self._shrink_jit = jax.jit(self._shrink_op, donate_argnums=dn)
        # per-lane node banks: the scatter of one node's PackageParams row
        # into a heterogeneous fleet state (one program per capacity, warmed
        # with the rest); rows are cached per node name
        self._node_jit = jax.jit(self._node_op, donate_argnums=dn)
        self._node_rows: dict[str, object] = {}
        # one persistent jit for workload generation: eager `make_trace`
        # rebuilds its lax.scan closure every call, which recompiles every
        # tick — under ONE jit object the (kind, shape) programs cache
        self._make_trace = jax.jit(make_trace, static_argnums=(1, 2, 3))
        self.state = self._fresh(self.registry.capacity)

    # ------------------------------------------------------------ templates
    def _fresh(self, capacity: int) -> SchedulerState:
        # strip weak types: init's eager-built leaves are weak-typed while
        # every jit output is strong-typed, and a mixed-provenance state
        # would retrace the surgery jits (breaking the zero-recompile
        # contract) even though shapes and dtypes match
        return jax.tree_util.tree_map(lambda a: a.astype(a.dtype),
                                      self.engine.init(capacity))

    def _template(self, capacity: int) -> SchedulerState:
        """Cached fresh state per capacity — the scatter source for
        attaches and the target skeleton for grows.  Cached so steady-state
        operation re-runs no eager init ops (the zero-recompile test
        counts every backend compile after warmup)."""
        tpl = self._templates.get(capacity)
        if tpl is None:
            tpl = self._templates[capacity] = self._fresh(capacity)
        return tpl

    # -------------------------------------------------------- state surgery
    # All three ops discriminate per-lane leaves by their leading capacity
    # axis (in the broadcast layout every ndim≥1 leaf is per-lane; scalars
    # are the shared fleet clock and ring pointer, which surgery must NOT
    # reset — an attached lane joins the running fleet's clock).
    @staticmethod
    def _attach_op(state, template, lane):
        cap = state.freq.shape[0]

        def scatter(a, b):
            if getattr(a, "ndim", 0) >= 1 and a.shape[0] == cap:
                return a.at[lane].set(b[lane])
            return a
        return jax.tree_util.tree_map(scatter, state, template)

    @staticmethod
    def _grow_op(state, template):
        old = state.freq.shape[0]

        def grow(a, b):
            if getattr(a, "ndim", 0) >= 1 and b.shape[0] != a.shape[0]:
                return b.at[:old].set(a)
            return a
        return jax.tree_util.tree_map(grow, state, template)

    @staticmethod
    def _node_op(state, row, lane):
        """Scatter one node bank's `PackageParams` row (batch 1) into the
        heterogeneous per-lane draws at ``lane`` — the jitted tail of a
        profile-carrying attach."""
        pkg = jax.tree_util.tree_map(lambda a, b: a.at[lane].set(b[0]),
                                     state.pkg, row)
        return state._replace(pkg=pkg)

    @staticmethod
    def _shrink_op(state, perm):
        old = state.freq.shape[0]

        def take(a):
            if getattr(a, "ndim", 0) >= 1 and a.shape[0] == old:
                return a[perm]
            return a
        return jax.tree_util.tree_map(take, state)

    def _apply_plan(self, plan) -> None:
        if plan.kind == "grow":
            self.state = self._grow_jit(self.state,
                                        self._template(plan.new_capacity))
            self._surgery_since_flush.append(
                {"op": "grow", "old": plan.old_capacity,
                 "new": plan.new_capacity})
        elif plan.kind == "shrink":
            perm = jnp.asarray(np.asarray(plan.perm, np.int32))
            self.state = self._shrink_jit(self.state, perm)
            self._surgery_since_flush.append(
                {"op": "shrink", "old": plan.old_capacity,
                 "new": plan.new_capacity,
                 "perm": [int(p) for p in plan.perm]})

    # ----------------------------------------------------------- journaling
    def _journal(self, entry: dict) -> None:
        """Append one membership/threshold/ingest op to the journal —
        crash-consistent bookkeeping between snapshots.  Entries carry a
        monotonic ``seq`` and the flush count they happened AFTER, so
        `restore()` can re-drive exactly the post-snapshot suffix at the
        right points of the re-synthesised flush stream."""
        if self._ckpt is None or self._restoring:
            return
        entry = {"seq": self._journal_seq, "flush": self.flushes, **entry}
        self._journal_seq += 1
        with open(self._journal_path, "a") as f:
            f.write(json.dumps(entry) + "\n")
            f.flush()
            os.fsync(f.fileno())

    # ---------------------------------------------------- per-lane profiles
    def _node_row(self, node: str):
        """Cached single-lane `PackageParams` row for ``node`` (strong-typed
        like every other scatter source)."""
        row = self._node_rows.get(node)
        if row is None:
            from repro.core.nodebank import fleet_package_params
            row = fleet_package_params(self.engine.sched, [node])
            row = jax.tree_util.tree_map(lambda a: a.astype(a.dtype), row)
            self._node_rows[node] = row
        return row

    def _profile_for(self, node: str, mode: str,
                     plant: str | None) -> LaneProfile:
        """Validate one attach's profile against the service config: node
        names must exist, non-base nodes need a heterogeneous fleet,
        reactive pins need `mixed_mode`, and the resident engine serves
        exactly ONE plant group (a fidelity mix runs through
        `repro.fleet.groups.GroupedFleetEngine`)."""
        from repro.core.nodebank import available_nodes, get_node
        get_node(node)                       # raises on unknown names
        if node != "base" and not self.cfg.heterogeneous:
            raise ValueError(
                f"node {node!r} needs SchedulerConfig(heterogeneous=True) "
                f"— a homogeneous fleet carries no per-lane parameter rows "
                f"(available nodes: {', '.join(available_nodes())})")
        if mode == "reactive_poll" and not self.cfg.mixed_mode:
            raise ValueError(
                "pinning mode='reactive_poll' needs "
                "SchedulerConfig(mixed_mode=True) — the fleet carries no "
                "ctrl_mode plane otherwise")
        plant = self.cfg.plant if plant is None else plant
        if plant != self.cfg.plant:
            raise ValueError(
                f"this service steps plant group {self.cfg.plant!r}; "
                f"got plant={plant!r} — run a fidelity mix through "
                f"repro.fleet.groups.GroupedFleetEngine")
        return LaneProfile(node=node, mode=mode, plant=plant)

    def _refresh_ctrl(self) -> None:
        """Re-derive the traced ctrl_mode plane from the registry's
        profiles.  Pure value substitution on one state leaf — shifting the
        fleet's mode mix never compiles anything."""
        if self.state.ctrl_mode is not None:
            self.state = self.state._replace(
                ctrl_mode=jnp.asarray(self.registry.ctrl_mode_mask()))

    def canary(self, reactive_frac: float) -> dict:
        """Canary rollout: pin the first ``round(frac·n_active)`` packages
        (sorted-id order — monotone and idempotent, see
        `FleetRegistry.canary`) to reactive_poll, the rest back to v24,
        live.  The pins land in the ctrl_mode value plane, so fraction
        shifts after warmup trigger ZERO XLA compiles."""
        with self.lock:
            if not self.cfg.mixed_mode:
                raise ValueError(
                    "canary rollout needs SchedulerConfig(mixed_mode=True)")
            out = self.registry.canary(float(reactive_frac))
            self._refresh_ctrl()
            self._journal({"op": "canary",
                           "frac": float(reactive_frac)})
            return out

    def set_mode(self, package: str, mode: str) -> dict:
        """Pin ONE package's controller mode (v24 ↔ reactive_poll)."""
        with self.lock:
            if mode == "reactive_poll" and not self.cfg.mixed_mode:
                raise ValueError(
                    "pinning mode='reactive_poll' needs "
                    "SchedulerConfig(mixed_mode=True)")
            pr = self.registry.set_mode(package, mode)
            self._refresh_ctrl()
            self._journal({"op": "mode", "package": package, "mode": mode})
            return {"package": package, "node": pr.node, "mode": pr.mode,
                    "plant": pr.plant}

    # ------------------------------------------------------------ membership
    def attach(self, package: str, tenant: str = "default",
               kind: str = "inference", *, node: str = "base",
               mode: str = "v24", plant: str | None = None) -> dict:
        """Attach a package: bucket surgery if occupancy crosses a boundary,
        then scatter a fresh lane state in (jitted, traced lane index).

        ``node``/``mode``/``plant`` pin the lane's `LaneProfile`: a
        non-base node scatters that node bank's `PackageParams` row into
        the lane (heterogeneous fleets), and a reactive mode pin lands in
        the ctrl_mode plane (mixed-mode fleets)."""
        if kind not in KINDS:
            raise ValueError(f"unknown workload kind {kind!r}; "
                             f"want one of {KINDS}")
        profile = self._profile_for(node, mode, plant)
        with self.lock:
            lane, plan = self.registry.attach(package, tenant,
                                              profile=profile)
            self._apply_plan(plan)
            self.state = self._attach_jit(
                self.state, self._template(self.registry.capacity),
                jnp.asarray(lane, jnp.int32))
            if node != "base":
                self.state = self._node_jit(self.state,
                                            self._node_row(node),
                                            jnp.asarray(lane, jnp.int32))
            self._refresh_ctrl()
            self._kind_of[package] = kind
            self._pkg_key[package] = self._next_key
            self._next_key += 1
            self._attached_since_flush.append(lane)
            self._surgery_since_flush.append({"op": "attach", "lane": lane})
            self._journal({"op": "attach", "package": package,
                           "tenant": tenant, "workload": kind,
                           "profile": {"node": node, "mode": mode,
                                       "plant": profile.plant}})
            return {"package": package, "tenant": tenant, "kind": kind,
                    "lane": lane, "capacity": self.registry.capacity,
                    "plan": plan.kind, "node": profile.node,
                    "mode": profile.mode, "plant": profile.plant}

    def detach(self, package: str) -> dict:
        with self.lock:
            lane, plan = self.registry.detach(package)
            self._apply_plan(plan)
            self._kind_of.pop(package, None)
            self._pkg_key.pop(package, None)
            if plan.kind == "shrink":
                remap = {old: new for new, old in enumerate(plan.perm)}
                self._attached_since_flush = [
                    remap[l] for l in self._attached_since_flush
                    if l in remap]
            else:
                self._attached_since_flush = [
                    l for l in self._attached_since_flush if l != lane]
            self._refresh_ctrl()    # departed pin + any capacity change
            self._journal({"op": "detach", "package": package})
            return {"package": package, "lane": lane,
                    "capacity": self.registry.capacity, "plan": plan.kind}

    def set_thresholds(self, tenant: str, **kw) -> dict:
        with self.lock:
            t = self.registry.set_thresholds(tenant, **kw)
            self._journal({"op": "thresholds", "tenant": tenant,
                           "kw": {k: float(v) for k, v in kw.items()
                                  if v is not None}})
            return {"tenant": t.name, "t_crit_c": t.t_crit_c,
                    "at_risk_limit": t.at_risk_limit,
                    "drift_budget_nm": t.drift_budget_nm,
                    "degraded_limit": t.degraded_limit}

    # ---------------------------------------------------------------- ingest
    def ingest(self, tenant: str, chunk) -> dict:
        """Queue one POSTed density chunk for ``tenant``'s packages.

        ``chunk`` is [flush_every, n_tiles] (or [flush_every], broadcast
        over tiles): the density every package of the tenant runs for one
        upcoming flush window.  Chunks queue in a per-tenant bounded
        `HintQueue` (capacity ``feed_capacity`` — the service-side hint
        horizon) and are consumed one per `tick()`, routed through
        `merge_sources` onto the tenant's lanes; lanes with no queued feed
        keep their synthetic workloads.  A full queue REFUSES the chunk
        (``accepted: false`` / HTTP 429) — back-pressure is the poster's
        signal to slow down, never a silent drop.
        """
        with self.lock:
            if tenant not in self.registry.tenants:
                raise ValueError(f"unknown tenant {tenant!r}; attach a "
                                 f"package for it first")
            arr = np.asarray(chunk, np.float32)
            if arr.ndim == 1:
                arr = np.repeat(arr[:, None], self.cfg.n_tiles, axis=1)
            if arr.shape != (self.flush_every, self.cfg.n_tiles):
                raise ValueError(
                    f"chunk must be [{self.flush_every}, "
                    f"{self.cfg.n_tiles}] (one flush window), got "
                    f"{tuple(arr.shape)}")
            if not np.all(np.isfinite(arr)) or arr.min() < 0:
                raise ValueError("chunk must be finite and non-negative")
            q = self._feeds.get(tenant)
            if q is None:
                q = self._feeds[tenant] = HintQueue(self.feed_capacity)
            accepted = q.offer(arr)
            if accepted:
                # journal the ACCEPTED chunk: tenant-POSTed density is real
                # data, not an advisory hint — a crash between accept and
                # flush must not silently swap it for a synthetic workload.
                # Replay re-offers at the recorded flush cursor, and the
                # one-chunk-per-tick drain makes queue state deterministic.
                self._journal({"op": "ingest", "tenant": tenant,
                               "chunk": arr.tolist()})
            return {"tenant": tenant, "accepted": bool(accepted),
                    "queued": len(q),
                    "lookahead_ms": q.lookahead_ms(self.flush_every,
                                                   self.cfg.step_ms)}

    # ----------------------------------------------------------------- flush
    def _flush_impl(self, state, chunk, active, tenant_ids, thresholds):
        """ONE jitted program per (capacity, chunk-length): advance the
        window, reduce fleet telemetry and per-tenant stats/alarms — the
        caller fetches the whole result in a single device_get."""
        ev0_lane = state.events
        ev0 = jnp.where(active, state.events, 0).sum()
        state0 = state
        state, temps, freqs = self.engine.block_traces(state, chunk)
        telem = self.engine.window_telemetry(
            chunk, temps, freqs, ev0, state0, active).reduce()
        stats, alarms = tenant_window_stats(
            temps, freqs, ev0_lane, state.events, active, tenant_ids,
            self.registry.max_tenants, self.cfg.straggler_threshold,
            self.fp.kappa_to_nm_per_c, thresholds,
            degraded=state.degraded)
        return state, telem, stats, alarms

    def _chunk(self, n_steps: int) -> tuple[np.ndarray, list[str]]:
        """Assemble the next [n_steps, capacity, tiles] density chunk: each
        attached package runs its synthetic workload, EXCEPT lanes of a
        tenant with a queued `ingest` feed — those take the head chunk of
        the tenant's HintQueue, assembled onto their lanes via
        `merge_sources`.  Free lanes idle at ``pad_rho`` (they step, but
        the mask keeps them out of telemetry).  Returns the chunk plus the
        tenants fed this flush (recorded in the flush record)."""
        cap, tiles = self.registry.capacity, self.cfg.n_tiles
        fed: dict[str, np.ndarray] = {}
        for tenant, q in self._feeds.items():
            if len(q) and tenant in self.registry.tenants:
                fed[tenant] = q.take()
        fed_lanes: dict[int, object] = {}
        tenants = self.registry.tenants
        for tname, rho in fed.items():
            for pkg in tenants[tname].packages:
                fed_lanes[self.registry.lane(pkg)] = iter([rho])
        with span("service.chunk"):
            chunk = np.full((n_steps, cap, tiles), self.pad_rho, np.float32)
            merged = (next(merge_sources(fed_lanes, cap, tiles,
                                         pad_rho=self.pad_rho))
                      if fed_lanes else None)
            for pkg, lane in self.registry.packages.items():
                if merged is not None and lane in fed_lanes:
                    chunk[:, lane, :] = merged[:, lane, :]
                    continue
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self._seed + self._pkg_key[pkg]),
                    self.flushes)
                chunk[:, lane, :] = np.asarray(self._make_trace(
                    key, n_steps, self._kind_of[pkg], tiles))
        return chunk, sorted(fed)

    def tick(self, chunk=None) -> dict | None:
        """One flush: step the fleet `flush_every` steps (or an explicit
        [K, capacity, tiles] chunk), sync ONCE, record, and run alerts.
        Returns the flush record (None when the fleet is empty)."""
        with self.lock:
            if self.registry.n_active == 0 and chunk is None:
                return None
            fed: list[str] = []
            if chunk is None:
                chunk, fed = self._chunk(self.flush_every)
            chunk = np.asarray(chunk, np.float32)
            cap = self.registry.capacity
            if chunk.ndim != 3 or chunk.shape[1:] != (cap, self.cfg.n_tiles):
                raise ValueError(
                    f"chunk must be [K, {cap}, {self.cfg.n_tiles}], "
                    f"got {chunk.shape}")
            step0 = self.steps
            active = jnp.asarray(self.registry.active_mask())
            ids = jnp.asarray(self.registry.tenant_lane_ids())
            th = {k: jnp.asarray(v)
                  for k, v in self.registry.threshold_arrays().items()}
            self.state, telem, stats, alarms = self._flush_jit(
                self.state, jnp.asarray(chunk), active, ids, th)
            # the single host sync of the flush
            telem_h, stats_h, alarms_h = jax.device_get(
                (telem, stats, alarms))
            names = self.registry.slot_names()
            fired = self.alerts.process(
                flush=self.flushes, step=step0, slot_names=names,
                stats=stats_h._asdict(), alarms=alarms_h,
                thresholds=self.registry.threshold_arrays())
            with span("service.record"):
                # coerce numpy leaves to plain python here — TelemetryLog's
                # _jsonable does not recurse into the nested dicts
                tdict = {k: (int(v) if k in ("n_packages", "degraded_count")
                             else float(v))
                         for k, v in telem_h._asdict().items()}
                sdict = stats_h._asdict()
                record = {
                    "kind": "flush", "flush": self.flushes,
                    "capacity": cap,
                    "active": self.registry.active_mask().astype(int).tolist(),
                    "attached": [int(l) for l in self._attached_since_flush],
                    "surgery": list(self._surgery_since_flush),
                    "telemetry": tdict,
                    "tenants": {
                        names[s]: {k: (int(v[s]) if k in ("n_lanes", "events",
                                                          "degraded_lanes")
                                       else float(v[s]))
                                   for k, v in sdict.items()}
                        for s in range(self.registry.max_tenants)
                        if names[s] is not None and sdict["n_lanes"][s] > 0},
                    "alerts": fired,
                    "ingest_fed": fed,
                    "rho": chunk.tolist(),
                }
                self.log.record(step0, **record)
            self._attached_since_flush = []
            self._surgery_since_flush = []
            self.flushes += 1
            self.steps += chunk.shape[0]
            self.last_degraded = tdict.get("degraded_count", 0)
            if self.heartbeat is not None:
                self.heartbeat.beat()
            if (self._ckpt is not None and self.snapshot_every
                    and not self._restoring
                    and self.flushes % self.snapshot_every == 0):
                self.save_snapshot(blocking=False)
            return record

    # ---------------------------------------------------------------- warmup
    def warmup(self, max_packages: int) -> int:
        """Pre-compile every program steady-state operation can need up to
        ``max_packages`` occupancy: per-capacity flush, attach scatter,
        grow and shrink surgery, templates, and one workload trace per
        kind.  After this, attach/detach/tick cycles within the warmed
        range trigger ZERO XLA compiles (asserted in
        tests/test_fleet_service.py via `jax.monitoring`)."""
        from repro.fleet.registry import next_pow2
        with self.lock:
            self._warmed_max = max(self._warmed_max, int(max_packages))
            caps = []
            c = self.registry.min_capacity
            top = max(self.registry.min_capacity,
                      next_pow2(max_packages))
            while c <= top:
                caps.append(c)
                c *= 2
            tiles = self.cfg.n_tiles
            for kind in KINDS:             # compile the workload generators
                self._make_trace(
                    jax.random.fold_in(jax.random.PRNGKey(0), 0),
                    self.flush_every, kind, tiles)
            zero_th = {k: jnp.asarray(v) for k, v in
                       self.registry.threshold_arrays().items()}
            for cap in caps:
                tpl = self._template(cap)
                st = self._fresh(cap)
                st = self._attach_jit(st, tpl, jnp.asarray(0, jnp.int32))
                if self.cfg.heterogeneous:
                    # node-row scatter: same program for every node (rows
                    # share shapes) — one compile per capacity
                    st = self._node_jit(st, self._node_row("base"),
                                        jnp.asarray(0, jnp.int32))
                chunk = jnp.full((self.flush_every, cap, tiles),
                                 self.pad_rho, jnp.float32)
                active = jnp.asarray(np.ones(cap, bool))
                ids = jnp.asarray(np.zeros(cap, np.int32))
                st, *_ = self._flush_jit(st, chunk, active, ids, zero_th)
            for small, big in zip(caps, caps[1:]):
                st = self._grow_jit(self._fresh(small), self._template(big))
                perm = jnp.asarray(np.arange(small, dtype=np.int32))
                self._shrink_jit(st, perm)
            return len(caps)

    # ------------------------------------------------------------- snapshots
    def save_snapshot(self, blocking: bool = False) -> int:
        """Snapshot the WHOLE service: the engine state pytree through
        `CheckpointManager` (atomic rename, async by default) with every
        piece of host-side bookkeeping — registry membership, tenant
        thresholds, workload assignments, flush/step counters, alert
        latches, the journal cursor — in the manifest's ``extra`` dict.
        Returns the snapshot's step id."""
        if self._ckpt is None:
            raise ValueError("snapshots need FleetService(snapshot_dir=...)")
        with self.lock:
            r = self.registry
            meta = {
                "cfg": dataclasses.asdict(self.cfg),
                "backend": self.backend_name,
                "service": {"min_capacity": r.min_capacity,
                            "max_tenants": r.max_tenants,
                            "flush_every": self.flush_every,
                            "pad_rho": self.pad_rho,
                            "seed": self._seed,
                            "feed_capacity": self.feed_capacity,
                            "snapshot_every": self.snapshot_every},
                "registry": {
                    "capacity": r.capacity,
                    "lane_of": dict(r._lane_of),
                    "tenant_of": dict(r._tenant_of),
                    "profiles": {p: [pr.node, pr.mode, pr.plant]
                                 for p, pr in r._profile_of.items()},
                    "free": list(r._free),     # pop ORDER matters: lane
                    #          assignment must resume deterministically
                    "tenants": {t.name: {
                        "slot": t.slot, "t_crit_c": t.t_crit_c,
                        "at_risk_limit": t.at_risk_limit,
                        "drift_budget_nm": t.drift_budget_nm,
                        "degraded_limit": t.degraded_limit,
                        "packages": sorted(t.packages)}
                        for t in r._tenants.values()},
                },
                "kind_of": dict(self._kind_of),
                # queued-but-unflushed /ingest chunks: journal entries from
                # BEFORE the snapshot are not replayed, so chunks still
                # sitting in a HintQueue at snapshot time must ride the
                # manifest or a crash would drop them (restore re-offers
                # these, then the journal re-drives post-snapshot posts)
                "feeds": {t: [c.tolist() for c in q._q]
                          for t, q in self._feeds.items() if len(q)},
                "pkg_key": dict(self._pkg_key),
                "next_key": self._next_key,
                "flushes": self.flushes, "steps": self.steps,
                "journal_seq": self._journal_seq,
                "latched": [[name, kind] for (name, kind), v
                            in self.alerts._latched.items() if v],
                "warmed_max": self._warmed_max,
            }
            self._ckpt.save(self.steps, self.state, blocking=blocking,
                            extra=meta)
            return self.steps

    @classmethod
    def restore(cls, snapshot_dir: str, *, sinks=(),
                debug_nan: bool = False, heartbeat_timeout_s: float = 0.0,
                fp: Fingerprint = FINGERPRINT) -> "FleetService":
        """Resume a killed service from its newest snapshot + journal.

        Rebuilds the service from the manifest's metadata (config, backend,
        registry membership, counters, alert latches), restores the engine
        state pytree, re-warms the compiled-program cache to the snapshot's
        warmup horizon, then re-drives every journaled membership/threshold
        op recorded AFTER the snapshot — interleaved with re-synthesised
        flushes at the journal's flush cursors, which the deterministic
        per-package workload keys make bit-identical to the lost originals.
        The resumed stream is ≤1e-5-equivalent to an uninterrupted run
        (gated in tests/test_fleet_service_recovery.py).  Tenant-POSTed
        `/ingest` chunks are recovered too: chunks queued but unflushed at
        the snapshot ride the manifest's ``feeds`` dict, and accepted posts
        after it are journaled (op ``ingest``) and re-offered at their
        recorded flush cursor — the one-chunk-per-tick drain makes the
        reconstructed queue state, and hence every fed flush window,
        deterministic (gated in tests/test_service_ingest_recovery.py)."""
        from repro.checkpoint.manager import CheckpointManager
        ckpt = CheckpointManager(snapshot_dir)
        steps = ckpt.steps()
        if not steps:
            raise FileNotFoundError(
                f"no complete snapshot under {snapshot_dir!r}")
        step = steps[-1]
        meta = ckpt.manifest(step).get("extra")
        if meta is None:
            raise ValueError(
                f"snapshot step {step} carries no service metadata "
                f"(was it written by FleetService.save_snapshot?)")
        svc = cls(SchedulerConfig(**meta["cfg"]), fp,
                  backend=meta["backend"], sinks=sinks,
                  snapshot_dir=snapshot_dir, debug_nan=debug_nan,
                  heartbeat_timeout_s=heartbeat_timeout_s,
                  **meta["service"])
        from repro.fleet.registry import Tenant
        r, reg = svc.registry, meta["registry"]
        r.capacity = int(reg["capacity"])
        r._lane_of = {p: int(l) for p, l in reg["lane_of"].items()}
        r._tenant_of = dict(reg["tenant_of"])
        # pre-profile snapshots default every lane to the service's plant
        r._profile_of = {
            p: (LaneProfile(*reg["profiles"][p])
                if p in reg.get("profiles", {})
                else LaneProfile(plant=svc.cfg.plant))
            for p in r._lane_of}
        r._free = [int(l) for l in reg["free"]]
        r._tenants = {
            name: Tenant(name=name, slot=int(t["slot"]),
                         t_crit_c=float(t["t_crit_c"]),
                         at_risk_limit=float(t["at_risk_limit"]),
                         drift_budget_nm=float(t["drift_budget_nm"]),
                         degraded_limit=float(t.get("degraded_limit",
                                                    float("inf"))),
                         packages=set(t["packages"]))
            for name, t in reg["tenants"].items()}
        svc._kind_of = dict(meta["kind_of"])
        for tenant, chunks in meta.get("feeds", {}).items():
            q = svc._feeds[tenant] = HintQueue(svc.feed_capacity)
            for c in chunks:
                q.offer(np.asarray(c, np.float32))
        svc._pkg_key = {p: int(k) for p, k in meta["pkg_key"].items()}
        svc._next_key = int(meta["next_key"])
        svc.flushes = int(meta["flushes"])
        svc.steps = int(meta["steps"])
        svc._journal_seq = int(meta["journal_seq"])
        svc._warmed_max = int(meta.get("warmed_max", 0))
        for name, kind in meta.get("latched", []):
            svc.alerts._latched[(name, kind)] = True
        svc.state = ckpt.restore(step, template=svc._fresh(r.capacity))
        svc._refresh_ctrl()        # ctrl plane re-derived from profiles
        if svc._warmed_max:        # compile cache back before any stepping
            svc.warmup(svc._warmed_max)
        svc._replay_journal()
        return svc

    def _replay_journal(self) -> None:
        """Apply journal entries with ``seq >= journal_seq``: tick to each
        entry's flush cursor (deterministic chunk synthesis regenerates the
        lost windows exactly), then re-apply the op.  Journaling and
        snapshots are suppressed for the duration — the entries are already
        on disk."""
        path = getattr(self, "_journal_path", None)
        if path is None or not os.path.exists(path):
            return
        entries = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    e = json.loads(line)
                    if e["seq"] >= self._journal_seq:
                        entries.append(e)
        if not entries:
            return
        self._restoring = True
        try:
            for e in sorted(entries, key=lambda x: x["seq"]):
                while self.flushes < e["flush"]:
                    self.tick()
                if e["op"] == "attach":
                    self.attach(e["package"], e["tenant"], e["workload"],
                                **e.get("profile", {}))
                elif e["op"] == "detach":
                    self.detach(e["package"])
                elif e["op"] == "thresholds":
                    self.set_thresholds(e["tenant"], **e["kw"])
                elif e["op"] == "canary":
                    self.canary(e["frac"])
                elif e["op"] == "mode":
                    self.set_mode(e["package"], e["mode"])
                elif e["op"] == "ingest":
                    self.ingest(e["tenant"], e["chunk"])
                else:
                    raise ValueError(f"unknown journal op {e['op']!r}")
                self._journal_seq = e["seq"] + 1
        finally:
            self._restoring = False

    # ---------------------------------------------------------------- replay
    def replay(self, path: str, atol: float = 0.0) -> list[dict]:
        """Re-drive a recorded telemetry stream (`TelemetryLog.dump_jsonl`
        of flush records) through the HintQueue ingest path against a fresh
        fleet, and return the reproduced flush records.

        Capacity transitions replay too: each flush record carries the
        ORDERED surgery ops applied since the previous flush (attach
        scatters, grow/shrink bucket transitions), and replay re-drives
        them through the same jitted surgery programs before re-running
        the window — so grow/shrink scenarios reproduce to float tolerance
        (gated ≤1e-5 in tests).  Legacy recordings without a ``surgery``
        key fall back to their ``attached`` lane lists and must keep ONE
        capacity throughout (a mixed legacy recording raises ValueError)."""
        rows = []
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row.get("kind") == "flush":
                    rows.append(row)
        if not rows:
            raise ValueError(f"no flush records in {path}")
        # TelemetryLog's JSON coercion floats scalar ints — re-int them
        legacy = any("surgery" not in r for r in rows)
        if legacy:
            caps = {int(r["capacity"]) for r in rows}
            if len(caps) != 1:
                raise ValueError(
                    f"replaying a legacy (no surgery journal) recording "
                    f"needs a fixed capacity, got capacities "
                    f"{sorted(caps)}; re-record with the current service")
            cap0 = caps.pop()
        else:
            # boot capacity: what the state held BEFORE the first recorded
            # capacity transition (= first row's capacity when none occur)
            cap0 = int(rows[0]["capacity"])
            for row in rows:
                trans = [o for o in row["surgery"]
                         if o["op"] in ("grow", "shrink")]
                if trans:
                    cap0 = int(trans[0]["old"])
                    break
        eng = self.engine
        state = self._fresh(cap0)
        queue = HintQueue(capacity=2)
        out = []
        for row in rows:
            if "surgery" in row:
                for op in row["surgery"]:
                    if op["op"] == "grow":
                        state = self._grow_jit(
                            state, self._template(int(op["new"])))
                    elif op["op"] == "shrink":
                        state = self._shrink_jit(
                            state, jnp.asarray(
                                np.asarray(op["perm"], np.int32)))
                    else:      # attach scatter at the CURRENT capacity
                        state = self._attach_jit(
                            state, self._template(state.freq.shape[0]),
                            jnp.asarray(int(op["lane"]), jnp.int32))
            else:
                tpl = self._template(cap0)
                for lane in row["attached"]:
                    state = self._attach_jit(
                        state, tpl, jnp.asarray(int(lane), jnp.int32))
            active = jnp.asarray(np.asarray(row["active"], bool))
            queue.offer(np.asarray(row["rho"], np.float32))
            chunk = queue.take()
            state, telem = eng.run_block(state, chunk, active=active)
            out.append({"flush": row["flush"],
                        "telemetry": telem.as_dict()})
        return out

    # ----------------------------------------------------------------- intro
    def snapshot(self, last: int = 1) -> dict:
        # an operator read's wait for the lock, behind GET /telemetry
        with span("service.lock_wait.snapshot"):
            self.lock.acquire()
        try:
            recs = self.log.rows()[-last:]
            return {"flushes": self.flushes,
                    "capacity": self.registry.capacity,
                    "n_active": self.registry.n_active,
                    "records": [{k: v for k, v in r.items() if k != "rho"}
                                for r in recs]}
        finally:
            self.lock.release()

    def shutdown(self) -> None:
        self._shutdown.set()

    @property
    def shutting_down(self) -> bool:
        return self._shutdown.is_set()


# --------------------------------------------------------------- dashboard
_BLOCKS = " ▁▂▃▄▅▆▇█"


def _spark(values, width: int = 60, lo=None, hi=None) -> str:
    """Unicode block sparkline of a numeric series (terminal-dashboard
    idiom, HTML-safe in a monospace span)."""
    values = [float(v) for v in values]
    if not values:
        return ""
    n = min(width, len(values))
    pick = [values[round(i * (len(values) - 1) / max(n - 1, 1))]
            for i in range(n)]
    lo = min(pick) if lo is None else lo
    hi = max(pick) if hi is None else hi
    span = max(hi - lo, 1e-9)
    return "".join(
        _BLOCKS[int(min(max((x - lo) / span, 0.0), 1.0) * (len(_BLOCKS) - 1))]
        for x in pick)


def _dashboard_html(svc: FleetService, last: int = 60) -> str:
    """One self-contained page for GET /dashboard: fleet vitals, flush-
    history sparklines, per-tenant stats and the recent alert feed —
    stdlib-rendered (no templates, no static assets) with a meta-refresh
    tag so a plain browser tab is a live operator view."""
    import html as _html

    esc = _html.escape
    snap = svc.snapshot(last=last)
    with svc.lock:
        alerts = list(svc.alerts.history)[-10:]
        backend = svc.engine.backend_impl.describe()
        stalled = (svc.heartbeat.stalled if svc.heartbeat is not None
                   else False)
        degraded = int(svc.last_degraded)
        lanes = svc.registry.describe()["packages"]
    recs = [r for r in snap["records"] if r.get("kind") == "flush"]
    series = lambda k: [r["telemetry"][k] for r in recs]
    rows = [
        ("T_p99 (°C)", _spark(series("temp_p99_c"))),
        ("T_max (°C)", _spark(series("temp_max_c"))),
        ("f_mean", _spark(series("freq_mean"), lo=0.5, hi=1.0)),
        ("at-risk", _spark(series("at_risk_frac"), lo=0.0, hi=1.0)),
        ("released MTPS", _spark(series("released_mtps"))),
    ] if recs else []
    parts = [
        "<!doctype html><html><head><meta charset='utf-8'>",
        "<meta http-equiv='refresh' content='2'>",
        "<title>fleet dashboard</title>",
        "<style>body{font-family:monospace;background:#111;color:#ddd;"
        "margin:2em}h1{font-size:1.1em}table{border-collapse:collapse}"
        "td,th{padding:2px 10px;text-align:left}.spark{color:#6cf}"
        ".bad{color:#f66}.ok{color:#6f6}</style></head><body>",
        f"<h1>fleet control plane — {esc(svc.backend_name)} backend, "
        f"plant <b>{esc(svc.cfg.plant)}</b></h1>",
        f"<p>engine {esc(backend)} · capacity {snap['capacity']} · "
        f"{snap['n_active']} active · {snap['flushes']} flushes · "
        f"degraded {degraded} · health "
        + ("<span class='bad'>STALLED</span>" if stalled
           else "<span class='ok'>ok</span>") + "</p>",
    ]
    if recs:
        parts.append(f"<p>flushes {int(recs[0]['flush'])}.."
                     f"{int(recs[-1]['flush'])} ({len(recs)} shown)</p>")
        parts.append("<table>")
        for label, line in rows:
            parts.append(f"<tr><td>{esc(label)}</td>"
                         f"<td class='spark'>{esc(line)}</td></tr>")
        parts.append("</table>")
        tenants = recs[-1].get("tenants", {})
        if tenants:
            parts.append("<h1>tenants (last flush)</h1><table>"
                         "<tr><th>tenant</th><th>pkgs</th><th>peak °C</th>"
                         "<th>f_min</th><th>drift nm</th>"
                         "<th>degraded</th></tr>")
            for name, st in sorted(tenants.items()):
                parts.append(
                    f"<tr><td>{esc(name)}</td><td>{int(st['n_lanes'])}</td>"
                    f"<td>{st['temp_peak_c']:.1f}</td>"
                    f"<td>{st['freq_min']:.3f}</td>"
                    f"<td>{st['drift_nm']:.3f}</td>"
                    f"<td>{int(st.get('degraded_lanes', 0))}</td></tr>")
            parts.append("</table>")
    else:
        parts.append("<p>(no flushes recorded yet — attach a package and "
                     "wait one flush)</p>")
    if lanes:
        # per-lane profile columns: which node bank, controller mode and
        # plant group each attached package runs under (canary rollouts
        # show up here as a growing reactive_poll column)
        parts.append("<h1>lane profiles</h1><table>"
                     "<tr><th>package</th><th>lane</th><th>tenant</th>"
                     "<th>node</th><th>mode</th><th>plant</th></tr>")
        for pkg, row in sorted(lanes.items()):
            parts.append(
                f"<tr><td>{esc(pkg)}</td><td>{int(row['lane'])}</td>"
                f"<td>{esc(str(row['tenant']))}</td>"
                f"<td>{esc(str(row['node']))}</td>"
                f"<td>{esc(str(row['mode']))}</td>"
                f"<td>{esc(str(row['plant']))}</td></tr>")
        parts.append("</table>")
    parts.append(f"<h1>alerts (last {len(alerts)})</h1>")
    if alerts:
        parts.append("<table>")
        for ev in alerts:
            parts.append(
                f"<tr><td>flush {int(ev['flush'])}</td>"
                f"<td>{esc(str(ev['tenant']))}</td>"
                f"<td class='bad'>{esc(str(ev['kind']))}</td>"
                f"<td>{ev['value']:.4g} &gt; {ev['limit']:.4g}</td></tr>")
        parts.append("</table>")
    else:
        parts.append("<p class='ok'>none fired</p>")
    parts.append("</body></html>")
    return "".join(parts)


# ------------------------------------------------------------------- HTTP
class _Handler(BaseHTTPRequestHandler):
    """JSON over stdlib http.server; the service reference rides on the
    server object.  Errors map to 4xx with a JSON body — the serving loop
    itself can never be crashed from the API."""

    server_version = "FleetService/1.0"

    def log_message(self, fmt, *args):      # silence per-request stderr spam
        pass

    def _send(self, code: int, payload: dict) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_html(self, code: int, body: str) -> None:
        data = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "text/html; charset=utf-8")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _body(self) -> dict:
        n = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(n) if n else b""
        return json.loads(raw) if raw else {}

    def do_GET(self):          # noqa: N802 — http.server API
        svc: FleetService = self.server.service
        path, _, query = self.path.partition("?")
        if path == "/healthz":
            stalled = (svc.heartbeat.stalled if svc.heartbeat is not None
                       else False)
            self._send(200, {"ok": not stalled, "flushes": svc.flushes,
                             "capacity": svc.registry.capacity,
                             "n_active": svc.registry.n_active,
                             "stalled": stalled,
                             "degraded_count": int(svc.last_degraded)})
        elif path == "/telemetry":
            last = 1
            for part in query.split("&"):
                if part.startswith("last="):
                    last = max(1, int(part[5:]))
            self._send(200, svc.snapshot(last=last))
        elif path == "/fleet":
            with svc.lock:
                self._send(200, svc.registry.describe())
        elif path == "/alerts":
            with svc.lock:
                self._send(200, {"alerts": list(svc.alerts.history)})
        elif path == "/dashboard":
            last = 60
            for part in query.split("&"):
                if part.startswith("last="):
                    last = max(1, int(part[5:]))
            self._send_html(200, _dashboard_html(svc, last=last))
        else:
            self._send(404, {"error": f"unknown path {path!r}"})

    def do_POST(self):         # noqa: N802 — http.server API
        svc: FleetService = self.server.service
        try:
            body = self._body()
            if self.path == "/attach":
                self._send(200, svc.attach(
                    body["package"], body.get("tenant", "default"),
                    body.get("kind", "inference"),
                    node=body.get("node", "base"),
                    mode=body.get("mode", "v24"),
                    plant=body.get("plant")))
            elif self.path == "/detach":
                self._send(200, svc.detach(body["package"]))
            elif self.path == "/canary":
                self._send(200, svc.canary(body["reactive_frac"]))
            elif self.path == "/mode":
                self._send(200, svc.set_mode(body["package"],
                                             body["mode"]))
            elif self.path == "/thresholds":
                tenant = body.pop("tenant")
                allowed = {"t_crit_c", "at_risk_limit", "drift_budget_nm",
                           "degraded_limit"}
                bad = set(body) - allowed
                if bad:
                    raise ValueError(f"unknown threshold field(s) "
                                     f"{sorted(bad)}; want {sorted(allowed)}")
                self._send(200, svc.set_thresholds(tenant, **body))
            elif self.path == "/ingest":
                out = svc.ingest(body["tenant"], body["chunk"])
                # a refused chunk is back-pressure, not an error: 429 tells
                # the poster to retry after a flush drains the queue
                self._send(200 if out["accepted"] else 429, out)
            elif self.path == "/replay":
                self._send(200, {"replayed": svc.replay(body["path"])})
            elif self.path == "/shutdown":
                svc.shutdown()
                self._send(200, {"ok": True})
            else:
                self._send(404, {"error": f"unknown path {self.path!r}"})
        except (KeyError, ValueError, FileNotFoundError) as e:
            self._send(400, {"error": f"{type(e).__name__}: {e}"})


def serve_http(service: FleetService, host: str = "127.0.0.1",
               port: int = 0) -> tuple[ThreadingHTTPServer, threading.Thread]:
    """Start the control/telemetry API in a daemon thread; returns the
    server (``server.server_address[1]`` is the bound port — port 0 gets
    an ephemeral one, the test path) and its thread.  Call
    ``server.shutdown()`` to stop."""
    server = ThreadingHTTPServer((host, port), _Handler)
    server.service = service
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, thread
