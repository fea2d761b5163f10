"""sharded backend — partition the package axis over a 1-D device mesh.

The fleet's package axis is embarrassingly parallel, so `shard_map` runs the
plain broadcast-layout `ThermalScheduler.update` on each device's package
partition with NO collectives inside the step; only the engine's telemetry
reductions (percentiles, fleet sums) communicate, and those sit outside the
shard_map in the same jitted program.  State leaves are placed at creation
via `ThermalScheduler.init(shardings=...)` so the full fleet never
materialises on one device.

Graceful degradation: requesting more devices than the host has, or a fleet
size the mesh doesn't divide, falls back to the largest compatible mesh
(worst case a trivial 1-device mesh, where sharded ≡ broadcast —
bit-identical, see tests/test_fleet_sharded.py).  The fallback is LOUD: a
`RuntimeWarning` names the requested→actual device counts, and
`describe()` always carries the actual mesh size, so a soak run can't
silently collapse onto one device.

Multi-host (`jax.distributed` process group): the mesh spans every global
device and the SAME backend runs SPMD on every process.  Degradation is
then forbidden — a shrunken mesh would drop some process's devices from
the program and deadlock the collectives — so an indivisible fleet size or
a devices= budget below the global count RAISES instead of warning.
`put_trace` gains a second input shape: a chunk whose package dim equals
this process's LOCAL lane span (`multihost.local_lane_range`) is assembled
into the global array with zero cross-host movement
(`jax.make_array_from_process_local_data`) — the per-host streaming ingest
path (`repro.fleet.distributed_ingest`).  Global-shape chunks still work
(every process must then hold the identical full chunk).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import (SchedulerOutput, SchedulerState,
                                  ThermalScheduler)
from repro.distributed import multihost
from repro.distributed.sharding import (FLEET_AXIS, fleet_mesh,
                                        fleet_trace_spec, to_shardings)
from repro.fleet.backends.base import FleetBackend, register


@register
class ShardedBackend(FleetBackend):
    name = "sharded"
    accepts_devices = True

    def __init__(self, sched: ThermalScheduler, devices: int | None = None):
        super().__init__(sched)
        self._requested = devices
        self.mesh = fleet_mesh(devices)
        self.n_global = None     # global fleet size, set at init(); the
        #                          multi-host put_trace needs it to tell a
        #                          process-local slab from a global chunk
        self._state_specs = sched.state_pspecs(batch_axes=(FLEET_AXIS,))
        self._out_specs = sched.output_pspecs(batch_axes=(FLEET_AXIS,))

    # -- state ------------------------------------------------------------
    def _resolve_mesh(self, n_packages: int) -> None:
        """Re-derive the mesh from the requested budget for this fleet size.

        Re-derived on every init — a previous indivisible fleet must not
        stick the engine on a shrunken mesh once a divisible size comes
        along.  Any downgrade (host has fewer devices than requested, or
        the fleet size is indivisible) warns with the requested→actual
        counts instead of degrading silently.

        In a multi-process group degradation is an ERROR, not a warning:
        every process must run the identical SPMD program over the full
        global mesh, and a mesh that excludes any process's devices would
        deadlock the first collective.
        """
        visible = len(jax.devices())
        if multihost.is_multiprocess():
            if self._requested and self._requested != visible:
                raise ValueError(
                    f"{self.name} fleet backend: devices={self._requested} "
                    f"in a {jax.process_count()}-process group — the mesh "
                    f"must span all {visible} global devices (pass "
                    f"devices=None/0)")
            if n_packages % visible:
                raise ValueError(
                    f"{self.name} fleet backend: n_packages={n_packages} "
                    f"must divide the {visible} global devices in "
                    f"multi-process mode (no silent mesh degradation "
                    f"across hosts)")
            self.mesh = fleet_mesh(visible)
            return
        requested = self._requested or visible
        clamped = len(fleet_mesh(self._requested).devices.ravel())
        budget = clamped
        if n_packages % budget:
            # largest divisor of n_packages the device budget covers
            budget = max(d for d in range(1, budget + 1)
                         if n_packages % d == 0)
        if budget != requested:
            # name the cause(s) precisely — a visible-device clamp and an
            # indivisible fleet size call for different operator fixes —
            # and only say "requested" when devices= was actually passed
            causes = []
            if clamped < requested:
                causes.append(f"only {visible} devices visible")
            if budget < clamped:
                causes.append(f"n_packages={n_packages} must divide "
                              f"the mesh")
            what = (f"requested {requested} devices but running on {budget}"
                    if self._requested else
                    f"using {budget} of {visible} visible devices")
            warnings.warn(
                f"{self.name} fleet backend: {what} "
                f"({'; '.join(causes)}) — check describe() before "
                f"trusting scaling numbers",
                RuntimeWarning, stacklevel=3)
        self.mesh = fleet_mesh(budget)

    def init(self, n_packages: int, pkg=None,
             filtration_fill=None) -> SchedulerState:
        self._resolve_mesh(n_packages)
        self.n_global = n_packages
        return self.sched.init(
            batch_shape=(n_packages,),
            shardings=to_shardings(self.mesh, self._state_specs),
            pkg=pkg, filtration_fill=filtration_fill)

    def update(self, state: SchedulerState, rho: jnp.ndarray
               ) -> tuple[SchedulerState, SchedulerOutput]:
        # plain shard_map, replication checking ON: the pure-JAX update HAS
        # replication rules, so keep the static verifier that would catch a
        # wrong scalar-leaf spec (the checks-off `fleet_shard_map` wrapper
        # is only for the pallas_call in the sharded_fused subclass)
        fn = jax.shard_map(self.sched.update, mesh=self.mesh,
                           in_specs=(self._state_specs, fleet_trace_spec(2)),
                           out_specs=(self._state_specs, self._out_specs))
        return fn(state, rho)

    # -- placement --------------------------------------------------------
    def _spans_processes(self) -> bool:
        return multihost.spans_processes(self.mesh)

    def put_trace(self, trace) -> jnp.ndarray:
        """Upload a density chunk with each package partition landing on its
        owning device.  The package axis always sits just before the tile
        axis: [n, t] chunks shard dim 0, [T, n, t] dim 1, pre-chunked
        [C, K, n, t] traces dim 2.

        Under a multi-process mesh the chunk may instead cover only THIS
        process's lane span — see `_put_trace_multihost`."""
        if isinstance(trace, jax.Array) and not trace.is_fully_addressable:
            return trace             # already a global array — placed once
        if self._spans_processes():
            return self._put_trace_multihost(np.asarray(trace, np.float32))
        trace = jnp.asarray(trace)
        pdim = max(trace.ndim - 2, 0)
        spec = fleet_trace_spec(trace.ndim, package_dim=pdim)
        if trace.shape[pdim] % len(self.mesh.devices.ravel()):
            spec = fleet_trace_spec(trace.ndim, package_dim=pdim, axis=None)
        return jax.device_put(trace, jax.sharding.NamedSharding(self.mesh, spec))

    def _put_trace_multihost(self, trace: np.ndarray) -> jax.Array:
        """Two legal chunk shapes on a process-spanning mesh, told apart by
        the package dim (n_global ≠ n_local whenever >1 process):

          * package dim == n_global — every process holds the identical
            full chunk (the run()/run_chunked replicated-input path);
            `device_put` scatters each partition to its owner.
          * package dim == n_local (this process's `local_lane_range`
            span) — the per-host streaming ingest path; the global array
            is ASSEMBLED from the process-local slab with zero cross-host
            movement.
        """
        if self.n_global is None:
            raise RuntimeError(f"{self.name}: init() must run before "
                               f"put_trace on a multi-process mesh (the "
                               f"global fleet size disambiguates local "
                               f"slabs from global chunks)")
        pdim = max(trace.ndim - 2, 0)
        lo, hi = multihost.local_lane_range(self.n_global, self.mesh)
        spec = fleet_trace_spec(trace.ndim, package_dim=pdim)
        sh = jax.sharding.NamedSharding(self.mesh, spec)
        n_in = trace.shape[pdim]
        if n_in == self.n_global:
            return jax.device_put(trace, sh)
        if n_in == hi - lo:
            gshape = trace.shape[:pdim] + (self.n_global,
                                           ) + trace.shape[pdim + 1:]
            return multihost.assemble_local_slab(sh, trace, gshape)
        raise ValueError(
            f"{self.name}: chunk package dim {n_in} is neither the global "
            f"fleet size {self.n_global} nor this process's local span "
            f"{hi - lo} (lanes [{lo}, {hi}))")

    def put_mask(self, mask) -> jnp.ndarray:
        """An active-lane mask partitions like the state's package axis
        (the same `FLEET_AXIS` pspec the state leaves carry), so the
        engine's masked telemetry reductions stay collective-free until
        the final all-reduce; an indivisible capacity replicates it, like
        `put_trace`'s fallback.  Multi-process: every process passes the
        identical GLOBAL [capacity] mask (membership is control-plane
        state, tiny and host-replicated by construction)."""
        from jax.sharding import PartitionSpec as P
        mask = np.asarray(mask)
        axis = (None if mask.shape[0] % len(self.mesh.devices.ravel())
                else FLEET_AXIS)
        return jax.device_put(mask,
                              jax.sharding.NamedSharding(self.mesh, P(axis)))

    # -- introspection ----------------------------------------------------
    def n_devices(self) -> int:
        return len(self.mesh.devices.ravel())

    def describe(self) -> str:
        if self._spans_processes():
            return (f"{self.name}[{self.n_devices()}dev/"
                    f"{jax.process_count()}proc]")
        return f"{self.name}[{self.n_devices()}dev]"
