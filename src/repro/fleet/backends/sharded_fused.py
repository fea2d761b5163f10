"""sharded_fused backend — the fused Pallas whole-step kernel over a mesh.

Composes the two fleet fast paths, which were previously mutually
exclusive:

  * `sharded` partitions the package axis over a 1-D device mesh
    (`shard_map`, state born sharded via `ThermalScheduler.state_pspecs`);
  * `fused` advances a whole [T, n_packages, n_tiles] chunk inside ONE
    Pallas kernel (`repro.kernels.fleet_step`), ring/stats/two-pole state
    VMEM-resident across the chunk.

Here every device runs the whole-step kernel on its OWN package partition:
`run_block` shard_maps `FusedBackend.run_block` over the fleet mesh, so the
kernel sees a [T, n/d, tiles] shard and sizes its grid for that partition
(interpret mode packs small shards to the sublane tile instead of 128
lanes).  There are no collectives inside the block — the engine's telemetry
reductions over the streamed temp/freq traces are the only cross-device
ops, and they run in the SAME jitted program (XLA all-reduces them in-graph
before the single host sync per flush).  `put_trace` (inherited) lands each
package partition of a streaming chunk directly on its owning device, so
the `HintQueue` double-buffering composes with `NamedSharding` unchanged.

Per-step `update` falls back to the sharded pure-JAX path, and the mesh
degradation contract (largest compatible mesh + RuntimeWarning) is
inherited from `ShardedBackend` — as is `put_mask`: an active-lane mask
partitions over the same `FLEET_AXIS` pspec as the state, stays OUTSIDE
the shard_mapped kernel (each device's kernel steps its whole partition,
padded lanes included), and only meets the streamed temp/freq traces in
the engine's masked telemetry reductions, which XLA all-reduces in-graph
before the single host sync.  Equivalence to both parents is gated:
≤1e-5 vs `fused` and `vmap` over the 90k-step trace on 1/2/4 emulated
devices (tests/test_fleet_sharded_fused.py, `fleet.equiv90k_sharded_fused`
bench row).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.scheduler import SchedulerState, ThermalScheduler
from repro.distributed.sharding import fleet_shard_map, fleet_trace_spec
from repro.fleet.backends.base import register
from repro.fleet.backends.fused import FusedBackend
from repro.fleet.backends.sharded import ShardedBackend


@register
class ShardedFusedBackend(ShardedBackend):
    name = "sharded_fused"

    def __init__(self, sched: ThermalScheduler, devices: int | None = None,
                 block_packages: int = 128, time_chunk: int = 256,
                 interpret: bool | None = None):
        super().__init__(sched, devices=devices)
        # the per-device kernel wrapper: holds the baked FleetStepParams and
        # the ring-normalisation/state-rebuild logic, all trace-safe, so it
        # can run inside shard_map on each shard independently
        self._fused = FusedBackend(sched, block_packages=block_packages,
                                   time_chunk=time_chunk, interpret=interpret)
        if self._fused.run_block is None:
            # non-pole-family plant (grid): the wrapped kernel declined the
            # fast path — shadow ours too so the engine falls back to the
            # sharded pure-JAX scan (shard_map'd update) transparently
            self.run_block = None

    # -- fused fast path ---------------------------------------------------
    def run_block(self, state: SchedulerState, rho_trace: jnp.ndarray):
        """Advance T steps: one Pallas kernel per device on its partition.

        rho_trace: [T, n, tiles] (n divisible by the mesh — guaranteed by
        `init`'s mesh resolution).  Returns (state', temps, freqs) with the
        trace outputs sharded over packages like the state.
        """
        tspec = fleet_trace_spec(3, package_dim=1)
        fn = fleet_shard_map(
            self._fused.run_block, self.mesh,
            in_specs=(self._state_specs, tspec),
            out_specs=(self._state_specs, tspec, tspec))
        return fn(state, rho_trace)

    def describe(self) -> str:
        # parent renders the mesh (and process span, when distributed);
        # append the kernel's lane-block size and mode inside the brackets
        return (super().describe()[:-1]
                + f",blk={self._fused.block_packages},"
                f"{self._fused.kernel_mode()}]")
