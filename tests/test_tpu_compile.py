"""Compile the fleet's Pallas whole-step kernel for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a `v5e:2x2`
topology that is only described, which catches what interpret mode cannot
(Mosaic lowering, tiling, VMEM limits).  Shapes are the main path's: 4,096
packages and a 512-step chunk on one chip, 16,384 packages over the four
described devices.  Every test asserts that the kernel (`tpu_custom_call`)
is in the compiled program.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.scheduler import SchedulerConfig, ThermalScheduler
from repro.distributed.sharding import (FLEET_AXIS, fleet_trace_spec,
                                        to_shardings)
from repro.fleet.backends.fused import FusedBackend
from repro.fleet.backends.sharded_fused import ShardedFusedBackend

N, T = 4096, 512
SERVE = SchedulerConfig(n_tiles=1, mode="v24", step_ms=5.0,
                        mixed_mode=True, degraded_fallback=True)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile(backend, n, state_sharding, rho_sharding):
    """Compile ``backend.run_block`` from shapes; returns the HLO text."""
    state = backend.sched.init(batch_shape=(n,))
    spec = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state = jax.tree_util.tree_map(spec, state, state_sharding(state))
    rho = jax.ShapeDtypeStruct((T, n, backend.sched.cfg.n_tiles),
                               jnp.float32, sharding=rho_sharding)
    return jax.jit(backend.run_block).lower(state, rho).compile().as_text()


@pytest.mark.parametrize("cfg", [
    SchedulerConfig(n_tiles=4, mode="v24"),
    SchedulerConfig(n_tiles=8, mode="reactive_poll", heterogeneous=True,
                    two_pole=False, use_coupling=False),
    SERVE,
], ids=["v24-4tiles", "hetero-reactive_poll", "serve-mixed-fallback"])
def test_fused_kernel_compiles_for_v5e(topo, cfg):
    one = SingleDeviceSharding(topo.devices[0])
    backend = FusedBackend(ThermalScheduler(cfg), interpret=False)
    assert backend.describe() == "fused[blk=128,compiled]"
    text = _compile(backend, N,
                    lambda st: jax.tree_util.tree_map(lambda _: one, st), one)
    assert "tpu_custom_call" in text


def test_sharded_fused_compiles_over_four_v5e(topo):
    backend = ShardedFusedBackend(ThermalScheduler(SERVE), interpret=False)
    mesh = Mesh(np.array(topo.devices[:4]), (FLEET_AXIS,))
    backend.mesh = mesh
    text = _compile(
        backend, 4 * N,
        lambda st: to_shardings(mesh, backend.sched.state_pspecs(
            batch_axes=(FLEET_AXIS,))),
        NamedSharding(mesh, fleet_trace_spec(3, package_dim=1)))
    assert "tpu_custom_call" in text
