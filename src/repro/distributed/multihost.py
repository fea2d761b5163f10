"""Multi-host process bootstrap + the global package mesh.

Scale-out past one host's H2D bandwidth (ROADMAP: 10⁶+ packages) keeps the
fleet architecture unchanged — the package axis is embarrassingly parallel,
state is device-resident, telemetry all-reduces in-graph — and adds exactly
one new ingredient: a `jax.distributed` process group whose devices form ONE
global mesh.  Every process runs the SAME program (SPMD); each feeds only its
own contiguous span of package lanes (`local_lane_range`) through its own
`HintQueue`, and `ShardedBackend.put_trace` assembles those process-local
slabs into global arrays without any cross-host data movement
(`jax.make_array_from_process_local_data`).  The telemetry reductions inside
the jitted flush program become cross-host collectives automatically (GSPMD),
and their scalar outputs are fully replicated — so every process fetches the
identical flush record with its own single `device_get`, preserving the
one-host-sync-per-flush contract globally (asserted per process in
tests/test_fleet_distributed.py).

Bootstrap order matters: `initialize()` must run before ANY jax computation
(backend creation pins the process topology), which is why the CLI
(`repro.launch.serve --distributed`) calls it first thing and why the
emulated process-group launcher here spawns FRESH interpreters.  On CPU the
cross-process collective transport is gloo — available in stock jaxlib, so
the emulated 2/4-process CI job needs no extra dependencies.
"""
from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import sys

import jax
import numpy as np

__all__ = ["ProcessTopology", "initialize", "bootstrap_from_env",
           "topology", "is_multiprocess", "spans_processes",
           "local_lane_range", "free_port", "run_process_group"]


@dataclasses.dataclass(frozen=True)
class ProcessTopology:
    """This process's view of the group (all fields post-initialize)."""

    process_id: int
    num_processes: int
    local_devices: int
    global_devices: int

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    def describe(self) -> str:
        return (f"process {self.process_id}/{self.num_processes} "
                f"({self.local_devices} local / {self.global_devices} "
                f"global devices)")


_INITIALIZED = False


def initialize(coordinator: str = "127.0.0.1:8476", num_processes: int = 1,
               process_id: int = 0) -> ProcessTopology:
    """Join (or create) the process group; idempotent per process.

    MUST run before any other jax call in the process — backend creation
    freezes the topology, so a late initialize raises inside jax.  On CPU
    the collective transport is switched to gloo first.
    """
    global _INITIALIZED
    if num_processes > 1 and not _INITIALIZED:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
        _INITIALIZED = True
    return topology()


def bootstrap_from_env() -> ProcessTopology:
    """`initialize()` from the env vars `run_process_group` plants
    (REPRO_COORDINATOR / REPRO_NUM_PROCESSES / REPRO_PROCESS_ID) — the
    one-liner every emulated worker starts with.  A bare environment is a
    single-process group (no-op)."""
    return initialize(
        coordinator=os.environ.get("REPRO_COORDINATOR", "127.0.0.1:8476"),
        num_processes=int(os.environ.get("REPRO_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("REPRO_PROCESS_ID", "0")))


def topology() -> ProcessTopology:
    return ProcessTopology(process_id=jax.process_index(),
                           num_processes=jax.process_count(),
                           local_devices=len(jax.local_devices()),
                           global_devices=len(jax.devices()))


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def spans_processes(obj) -> bool:
    """True when a Mesh / Sharding / Array's devices live on >1 process —
    the discriminator between the single-host placement paths (plain
    `device_put`) and the process-local-slab assembly paths."""
    if hasattr(obj, "sharding"):                 # jax.Array
        obj = obj.sharding
    if hasattr(obj, "device_set"):               # Sharding
        devs = obj.device_set
    elif hasattr(obj, "devices"):                # Mesh
        devs = obj.devices.ravel().tolist()
    else:
        raise TypeError(f"expected Mesh/Sharding/Array, got {type(obj)}")
    return len({d.process_index for d in devs}) > 1


def local_lane_range(n_packages: int, mesh) -> tuple[int, int]:
    """[lo, hi) span of the global package axis this process's devices own.

    Requires the mesh's device order to be contiguous per process (the
    (process_index, id) sort in `fleet_mesh` guarantees it) — a contiguous
    span is what lets a per-host ingest source slice its slab out of a
    global trace with one basic slice, and what
    `jax.make_array_from_process_local_data` needs to assemble the global
    array without data movement.
    """
    devs = mesh.devices.ravel().tolist()
    d = len(devs)
    if n_packages % d:
        raise ValueError(f"n_packages={n_packages} must divide the mesh's "
                         f"{d} devices for a process-local lane span")
    per = n_packages // d
    pid = jax.process_index()
    mine = [i for i, dev in enumerate(devs) if dev.process_index == pid]
    if not mine:
        raise ValueError(f"process {pid} owns no devices of the mesh — it "
                         f"cannot participate in the SPMD program")
    if mine != list(range(mine[0], mine[-1] + 1)):
        raise ValueError(f"process {pid}'s mesh devices are not contiguous "
                         f"({mine}); build the mesh with fleet_mesh() "
                         f"(devices sorted by (process_index, id))")
    return mine[0] * per, (mine[-1] + 1) * per


# ------------------------------------------------- emulated process groups
def free_port() -> int:
    """An OS-assigned free TCP port for a local coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_process_group(code: str, num_processes: int, *,
                      local_devices: int = 1, timeout: float = 540.0,
                      env: dict | None = None) -> list[str]:
    """Run ``code`` in ``num_processes`` FRESH interpreters wired to one
    local coordinator — the emulated multi-host harness tests and benches
    use (real deployments launch one `serve --distributed` per host).

    Each worker gets ``local_devices`` emulated CPU devices (XLA_FLAGS must
    be set before jax imports — hence fresh interpreters) and the
    REPRO_COORDINATOR / REPRO_NUM_PROCESSES / REPRO_PROCESS_ID env vars
    `bootstrap_from_env` reads.  Returns each process's combined
    stdout+stderr in rank order; any nonzero exit raises with every rank's
    output (a distributed failure usually only explains itself on one rank).
    """
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    base = dict(os.environ)
    base.update(env or {})
    base["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                         f"{local_devices}")
    base["JAX_PLATFORMS"] = "cpu"
    base["REPRO_COORDINATOR"] = f"127.0.0.1:{free_port()}"
    base["REPRO_NUM_PROCESSES"] = str(num_processes)
    base["PYTHONPATH"] = src + os.pathsep + base.get("PYTHONPATH", "")
    procs = []
    try:
        for pid in range(num_processes):
            e = dict(base, REPRO_PROCESS_ID=str(pid))
            procs.append(subprocess.Popen(
                [sys.executable, "-c", code], env=e, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(p.returncode for p in procs):
        report = "\n".join(f"--- rank {i} (rc={p.returncode}) ---\n{o}"
                           for i, (p, o) in enumerate(zip(procs, outs)))
        raise RuntimeError(f"process group failed:\n{report}")
    return outs


def assemble_local_slab(sharding, local_slab: np.ndarray,
                        global_shape: tuple[int, ...]):
    """Global array from this process's slab — zero cross-host movement.

    Thin, named wrapper over `jax.make_array_from_process_local_data` so
    the sharded backends read as intent; ``local_slab`` must be exactly the
    rows of ``global_shape`` this process's devices own under ``sharding``
    (`local_lane_range` computes the span for the package axis).
    """
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_slab), global_shape)
