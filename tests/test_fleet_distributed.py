"""Multi-host streaming fleets: emulated `jax.distributed` process groups.

Each test spawns a REAL process group (N fresh interpreters + a local
coordinator on 127.0.0.1, 2 emulated CPU devices per process — see
`repro.distributed.multihost.run_process_group`) and checks the scale-out
contract end to end:

  * per-host ingest: each process streams only its own lane slab through
    its own HintQueue; `put_trace` assembles global arrays with zero
    cross-host movement,
  * global SPMD equivalence: the all-reduced flush telemetry matches the
    single-process vmap oracle (≤1e-5 on continuous aggregates; the two
    knife-edge statistics get a discrete 1e-3 bound, events exact),
  * the sync contract: exactly ONE `jax.device_get` per flush PER process
    (counted by monkeypatching inside the workers),
  * real partitioning: state spans every process and is NOT fully
    addressable (so the gates can't pass on a silently-degraded mesh).

The big weak-scaling + 90k-step gates live in
benchmarks/bench_fleet_distributed.py; these tests are the fast CI tier.

Fleet sizing note: N keeps every device shard at ≥2 lanes.  At the
degenerate [1, tiles] per-device shard, XLA CPU picks a different codegen
for the per-step math whose ulp-level differences accumulate through the
IIR pole states (≈3e-3 on knife-edge stats over 600 steps vs vmap) — a
single-host property of the sharded backend (reproducible with 8 emulated
devices and n=8, no process group involved), not a distribution effect,
and not a shape real fleets run (128 lanes/device in the scaling bench).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.scheduler import SchedulerConfig                 # noqa: E402
from repro.distributed import multihost                          # noqa: E402
from repro.fleet import FleetEngine, chunk_source, stream        # noqa: E402

N, TILES, T, K = 16, 4, 600, 100
BURN = 50

# knife-edge fields: freq_min rides the exact throttle boundary and
# at_risk_frac counts threshold crossings — a 1-ulp reassociation flips
# them by a discrete quantum, so they get an absolute bound; events are
# integer counters and must be exact
KNIFE = {"freq_min": 1e-3, "at_risk_frac": 1e-3}
EXACT = {"events_total", "events_step", "n_packages"}


def _trace(kind: str = "swell") -> np.ndarray:
    """"swell" parks the fleet on the throttle boundary — the hardest case
    for cross-layout equivalence, exact for the pure-JAX sharded backend
    (per-lane math is bitwise-identical across partitionings).  The fused
    Pallas kernel reorders float ops, so ON the boundary a 1-ulp difference
    flips a throttle decision and shifts window temps by a whole throttle
    quantum — its gate therefore uses the same "uniform" trace family as
    the established single-host 90k kernel gates (test_fleet_fused.py,
    test_fleet_sharded_fused.py)."""
    if kind == "uniform":
        rng = np.random.default_rng(5)
        return (0.9 + 1.8 * rng.random((T, N, TILES))).astype(np.float32)
    t = np.linspace(0.0, np.pi, T, dtype=np.float32)
    swell = 1.8 * (0.85 + 0.3 * np.sin(t) ** 2)
    off = 0.1 * np.cos(np.arange(N, dtype=np.float32))
    tilt = 1.0 + 0.05 * np.sin(np.arange(TILES, dtype=np.float32))
    tr = swell[:, None, None] + off[None, :, None]
    return np.clip(tr * tilt[None, None, :], 0.9, 2.7).astype(np.float32)


_WORKER = r"""
from repro.distributed import multihost
topo = multihost.bootstrap_from_env()
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.scheduler import SchedulerConfig
from repro.fleet import (FleetEngine, chunk_source, distributed_stream,
                         local_chunk_source, local_lanes)

BACKEND = "%(backend)s"
N, TILES, T, K, BURN = %(n)d, %(tiles)d, %(t)d, %(k)d, %(burn)d
assert topo.num_processes == %(procs)d, topo

cfg = SchedulerConfig(n_tiles=TILES, mode="v24")
eng = FleetEngine(cfg, backend=BACKEND)
state = eng.init(N)

# the partitioning must be REAL: global mesh over every process, state not
# fully addressable on any one of them
assert multihost.spans_processes(eng.backend_impl.mesh)
assert not state.freq.is_fully_addressable
assert len(state.freq.sharding.device_set) == len(jax.devices())
lanes = local_lanes(eng)
assert lanes.n == N * len(jax.local_devices()) // len(jax.devices()), lanes

if "%(trace)s" == "uniform":
    trace = (0.9 + 1.8 * np.random.default_rng(5).random(
        (T, N, TILES))).astype(np.float32)
else:
    t = np.linspace(0.0, np.pi, T, dtype=np.float32)
    swell = 1.8 * (0.85 + 0.3 * np.sin(t) ** 2)
    off = 0.1 * np.cos(np.arange(N, dtype=np.float32))
    tilt = 1.0 + 0.05 * np.sin(np.arange(TILES, dtype=np.float32))
    trace = np.clip((swell[:, None, None] + off[None, :, None])
                    * tilt[None, None, :], 0.9, 2.7).astype(np.float32)

# ---- dense stream, host-sync contract counted per process --------------
calls = {"n": 0}
orig_get = jax.device_get
def counting_get(x):
    calls["n"] += 1
    return orig_get(x)
jax.device_get = counting_get
src = local_chunk_source(chunk_source(trace, K), lanes)
state, flushed, stats = distributed_stream(eng, state, src)
jax.device_get = orig_get
n_flush = -(-T // K)
assert stats.flushes == n_flush, stats
assert stats.host_syncs == stats.flushes, stats
assert calls["n"] == stats.flushes, (calls, stats)

# ---- masked stream (global [N] mask, identical on every process) -------
mask = np.ones(N, bool)
mask[1] = False
st2 = eng.init(N)
st2, masked, _ = distributed_stream(
    eng, st2, local_chunk_source(chunk_source(trace, K), lanes),
    active=mask)

# ---- per-lane survey over the local slab -------------------------------
st3 = eng.init(N)
st3, survey = eng.run_survey(st3, trace[:, lanes.lo:lanes.hi, :],
                             burn_in=BURN)
rep = jax.jit(lambda x: x, out_shardings=NamedSharding(
    eng.backend_impl.mesh, P()))
peak = np.asarray(orig_get(rep(survey.peak_t_c)))
exceed = np.asarray(orig_get(rep(survey.exceed_frac)))
fmean = np.asarray(orig_get(rep(survey.freq_mean)))

if topo.process_id == 0:
    print("RESULT " + json.dumps({
        "describe": eng.backend_impl.describe(),
        "flushed": flushed,
        "masked": masked,
        "peak": peak.tolist(),
        "exceed": exceed.tolist(),
        "fmean": fmean.tolist(),
    }))
"""


def _run_group(backend: str, procs: int, trace: str = "swell") -> dict:
    code = _WORKER % {"backend": backend, "procs": procs, "n": N,
                      "tiles": TILES, "t": T, "k": K, "burn": BURN,
                      "trace": trace}
    outs = multihost.run_process_group(code, procs, local_devices=2)
    for line in outs[0].splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"rank 0 printed no RESULT:\n{outs[0]}")


def _oracle(active=None, trace: str = "swell"):
    eng = FleetEngine(SchedulerConfig(n_tiles=TILES, mode="v24"),
                      backend="vmap")
    state = eng.init(N)
    state, flushed, _ = stream(eng, state, chunk_source(_trace(trace), K),
                               active=active)
    return flushed


def _check_records(dist: list[dict], ref: list[dict]) -> None:
    assert len(dist) == len(ref) == -(-T // K)
    for a, b in zip(dist, ref):
        for k, rv in b.items():
            dv = a[k]
            if k in EXACT:
                assert dv == pytest.approx(rv, abs=0.5), (k, dv, rv)
            elif k in KNIFE:
                assert dv == pytest.approx(rv, abs=KNIFE[k]), (k, dv, rv)
            else:
                assert dv == pytest.approx(rv, rel=1e-5, abs=1e-5), \
                    (k, dv, rv)


@pytest.mark.parametrize("procs", [2, 4])
def test_distributed_sharded_matches_vmap_oracle(procs):
    """2- and 4-process emulated groups reproduce the single-process
    oracle's flush telemetry, masked telemetry and per-lane survey — with
    one host sync per flush per process (asserted inside the workers)."""
    res = _run_group("sharded", procs)
    assert res["describe"] == f"sharded[{2 * procs}dev/{procs}proc]"
    _check_records(res["flushed"], _oracle())

    mask = np.ones(N, bool)
    mask[1] = False
    _check_records(res["masked"], _oracle(active=mask))

    # per-lane survey: lane physics never crosses hosts, so the per-lane
    # records match the oracle at the usual cross-layout tolerance
    eng = FleetEngine(SchedulerConfig(n_tiles=TILES, mode="v24"),
                      backend="vmap")
    st = eng.init(N)
    st, sv = eng.run_survey(st, _trace(), burn_in=BURN)
    np.testing.assert_allclose(res["peak"], np.asarray(sv.peak_t_c),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(res["exceed"], np.asarray(sv.exceed_frac),
                               atol=1e-5)
    np.testing.assert_allclose(res["fmean"], np.asarray(sv.freq_mean),
                               rtol=1e-5, atol=1e-5)


def test_distributed_sharded_fused_matches_vmap_oracle():
    """The Pallas whole-step kernel shard_mapped across a process-spanning
    mesh (2 processes): same contracts, vmap oracle on the uniform trace
    family the single-host kernel gates use (see the `_trace` docstring —
    the kernel's reassociated float ops can flip throttle decisions when a
    trace is engineered to RIDE the boundary, which is a property the
    single-host sharded_fused 90k gates already bound, not a distribution
    effect)."""
    res = _run_group("sharded_fused", 2, trace="uniform")
    assert res["describe"] == "sharded_fused[4dev/2proc,blk=128,interpret]"
    _check_records(res["flushed"], _oracle(trace="uniform"))


def test_multiprocess_rejects_degraded_mesh():
    """In a process group an indivisible fleet size must RAISE (silent
    mesh degradation would drop a process from the SPMD program)."""
    code = r"""
from repro.distributed import multihost
topo = multihost.bootstrap_from_env()
from repro.core.scheduler import SchedulerConfig
from repro.fleet import FleetEngine
eng = FleetEngine(SchedulerConfig(n_tiles=2), backend="sharded")
try:
    eng.init(7)        # 7 lanes over 4 global devices
except ValueError as e:
    assert "multi-process" in str(e), e
else:
    raise AssertionError("indivisible fleet did not raise")
try:
    FleetEngine(SchedulerConfig(n_tiles=2), backend="sharded",
                devices=2).init(8)   # budget below the global mesh
except ValueError as e:
    assert "global devices" in str(e), e
else:
    raise AssertionError("partial device budget did not raise")
"""
    multihost.run_process_group(code, 2, local_devices=2)


def test_local_lane_range_single_process():
    """Sanity of the span helper: the real mesh yields the full range in a
    single process; the error paths (indivisible size, process owning no
    devices, non-contiguous device order) are exercised on a fake mesh so
    they're covered regardless of the local device count."""
    from types import SimpleNamespace

    from repro.distributed.sharding import fleet_mesh
    mesh = fleet_mesh()
    d = len(mesh.devices.ravel())
    assert multihost.local_lane_range(8 * d, mesh) == (0, 8 * d)

    def fake_mesh(pids):
        devs = np.empty(len(pids), dtype=object)
        for i, pid in enumerate(pids):
            devs[i] = SimpleNamespace(process_index=pid, id=i)
        return SimpleNamespace(devices=devs)

    with pytest.raises(ValueError, match="must divide"):
        multihost.local_lane_range(5, fake_mesh([0, 0]))
    with pytest.raises(ValueError, match="owns no devices"):
        multihost.local_lane_range(4, fake_mesh([1, 1]))
    with pytest.raises(ValueError, match="not contiguous"):
        multihost.local_lane_range(3, fake_mesh([0, 1, 0]))


def test_local_chunk_source_slices_lanes():
    from repro.fleet import LaneSpan, local_chunk_source
    chunks = [np.arange(2 * 8 * 3, dtype=np.float32).reshape(2, 8, 3) + i
              for i in range(3)]
    span = LaneSpan(2, 5)
    out = list(local_chunk_source(iter(chunks), span))
    assert all(o.shape == (2, 3, 3) for o in out)
    np.testing.assert_array_equal(out[1], chunks[1][:, 2:5, :])
