"""The 47-tile streaming cell's driver on the CPU at a tiny size: it runs
and reports its metrics, and the control and each planted fault come out
not correct.  (At 16 packages the fleet statistics are too few for the
cell's limit, which is set for 63,744: the sound run is checked on the
chip.)"""
import pytest

from bench_cells import control, run

CELL = "stream_pvc47_aurora"


def test_stream_driver_runs_and_reports_its_metrics():
    r = run(CELL, seconds=0.3)
    assert set(r["metrics"]) == {"pkg_steps_per_s", "flush_p95_ms",
                                 "setup_s"}
    assert r["attempted"] >= 1 and set(r["checks"]) == {
        "telemetry_err", "events_err", "members_err"}


def test_stream_control_is_not_correct():
    numbers, limits = control(CELL, units=3)
    assert any(numbers[k] > limits[k] for k in numbers), numbers


def _unchanged_state(monkeypatch):
    from repro.fleet.backends.fused import FusedBackend
    orig = FusedBackend.run_block

    def run_block(self, state, rho):
        _, temps, freqs = orig(self, state, rho)
        return state, temps, freqs
    monkeypatch.setattr(FusedBackend, "run_block", run_block)


def _half_batch(monkeypatch):
    from repro.fleet.engine import FleetEngine
    orig = FleetEngine._traces_record

    def traces_record(self, rho, temps, freqs, *a, **kw):
        n = temps.shape[1] // 2
        return orig(self, rho[:, :n], temps[:, :n], freqs[:, :n], *a, **kw)
    monkeypatch.setattr(FleetEngine, "_traces_record", traces_record)


def _altered_answer(monkeypatch):
    from repro.fleet.engine import FleetTelemetry
    orig = FleetTelemetry.as_dict

    def as_dict(self):
        d = orig(self)
        d["temp_p99_c"] += 5.0
        return d
    monkeypatch.setattr(FleetTelemetry, "as_dict", as_dict)


def _altered_count(monkeypatch):
    from repro.fleet.engine import FleetTelemetry
    orig = FleetTelemetry.as_dict

    def as_dict(self):
        d = orig(self)
        d["events_total"] = int(d["events_total"] * 1.5) + 50
        return d
    monkeypatch.setattr(FleetTelemetry, "as_dict", as_dict)


def _lost_member(monkeypatch):
    from repro.fleet.engine import FleetTelemetry
    orig = FleetTelemetry.as_dict

    def as_dict(self):
        d = orig(self)
        d["n_packages"] -= 1
        return d
    monkeypatch.setattr(FleetTelemetry, "as_dict", as_dict)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer, _altered_count,
                                   _lost_member])
def test_stream_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run(CELL, seconds=0.3)
    assert not r["correct"], r["checks"]
    number = {_altered_count: "events_err",
              _lost_member: "members_err"}.get(fault)
    if number:
        c = r["checks"][number]
        assert c["value"] > c["limit"], r["checks"]


def test_pool_holds_the_seeds_chunks():
    """The pool holds the seed's distinct chunks as the jitted
    `fleet_chunk` makes them, on the host."""
    import jax
    import numpy as np

    from bench import harness
    from bench.reference.workload import fleet_chunk
    from bench_cells import tiny
    _, _, cfg, traffic = tiny(CELL)
    seed = 2 ** 31 + 11
    drv = harness.driver_class(traffic)(cfg, traffic, seed, jax.devices())
    drv.make_pool()
    key = jax.random.PRNGKey(seed % harness.SEED_SPAN)
    make = jax.jit(fleet_chunk, static_argnums=(1, 2, 3))
    assert len(drv.pool) == traffic["pool_chunks"]
    for j, got in enumerate(drv.pool):
        want = np.asarray(make(jax.random.fold_in(key, j), cfg["flush_every"],
                               16, cfg["scheduler"]["n_tiles"]))
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        assert np.array_equal(got, want)
    assert not np.array_equal(drv.pool[0], drv.pool[1])
