"""The control-plane cell's driver on the CPU at a tiny size: a sound run
is correct, and the control and each planted fault come out not correct."""
import numpy as np
import pytest

from bench_cells import control, run

CELL = "serve_synth_4k"


def test_serve_driver_runs_correct_and_reports_its_metrics():
    r = run(CELL)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"pkg_steps_per_s", "api_p95_ms", "setup_s"}
    assert r["failed"] == 0 and r["attempted"] > 1


def test_serve_control_is_not_correct():
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
    import jax.numpy as jnp

    from repro.fleet.engine import FleetEngine
    orig = FleetEngine.window_telemetry

    def window_telemetry(self, rho, temps, freqs, prev, state0, active=None):
        half = jnp.arange(temps.shape[1]) < temps.shape[1] // 2
        return orig(self, rho, temps, freqs, prev, state0, active & half)
    monkeypatch.setattr(FleetEngine, "window_telemetry", window_telemetry)


def _altered_answer(monkeypatch):
    from repro.fleet.service import FleetService
    orig = FleetService.tick

    def tick(self, chunk=None):
        rec = orig(self, chunk)
        if rec["flush"] == 1:
            rec["telemetry"]["freq_mean"] *= 1.01
        return rec
    monkeypatch.setattr(FleetService, "tick", tick)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
def test_serve_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run(CELL)
    assert not r["correct"], r["checks"]
    assert np.isfinite(r["metrics"]["pkg_steps_per_s"]["value"])
