"""The Monte Carlo cell's driver on the CPU at a tiny size: a sound run is
correct, and the control and each planted fault come out not correct."""
import pytest

from bench_cells import control, run

CELL = "mc_sec10_2k"


def test_montecarlo_driver_runs_correct():
    r = run(CELL)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"pkg_steps_per_s", "setup_s"}


def test_montecarlo_control_is_not_correct():
    numbers, limits = control(CELL, units=4)
    assert any(numbers[k] > limits[k] for k in numbers), numbers


def _unchanged_state(monkeypatch):
    from repro.core import montecarlo
    from repro.fleet.backends.fused import FusedBackend
    montecarlo._engine.cache_clear()       # engines trace the patched step
    orig = FusedBackend.run_block

    def run_block(self, state, rho):
        _, temps, freqs = orig(self, state, rho)
        return state, temps, freqs
    monkeypatch.setattr(FusedBackend, "run_block", run_block)
    monkeypatch.setattr(montecarlo, "_engine",
                        montecarlo._engine.__wrapped__)


def _half_batch(monkeypatch):
    from repro.core import montecarlo
    orig = montecarlo.MCResult.stats

    def stats(self):
        n = self.peak_t_baseline.shape[0] // 2
        return orig(montecarlo.MCResult(*(a[:n] for a in self)))
    monkeypatch.setattr(montecarlo.MCResult, "stats", stats)


def _altered_answer(monkeypatch):
    from repro.core import montecarlo
    orig = montecarlo.MCResult.stats

    def stats(self):
        s = orig(self)
        s["v24_mean_c"] *= 1.01          # about 0.8 degrees C
        return s
    monkeypatch.setattr(montecarlo.MCResult, "stats", stats)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
def test_montecarlo_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run(CELL)
    assert not r["correct"], r["checks"]
