"""End-to-end behaviour tests: the assembled system (paper technique wired
into training/serving), dataset statistics, telemetry, data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, SHAPES, live_cells, reduced
from repro.core import dataset90k, telemetry
from repro.core.density import rho_v24
from repro.core.scheduler import SchedulerConfig, ThermalScheduler
from repro.data import DataConfig, SyntheticLMData
from repro.launch import steps as S


def test_training_reduces_loss():
    """The paper's technique wrapped around a real training loop: loss falls
    and the thermal envelope stays inside the safe limit."""
    cfg = reduced(ALL_ARCHS["gemma-2b"], n_layers=2)
    key = jax.random.PRNGKey(0)
    data = SyntheticLMData(cfg, DataConfig(batch=4, seq_len=64, seed=1))
    state = S.init_train_state(key, cfg, n_tiles=4)
    step_fn = jax.jit(S.make_train_step(cfg, 4))
    losses, temps = [], []
    for _ in range(12):
        b = data.next()
        state, m = step_fn(state, {"tokens": jnp.asarray(b["tokens"]),
                                   "labels": jnp.asarray(b["labels"]),
                                   "rho": jnp.full((4,), 1.8)})
        losses.append(float(m["loss"]))
        temps.append(float(m["thermal_temp_max"]))
    data.close()
    assert losses[-1] < losses[0]
    assert max(temps) < 85.0
    assert int(state.sched.events) == 0


def test_scheduler_throttles_under_overload():
    """Sustained max density ⇒ the PDU gate pre-positions f < 1 but never
    lets the junction cross T_crit (Effect ① in the scheduler API)."""
    sched = ThermalScheduler(SchedulerConfig(n_tiles=4, mode="v24",
                                             step_ms=50.0))
    st = sched.init()
    for _ in range(200):
        st, out = sched.update(st, jnp.full((4,), 2.7))
    assert float(out.temp_c.max()) <= 85.0
    assert float(out.freq.min()) < 1.0          # pre-positioned, not tripped
    assert int(st.events) == 0
    assert bool(out.at_risk.any())              # straggler flags raised


def test_scheduler_reactive_vs_v24():
    reactive = ThermalScheduler(SchedulerConfig(n_tiles=1, mode="reactive",
                                                step_ms=50.0))
    v24 = ThermalScheduler(SchedulerConfig(n_tiles=1, mode="v24",
                                           step_ms=50.0))
    sr, sv = reactive.init(), v24.init()
    fr, fv = [], []
    for _ in range(300):
        sr, outr = reactive.update(sr, jnp.full((1,), 2.7))
        sv, outv = v24.update(sv, jnp.full((1,), 2.7))
        fr.append(float(outr.freq[0]))
        fv.append(float(outv.freq[0]))
    assert np.mean(fv[50:]) > np.mean(fr[50:])          # released compute
    assert np.std(fv[50:]) < np.std(fr[50:]) + 1e-6     # smooth envelope


def test_dataset90k_regression():
    """Appendix B: the R² = 0.9911 fingerprint fit with α ≈ 63, β ≈ −1256.6."""
    t = dataset90k.generate()
    a, b, r2 = dataset90k.fit_affine(t.rtok, t.dt_junction)
    assert a == pytest.approx(63.0, abs=1.0)
    assert b == pytest.approx(-1256.6, abs=25.0)
    assert r2 == pytest.approx(0.9911, abs=0.002)
    s = dataset90k.summary(t)
    assert s["rho"]["min"] >= 0.9 - 1e-5 and s["rho"]["max"] <= 2.7 + 1e-5
    assert 22.0 <= s["eta_pct"]["min"] <= 23.0
    assert 46.0 <= s["eta_pct"]["max"] <= 47.0
    assert s["drift_nm"]["max"] <= 0.36 + 1e-6
    assert s["rth"]["mean"] == pytest.approx(0.451, abs=0.002)
    assert t.rho.shape[0] == 90_000


def test_telemetry_budget():
    """§5.3: 64 B @ 1 Mbps = 512 µs ≪ 20 ms look-ahead."""
    b = telemetry.budget(n_tiles=8)
    assert b["per_packet_us"] == pytest.approx(512.0)
    assert b["fits_lookahead"]
    assert b["lookahead_margin_x"] > 10


def test_telemetry_log_bounded(tmp_path):
    log = telemetry.TelemetryLog(capacity=10)
    for i in range(25):
        log.record(i, loss=float(i))
    assert len(log) == 10
    assert log.last()["step"] == 24
    log.dump(str(tmp_path / "t.jsonl"))
    assert (tmp_path / "t.jsonl").read_text().count("\n") == 10


def test_data_pipeline_prefetch_and_balance():
    cfg = reduced(ALL_ARCHS["gemma-2b"])
    d = SyntheticLMData(cfg, DataConfig(batch=6, seq_len=32, seed=0))
    b = d.next()
    assert b["tokens"].shape == (6, 32)
    assert b["labels"].shape == (6, 32)
    assert b["tokens"].max() < cfg.vocab_size
    d.set_balance(np.array([0.5, 0.2, 0.2, 0.1]))
    split = d.microbatch_split(4)
    assert split.sum() == 6 and split[0] >= split[3]
    d.close()


def test_density_fleet_in_domain():
    """ρv24 of every live (arch × shape) cell lands in the paper's domain."""
    for arch, shape in live_cells():
        r = rho_v24(ALL_ARCHS[arch], SHAPES[shape])
        assert 0.9 - 1e-6 <= r <= 2.7 + 1e-6, (arch, shape, r)


def test_live_cells_cover_spec():
    """40 nominal cells − 7 documented long_500k skips = 33 live cells."""
    cells = live_cells()
    assert len(cells) == 33
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"zamba2-7b", "rwkv6-1.6b", "mixtral-8x7b"}


def test_serve_driver_smoke(capsys):
    from repro.launch import serve
    out = serve.main(["--arch", "granite-3-2b", "--reduced", "--batch", "2",
                      "--prompt-len", "16", "--gen", "4", "--waves", "2"])
    assert out["p99"] > 0
    assert all(1 <= a <= 2 for a in out["admitted"])


def test_compile_cache_follows_env_else_fixed_repo_dir(monkeypatch,
                                                       tmp_path):
    from pathlib import Path

    from repro.compile_cache import CACHE_DIR, use_compile_cache
    repo = Path(__file__).resolve().parents[1]
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", prev)
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev   # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        assert use_compile_cache() == str(CACHE_DIR)           # fixed path
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_train_driver_smoke(tmp_path):
    from repro.launch import train
    state = train.main(["--arch", "musicgen-large", "--reduced",
                        "--steps", "6", "--batch", "2", "--seq", "32",
                        "--ckpt-dir", str(tmp_path / "ck"),
                        "--ckpt-every", "3", "--log-every", "0"])
    assert int(state.step) == 6
