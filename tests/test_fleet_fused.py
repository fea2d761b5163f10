"""Fused (Pallas whole-step kernel) fleet backend vs the pure-JAX engine.

Mirrors tests/test_fleet_sharded.py's equivalence contract: per-package
trajectories and fleet telemetry from the fused `run_block`/`run_chunked`
fast path must match the vmap reference to ≤1e-5 (the kernel re-associates
float reductions, so bit-identity is not required), with event counters
exactly equal.  Runs in interpret mode off-TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pdu_gate
from repro.core.scheduler import SchedulerConfig, ThermalScheduler
from repro.fleet import FleetEngine

jax.config.update("jax_platform_name", "cpu")

TOL = dict(rtol=1e-5, atol=1e-5)


def _trace(steps, n, tiles, seed=0):
    key = jax.random.PRNGKey(seed)
    return 0.9 + 1.8 * jax.random.uniform(key, (steps, n, tiles))


def _ordered(ft):
    """Per-package age-ordered ring contents (handles per-lane ptr)."""
    ptr = jnp.broadcast_to(ft.ptr, ft.buf.shape[:1])
    return np.asarray(jax.vmap(lambda b, p: jnp.roll(b, -p, axis=0))(
        ft.buf, ptr))


def _assert_states_equiv(sa, sb):
    np.testing.assert_allclose(np.asarray(sa.thermal),
                               np.asarray(sb.thermal), **TOL)
    np.testing.assert_allclose(np.asarray(sa.freq), np.asarray(sb.freq),
                               **TOL)
    np.testing.assert_array_equal(np.asarray(sa.events),
                                  np.asarray(sb.events))
    np.testing.assert_allclose(_ordered(sa.filtration),
                               _ordered(sb.filtration), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode,n_tiles,n", [
    ("v24", 4, 16),        # coupled multi-tile fleet
    ("v24", 1, 16),        # scalar-Γ single tile
    ("reactive", 4, 16),
    ("off", 4, 16),
    ("v24", 4, 200),       # package count not a lane multiple (pad + slice)
])
def test_fused_run_block_matches_vmap(mode, n_tiles, n):
    cfg = SchedulerConfig(n_tiles=n_tiles, mode=mode)
    trace = _trace(24, n, n_tiles, seed=1)
    ev = FleetEngine(cfg, backend="vmap")
    ef = FleetEngine(cfg, backend="fused")
    sv, tv = ev.run_block(ev.init(n), trace)
    sf, tf = ef.run_block(ef.init(n), trace)
    for f in tv._fields:
        # min/threshold statistics flip on 1-ulp state differences — they
        # get the discrete bound, everything continuous carries 1e-5
        tol = (dict(rtol=1e-3, atol=1e-3)
               if f in ("freq_min", "at_risk_frac") else TOL)
        np.testing.assert_allclose(
            np.asarray(getattr(tv, f), np.float64),
            np.asarray(getattr(tf, f), np.float64), err_msg=f, **tol)
    _assert_states_equiv(sv, sf)


@pytest.mark.parametrize("impl", ["incremental", "ring"])
def test_fused_accepts_both_filtration_impls(impl):
    """The kernel internally runs sliding stats; the wrapper rebuilds either
    state representation, so both configs ride the fast path."""
    cfg = SchedulerConfig(n_tiles=4, mode="v24", filtration_impl=impl)
    trace = _trace(20, 8, 4, seed=2)
    ev = FleetEngine(cfg, backend="vmap")
    ef = FleetEngine(cfg, backend="fused")
    sv, tv = ev.run_block(ev.init(8), trace)
    sf, tf = ef.run_block(ef.init(8), trace)
    assert type(sf.filtration) is type(sv.filtration)
    np.testing.assert_allclose(np.asarray(tv.temp_p99_c),
                               np.asarray(tf.temp_p99_c), **TOL)
    _assert_states_equiv(sv, sf)
    if impl == "incremental":
        # stats leaves are exactly re-derived from the ring at block exit
        w, c, r = pdu_gate.exact_stats(sf.filtration.buf, sf.filtration.ptr)
        np.testing.assert_array_equal(np.asarray(sf.filtration.wsum),
                                      np.asarray(w))
        np.testing.assert_array_equal(np.asarray(sf.filtration.csum),
                                      np.asarray(c))


def test_fused_run_chunked_and_stream_continuity():
    """Chunk boundaries (state handoff kernel→kernel) lose nothing: two
    12-step fused blocks == one 24-step fused block == vmap."""
    cfg = SchedulerConfig(n_tiles=4, mode="v24")
    trace = _trace(24, 16, 4, seed=3)
    ef = FleetEngine(cfg, backend="fused")
    ev = FleetEngine(cfg, backend="vmap")
    s1, r1 = ef.run_chunked(ef.init(16), trace, flush_every=12)
    s2, r2 = ev.run_chunked(ev.init(16), trace, flush_every=12)
    assert r1.temp_p99_c.shape == (2,)
    for f in r1._fields:
        tol = (dict(rtol=1e-3, atol=1e-3)
               if f in ("freq_min", "at_risk_frac") else TOL)
        np.testing.assert_allclose(
            np.asarray(getattr(r1, f), np.float64),
            np.asarray(getattr(r2, f), np.float64), err_msg=f, **tol)
    _assert_states_equiv(s2, s1)


def test_fused_merged_branch_mixes_lane_modes_in_one_block():
    """The kernel's merged fallback/pinned branch with every lane kind in
    ONE package block: pinned clean lanes bit-match a fused reactive_poll
    fleet, healthy unpinned lanes bit-match a fused plain-v24 fleet, and
    the stale-hint lanes (one pinned, one not) match the pure path with
    events, staleness and degraded latches exact."""
    n, t, tiles = 8, 96, 2
    cfg = dict(n_tiles=tiles, mode="v24", filtration_window=16,
               stale_limit_steps=4, recover_steps=8)
    trace = np.array(_trace(t, n, tiles, seed=7))
    trace[32:48, 2, :] = np.nan          # unpinned lane goes dark, recovers
    trace[40:52, 5, 1] = np.inf          # pinned lane with a corrupt tile
    pin = np.zeros(n, bool)
    pin[[0, 3, 5]] = True
    clean = [i for i in range(n) if i not in (2, 5)]

    def run(backend, **kw):
        e = FleetEngine(SchedulerConfig(**{**cfg, **kw}), backend=backend)
        st = e.init(n)
        if st.ctrl_mode is not None:
            st = st._replace(ctrl_mode=jnp.asarray(pin))
        st, temps, freqs = e.block_traces(st, jnp.asarray(trace))
        return st, np.asarray(temps), np.asarray(freqs)

    merged = dict(mixed_mode=True, degraded_fallback=True)
    sf, tf, ff = run("fused", **merged)
    sb, tb, fb = run("broadcast", **merged)
    np.testing.assert_allclose(tf, tb, **TOL)
    np.testing.assert_allclose(ff, fb, **TOL)
    for f in ("events", "stale", "degraded"):
        np.testing.assert_array_equal(np.asarray(getattr(sf, f)),
                                      np.asarray(getattr(sb, f)), err_msg=f)
    _, t_rp, f_rp = run("fused", mode="reactive_poll")
    _, t_v, f_v = run("fused")
    for lane in clean:
        want_t, want_f = (t_rp, f_rp) if pin[lane] else (t_v, f_v)
        assert np.array_equal(tf[:, lane], want_t[:, lane]), f"lane {lane}"
        assert np.array_equal(ff[:, lane], want_f[:, lane]), f"lane {lane}"


def test_fused_step_fallback_matches_broadcast():
    """Per-step `step()` on the fused backend is the pure-JAX fallback."""
    cfg = SchedulerConfig(n_tiles=4, mode="v24")
    trace = _trace(5, 8, 4, seed=4)
    eb = FleetEngine(cfg, backend="broadcast")
    ef = FleetEngine(cfg, backend="fused")
    sb, sf = eb.init(8), ef.init(8)
    for t in range(5):
        sb, ob, _ = eb.step(sb, trace[t])
        sf, of, _ = ef.step(sf, trace[t])
        np.testing.assert_array_equal(np.asarray(ob.freq),
                                      np.asarray(of.freq))


def test_fused_registered_and_describe():
    from repro.fleet import available_backends
    assert "fused" in available_backends()
    ef = FleetEngine(SchedulerConfig(n_tiles=4), backend="fused")
    assert ef.backend == "fused"
    assert "fused" in ef.backend_impl.describe()


def test_donated_state_soak():
    """State donation: a rebinding soak loop works with donation forced on
    (on CPU XLA ignores the donation; on TPU/GPU it updates in place), and
    the trajectory matches the undonated engine."""
    cfg = SchedulerConfig(n_tiles=4, mode="v24")
    trace = _trace(12, 8, 4, seed=5)
    e1 = FleetEngine(cfg, backend="broadcast", donate_state=False)
    e2 = FleetEngine(cfg, backend="broadcast", donate_state=True)
    assert not e1.donate_state and e2.donate_state
    s1, s2 = e1.init(8), e2.init(8)
    for t in range(0, 12, 4):
        s1, r1 = e1.run_block(s1, trace[t:t + 4])
        s2, r2 = e2.run_block(s2, trace[t:t + 4])
    np.testing.assert_allclose(np.asarray(r1.released_mtps),
                               np.asarray(r2.released_mtps), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s1.events),
                                  np.asarray(s2.events))


def test_fused_long_soak_drift_bounded():
    """A multi-wrap soak (10 windows deep) stays within the 1e-5 contract —
    the per-chunk exact stats refresh keeps kernel drift bounded."""
    cfg = SchedulerConfig(n_tiles=2, mode="v24", filtration_window=16)
    trace = _trace(160, 4, 2, seed=6)
    ev = FleetEngine(cfg, backend="vmap")
    ef = FleetEngine(cfg, backend="fused")
    sv, rv = ev.run_chunked(ev.init(4), trace, flush_every=20)
    sf, rf = ef.run_chunked(ef.init(4), trace, flush_every=20)
    np.testing.assert_allclose(np.asarray(rv.temp_p99_c),
                               np.asarray(rf.temp_p99_c), **TOL)
    np.testing.assert_allclose(np.asarray(rv.released_mtps),
                               np.asarray(rf.released_mtps), rtol=1e-5)
    _assert_states_equiv(sv, sf)
