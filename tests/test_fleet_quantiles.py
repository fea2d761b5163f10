"""Fleet percentiles by counting selection are bitwise the sort path's.

`repro.fleet.quantiles.fleet_percentiles` replaced a sort of each step's
[n·tiles] temperature row.  The oracle below is that sort path as it was:
`jnp.percentile` over the flattened row without a mask, and the traced-
count interpolation over the +inf-padded sorted row with one.  Equal means
the same f32 bits; a NaN equals any NaN, since a masked row whose rank
lands on a NaN may carry another payload."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scheduler import SchedulerConfig
from repro.fleet import FleetEngine, FleetService, chunk_source, stream
from repro.fleet import engine as engine_module
from repro.fleet.quantiles import fleet_percentiles

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------- the oracle
def _masked_quantile(sorted_v, cnt, q):
    pos = q / 100.0 * (cnt - 1).astype(sorted_v.dtype)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    frac = pos - lo
    take = lambda i: jnp.take_along_axis(
        sorted_v, jnp.broadcast_to(i, sorted_v.shape[:-1])[..., None],
        axis=-1)[..., 0]
    return take(lo) * (1.0 - frac) + take(hi) * frac


def sort_percentiles(values, mask=None, cnt=None):
    """The sort path, with the signature of `fleet_percentiles`."""
    rows = values.shape[0]
    flat = values.reshape(rows, -1)
    if mask is None:
        return (jnp.percentile(flat, 50.0, axis=1),
                jnp.percentile(flat, 99.0, axis=1))
    mf = jnp.broadcast_to(mask, values.shape).reshape(rows, -1)
    srt = jnp.sort(jnp.where(mf, flat, jnp.inf), axis=1)
    return _masked_quantile(srt, cnt, 50.0), _masked_quantile(srt, cnt, 99.0)


def _assert_same_bits(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(np.where(nan, 0, got).view(np.uint32),
                                  np.where(nan, 0, want).view(np.uint32))


# ------------------------------------------------------ the helper alone
def _values(case, rng):
    pick = lambda vals, shape: rng.choice(np.asarray(vals, np.float32), shape)
    if case == "ties":
        return pick([-2.0, -1.0, 0.5, 1.0, 3.0], (4, 33, 7))
    if case == "all_equal":
        return np.full((3, 20, 3), 71.5, np.float32)
    if case == "signed_zeros":       # ±0.0 interleaved in random index order
        return pick([-0.0, 0.0, -1.0, 1.0, -2.5], (6, 40, 5))
    if case == "only_zeros":
        return pick([-0.0, 0.0], (5, 9, 3))
    if case == "negative":
        return rng.normal(-40.0, 30.0, (3, 50, 3)).astype(np.float32)
    if case == "nan":                # rows 0 and 2 hold a NaN
        v = rng.normal(60.0, 10.0, (4, 16, 4)).astype(np.float32)
        v[0, 3, 1] = v[2, 15, 0] = np.nan
        return v
    if case == "inf":
        v = rng.normal(0.0, 1.0, (3, 24, 2)).astype(np.float32)
        v[:, :3, 0], v[:, 20:, 1] = -np.inf, np.inf
        return v
    if case == "temps_130_lanes":    # a row length not a multiple of 128
        return rng.normal(60.0, 10.0, (5, 130, 1)).astype(np.float32)
    if case == "temps_47_tiles":
        return rng.normal(70.0, 5.0, (2, 37, 47)).astype(np.float32)
    raise ValueError(case)


def _mask(mode, n, rng):
    if mode == "none":
        return None
    lanes = {"one": 1, "two": 2, "all": n, "half": n // 2, "empty": 0}[mode]
    active = np.zeros(n, bool)
    active[rng.choice(n, lanes, replace=False)] = True
    return active


CASES = ("ties", "all_equal", "signed_zeros", "only_zeros", "negative",
         "nan", "inf", "temps_130_lanes", "temps_47_tiles")
MASKS = ("none", "one", "two", "half", "all", "empty")


@pytest.mark.parametrize("mask_mode", MASKS)
@pytest.mark.parametrize("case", CASES)
def test_selection_bitwise_matches_sort(case, mask_mode):
    """p50 and p99 of every row, masked and not, are the sort path's
    bits: ties, ±0.0 in either index order, NaN rows, infinities, rows of
    1 or 2 active lanes, an empty mask, rows not a multiple of 128."""
    rng = np.random.default_rng(CASES.index(case) * 10
                                + MASKS.index(mask_mode))
    values = jnp.asarray(_values(case, rng))
    active = _mask(mask_mode, values.shape[1], rng)

    def both(fn):
        if active is None:
            return jax.jit(fn)(values)

        def masked(v, act):
            m = jnp.broadcast_to(act[:, None], v.shape[1:])
            return fn(v, m[None], jnp.maximum(m.sum(), 1))
        return jax.jit(masked)(values, jnp.asarray(active))

    for got, want in zip(both(fleet_percentiles), both(sort_percentiles)):
        _assert_same_bits(got, want)


def test_selection_rejects_non_f32():
    with pytest.raises(TypeError, match="f32"):
        fleet_percentiles(jnp.zeros((1, 4, 2), jnp.bfloat16))


# --------------------------------------------------- whole flush programs
N, TILES, K = 40, 3, 6
CAPACITY = 64           # the service's lane pool: a power of two above N


def _trace(steps, n=N, seed=0):
    key = jax.random.PRNGKey(seed)
    return np.asarray(0.9 + 1.8 * jax.random.uniform(key, (steps, n, TILES)),
                      np.float32)


def _service(cfg):
    svc = FleetService(cfg, backend="fused", min_capacity=CAPACITY,
                       flush_every=K)
    for i in np.flatnonzero(_active()):
        svc.attach(f"p{i}")
    return svc


def _active():
    active = np.zeros(N, bool)
    active[np.random.default_rng(5).choice(N, 23, replace=False)] = True
    return active


def _percentiles_of(path):
    """[(p50, p99) per flush] of one whole program, fused in interpret
    mode where the path has a kernel."""
    cfg = SchedulerConfig(n_tiles=TILES)
    trace = _trace(3 * K)
    if path == "stream":
        eng = FleetEngine(cfg, backend="fused")
        _, tel, _ = stream(eng, eng.init(N), chunk_source(trace, K))
        return [(t["temp_p50_c"], t["temp_p99_c"]) for t in tel]
    if path in ("run_block_masked", "step_scan", "step_scan_masked"):
        backend = "fused" if path == "run_block_masked" else "broadcast"
        eng = FleetEngine(cfg, backend=backend)
        active = None if path == "step_scan" else jnp.asarray(_active())
        if path == "run_block_masked":
            _, t = eng.run_block(eng.init(N), jnp.asarray(trace), active)
        else:
            _, t = eng.run(eng.init(N), jnp.asarray(trace), active)
        return list(zip(np.asarray(t.temp_p50_c).ravel(),
                        np.asarray(t.temp_p99_c).ravel()))
    if path == "service_flush":
        svc = _service(cfg)
        recs = [svc.tick(c) for c in chunk_source(_trace(3 * K, CAPACITY),
                                                   K)]
        return [(r["telemetry"]["temp_p50_c"], r["telemetry"]["temp_p99_c"])
                for r in recs]
    raise ValueError(path)


PATHS = ("stream", "run_block_masked", "service_flush", "step_scan",
         "step_scan_masked")


@pytest.mark.parametrize("path", PATHS)
def test_flush_percentiles_bitwise_match_sort_path(path, monkeypatch):
    """`stream()`, the masked `run_block`, the service's jitted flush and
    the per-step scan report the percentiles the sort path reported."""
    got = _percentiles_of(path)
    monkeypatch.setattr(engine_module, "fleet_percentiles", sort_percentiles)
    want = _percentiles_of(path)
    assert len(got) == len(want) > 0
    _assert_same_bits(got, want)


def _lowered(program):
    cfg = SchedulerConfig(n_tiles=TILES)
    if program == "service_flush":
        svc = _service(cfg)
        th = {k: jnp.asarray(v)
              for k, v in svc.registry.threshold_arrays().items()}
        return jax.jit(svc._flush_impl).lower(
            svc.state, jnp.asarray(_trace(K, CAPACITY)), jnp.asarray(svc.registry.active_mask()),
            jnp.asarray(svc.registry.tenant_lane_ids()), th)
    eng = FleetEngine(cfg, backend="fused")
    active = jnp.asarray(_active()) if program == "run_block_masked" else None
    return jax.jit(eng._run_block_impl).lower(eng.init(N),
                                              jnp.asarray(_trace(K)), active)


@pytest.mark.parametrize("program", ("run_block", "run_block_masked",
                                     "service_flush"))
def test_flush_programs_hold_no_sort(program):
    lowered = _lowered(program)
    assert "stablehlo.sort" not in lowered.as_text()
    assert " sort(" not in lowered.compile().as_text()
