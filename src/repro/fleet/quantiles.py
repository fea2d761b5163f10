"""Exact fleet percentiles by counting selection, not sorting.

The fleet telemetry needs each step's p50 and p99 junction temperature:
per row, two pairs of neighbouring order statistics fed to a linear
interpolation.  A comparison sort of a [T, n·tiles] trace to read four
elements a row costs far more than the kernel that made the trace, so
each rank is found instead by a most-significant-first radix descent over
an order-preserving integer key of the value: every pass resolves
`_BITS` bits of each rank's key with one fused compare-and-count
reduction over the row (all ranks of a row share the pass), and one more
pass finds each rank's upper neighbour.

The results are bitwise those of the sort path this replaced:

  * integer order of the key is `lax.sort`'s float order: −0.0 ties +0.0
    (the stable sort keeps their index order, and `_signed_zeros` reads a
    rank inside a run of zeros the same way), every NaN sorts last, and a
    masked row's +inf padding sorts after every real value;
  * the interpolation is the arithmetic each site used: `jnp.percentile`'s
    linear rule without a mask (a row holding a NaN reads NaN), the
    traced-count rule of the masked reductions with one.  A masked row
    whose rank lands on a NaN reads a NaN, though not always with the
    sorted element's payload.

Counts do not depend on element order, so the reductions read the values
in whatever layout they arrive, and on a sharded package axis each count
is a cross-device sum.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
from jax import lax

QS = (50.0, 99.0)          # the fleet telemetry's percentiles
_BITS = 2                  # key bits a descent pass resolves: on a TPU v5e
#                            at [50, 47, 63,744], 1/2/3/4 bits took
#                            32.0/19.1/27.3/39.5 ms (a sort: 473 ms)
_PASSES = -(-32 // _BITS)
_U = np.uint32
_SIGN = _U(0x80000000)     # also the key of ±0.0
_NAN_BITS = _U(0x7FC00000)
_NONE = _U(0xFFFFFFFF)     # above every key


def _keys(v):
    """uint32 key whose integer order is `lax.sort`'s order of f32 ``v``."""
    u = lax.bitcast_convert_type(v, jnp.uint32)
    u = jnp.where(v == 0, _U(0), u)
    u = jnp.where(jnp.isnan(v), _NAN_BITS, u)
    return jnp.where(u >= _SIGN, ~u, u | _SIGN)


def _values(k):
    u = jnp.where(k >= _SIGN, k ^ _SIGN, ~k)
    return lax.bitcast_convert_type(u, jnp.float32)


def _col(a, k, ndim):
    """Column ``k`` of a [R, K] array, shaped to broadcast against [R, ...]
    values of rank ``ndim``."""
    return a[:, k].reshape((-1,) + (1,) * (ndim - 1))


def _count(pred):
    return pred.sum(tuple(range(1, pred.ndim)), dtype=jnp.int32)


def _select(keys, ranks):
    """[R, K]: the ``ranks``-th smallest (0-based) key of each row of
    ``keys`` [R, ...] — the largest key with at most ``ranks`` keys below
    it, built `_BITS` bits a pass from the top."""
    nd = keys.ndim

    def descend(i, prefix):
        # compared shifted, a candidate prefix + j·2^s never wraps, not even
        # in a top pass narrower than _BITS
        s = _U(_BITS) * (_PASSES - 1 - i).astype(jnp.uint32)
        top, base = keys >> s, prefix >> s
        digit = jnp.stack([
            sum((_count(top < _col(base, k, nd) + _U(j))
                 <= ranks[:, k]).astype(jnp.uint32)
                for j in range(1, 2 ** _BITS))
            for k in range(ranks.shape[1])], 1)
        return prefix + (digit << s)

    return lax.fori_loop(0, _PASSES, descend,
                         jnp.zeros(ranks.shape, jnp.uint32))


def _order_stats(keys, lo, hi):
    """Keys at ranks ``lo`` and ``hi`` [R, K] of each row, ``hi`` ∈ {lo,
    lo + 1}: the upper neighbour repeats the lower key while more keys than
    ``hi`` lie at or below it, else it is the least key above."""
    nd = keys.ndim
    k_lo = _select(keys, lo)
    ks = range(lo.shape[1])
    at_or_below = jnp.stack(
        [_count(keys <= _col(k_lo, k, nd)) for k in ks], 1)
    above = jnp.stack(
        [jnp.where(keys > _col(k_lo, k, nd), keys, _NONE).min(
            tuple(range(1, nd))) for k in ks], 1)
    return k_lo, jnp.where(at_or_below > hi, k_lo, above)


def _signed_zeros(v, keys, ranks, sel, idx):
    """Float values of the selected keys ``sel`` [R, K].  A key of ±0.0
    reads the zero that `lax.sort`'s stable order puts at that rank: the
    (rank − negatives)-th zero of the row in the order of ``idx``, each
    element's place in the row the sort saw.  Only rows that select a zero
    pay for the second descent, over the zeros' places."""
    nd = v.ndim

    def fix(val):
        z = keys == _SIGN
        nth = jnp.maximum(ranks - _count(keys < _SIGN)[:, None], 0)
        at = _select(jnp.where(z, idx, _NONE), nth)
        neg = jnp.stack(
            [_count(z & (idx == _col(at, k, nd)) & jnp.signbit(v)) > 0
             for k in range(ranks.shape[1])], 1)
        return jnp.where(sel == _SIGN,
                         jnp.where(neg, jnp.float32(-0.0), jnp.float32(0.0)),
                         val)

    return lax.cond((sel == _SIGN).any(), fix, lambda val: val,
                    _values(sel))


def fleet_percentiles(values, mask=None, cnt=None):
    """(p50, p99), each [R], of each row of f32 ``values`` [R, n, tiles]
    (packages × tiles, ordered as the row-major flattened row).

    Without ``mask``: `jnp.percentile(values.reshape(R, -1), q, axis=1)`.
    With a bool ``mask`` (broadcastable to ``values``) and its traced
    ``cnt`` of active elements (≥ 1): the linear interpolation over the
    first ``cnt`` entries of each sorted row with inactive entries set to
    +inf — mask flips never re-specialise the program.

    The counts read the [R, tiles, n] view, the fused kernel's own layout:
    packages on lanes, with no padding of a narrow tile axis."""
    if values.dtype != jnp.float32:
        raise TypeError(f"fleet percentiles take f32 values, got "
                        f"{values.dtype}")
    rows, n, tiles = values.shape
    v = values if mask is None else jnp.where(mask, values, jnp.inf)
    v = jnp.swapaxes(v, 1, 2)
    idx = (jnp.arange(n, dtype=jnp.uint32)[None, :] * _U(tiles)
           + jnp.arange(tiles, dtype=jnp.uint32)[:, None])
    lo, hi, weights = [], [], []
    for q in QS:
        if mask is None:
            # jax.numpy.percentile's linear rule (jax._src.numpy.reductions
            # ._quantile), step for step
            pos = jnp.asarray(q, jnp.float32) / 100
            nf = jnp.asarray(n * tiles, jnp.float32)
            pos = pos * (nf - 1)
            low, high = jnp.floor(pos), jnp.ceil(pos)
            hw = pos - low
            lw = 1 - hw
            low = lax.clamp(jnp.float32(0), low, nf - 1).astype(jnp.int32)
            high = lax.clamp(jnp.float32(0), high, nf - 1).astype(jnp.int32)
        else:
            pos = q / 100.0 * (cnt - 1).astype(values.dtype)
            low = jnp.floor(pos).astype(jnp.int32)
            high = jnp.ceil(pos).astype(jnp.int32)
            hw = pos - low
            lw = 1.0 - hw
        lo.append(jnp.broadcast_to(low, (rows,)))
        hi.append(jnp.broadcast_to(high, (rows,)))
        weights.append((lw, hw))
    lo, hi = jnp.stack(lo, 1), jnp.stack(hi, 1)
    keys = _keys(v)
    k_lo, k_hi = _order_stats(keys, lo, hi)
    both = _signed_zeros(v, keys, jnp.concatenate([lo, hi], 1),
                         jnp.concatenate([k_lo, k_hi], 1), idx)
    if mask is None:
        both = jnp.where(jnp.isnan(v).any((1, 2))[:, None],
                         jnp.float32(jnp.nan), both)
    nq = len(QS)
    return tuple(both[:, i] * lw + both[:, nq + i] * hw
                 for i, (lw, hw) in enumerate(weights))
