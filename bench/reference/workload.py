"""Synthetic density traces, copied from the program's
`repro.core.workload.make_trace` so that the benchmark's inputs and its
reference do not move when the program's generator changes.

A trace is rho(t) in the paper's density domain [0.9, 2.7], shape
[n_steps, n_tiles], from a PRNG key and one of four workload kinds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = ("inference", "training", "vision", "batch")
RHO_MIN, RHO_MAX = 0.9, 2.7


def _ou(key, n_steps, n_tiles, mean, std, theta=0.01):
    """Clipped Ornstein-Uhlenbeck base load."""
    def tick(x, eps):
        x = x + theta * (mean - x) + std * jnp.sqrt(2 * theta) * eps
        return x, x
    eps = jax.random.normal(key, (n_steps, n_tiles))
    _, xs = jax.lax.scan(tick, jnp.full((n_tiles,), mean), eps)
    return xs


def _bursts(key, n_steps, n_tiles, rate_per_ms, dur_ms, amp):
    """Box-filtered Bernoulli arrivals: a running count of the spikes in the
    trailing ``dur_ms`` steps, clipped to 1, times a jittered amplitude."""
    k1, k2 = jax.random.split(key)
    spikes = (jax.random.uniform(k1, (n_steps, n_tiles)) < rate_per_ms)
    csum = jnp.cumsum(spikes.astype(jnp.float32), axis=0)
    lagged = jnp.concatenate(
        [jnp.zeros((min(dur_ms, n_steps), n_tiles)), csum])[:n_steps]
    env = csum - lagged
    jitter = 0.75 + 0.5 * jax.random.uniform(k2, (n_steps, n_tiles))
    return jnp.minimum(env, 1.0) * amp * jitter


def make_trace(key, n_steps: int, kind: str = "inference",
               n_tiles: int = 1) -> jnp.ndarray:
    """rho(t) trace, [n_steps, n_tiles]."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, KINDS.index(kind)))
    if kind == "inference":
        base = _ou(k1, n_steps, n_tiles, mean=1.55, std=0.18)
        trace = base + _bursts(k2, n_steps, n_tiles,
                               rate_per_ms=0.011, dur_ms=260, amp=1.3)
    elif kind == "training":
        period, duty = 500, 0.7
        t = jnp.arange(n_steps)
        phase = (t % period) / period
        wave = jnp.where(phase < duty, 2.65, 1.55)[:, None]
        trace = wave + _ou(k1, n_steps, n_tiles, mean=0.0, std=0.08)
    elif kind == "vision":
        base = _ou(k1, n_steps, n_tiles, mean=2.0, std=0.15)
        trace = base + _bursts(k2, n_steps, n_tiles,
                               rate_per_ms=0.008, dur_ms=140, amp=1.0)
    elif kind == "batch":
        trace = _ou(k1, n_steps, n_tiles, mean=2.5, std=0.25, theta=0.004)
    else:
        raise ValueError(f"unknown workload kind {kind!r}; want one of {KINDS}")
    return jnp.clip(trace, RHO_MIN, RHO_MAX)


def fleet_chunk(key, n_steps: int, n_packages: int, n_tiles: int):
    """[n_steps, n_packages, n_tiles] chunk: package i runs kind
    ``KINDS[i % 4]`` from key ``fold_in(key, i)``.  Trace-safe (jit it)."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_packages))
    out = jnp.zeros((n_steps, n_packages, n_tiles), jnp.float32)
    for j, kind in enumerate(KINDS):
        sel = keys[j::len(KINDS)]
        tr = jax.vmap(lambda k: make_trace(k, n_steps, kind, n_tiles))(sel)
        out = out.at[:, j::len(KINDS), :].set(jnp.moveaxis(tr, 0, 1))
    return out
