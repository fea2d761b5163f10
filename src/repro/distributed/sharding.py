"""Sharding rules: parameter / batch / cache PartitionSpecs for every arch.

Parallelism map (DESIGN.md §3):
  * DP   — batch over ("pod", "data") (pods are pure-DP replicas: heavy
           weight collectives stay intra-pod; only the gradient all-reduce
           crosses the pod axis).
  * TP   — "model" axis: attention head/projection dims, MLP hidden, vocab.
  * EP   — MoE expert axis over "model" when n_experts % model_size == 0
           (deepseek-v2: 160/16 = 10 experts per chip); otherwise TP inside
           the expert FFN (mixtral: 8 experts < 16 chips).
  * FSDP — for ≥~30B configs, weight + optimizer-state sharding over "data"
           on a second dim (ZeRO-3 style; XLA inserts the per-layer
           all-gathers inside the scan body).
  * SP   — long-context decode (batch=1) shards recurrent state / KV window
           over "model"; the data axis is idle by the cell's construction.

Divisibility: specs only shard dims divisible by the axis size; a helper
downgrades non-divisible entries to replicated (GSPMD could pad, but explicit
downgrades keep memory accounting honest).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

# Parameter count threshold above which FSDP weight sharding turns on.
FSDP_THRESHOLD = 20e9


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def use_fsdp(cfg: ArchConfig) -> bool:
    return cfg.param_count() > FSDP_THRESHOLD


def _div(n: int, mesh, axis) -> bool:
    if axis is None:
        return True
    size = np.prod([mesh.shape[a] for a in
                    (axis if isinstance(axis, tuple) else (axis,))])
    return n % size == 0


def _spec(mesh, shape, *axes):
    """PartitionSpec with per-dim divisibility downgrade."""
    fixed = []
    for dim, ax in zip(shape, axes):
        fixed.append(ax if _div(dim, mesh, ax) else None)
    return P(*fixed)


def param_specs(cfg: ArchConfig, params, mesh, *,
                tp_attention: bool = True) -> Any:
    """Pytree of PartitionSpec congruent with ``params``.

    ``tp_attention=False`` = EP-only mode (§Perf cell C): the "model" axis
    shards ONLY the expert weights; attention/MLP/embedding weights shard
    over the FSDP ("data") axis and replicate over "model" — trading the
    per-layer Megatron activation all-reduces for weight all-gathers, a win
    whenever the model is activation-collective-bound.
    """
    fsdp = "data" if ((use_fsdp(cfg) or not tp_attention)
                      and "data" in mesh.axis_names) else None
    ep = (cfg.is_moe and cfg.n_experts % mesh.shape["model"] == 0)
    tp_ax = "model" if tp_attention else None

    def leaf(path, x) -> P:
        name = path[-1] if path else ""
        shape = x.shape
        nd = len(shape)
        if nd <= 1:
            return P()                              # norms, biases, scalars
        # --- embeddings / head -------------------------------------------
        if name == "embed":
            return _spec(mesh, shape, tp_ax, fsdp)
        if name == "lm_head":
            return _spec(mesh, shape, fsdp, tp_ax)
        # --- MoE ----------------------------------------------------------
        if name.startswith("we_"):                  # [L, E, D, F] or [E, D, F]
            if ep:
                ax = ([None] * (nd - 3)) + ["model", fsdp, None]
            elif name == "we_down":
                ax = ([None] * (nd - 3)) + [None, "model", fsdp]
            else:
                ax = ([None] * (nd - 3)) + [None, fsdp, "model"]
            return _spec(mesh, shape, *ax)
        if name == "router":
            return P()
        # --- projections: shard the "wide" output dim over model, the input
        #     (d_model) dim over the FSDP axis ------------------------------
        out_sharded = ("wq", "wk", "wv", "wg", "wr", "w_up", "w_gate",
                       "ws_up", "ws_gate", "in_proj", "ck", "w_uk", "w_uv")
        in_sharded = ("wo", "w_down", "ws_down", "out_proj", "cv")
        if name in out_sharded:
            ax = ([None] * (nd - 2)) + [fsdp, tp_ax]
            return _spec(mesh, shape, *ax)
        if name in in_sharded:
            ax = ([None] * (nd - 2)) + [tp_ax, fsdp]
            return _spec(mesh, shape, *ax)
        if name in ("w_dkv", "bcdt_proj", "conv_w", "w1", "w2", "mix"):
            return P()                              # small / awkward dims
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda kp, x: leaf(tuple(getattr(k, "key", getattr(k, "idx", None))
                                 for k in kp), x), params)


def batch_spec(mesh, ndim: int = 2, batch: int | None = None) -> P:
    """tokens/labels [B, S(, D)]: batch over the DP axes.

    If ``batch`` is given and the DP axes don't divide it (long_500k's
    global_batch=1), the batch dim is left replicated — that cell's
    parallelism comes from model/state sharding instead (SP; DESIGN.md §3).
    """
    dp = dp_axes(mesh)
    if batch is not None and dp:
        n = 1
        for a in dp:
            n *= mesh.shape[a]
        if batch % n:
            return P(*([None] * ndim))
    return P(dp, *([None] * (ndim - 1)))


def state_specs(cfg: ArchConfig, opt_state, params_specs) -> Any:
    """Optimizer state inherits parameter sharding (m, v congruent)."""
    import dataclasses

    from repro.optim.adamw import AdamWState
    return AdamWState(m=params_specs, v=params_specs,
                      count=P())


def cache_specs(cfg: ArchConfig, cache, mesh) -> Any:
    """Decode-cache specs.  Batch over DP axes; heads/latent over "model".

    For batch-1 long-context cells the DP axes don't divide the batch, so the
    helper's divisibility downgrade automatically falls back to model-axis
    (SP-style) sharding of the state dims.
    """
    dp = dp_axes(mesh)

    def leaf(path, x):
        name = path[-1] if path else ""
        shape = x.shape
        if name in ("k", "v", "ks", "vs"):   # [L, B, S, KV, dh|1]
            sp = _spec(mesh, shape, None, dp, None, "model", None)
            if sp[3] is None:        # KV not divisible ⇒ shard head_dim
                sp = _spec(mesh, shape, None, dp, None, None, "model")
            return sp
        if name == "c":              # MLA latent [L, B, S, r]
            return _spec(mesh, shape, None, dp, None, "model")
        if name == "kr":
            return _spec(mesh, shape, None, dp, None, None)
        if name == "pos":
            return _spec(mesh, shape, None, dp, None)
        if name == "h":              # SSM state [L, B, H, N, P]
            return _spec(mesh, shape, None, dp, "model", None, None)
        if name == "conv":           # [L, B, 3, di]
            return _spec(mesh, shape, None, dp, None, "model")
        if name in ("prev_t", "prev_c"):   # [L, B, 1, D]
            return _spec(mesh, shape, None, dp, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda kp, x: leaf(tuple(getattr(k, "key", getattr(k, "idx", None))
                                 for k in kp), x), cache)


def to_shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


# ========================================================== fleet-serving mesh
# The fleet engine's package axis is embarrassingly parallel: a 1-D mesh over
# it needs no collectives inside the scheduler update (only the telemetry
# reductions communicate).  FLEET_AXIS is the axis name the sharded fleet
# backend, `ThermalScheduler.state_pspecs`, and `bench_fleet` all agree on.
FLEET_AXIS = "packages"


def fleet_mesh(n_devices: int | None = None, axis: str = FLEET_AXIS):
    """1-D device mesh over the fleet's package axis.

    ``n_devices`` of None or 0 takes every visible device (matching the
    CLI's ``--fleet-devices 0`` convention); a request larger than the host
    provides degrades to what is available (single-device JAX yields a
    trivial 1-mesh, on which sharded == broadcast).

    Devices are ordered by (process_index, id): in a `jax.distributed`
    process group this makes each process's mesh positions CONTIGUOUS, so
    every process owns one contiguous span of package lanes
    (`repro.distributed.multihost.local_lane_range`) and per-host ingest
    slabs assemble into global arrays without cross-host movement.  On one
    process the sort is the identity, so single-host meshes are unchanged.
    """
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n = len(devs) if not n_devices else max(1, min(n_devices, len(devs)))
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))


def fleet_trace_spec(ndim: int, axis: str = FLEET_AXIS,
                     package_dim: int = 0) -> P:
    """Spec for density traces: shard ``package_dim`` over the fleet axis.

    [n_packages, n_tiles] chunks use the default; [T, n_packages, n_tiles]
    streaming chunks pass ``package_dim=1`` and [C, K, n_packages, n_tiles]
    pre-chunked traces ``package_dim=2`` (the package axis always sits just
    before the tile axis).
    """
    dims = [None] * ndim
    dims[package_dim] = axis
    return P(*dims)


def fleet_shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking OFF.

    The sharded-fused fleet backend maps a `pallas_call` over the package
    mesh; pallas has no replication rule, so `check_vma` must be disabled.
    The out_specs still place every result, so disabling the check loses
    nothing but the static verifier.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ===================================================== activation constraints
# Model code runs both unsharded (unit tests, examples) and under the
# production mesh (launcher, dry-run).  `axis_env(mesh)` publishes the mesh's
# axis roles; `constrain(x, roles)` then places with_sharding_constraint on
# activations — the lever that keeps logits / attention intermediates from
# silently replicating (GSPMD propagation through scans is not reliable
# enough at 256-way for peak-memory-critical tensors).
import contextlib

_AXIS_ENV: dict | None = None


@contextlib.contextmanager
def axis_env(mesh, tp_activations: bool = True):
    """``tp_activations=False`` (EP-only mode) disables the "tp" role for
    attention/MLP activations while the "ep" role (expert tensors) keeps
    sharding over the model axis."""
    global _AXIS_ENV
    prev = _AXIS_ENV
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    _AXIS_ENV = {"dp": tuple(a for a in ("pod", "data") if a in sizes),
                 "tp": ("model" if "model" in sizes and tp_activations
                        else None),
                 "ep": "model" if "model" in sizes else None,
                 "sizes": sizes}
    try:
        yield
    finally:
        _AXIS_ENV = prev


def _role_axes(role):
    env = _AXIS_ENV
    if role is None or env is None:
        return None, 1
    if role == "dp":
        axes = env["dp"]
        n = 1
        for a in axes:
            n *= env["sizes"][a]
        return (axes if axes else None), n
    if role in ("tp", "ep"):
        ax = env[role]
        return ax, env["sizes"].get("model", 1) if ax else 1
    raise ValueError(role)


def constrain(x, roles):
    """with_sharding_constraint by symbolic role per dim: None | 'dp' | 'tp'.

    No-op outside an `axis_env` (unit tests / single-device runs) and for any
    dim the axis doesn't divide.
    """
    if _AXIS_ENV is None:
        return x
    spec = []
    for dim, role in zip(x.shape, roles):
        ax, n = _role_axes(role)
        spec.append(ax if (ax and dim % n == 0 and n > 1) else None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_heads(x):
    """[B, S|T, H, dh]: prefer sharding H over tp; fall back to dh (MQA)."""
    if _AXIS_ENV is None:
        return x
    _, n = _role_axes("tp")
    if n > 1 and x.shape[2] % n == 0:
        return constrain(x, ("dp", None, "tp", None))
    return constrain(x, ("dp", None, None, "tp"))
