"""Logical-axis partitioning rules → NamedSharding trees.

Strategy (1000+-chip posture, DESIGN.md §5):

  * mesh axes ``("pod", "data", "model")`` (multi-pod) or
    ``("data", "model")`` (single pod); ``pod``+``data`` form one
    FSDP/DP super-axis (batch sharding + ZeRO-3 parameter/optimizer
    sharding), ``model`` carries tensor/expert parallelism;
  * every rule checks divisibility against the actual mesh and falls
    back (shard a different dim, or replicate) — this is what lets one
    rule set serve all ten architectures (e.g. gemma-2's 4 KV heads
    can't split 16-ways → its decode caches shard over sequence
    instead);
  * stacked (scan) parameters carry a leading repeat dim that is never
    sharded.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "fsdp_axes",
    "param_pspec",
    "state_shardings",
    "batch_pspec",
    "cache_pspec",
    "make_sharding_tree",
]


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return dim % _axis_size(mesh, axes) == 0


def _maybe(dim: int, mesh: Mesh, axes):
    """axes if divisible else None (replicate)."""
    return axes if _fits(dim, mesh, axes) else None


def param_pspec(path: Tuple[str, ...], leaf, mesh: Mesh, cfg) -> P:
    """Partition spec for one parameter, keyed by its tree path."""
    fsdp = fsdp_axes(mesh)
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    name = names[-1]
    # leading scan-repeat dim; a hybrid's shared blocks and invocations
    stacked = bool({"slots", "shared", "hybrid"} & set(names))
    shape = leaf.shape[1:] if stacked else leaf.shape

    def out(*spec):
        spec = tuple(
            _maybe(shape[i], mesh, ax) if ax is not None else None
            for i, ax in enumerate(spec)
        )
        return P(*((None,) + spec)) if stacked else P(*spec)

    if name == "embed":
        return out(fsdp, "model")
    if name == "unembed":
        return out(fsdp, "model")
    if name in ("wq", "wk", "wv", "wz", "wx", "wb", "wc", "wdt",
                "w_gate", "w_up", "router"):
        if len(shape) == 3:  # MoE expert-stacked (E, M, F)
            if _fits(shape[0], mesh, ("model",)):
                return out("model", fsdp, None)   # expert parallel
            return out(None, fsdp, "model")       # TP inside each expert
        return out(fsdp, "model")
    if name in ("wo", "w_down"):
        if len(shape) == 3:  # MoE (E, F, M)
            if _fits(shape[0], mesh, ("model",)):
                return out("model", None, fsdp)
            return out(None, "model", fsdp)
        return out("model", fsdp)
    if name.startswith("conv_"):
        return out(None, "model")
    if name == "norm":  # ssm gated-norm scale over d_inner
        return out("model")
    # 1-D scales / biases (ln*, final_norm, a_log, dt_bias, d_skip)
    return P(*((None,) * leaf.ndim))


def make_sharding_tree(tree, mesh: Mesh, cfg, spec_fn):
    """Map a pytree of arrays/ShapeDtypeStructs to NamedShardings."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_fn(path, leaf, mesh, cfg)),
        tree,
    )


def state_shardings(state_shapes, mesh: Mesh, cfg):
    """Shardings for a TrainState {params, opt{mu, nu}, step}: optimizer
    moments inherit the parameter rule (ZeRO: they are sharded exactly
    like the FSDP parameters)."""

    def spec(path, leaf, mesh_, cfg_):
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        if names and names[0] in ("params", "mu", "nu"):
            return param_pspec(tuple(path[1:]), leaf, mesh_, cfg_)
        if names[:2] == ["opt", "mu"] or names[:2] == ["opt", "nu"]:
            return param_pspec(tuple(path[2:]), leaf, mesh_, cfg_)
        return P()  # scalars (step counters, loss scales)

    return make_sharding_tree(state_shapes, mesh, cfg, spec)


def batch_pspec(mesh: Mesh, batch_size: int, ndim: int) -> P:
    """Batch-leading activations: shard batch over the FSDP axes when
    divisible (long_500k's batch=1 replicates)."""
    fsdp = fsdp_axes(mesh)
    lead = fsdp if batch_size % _axis_size(mesh, fsdp) == 0 else None
    return P(*((lead,) + (None,) * (ndim - 1)))


def cache_pspec(path: Tuple[str, ...], leaf, mesh: Mesh, cfg) -> P:
    """Decode-cache shardings (stacked leading repeat dim).

    kv caches (R, B, T, K, D): batch over FSDP when divisible; KV heads
    over ``model`` when divisible, else sequence over ``model`` (and for
    batch=1, sequence additionally takes the FSDP axes)."""
    fsdp = fsdp_axes(mesh)
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    name = names[-1]
    shape = leaf.shape
    if name in ("hk", "hv"):
        # hot decode ring: mutable every step → batch-local only; heads
        # over model when divisible, NEVER the (tiny) sequence dim
        _, b, _, k, _ = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        return P(None, b_ax, None, _maybe(k, mesh, ("model",)), None)
    if name == "h_pos":
        _, b, _ = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        return P(None, b_ax, None)
    if name in ("k", "v"):
        _, b, t, k, d = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        if _fits(k, mesh, ("model",)):
            t_ax = None if b_ax is not None else _maybe(t, mesh, fsdp)
            return P(None, b_ax, t_ax, "model", None)
        # sequence sharding fallback
        t_axes = ("model",) if b_ax is not None else tuple(fsdp) + ("model",)
        return P(None, b_ax, _maybe(t, mesh, t_axes), None, None)
    if name == "kv_pos":
        _, b, t = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        kv = cfg.num_kv_heads
        if _fits(kv, mesh, ("model",)):
            t_ax = None if b_ax is not None else _maybe(t, mesh, fsdp)
            return P(None, b_ax, t_ax)
        t_axes = ("model",) if b_ax is not None else tuple(fsdp) + ("model",)
        return P(None, b_ax, _maybe(t, mesh, t_axes))
    if name == "state":  # (R, B, H, P, N)
        _, b, h, _, _ = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        return P(None, b_ax, _maybe(h, mesh, ("model",)), None, None)
    if name.startswith("conv_"):  # (R, B, K-1, C)
        _, b, _, c = shape
        b_ax = fsdp if _fits(b, mesh, fsdp) else None
        return P(None, b_ax, None, _maybe(c, mesh, ("model",)))
    return P(*((None,) * leaf.ndim))
