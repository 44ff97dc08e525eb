"""Mamba-2 mixer block (SSD core + projections, causal conv, gated norm).

Layout follows Dao & Gu [arXiv:2405.21060]: separate projections for
z (gate), x, B, C, dt (kept as distinct weights so each shards cleanly —
see sharding/partition.py), a short causal depthwise conv over x/B/C,
the SSD recurrence (via ``repro.kernels.ssd``), a gated RMSNorm (per
group of B/C, as Zamba2 normalises) and the
output projection. Decode carries (conv tail, SSD state) per layer.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ssd import ops as ssd_ops
from ..kernels.ssd.decode import ssd_decode_layer
from ..kernels.ssd.ref import ssd_decode_step
from .common import rms_norm, truncated_normal

__all__ = ["init_ssm_params", "ssm_forward", "init_ssm_cache", "ssm_decode"]


def init_ssm_params(key, cfg) -> Dict[str, jax.Array]:
    m = cfg.d_model
    d_in = cfg.ssm_d_inner
    h = cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    dc = cfg.ssm_conv
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    return {
        "wz": truncated_normal(ks[0], (m, d_in), 1.0, dtype),
        "wx": truncated_normal(ks[1], (m, d_in), 1.0, dtype),
        "wb": truncated_normal(ks[2], (m, gn), 1.0, dtype),
        "wc": truncated_normal(ks[3], (m, gn), 1.0, dtype),
        "wdt": truncated_normal(ks[4], (m, h), 1.0, dtype),
        "dt_bias": jnp.zeros((h,), dtype),
        "a_log": jnp.zeros((h,), dtype),            # A = -exp(a_log) = -1
        "d_skip": jnp.ones((h,), dtype),
        "conv_x": truncated_normal(ks[5], (dc, d_in), 1.0, dtype),
        "conv_b": truncated_normal(ks[6], (dc, gn), 1.0, dtype),
        "conv_c": truncated_normal(ks[7], (dc, gn), 1.0, dtype),
        "norm": jnp.zeros((d_in,), dtype),
        "wo": truncated_normal(ks[4], (d_in, m), 1.0, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 tail: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv. x: (B, L, C); w: (K, C); tail: (B, K-1, C)
    carries context across calls (decode)."""
    k = w.shape[0]
    if tail is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    # windows: out[:, t] = sum_i w[i] * xp[:, t + i]
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i : i + x.shape[1], :] * w[i].astype(x.dtype)
    return jax.nn.silu(out)


def _gated_norm(cfg, y, z, scale):
    """RMSNorm of ``y * silu(z)`` over each of the ``ssm_groups`` groups of
    channels (Zamba2's per-group norm; over all channels at one group)."""
    h = y * jax.nn.silu(z)
    g = cfg.ssm_groups
    if g == 1:
        return rms_norm(h, scale, cfg.norm_eps)
    shape = h.shape
    h = h.reshape(shape[:-1] + (g, shape[-1] // g))
    return rms_norm(h, scale.reshape(g, -1), cfg.norm_eps).reshape(shape)


def _project(cfg, p, h):
    cdt = h.dtype
    z = h @ p["wz"].astype(cdt)
    x = h @ p["wx"].astype(cdt)
    b = h @ p["wb"].astype(cdt)
    c = h @ p["wc"].astype(cdt)
    dt = jax.nn.softplus(
        (h @ p["wdt"].astype(cdt)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)
    )
    return z, x, b, c, dt


def ssm_forward(cfg, p: Dict[str, jax.Array], h: jax.Array,
                build_cache: bool = False):
    """Full-sequence forward. h: (B, L, M) (post-norm input).

    With ``build_cache`` also returns the decode carry (final SSD state +
    conv tails), enabling prefill→decode handoff for SSM layers.
    """
    bsz, l, _ = h.shape
    d_in = cfg.ssm_d_inner
    nh, hp = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    k = cfg.ssm_conv
    z, x_raw, b_raw, c_raw, dt = _project(cfg, p, h)
    x = _causal_conv(x_raw, p["conv_x"])
    b = _causal_conv(b_raw, p["conv_b"])
    c = _causal_conv(c_raw, p["conv_c"])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    from ..kernels.ssd.ref import ssd_reference

    with jax.named_scope("state_update"):   # the SSD chunked scan
        if build_cache:
            y, state = ssd_reference(
                x.reshape(bsz, l, nh, hp), dt, a,
                b.reshape(bsz, l, g, n), c.reshape(bsz, l, g, n),
                chunk=cfg.ssm_chunk, d_skip=p["d_skip"].astype(jnp.float32),
                return_final_state=True,
            )
        else:
            y = ssd_ops.ssd(
                x.reshape(bsz, l, nh, hp), dt, a,
                b.reshape(bsz, l, g, n), c.reshape(bsz, l, g, n),
                chunk=cfg.ssm_chunk, d_skip=p["d_skip"].astype(jnp.float32),
            )
    y = y.reshape(bsz, l, d_in)
    y = _gated_norm(cfg, y, z, p["norm"])
    out = y @ p["wo"].astype(y.dtype)
    if build_cache:
        cdt = jnp.dtype(cfg.compute_dtype) if hasattr(cfg, "compute_dtype") else x_raw.dtype
        cache = {
            "state": state,
            "conv_x": x_raw[:, -(k - 1):].astype(cdt),
            "conv_b": b_raw[:, -(k - 1):].astype(cdt),
            "conv_c": c_raw[:, -(k - 1):].astype(cdt),
        }
        return out, cache
    return out


def init_ssm_cache(cfg, batch: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    d_in = cfg.ssm_d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    k = cfg.ssm_conv
    return {
        "state": jnp.zeros(
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
        ),
        "conv_x": jnp.zeros((batch, k - 1, d_in), dtype),
        "conv_b": jnp.zeros((batch, k - 1, gn), dtype),
        "conv_c": jnp.zeros((batch, k - 1, gn), dtype),
    }


def ssm_decode(
    cfg, p: Dict[str, jax.Array], h: jax.Array, cache: Dict[str, jax.Array],
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. h: (B, 1, M). Without ``layer`` the cache is one
    layer's; with it, ``cache["state"]`` is the stack of every layer's SSD
    state, updated in place at row ``layer`` (``ssd_decode_layer``), and
    the conv tails are that layer's."""
    bsz = h.shape[0]
    nh, hp = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    z, x, b, c, dt = _project(cfg, p, h)
    new_cache = dict(cache)
    outs = {}
    for name, val in (("conv_x", x), ("conv_b", b), ("conv_c", c)):
        tail = cache[name]
        outs[name] = _causal_conv(val, p[name.replace("conv_", "conv_")],
                                  tail=tail)
        new_cache[name] = jnp.concatenate([tail[:, 1:], val.astype(tail.dtype)],
                                          axis=1)
    x, b, c = outs["conv_x"], outs["conv_b"], outs["conv_c"]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    args = (x[:, 0].reshape(bsz, nh, hp), dt[:, 0], a,
            b[:, 0].reshape(bsz, g, n), c[:, 0].reshape(bsz, g, n),
            cache["state"])
    d_skip = p["d_skip"].astype(jnp.float32)
    with jax.named_scope("state_update"):   # the SSD recurrence
        if layer is None:
            y, state = ssd_decode_step(*args, d_skip=d_skip)
        else:
            y, state = ssd_decode_layer(*args, layer, d_skip=d_skip)
    new_cache["state"] = state
    y = y.reshape(bsz, 1, cfg.ssm_d_inner)
    y = _gated_norm(cfg, y, z, p["norm"])
    return y @ p["wo"].astype(y.dtype), new_cache
