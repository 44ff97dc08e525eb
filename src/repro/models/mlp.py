"""Dense gated feed-forward blocks: SwiGLU, and Zamba2's shared MLP (a
fused gate-up product plus a per-invocation LoRA term, GELU gate)."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .common import truncated_normal

__all__ = ["init_mlp_params", "mlp_forward", "init_lora_mlp_params",
           "lora_mlp_forward"]


def init_mlp_params(key, cfg) -> Dict[str, jax.Array]:
    m, f = cfg.d_model, cfg.d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": truncated_normal(k1, (m, f), 1.0, dtype),
        "w_up": truncated_normal(k2, (m, f), 1.0, dtype),
        "w_down": truncated_normal(k3, (f, m), 1.0, dtype),
    }


def mlp_forward(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    dt = x.dtype
    g = jax.nn.silu(x @ p["w_gate"].astype(dt))
    u = x @ p["w_up"].astype(dt)
    return (g * u) @ p["w_down"].astype(dt)


def init_lora_mlp_params(key, cfg) -> Dict[str, jax.Array]:
    """The shared part: ``w_gu`` (M, 2F) holds the gate then the up
    columns; ``w_down`` (F, M)."""
    m, f = cfg.d_model, cfg.d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "w_gu": truncated_normal(k1, (m, 2 * f), 1.0, dtype),
        "w_down": truncated_normal(k2, (f, m), 1.0, dtype),
    }


def lora_mlp_forward(p: Dict[str, jax.Array], lora_a: jax.Array,
                     lora_b: jax.Array, x: jax.Array) -> jax.Array:
    """``down(GELU(G) * U)`` with ``[G | U] = x W_gu + (x A) B`` (exact,
    erf GELU)."""
    dt = x.dtype
    gu = x @ p["w_gu"].astype(dt) + (x @ lora_a.astype(dt)) @ lora_b.astype(dt)
    g, u = jnp.split(gu, 2, axis=-1)
    return (jax.nn.gelu(g, approximate=False) * u) @ p["w_down"].astype(dt)
