"""Step-function factories + abstract input specs for every assigned
(architecture × shape) cell.

``input_specs(cfg, shape)`` returns weak-type-correct ShapeDtypeStruct
stand-ins for every model input — shardable, no device allocation — the
dry-run lowers against these. Train cells lower ``train_step`` (fwd +
bwd + AdamW update); prefill cells lower ``prefill_step``; decode cells
lower ``serve_step`` (one new token against a seq_len cache).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..core.backends.analytical import StepModel, device_peak
from ..models import lm
from ..optim.adamw import AdamWConfig, adamw_update, init_opt_state

__all__ = [
    "init_train_state",
    "train_state_shapes",
    "make_train_step",
    "make_prefill_step",
    "make_serve_step",
    "input_specs",
    "init_serve_params",
    "serve_params_shapes",
    "step_flop_model",
    "model_flops",
]


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def init_train_state(cfg: ModelConfig, key) -> Dict[str, Any]:
    params = lm.init_params(cfg, key)
    return {
        "params": params,
        "opt": init_opt_state(params),
        "step": jnp.zeros((), jnp.int32),
    }


def train_state_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(init_train_state, cfg), jax.random.PRNGKey(0)
    )


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig = AdamWConfig()):
    cdt = jnp.dtype(cfg.compute_dtype)

    def cast_params(p):
        # One bf16 cast per step OUTSIDE the layer scan: FSDP weight
        # all-gathers then move bf16 shards, not fp32 masters (§Perf
        # iter C2 — halves the dominant all-gather bytes). fp32 masters
        # are touched only by the optimizer.
        return jax.tree.map(
            lambda x: x.astype(cdt)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            p,
        )

    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm.train_loss(cfg, cast_params(p), batch),
            has_aux=True,
        )(state["params"])
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, state["params"], grads, state["opt"]
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        return new_state, {**metrics, **opt_metrics}

    return train_step


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def init_serve_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Serving weights are bf16 (fp32 masters live in the train state).
    Under one ``jit`` the fp32 initial values are temporaries of the cast,
    so only the bf16 weights are ever held as a whole."""
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        lm.init_params(cfg, key),
    )


def serve_params_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(init_serve_params, cfg), jax.random.PRNGKey(0)
    )


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, inputs):
        return lm.prefill(cfg, params, inputs)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, token, pos, caches):
        return lm.decode_step(cfg, params, token, pos, caches)

    return serve_step


# ---------------------------------------------------------------------------
# abstract input specs
# ---------------------------------------------------------------------------
def _token_spec(cfg: ModelConfig, batch: int, seq: int):
    if cfg.frontend == "token":
        return jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    # VLM/audio stub: precomputed frame/patch embeddings
    return jax.ShapeDtypeStruct(
        (batch, seq, cfg.d_model), jnp.dtype(cfg.compute_dtype)
    )


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[Any, ...]:
    """Abstract inputs for the step the shape lowers (excl. params/state)."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {
            "inputs": _token_spec(cfg, b, s),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        return (batch,)
    if shape.kind == "prefill":
        return (_token_spec(cfg, b, s),)
    if shape.kind == "decode":
        token = _token_spec(cfg, b, 1)
        pos = jax.ShapeDtypeStruct((b,), jnp.int32)
        caches = jax.eval_shape(
            functools.partial(
                lm.init_decode_caches, cfg, b, s, filled=True
            )
        )
        return (token, pos, caches)
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# model FLOPs accounting (roofline §"useful" numerator)
# ---------------------------------------------------------------------------
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·tokens for training (fwd+bwd), 2·N·tokens for inference
    forward passes (decode: one token per sequence). N = active params
    contributing matmul FLOPs (embedding-gather excluded)."""
    n = cfg.n_flops_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: 1 new token


def step_flop_model(cfg: ModelConfig, shape: ShapeConfig,
                    world_size: int = 1) -> Optional[StepModel]:
    """Per-device model FLOPs of one step with the peaks of the device
    JAX runs on, for TALP's Computational Efficiency. ``None`` off the
    TPU: there is no peak, so that metric is not measured."""
    hw = device_peak(jax.devices()[0])
    if hw is None:
        return None
    return StepModel(
        flops=0.0, hbm_bytes=0.0, collective_bytes=0.0,
        model_flops=model_flops(cfg, shape) / max(world_size, 1), hw=hw,
    )
