"""Decoder-only LM assembled from config-driven block patterns.

One generic trunk covers all ten assigned architectures:

  * the layer stack is a loop over ``cfg.repeats`` repetitions of
    a "super-layer" (``cfg.pattern`` — e.g. ``("attn",)`` for llama,
    ``("attn_local", "attn_global")`` for gemma-2, ``("ssm",)`` for
    mamba-2 and zamba-2) — keeping the HLO O(1) in depth and the live
    activation set bounded (remat policy per config);
  * Zamba-2 (``cfg.hybrid_layer_ids``): before each listed Mamba layer
    one of ``cfg.num_mem_blocks`` shared attention + MLP blocks runs
    over [x, embedding]; its output, through a per-invocation LoRA'd MLP
    and projection, is added to that layer's input. Its layers are
    unrolled (see ``_hybrid_fwd``); each invocation keeps its own KV
    cache (the ``hybrid`` stack of the caches);
  * frontends: ``token`` (embedding table) or ``embed`` (precomputed
    patch/frame embeddings — the VLM/audio stub per the assignment);
  * losses use chunked cross-entropy (never materializes the full
    (tokens × vocab) logits).

Three entry points map to the assigned shapes: :func:`train_loss`
(train_4k), :func:`prefill` (prefill_32k), :func:`decode_step`
(decode_32k / long_500k serve_step).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding.act_sharding import constrain
from .attention import (
    attn_decode,
    attn_forward,
    decode_attend,
    decode_qkv,
    init_attn_params,
    init_kv_cache,
)
from .common import chunked_softmax_xent, rms_norm, soft_cap, truncated_normal
from .mlp import (
    init_lora_mlp_params,
    init_mlp_params,
    lora_mlp_forward,
    mlp_forward,
)
from .moe import init_moe_params, moe_forward
from .ssm import init_ssm_cache, init_ssm_params, ssm_decode, ssm_forward

__all__ = [
    "init_params",
    "train_loss",
    "prefill",
    "decode_step",
    "init_decode_caches",
    "param_count",
]

MOE_AUX_WEIGHT = 0.01


def _is_attn(kind: str) -> bool:
    return kind in ("attn", "attn_local", "attn_global")


def _cache_kinds(cfg):
    """``(key, kind)`` of each stack of the decode caches."""
    out = [(f"slot{i}", kind) for i, kind in enumerate(cfg.pattern)]
    if cfg.hybrid_layer_ids:
        out.append(("hybrid", "attn"))
    return out


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_block(cfg, kind: str, key) -> Dict[str, Any]:
    if kind == "ssm":
        k1, k2 = jax.random.split(key)
        return {
            "ln": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
            "ssm": init_ssm_params(k1, cfg),
        }
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {
        "ln1": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
        "attn": init_attn_params(k1, cfg),
    }
    if cfg.is_moe:
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype))
        p["moe"] = init_moe_params(k2, cfg)
    elif cfg.d_ff:
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype))
        p["mlp"] = init_mlp_params(k2, cfg)
    return p


def _init_shared(cfg, key) -> Dict[str, Any]:
    """One shared block: attention over [x, embedding], then its MLP."""
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.attn_in_dim,), dtype),
        "attn": init_attn_params(k1, cfg),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "mlp": init_lora_mlp_params(k2, cfg),
    }


def _init_invocation(cfg, key) -> Dict[str, Any]:
    """One invocation's own weights: its MLP's LoRA (A, B) and the
    projection of the block's output into the Mamba layer's input."""
    m, r = cfg.d_model, cfg.adapter_rank
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "lora_a": truncated_normal(k1, (m, r), 1.0, dtype),
        "lora_b": truncated_normal(k2, (r, 2 * cfg.d_ff), 0.1, dtype),
        "proj": truncated_normal(k3, (m, m), 1.0, dtype),
    }


def init_params(cfg, key) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.pattern) + 4)
    params: Dict[str, Any] = {}
    if cfg.frontend == "token":
        params["embed"] = truncated_normal(
            keys[-1], (cfg.padded_vocab, cfg.d_model), 1.0,
            jnp.dtype(cfg.param_dtype),
        )
    slots: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.pattern):
        rkeys = jax.random.split(keys[i], cfg.repeats)
        slots[f"slot{i}"] = jax.vmap(
            functools.partial(_init_block, cfg, kind)
        )(rkeys)
    params["slots"] = slots
    if cfg.hybrid_layer_ids:
        k_shared, k_calls = jax.random.split(keys[-2])
        params["shared"] = jax.vmap(functools.partial(_init_shared, cfg))(
            jax.random.split(k_shared, cfg.num_mem_blocks))
        params["hybrid"] = jax.vmap(functools.partial(_init_invocation, cfg))(
            jax.random.split(k_calls, len(cfg.hybrid_layer_ids)))
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype))
    params["unembed"] = truncated_normal(
        keys[-3], (cfg.d_model, cfg.padded_vocab), 1.0,
        jnp.dtype(cfg.param_dtype),
    )
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
# Each block's parts run under a ``jax.named_scope`` (``ssm``, ``attn``,
# ``ffn``; ``embed`` and ``head`` around the trunk), so a profiler trace
# names the device's operations by the layer they belong to. Scopes are
# metadata: they change neither the program nor its numbers.
def _ffn(cfg, bp, x, aux):
    if not (cfg.is_moe or cfg.d_ff):
        return x, aux
    with jax.named_scope("ffn"):
        h = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y, a = moe_forward(cfg, bp["moe"], h)
            return x + y, aux + a
        return x + mlp_forward(bp["mlp"], h), aux


def _block_fwd(cfg, kind, bp, x, positions, aux, build_cache, pre=None):
    """Full-sequence application (train / prefill). ``pre`` (a shared
    block's output) is added to an SSM block's input, not its residual."""
    cache = None
    if kind == "ssm":
        with jax.named_scope("ssm"):
            h = rms_norm(x if pre is None else x + pre, bp["ln"], cfg.norm_eps)
            if build_cache:
                y, cache = ssm_forward(cfg, bp["ssm"], h, build_cache=True)
                x = x + y
            else:
                x = x + ssm_forward(cfg, bp["ssm"], h)
    else:
        with jax.named_scope("attn"):
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            y, cache = attn_forward(cfg, bp["attn"], h, positions, kind,
                                    build_cache=build_cache)
            x = x + y
        x, aux = _ffn(cfg, bp, x, aux)
    return x, aux, cache


def _row(stack, i):
    """Row ``i`` of every leaf of a layer-stacked pytree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _write_rows(stack, old, new, i):
    """Write a layer's new cache leaves into row ``i`` of the stacked
    caches; a leaf the block passed through unchanged is not rewritten."""
    return jax.tree.map(
        lambda s, o, n: s if n is o else jax.lax.dynamic_update_index_in_dim(
            s, n, i, 0),
        stack, old, new,
    )


def _block_decode(cfg, kind, bp, x, pos, stack, i, pre=None):
    """Decode one block against row ``i`` of its stacked cache; returns the
    new activation and the stack with that row updated in place. The row's
    write-back runs in the block's scope, so the state traffic is named by
    the layer it belongs to. An SSM block is given its SSD state as the
    whole stack and updates row ``i`` itself; its conv tails are read and
    written back as rows. ``pre`` as in :func:`_block_fwd`."""
    if kind == "ssm":
        with jax.named_scope("ssm"):
            h = rms_norm(x if pre is None else x + pre, bp["ln"], cfg.norm_eps)
            tails = {k: v for k, v in stack.items() if k != "state"}
            rows = _row(tails, i)
            y, new = ssm_decode(cfg, bp["ssm"], h,
                                dict(rows, state=stack["state"]), layer=i)
            state = new.pop("state")
            return x + y, dict(_write_rows(tails, rows, new, i), state=state)
    cache = _row(stack, i)
    with jax.named_scope("attn"):
        h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        y, new = attn_decode(cfg, bp["attn"], h, pos, cache, kind)
        x = x + y
        stack = _write_rows(stack, cache, new, i)
    x, _ = _ffn(cfg, bp, x, 0.0)
    return x, stack


# ---------------------------------------------------------------------------
# shared blocks (Zamba-2)
# ---------------------------------------------------------------------------
# The attention scope holds the concatenation, input norm, q/k/v, RoPE,
# the KV write, scores, o-projection and the invocation's projection; the
# ffn scope the pre-FF norm, gate-up with its LoRA, the gate and down.
def _shared_mlp_proj(cfg, sp, hp, a):
    with jax.named_scope("ffn"):
        f = lora_mlp_forward(sp["mlp"], hp["lora_a"], hp["lora_b"],
                             rms_norm(a, sp["ln2"], cfg.norm_eps))
    with jax.named_scope("attn"):
        return f @ hp["proj"].astype(f.dtype)


def _shared_fwd(cfg, params, j, x, e, positions, build_cache):
    """Invocation ``j`` over the whole sequence: its output (added to the
    next Mamba layer's input) and its KV cache."""
    sp = _row(params["shared"], j % cfg.num_mem_blocks)
    hp = _row(params["hybrid"], j)
    with jax.named_scope("attn"):
        u = rms_norm(jnp.concatenate([x, e], -1), sp["ln1"], cfg.norm_eps)
        a, cache = attn_forward(cfg, sp["attn"], u, positions, "attn",
                                build_cache=build_cache)
    return _shared_mlp_proj(cfg, sp, hp, a), cache


def _shared_decode(cfg, params, j, x, e, pos, kv):
    """Invocation ``j`` for one token: its key and value go into row ``j``
    of the stacked hot rings in place, then it attends over that row (as
    ``attn_decode`` does over a cache of its own)."""
    sp = _row(params["shared"], j % cfg.num_mem_blocks)
    hp = _row(params["hybrid"], j)
    with jax.named_scope("attn"):
        u = rms_norm(jnp.concatenate([x, e], -1), sp["ln1"], cfg.norm_eps)
        q, k, v = decode_qkv(cfg, sp["attn"], u, pos)
        kv = _write_token(kv, j, k, v, pos)
        c = _row(kv, j)
        a = decode_attend(cfg, sp["attn"], q, pos, [
            (c["k"], c["v"], c["kv_pos"]), (c["hk"], c["hv"], c["h_pos"])],
            "attn")
    return _shared_mlp_proj(cfg, sp, hp, a), kv


def _write_token(kv, j, k, v, pos):
    """Write one token's keys and values (B, 1, K, D) into row ``j`` of the
    stacked hot rings at slot ``pos % decode_hot_len``, one request at a
    time: written at once (a scatter), the compiler copies the rings."""
    slot = (pos % kv["hk"].shape[2]).astype(jnp.int32)
    k, v = k.astype(kv["hk"].dtype), v.astype(kv["hv"].dtype)
    kv = dict(kv)
    for r in range(pos.shape[0]):
        at = (j, r, slot[r])
        kv["hk"] = jax.lax.dynamic_update_slice(kv["hk"], k[None, r:r + 1],
                                                at + (0, 0))
        kv["hv"] = jax.lax.dynamic_update_slice(kv["hv"], v[None, r:r + 1],
                                                at + (0, 0))
        kv["h_pos"] = jax.lax.dynamic_update_slice(
            kv["h_pos"], pos[None, r:r + 1, None].astype(jnp.int32), at)
    return kv


# ---------------------------------------------------------------------------
# stack (scan over repeats)
# ---------------------------------------------------------------------------
def _stack_fwd(cfg, params, x, positions, build_cache=False):
    if cfg.hybrid_layer_ids:
        return _hybrid_fwd(cfg, params, x, positions, build_cache)

    def body(carry, xs):
        x, aux = carry
        slot_rows = xs
        caches = {}
        x = constrain(x)   # layer-boundary activation sharding (SP)
        for i, kind in enumerate(cfg.pattern):
            x, aux, cache = _block_fwd(cfg, kind, slot_rows[f"slot{i}"], x,
                                       positions, aux, build_cache)
            if build_cache and cache is not None:
                caches[f"slot{i}"] = cache
        x = constrain(x)
        return (x, aux), (caches if build_cache else None)

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    if getattr(cfg, "scan_layers", True):
        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.float32(0.0)), params["slots"]
        )
        return x, aux, caches
    # unrolled path (dry-run cost calibration; also useful on small R)
    carry = (x, jnp.float32(0.0))
    cache_rows = []
    for r in range(cfg.repeats):
        rows = jax.tree.map(lambda a: a[r], params["slots"])
        carry, cache_r = body(carry, rows)
        if build_cache:
            cache_rows.append(cache_r)
    x, aux = carry
    caches = (
        jax.tree.map(lambda *xs: jnp.stack(xs), *cache_rows)
        if build_cache and cache_rows
        else None
    )
    return x, aux, caches


def _stack_decode(cfg, params, x, pos, caches):
    """The layer loop of a decode step. The stacked caches ride in the
    loop's carry and each layer updates its own row in place, so with the
    caches donated the step writes into its input buffers: as a scan's
    ``xs``/``ys`` they would need a second whole stack and a copy."""
    if cfg.hybrid_layer_ids:
        return _hybrid_decode(cfg, params, x, pos, caches)

    def body(i, carry):
        x, caches = carry
        caches = dict(caches)
        for j, kind in enumerate(cfg.pattern):
            key = f"slot{j}"
            x, caches[key] = _block_decode(
                cfg, kind, _row(params["slots"][key], i), x, pos,
                caches[key], i)
        return x, caches

    if getattr(cfg, "scan_layers", True):
        return jax.lax.fori_loop(0, cfg.repeats, body, (x, caches))
    carry = (x, caches)
    for r in range(cfg.repeats):
        carry = body(r, carry)
    return carry


# Zamba-2's layers are unrolled, each invocation of a shared block before
# its layer, reading weights and cache rows at static indices. Split into
# loops at the invocations, or with them inside one loop (under a
# conditional or a nested loop), the compiler lays the state and KV stacks
# out inside otherwise than the step's arguments, and copies them whole at
# every step. ``e`` is the embedding, carried to every invocation.
def _hybrid_fwd(cfg, params, x, positions, build_cache):
    e, aux = x, jnp.float32(0.0)
    ids = list(cfg.hybrid_layer_ids)
    layers = params["slots"]["slot0"]

    def layer(x, i):
        t = kv = None
        if i in ids:
            t, kv = _shared_fwd(cfg, params, ids.index(i), x, e, positions,
                                build_cache)
        x, _, cache = _block_fwd(cfg, "ssm", _row(layers, i), x, positions,
                                 aux, build_cache, pre=t)
        return constrain(x), cache, kv

    if cfg.remat == "full":
        layer = jax.checkpoint(layer, static_argnums=(1,))
    ssm_caches, kv_caches = [], []
    for i in range(cfg.repeats):
        x, cache, kv = layer(constrain(x), i)
        ssm_caches.append(cache)
        if kv is not None:
            kv_caches.append(kv)
    if not build_cache:
        return x, aux, None

    def stack(caches):
        return jax.tree.map(lambda *a: jnp.stack(a), *caches)

    return x, aux, {"slot0": stack(ssm_caches), "hybrid": stack(kv_caches)}


def _hybrid_decode(cfg, params, x, pos, caches):
    e = x
    ids = list(cfg.hybrid_layer_ids)
    state, kv = caches["slot0"], caches["hybrid"]
    for i in range(cfg.repeats):
        t = None
        if i in ids:
            t, kv = _shared_decode(cfg, params, ids.index(i), x, e, pos, kv)
        x, state = _block_decode(cfg, "ssm", _row(params["slots"]["slot0"], i),
                                 x, pos, state, i, pre=t)
    return x, {"slot0": state, "hybrid": kv}


# ---------------------------------------------------------------------------
# frontends / positions
# ---------------------------------------------------------------------------
@jax.named_scope("embed")
def _embed(cfg, params, inputs):
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.frontend == "token":
        table = params["embed"].astype(cdt)
        if getattr(cfg, "embed_onehot", True):
            # one-hot matmul: lowers to an MXU dot that partitions
            # cleanly over a sharded vocab (XLA fuses the iota-compare
            # one-hot); the plain gather was lowered as an fp32
            # mask-and-psum over vocab shards (§Perf iter C5).
            b, s = inputs.shape
            flat = inputs.reshape(-1)
            oh = jax.nn.one_hot(flat, table.shape[0], dtype=cdt)
            return (oh @ table).reshape(b, s, -1)
        return jnp.take(table, inputs, axis=0)
    return inputs.astype(cdt)  # precomputed embeddings (VLM/audio stub)


def _positions(cfg, batch: int, seq: int):
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(pos, (3, batch, seq))
    return pos


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def train_loss(cfg, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: {"inputs": (B,S) int32 or (B,S,M) embeds, "labels": (B,S)}."""
    inputs, labels = batch["inputs"], batch["labels"]
    b, s = labels.shape
    x = _embed(cfg, params, inputs)
    x, aux, _ = _stack_fwd(cfg, params, x, _positions(cfg, b, s))
    with jax.named_scope("head"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        loss_sum, count = chunked_softmax_xent(
            h.reshape(-1, cfg.d_model),
            params["unembed"],
            labels.reshape(-1),
            chunk=cfg.loss_chunk,
            final_softcap=cfg.final_logit_softcap,
        )
    loss = loss_sum / jnp.maximum(count, 1.0)
    metrics = {"loss": loss, "tokens": count}
    if cfg.is_moe:
        metrics["moe_aux"] = aux
        loss = loss + MOE_AUX_WEIGHT * aux
    return loss, metrics


def _logits(cfg, params, h):
    out = (h @ params["unembed"].astype(h.dtype)).astype(jnp.float32)
    return soft_cap(out, cfg.final_logit_softcap)


@jax.named_scope("head")
def _head(cfg, params, x):
    """Final norm and float32 logits."""
    return _logits(cfg, params, rms_norm(x, params["final_norm"],
                                         cfg.norm_eps))


def prefill(cfg, params, inputs) -> Tuple[jax.Array, Any, jax.Array]:
    """Full-sequence prefill; returns (last-token logits, caches, pos)."""
    if inputs.ndim == 2:
        b, s = inputs.shape
    else:
        b, s = inputs.shape[0], inputs.shape[1]
    x = _embed(cfg, params, inputs)
    x, _, caches = _stack_fwd(cfg, params, x, _positions(cfg, b, s),
                              build_cache=True)
    pos = jnp.full((b,), s, jnp.int32)
    return _head(cfg, params, x[:, -1:])[:, 0], caches, pos


def init_decode_caches(cfg, batch: int, cache_len: int, filled: bool = False):
    """Stacked (R-leading) cache pytree for decoding.

    ``filled=True`` marks every slot as holding real tokens (emulating a
    cache after ``cache_len`` tokens of prefill) — the decode dry-run
    shapes use this.
    """
    caches: Dict[str, Any] = {}
    dtype = jnp.dtype(cfg.compute_dtype)

    def stack(tree, r):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (r,) + x.shape), tree)

    for key, kind in _cache_kinds(cfg):
        r = len(cfg.hybrid_layer_ids) if key == "hybrid" else cfg.repeats
        if kind == "ssm":
            caches[key] = stack(init_ssm_cache(cfg, batch, dtype), r)
        elif _is_attn(kind):
            c = init_kv_cache(cfg, batch, cache_len, kind, dtype)
            if filled:
                t = c["kv_pos"].shape[1]
                c["kv_pos"] = jnp.broadcast_to(
                    jnp.arange(cache_len - t, cache_len, dtype=jnp.int32),
                    (batch, t),
                )
            caches[key] = stack(c, r)
    return caches


def grow_caches(cfg, caches, new_len: int):
    """Extend prefill caches to ``new_len`` slots for decoding (windowed
    layers cap at their window). Ring indexing then continues writing at
    ``pos % T`` without evicting live context."""
    out = {}
    for key, kind in _cache_kinds(cfg):
        if key not in caches:
            continue
        c = caches[key]
        if kind == "ssm":
            out[key] = c
            continue
        t_new = new_len
        if kind == "attn_local" or (kind == "attn" and cfg.window is not None):
            t_new = min(new_len, cfg.window)
        t_cur = c["k"].shape[2]  # stacked: (R or J, B, T, K, D)
        if t_new <= t_cur:
            out[key] = c
            continue
        pad = t_new - t_cur
        grown = dict(c)  # hot-ring keys pass through untouched
        grown["k"] = jnp.pad(c["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        grown["v"] = jnp.pad(c["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        grown["kv_pos"] = jnp.pad(c["kv_pos"], ((0, 0), (0, 0), (0, pad)),
                                  constant_values=-1)
        out[key] = grown
    return out


def consolidate_caches(cfg, caches):
    """Flush hot-ring entries into the prefix cache (amortized every
    ``decode_hot_len`` tokens by the serving layer) and reset the rings.
    Prefix writes use ring semantics (slot = pos % T) with out-of-range
    drops, so windowed and full layers share the path."""
    out = {}
    for key, kind in _cache_kinds(cfg):
        if key not in caches:
            continue
        c = caches[key]
        if kind == "ssm" or "hk" not in c:
            out[key] = c
            continue
        t = c["k"].shape[2]

        def flush(pk, pv, ppos, hk, hv, hpos):
            # per (repeat, batch) row: scatter valid hot slots into prefix
            valid = hpos >= 0
            idx = jnp.where(valid, hpos % t, t)   # t = out of range → drop
            pk = pk.at[idx].set(hk, mode="drop")
            pv = pv.at[idx].set(hv, mode="drop")
            ppos = ppos.at[idx].set(hpos, mode="drop")
            return pk, pv, ppos

        pk, pv, ppos = jax.vmap(jax.vmap(flush))(
            c["k"], c["v"], c["kv_pos"], c["hk"], c["hv"], c["h_pos"]
        )
        out[key] = {
            "k": pk, "v": pv, "kv_pos": ppos,
            "hk": jnp.zeros_like(c["hk"]),
            "hv": jnp.zeros_like(c["hv"]),
            "h_pos": jnp.full_like(c["h_pos"], -1),
        }
    return out


def decode_step(cfg, params, token, pos, caches):
    """One-token serve step. token: (B,1) int32 (or (B,1,M) embeds);
    pos: (B,) tokens decoded so far. Returns (logits (B,V), new caches,
    pos+1)."""
    x = _embed(cfg, params, token)
    x, new_caches = _stack_decode(cfg, params, x, pos, caches)
    return _head(cfg, params, x)[:, 0], new_caches, pos + 1
