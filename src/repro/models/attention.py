"""GQA attention with sliding-window, logit soft-capping, RoPE/M-RoPE,
chunked (memory-efficient) softmax, prefill-cache construction and
ring-buffer decode.

Memory model: training/prefill never materializes the full (S × T) score
matrix — scores are computed per KV chunk under a ``lax.scan`` with an
online-softmax carry (the XLA-path analogue of the Pallas flash kernel in
``repro.kernels.flash_attention``; the kernel is the TPU hot-path, this
is the portable path and the oracle's algorithmic twin).

Decode uses a uniform ring-buffer cache: every slot remembers the token
position it holds (``kv_pos``), so full-attention and windowed layers
share one code path (mask = slot holds a token within the window).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding.act_sharding import constrain_seq_gathered
from .common import apply_mrope, apply_rope, rms_norm, soft_cap, truncated_normal

__all__ = [
    "init_attn_params",
    "attn_forward",
    "init_kv_cache",
    "attn_decode",
    "decode_qkv",
    "decode_attend",
    "chunked_attention",
]

NEG_INF = -1e30


def init_attn_params(key, cfg) -> Dict[str, jax.Array]:
    """q/k/v read ``cfg.attn_in_dim`` wide inputs; the output is d_model."""
    m, m_in = cfg.d_model, cfg.attn_in_dim
    hd = cfg.resolved_head_dim
    h, k = cfg.num_heads, cfg.num_kv_heads
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4)
    return {
        "wq": truncated_normal(keys[0], (m_in, h * hd), 1.0, dtype),
        "wk": truncated_normal(keys[1], (m_in, k * hd), 1.0, dtype),
        "wv": truncated_normal(keys[2], (m_in, k * hd), 1.0, dtype),
        "wo": truncated_normal(keys[3], (h * hd, m), 1.0, dtype),
    }


def _project_qkv(cfg, p, h, barrier=False):
    b, s, _ = h.shape
    hd = cfg.resolved_head_dim
    nh, nk = cfg.num_heads, cfg.num_kv_heads
    cdt = h.dtype
    q, k, v = (h @ p[w].astype(cdt) for w in ("wq", "wk", "wv"))
    if barrier:
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    return (q.reshape(b, s, nh, hd), k.reshape(b, s, nk, hd),
            v.reshape(b, s, nk, hd))


def _rope(cfg, x, positions):
    if cfg.mrope_sections is not None:
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def _pos_1d(positions):
    """positions may be (B,S) or (3,B,S) (M-RoPE); masks use stream 0."""
    return positions[0] if positions.ndim == 3 else positions


def attention_parts(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, K, D)
    v: jax.Array,            # (B, T, K, D)
    q_pos: jax.Array,        # (B, S)
    kv_pos: jax.Array,       # (B, T)  (-1 = empty slot)
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
):
    """Unnormalized online-softmax accumulation over one KV source.
    Scores are scaled by ``scale`` (``D ** -0.5`` where None).

    Returns (m, l, acc): running max (B,S,K,G), denominator and fp32
    accumulator (B,S,K,G,D). Multiple sources (e.g. a frozen prefix
    cache + a hot decode buffer) combine exactly via
    :func:`combine_parts` — the flash-decoding split-softmax identity.
    """
    b, s, h, d = q.shape
    t, nk = k.shape[1], k.shape[2]
    g = h // nk
    # Keep q/k/v in compute dtype across any resharding boundary — the
    # MXU takes bf16 inputs with fp32 accumulation, and casting early
    # doubles the SP all-gather bytes (§Perf iter C1).
    qr = q.reshape(b, s, nk, g, d) * jnp.asarray(
        d ** -0.5 if scale is None else scale, q.dtype)
    kv_chunk = min(kv_chunk, t)
    if t % kv_chunk != 0:
        pad = kv_chunk - t % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        t = t + pad
    nc = t // kv_chunk
    # (nc, B, C, K, D) chunk-major for scan
    kc = jnp.moveaxis(k.reshape(b, nc, kv_chunk, nk, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, kv_chunk, nk, d), 1, 0)
    pc = jnp.moveaxis(kv_pos.reshape(b, nc, kv_chunk), 1, 0)

    def body(carry, xs):
        m_i, l_i, acc = carry
        k_i, v_i, p_i = xs
        sc = jnp.einsum(
            "bskgd,bckd->bskgc", qr, k_i,
            preferred_element_type=jnp.float32,
        )
        sc = soft_cap(sc, softcap)
        valid = (p_i[:, None, :] >= 0) & (p_i[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            valid &= p_i[:, None, :] > (q_pos[:, :, None] - window)
        sc = jnp.where(valid[:, :, None, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m_i - m_new)
        pexp = jnp.exp(sc - m_new[..., None])
        l_new = l_i * alpha + jnp.sum(pexp, axis=-1)
        # NOTE (§Perf iter B4, refuted): casting pexp to bf16 for the p·V
        # matmul ADDED a materialized convert buffer (+3% memory term) —
        # XLA already fuses the fp32 path. Keeping fp32; the real fix for
        # score traffic is the Pallas flash kernel (scores stay in VMEM).
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", pexp, v_i.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, nk, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, nk, g), jnp.float32)
    a0 = jnp.zeros((b, s, nk, g, d), jnp.float32)
    if nc == 1:
        (m_f, l_f, acc), _ = body((m0, l0, a0), (kc[0], vc[0], pc[0]))
    else:
        (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    return m_f, l_f, acc


def combine_parts(parts, out_shape, dtype):
    """Merge (m, l, acc) partial softmaxes from independent KV sources."""
    m = parts[0][0]
    for mp, _, _ in parts[1:]:
        m = jnp.maximum(m, mp)
    l_tot = 0.0
    acc_tot = 0.0
    for mp, lp, ap in parts:
        alpha = jnp.exp(mp - m)
        l_tot = l_tot + lp * alpha
        acc_tot = acc_tot + ap * alpha[..., None]
    out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(out_shape).astype(dtype)


def chunked_attention(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, K, D)
    v: jax.Array,            # (B, T, K, D)
    q_pos: jax.Array,        # (B, S)
    kv_pos: jax.Array,       # (B, T)  (-1 = empty slot)
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal online-softmax attention, scanning KV in chunks."""
    b, s, h, d = q.shape
    m, l, acc = attention_parts(q, k, v, q_pos, kv_pos, window=window,
                                softcap=softcap, kv_chunk=kv_chunk,
                                scale=scale)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, s, h, d).astype(q.dtype)


def attn_forward(
    cfg,
    p: Dict[str, jax.Array],
    x: jax.Array,            # (B, S, M) — post-norm input
    positions: jax.Array,    # (B, S) or (3, B, S)
    kind: str = "attn",
    build_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Training / prefill attention over a full sequence."""
    q, k, v = _project_qkv(cfg, p, x)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    # SP→attention boundary: queries stay sequence-sharded (each shard
    # computes its own rows); K/V gather across the sequence axis here,
    # post-projection and in bf16 — for GQA this moves K·D/M ≈ 8× fewer
    # bytes than gathering the residual stream (§Perf iter C4).
    k = constrain_seq_gathered(k)
    v = constrain_seq_gathered(v)
    pos1 = _pos_1d(positions)
    window = cfg.window if kind in ("attn_local",) or (
        kind == "attn" and cfg.window is not None
    ) else None
    out = chunked_attention(
        q, k, v, pos1, pos1,
        window=window,
        softcap=cfg.attn_logit_softcap,
        kv_chunk=cfg.attn_kv_chunk,
        scale=cfg.attn_scale,
    )
    b, s, _, _ = out.shape
    y = out.reshape(b, s, -1) @ p["wo"].astype(out.dtype)
    cache = None
    if build_cache:
        hot = cfg.decode_hot_len
        nk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cdt = k.dtype
        cache = {
            "k": k,
            "v": v,
            "kv_pos": jnp.broadcast_to(pos1, (b, s)).astype(jnp.int32),
            # empty hot ring, filled during decode
            "hk": jnp.zeros((b, hot, nk, hd), cdt),
            "hv": jnp.zeros((b, hot, nk, hd), cdt),
            "h_pos": jnp.full((b, hot), -1, jnp.int32),
        }
    return y, cache


def init_kv_cache(cfg, batch: int, cache_len: int, kind: str,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Split decode cache for one attention layer (paged-attention-style):

      * ``k/v/kv_pos`` — the *prefix*: immutable after prefill, safe to
        shard over the sequence axis (XLA never has to reshard it —
        decode steps only read it);
      * ``hk/hv/h_pos`` — the *hot ring*: a small mutable buffer holding
        freshly decoded tokens, batch-local (never sequence-sharded), so
        the per-step dynamic-update-slice is collective-free.

    A serving layer consolidates hot→prefix every ``decode_hot_len``
    tokens (see ``repro.models.lm.consolidate_caches``); windowed layers
    allocate only ``window`` prefix slots.
    """
    t = cache_len
    if kind == "attn_local" or (kind == "attn" and cfg.window is not None):
        t = min(cache_len, cfg.window)
    hd = cfg.resolved_head_dim
    hot = cfg.decode_hot_len
    return {
        "k": jnp.zeros((batch, t, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, t, cfg.num_kv_heads, hd), dtype),
        "kv_pos": jnp.full((batch, t), -1, jnp.int32),
        "hk": jnp.zeros((batch, hot, cfg.num_kv_heads, hd), dtype),
        "hv": jnp.zeros((batch, hot, cfg.num_kv_heads, hd), dtype),
        "h_pos": jnp.full((batch, hot), -1, jnp.int32),
    }


def _ring_write(cache_arr, new, idx):
    """cache_arr: (B, T, ...); new: (B, 1, ...); idx: (B,) slot index."""
    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n, (i,) + (0,) * (c.ndim - 1))
    return jax.vmap(upd)(cache_arr, new, idx)


def decode_qkv(cfg, p, x, pos):
    """q, k, v of one token (B, 1, M), rotated to its position. A head
    size off the 128-lane tile (Zamba2's 224) makes the TPU compiler lay
    the q/k/v weights out transposed to split the products into heads, a
    copy of each weight at every step; a barrier on the products keeps
    their own layout and moves the split to them."""
    b = x.shape[0]
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(pos[None, :, None], (3, b, 1))
    else:
        positions = pos[:, None]
    q, k_new, v_new = _project_qkv(cfg, p, x,
                                   barrier=cfg.resolved_head_dim % 128 != 0)
    return _rope(cfg, q, positions), _rope(cfg, k_new, positions), v_new


def decode_attend(cfg, p, q, pos, sources, kind):
    """One token's attention over ``(k, v, kv_pos)`` sources, combined
    exactly (flash-decoding split), and the output projection."""
    b = q.shape[0]
    window = cfg.window if kind in ("attn_local",) or (
        kind == "attn" and cfg.window is not None
    ) else None
    kw = dict(window=window, softcap=cfg.attn_logit_softcap,
              scale=cfg.attn_scale)
    # Single-shot (kv_chunk = full length): chunking would reshape the
    # sequence-sharded prefix and force XLA to all-gather it; unreshaped,
    # the q·K / softmax / p·V reductions over the sharded axis lower to
    # tiny per-stat all-reduces instead of cache movement.
    parts = [attention_parts(q, k, v, pos[:, None], kv_pos,
                             kv_chunk=k.shape[1], **kw)
             for k, v, kv_pos in sources]
    out = combine_parts(parts, (b, 1, q.shape[2], q.shape[3]), q.dtype)
    return out.reshape(b, 1, -1) @ p["wo"].astype(out.dtype)


def attn_decode(
    cfg,
    p: Dict[str, jax.Array],
    x: jax.Array,            # (B, 1, M) post-norm
    pos: jax.Array,          # (B,) current token position
    cache: Dict[str, jax.Array],
    kind: str = "attn",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode: write to the hot ring, read prefix + hot ring,
    combine the two partial softmaxes exactly (flash-decoding split)."""
    q, k_new, v_new = decode_qkv(cfg, p, x, pos)
    hot = cache["hk"].shape[1]
    slot = (pos % hot).astype(jnp.int32)
    cache = dict(cache)
    cache["hk"] = _ring_write(cache["hk"], k_new.astype(cache["hk"].dtype), slot)
    cache["hv"] = _ring_write(cache["hv"], v_new.astype(cache["hv"].dtype), slot)
    cache["h_pos"] = _ring_write(cache["h_pos"],
                                 pos[:, None].astype(jnp.int32), slot)
    y = decode_attend(cfg, p, q, pos, [
        (cache["k"], cache["v"], cache["kv_pos"]),
        (cache["hk"], cache["hv"], cache["h_pos"]),
    ], kind)
    return y, cache
