"""Pallas TPU flash-attention kernel (blockwise online softmax, GQA,
sliding window, logit soft-capping).

TPU adaptation (not a CUDA port): the grid is
``(batch, kv_head, q_group, S/bq, T/bk)`` with the KV-block index
innermost — on TPU the grid is executed sequentially minor-to-major, so
the (m, l, acc) running statistics live in VMEM scratch and persist
across the KV sweep for a fixed query tile (the canonical TPU
"revisiting output block" pattern; no atomics / shared-memory tricks as
on GPU). Block shapes are multiples of the (8, 128) VREG tile and sized
so the working set (q tile + kv tile + acc) fits VMEM.

Masking: causal + optional sliding window, applied per (q, kv) tile;
fully-masked tiles short-circuit via ``pl.when`` (the kv sweep still
visits them, but skips the matmuls).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,          # (bq, D), (bk, D), (bk, D)
    o_ref,                        # (bq, D)
    m_ref, l_ref, acc_ref,        # scratch: (bq, 128), (bq, 128), (bq, D)
    *,
    bq: int,
    bk: int,
    seq_q: int,
    seq_kv: int,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
):
    i = pl.program_id(3)          # q block
    j = pl.program_id(4)          # kv block
    nj = pl.num_programs(4)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # aligned ends: query r attends keys ≤ r + (T - S)
    shift = seq_kv - seq_q
    valid = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        valid &= cols <= rows + shift
        if window is not None:
            valid &= cols > rows + shift - window

    # skip tiles with no valid position (beyond causal frontier / window)
    if causal:
        block_live = j * bk <= (i * bq + bq - 1) + shift
        if window is not None:
            block_live &= (j * bk + bk - 1) > (i * bq) + shift - window
    else:
        block_live = jnp.bool_(True)

    @pl.when(block_live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (
            acc_ref[...] * alpha[:, None]
            + jax.lax.dot_general(
                p, v_ref[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_kv", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, K, D)
    v: jax.Array,            # (B, T, K, D)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    t, nk = k.shape[1], k.shape[2]
    if h % nk != 0:
        raise ValueError(f"GQA requires H % K == 0, got {h} % {nk}")
    g = h // nk
    bq = min(block_q, s)
    bk = min(block_kv, t)
    if s % bq or t % bk:
        raise ValueError(f"S/T must divide block sizes: {s}%{bq}, {t}%{bk}")

    grid = (b, nk, g, s // bq, t // bk)

    kernel = functools.partial(
        _flash_kernel,
        bq=bq, bk=bk, seq_q=s, seq_kv=t,
        causal=causal, window=window, softcap=softcap,
        scale=d ** -0.5,
    )
    # Head-major layout: Mosaic requires the last two block dims to be
    # (multiple of 8, multiple of 128) or the full array dims, so a block
    # squeezes the batch and head axes and tiles (sequence, head_dim).
    qh = jnp.swapaxes(q, 1, 2)               # (B, H, S, D)
    kh = jnp.swapaxes(k, 1, 2)               # (B, K, T, D)
    vh = jnp.swapaxes(v, 1, 2)
    q_spec = pl.BlockSpec(
        (None, None, bq, d),
        lambda bb, kk, gg, ii, jj: (bb, kk * g + gg, ii, 0),
    )
    kv_spec = pl.BlockSpec(
        (None, None, bk, d),
        lambda bb, kk, gg, ii, jj: (bb, kk, jj, 0),
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (running max)
            pltpu.VMEM((bq, 128), jnp.float32),   # l (running denom)
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        interpret=interpret,
    )
    return jnp.swapaxes(out(qh, kh, vh), 1, 2)
