"""Pallas TPU kernel for the Mamba-2 SSD primitive (chunked scan).

TPU adaptation of the SSD algorithm [arXiv:2405.21060]: the GPU
implementation leans on warp-level parallel scans; on TPU we instead
exploit the sequential minor-to-major grid order — the grid is
``(batch, head, L/Q)`` with the chunk index innermost, and the running
(P, N) state lives in VMEM scratch, carried across chunk iterations for
a fixed (batch, head). Within a chunk the dual quadratic form runs on
the MXU ((Q, N)·(N, Q) and (Q, Q)·(Q, P) matmuls); across chunks the
state update is a rank-Q outer-product accumulation — exactly the
structure the systolic array wants, no warp shuffles required.

B/C stay per group: the block index map sends head ``h`` to group
``h // (H / G)``, so the kernel sees per-head (Q, N) tiles without a
broadcast copy.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _ssd_kernel(
    x_ref,      # (Q, P)
    dtc_ref,    # (Q, 1)   dt as a column
    dtr_ref,    # (1, Q)   dt as a row
    b_ref,      # (Q, N)
    c_ref,      # (Q, N)
    a_ref,      # (H,) SMEM  per-head decay rate
    d_ref,      # (H,) SMEM  skip coefficient
    y_ref,      # (Q, P)
    state_ref,  # scratch (P, N) f32
    *,
    q_chunk: int,
):
    hi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)           # (Q, P)
    a = a_ref[hi]
    dt_col = dtc_ref[...].astype(jnp.float32)    # (Q, 1)
    dt_row = dtr_ref[...].astype(jnp.float32)    # (1, Q)
    bmat = b_ref[...].astype(jnp.float32)        # (Q, N)
    cmat = c_ref[...].astype(jnp.float32)        # (Q, N)

    # Inclusive prefix sums of dt·a as masked reductions (Mosaic has no
    # cumsum): cum_col[i] = Σ_{k≤i} da_k along lanes, cum_row the same
    # along sublanes, so no vector transpose is needed.
    rows = jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 1)
    lower = rows >= cols
    da_row = dt_row * a
    da_col = dt_col * a
    cum_col = jnp.sum(jnp.where(lower, da_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(rows <= cols, da_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(da_row, axis=1, keepdims=True)           # (1, 1)

    # ---- intra-chunk dual form ----
    seg = jnp.where(lower, cum_col - cum_row, -jnp.inf)      # cum_i - cum_j
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (Q, Q) = C_i · B_j
    gate = jnp.exp(seg) * scores * dt_row
    y = jax.lax.dot_general(
        gate, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (Q, P)

    # ---- inter-chunk: contribution of the carried state ----
    # y_inter_i = exp(cum_i) * C_i · S_prevᵀ  → (Q,N)·(N,P)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        cmat, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # ---- state update: S = S·exp(cum_last) + Σ_j w_j x_j B_jᵀ ----
    w = jnp.exp(total - cum_col) * dt_col        # (Q, 1)
    outer = jax.lax.dot_general(
        x * w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(total) + outer

    # ---- skip connection + write ----
    y = y + d_ref[hi] * x
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_pallas(
    x: jax.Array,       # (B, L, H, P)
    dt: jax.Array,      # (B, L, H)
    a: jax.Array,       # (H,)
    b_mat: jax.Array,   # (B, L, G, N)
    c_mat: jax.Array,   # (B, L, G, N)
    chunk: int = 128,
    d_skip: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    if l % chunk != 0:
        raise ValueError(f"L {l} must divide chunk {chunk}")
    rep = h // g
    # Head-major layout: the last two block dims must be (multiple of 8,
    # multiple of 128) or the full array dims, so every block tiles
    # (chunk, feature) with batch and head squeezed. B/C stay per group;
    # the index map picks group hi // rep for head hi.
    xh = jnp.swapaxes(x, 1, 2)                   # (B, H, L, P)
    dth = jnp.swapaxes(dt, 1, 2)                 # (B, H, L)
    bh = jnp.swapaxes(b_mat, 1, 2)               # (B, G, L, N)
    ch = jnp.swapaxes(c_mat, 1, 2)
    a1 = a.astype(jnp.float32)
    d1 = (d_skip if d_skip is not None else jnp.zeros((h,))).astype(jnp.float32)

    def head_block(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda bi, hi, ci: (bi, hi, ci, 0))

    def group_block(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda bi, hi, ci: (bi, hi // rep, ci, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, q_chunk=chunk),
        grid=(bsz, h, l // chunk),
        in_specs=[
            head_block(p),                                          # x
            head_block(1),                                          # dt col
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),       # dt row
            group_block(n),                                         # B
            group_block(n),                                         # C
            smem,                                                   # a
            smem,                                                   # d_skip
        ],
        out_specs=head_block(p),
        out_shape=jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )
    y = out(xh, dth[..., None], dth[:, :, None, :], bh, ch, a1, d1)
    return jnp.swapaxes(y, 1, 2)
