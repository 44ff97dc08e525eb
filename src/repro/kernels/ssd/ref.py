"""Pure-jnp oracle for the Mamba-2 SSD (state-space duality) primitive.

Implements the chunked SSD algorithm of Dao & Gu [arXiv:2405.21060]:
within a chunk the recurrence is computed in its "attention-like" dual
form (quadratic in the chunk length), across chunks a linear state
recurrence carries (H, P, N) states. This file is the correctness oracle
for the Pallas kernel in ``kernel.py`` and the default XLA execution
path used by the model (`repro.models.ssm`).

Recurrence (per head h, with Δ = dt):
    s_t = exp(Δ_t A) s_{t-1} + Δ_t B_t x_tᵀ           s ∈ R^{P×N}
    y_t = C_tᵀ s_t + D x_t
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ssd_reference", "ssd_sequential", "ssd_decode_step"]


def _segsum(x: jax.Array) -> jax.Array:
    """Stable "segment sum": out[..., i, j] = sum_{j < k <= i} x[..., k]
    for i >= j, -inf otherwise. x: (..., Q)."""
    q = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_reference(
    x: jax.Array,       # (B, L, H, P)
    dt: jax.Array,      # (B, L, H)           (already softplus'd, > 0)
    a: jax.Array,       # (H,)                (negative decay rates)
    b_mat: jax.Array,   # (B, L, G, N)
    c_mat: jax.Array,   # (B, L, G, N)
    chunk: int = 256,
    d_skip: Optional[jax.Array] = None,   # (H,) skip connection
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
    return_final_state: bool = False,
) -> jax.Array | Tuple[jax.Array, jax.Array]:
    """Chunked SSD forward. G (B/C groups) broadcasts over H (H % G == 0)."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    l_orig = l
    if l % chunk != 0:
        # pad the tail: dt=0 ⇒ decay=1 and no state contribution, so the
        # final state is unaffected; padded outputs are sliced off.
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l = l + pad
    nc = l // chunk
    rep = h // g

    f32 = jnp.float32
    xc = x.reshape(bsz, nc, chunk, h, p).astype(f32)
    dtc = dt.reshape(bsz, nc, chunk, h).astype(f32)
    bc = jnp.repeat(b_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3).astype(f32)
    cc = jnp.repeat(c_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3).astype(f32)

    da = dtc * a.astype(f32)[None, None, None, :]          # (B,nc,Q,H)
    cum = jnp.cumsum(da, axis=2)                           # (B,nc,Q,H)

    # ---- intra-chunk (dual / attention-like form) ----
    seg = _segsum(jnp.moveaxis(da, -1, 2))                 # (B,nc,H,Q,Q)
    decay = jnp.exp(seg)
    scores = jnp.einsum("bzihn,bzjhn->bzhij", cc, bc)      # (B,nc,H,Q,Q)
    dt_j = jnp.moveaxis(dtc, -1, 2)[:, :, :, None, :]      # (B,nc,H,1,Q)
    gate = decay * scores * dt_j
    # gate[..., i, j] = decay_ij * (C_i·B_j) * dt_j
    y_intra = jnp.einsum("bzhij,bzjhp->bzihp", gate, xc)   # (B,nc,Q,H,P)

    # ---- inter-chunk state recurrence ----
    # chunk-local final states: S_z = sum_j exp(cum_last - cum_j) dt_j B_j x_jᵀ
    last = cum[:, :, -1:, :]                               # (B,nc,1,H)
    w = jnp.exp(last - cum) * dtc                          # (B,nc,Q,H)
    s_local = jnp.einsum("bzjh,bzjhp,bzjhn->bzhpn", w, xc, bc)
    chunk_decay = jnp.exp(last[:, :, 0, :])                # (B,nc,H)

    s0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((bsz, h, p, n), f32)
    )

    def scan_body(s_prev, z):
        dec, s_loc = z                                     # (B,H), (B,H,P,N)
        s_new = s_prev * dec[..., None, None] + s_loc
        return s_new, s_prev

    dec_z = jnp.moveaxis(chunk_decay, 1, 0)                # (nc,B,H)
    sl_z = jnp.moveaxis(s_local, 1, 0)                     # (nc,B,H,P,N)
    s_final, s_prevs = jax.lax.scan(scan_body, s0, (dec_z, sl_z))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                  # (B,nc,H,P,N)

    # y_inter_i = exp(cum_i) * C_i · S_prev
    y_inter = jnp.einsum(
        "bzih,bzihn,bzhpn->bzihp", jnp.exp(cum), cc, s_prevs
    )

    y = (y_intra + y_inter).reshape(bsz, l, h, p)
    if d_skip is not None:
        y = y + d_skip.astype(f32)[None, None, :, None] * x.astype(f32)
    y = y[:, :l_orig].astype(x.dtype)
    if return_final_state:
        return y, s_final.astype(jnp.float32)
    return y


def ssd_sequential(
    x, dt, a, b_mat, c_mat, d_skip=None, initial_state=None,
    return_final_state: bool = False,
):
    """Token-by-token recurrence — the independent (slow) oracle used to
    validate the chunked form."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    f32 = jnp.float32
    bb = jnp.repeat(b_mat, rep, axis=2).astype(f32)
    cb = jnp.repeat(c_mat, rep, axis=2).astype(f32)
    s = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((bsz, h, p, n), f32)
    )

    def body(s, z):
        x_t, dt_t, b_t, c_t = z                            # (B,H,P),(B,H),(B,H,N),(B,H,N)
        dec = jnp.exp(dt_t * a.astype(f32))                # (B,H)
        s = s * dec[..., None, None] + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, b_t
        )
        y_t = jnp.einsum("bhn,bhpn->bhp", c_t, s)
        return s, y_t

    xs = (
        jnp.moveaxis(x.astype(f32), 1, 0),
        jnp.moveaxis(dt.astype(f32), 1, 0),
        jnp.moveaxis(bb, 1, 0),
        jnp.moveaxis(cb, 1, 0),
    )
    s_final, ys = jax.lax.scan(body, s, xs)
    y = jnp.moveaxis(ys, 0, 1)
    if d_skip is not None:
        y = y + d_skip.astype(f32)[None, None, :, None] * x.astype(f32)
    y = y.astype(x.dtype)
    if return_final_state:
        return y, s_final
    return y


def ssd_decode_step(
    x_t: jax.Array,     # (B, H, P)
    dt_t: jax.Array,    # (B, H)
    a: jax.Array,       # (H,)
    b_t: jax.Array,     # (B, G, N)
    c_t: jax.Array,     # (B, G, N)
    state: jax.Array,   # (B, H, P, N) fp32
    d_skip: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrence for serving."""
    bsz, h, p = x_t.shape
    g = b_t.shape[1]
    rep = h // g
    f32 = jnp.float32
    bb = jnp.repeat(b_t, rep, axis=1).astype(f32)
    cb = jnp.repeat(c_t, rep, axis=1).astype(f32)
    dec = jnp.exp(dt_t.astype(f32) * a.astype(f32))
    dtx = dt_t.astype(f32)[..., None] * x_t.astype(f32)            # (B,H,P)
    # y = C·s_new, reassociated to read only the old state: the new state
    # is then read by nothing but its write, which can go in place.
    y = dec[..., None] * jnp.einsum("bhn,bhpn->bhp", cb, state) + dtx * (
        jnp.einsum("bhn,bhn->bh", cb, bb)[..., None])
    state = state * dec[..., None, None] + dtx[..., None] * bb[:, :, None, :]
    if d_skip is not None:
        y = y + d_skip.astype(f32)[None, :, None] * x_t.astype(f32)
    return y.astype(x_t.dtype), state
