"""One-pass SSD decode step on a layer's row of the stacked state.

Serving keeps every SSM layer's f32 state in one stack ``(L, B, H, P, N)``
that the decode step updates in place. Written in jnp, the step is two
passes over the layer's row: XLA reduces ``C·s_old`` for ``y`` in one
fusion and forms ``s_new = dec·s_old + (dt·x) ⊗ B`` in another that writes
it into the stack, so the state is read twice. The Pallas kernel here
reads each ``(P, N)`` tile of row ``layer`` once, forms ``s_new`` in VMEM,
writes it back to the same place (the stack is aliased in and out; other
rows are never touched) and forms ``y = C·s_new`` from the tile it holds.

The row streams through VMEM in chunks of whole requests, by DMAs the
kernel issues itself: ``SLOTS`` chunk buffers, the reads of the next
``READS_AHEAD`` chunks in flight while one chunk is computed, and the
writes of the chunks before it draining behind. A chunk's new state is
formed in its slot and written back from there. (A BlockSpec pipeline
keeps one read and one write in flight, and moves the state more slowly.)
The grid runs over the chunks in order; the layer index is a
scalar-prefetch argument. Per head the kernel needs ``dt·x`` as a column
along the tile's sublanes, and it forms ``y`` on the MXU at full f32
precision, one column per head: both travel transposed, ``(B, P, H)``, in
small per-chunk blocks, and the wrapper turns them. (``y`` by a lane
reduction per head costs a twelfth more time.) The decay is a scalar per
(request, head), read from SMEM. B and C stay per group, head ``h``
reading group ``h // (H / G)``. Everything is float32, as in
``ssd_decode_step``.

``ssd_decode_layer`` chooses by the platform the step is lowered for: the
kernel on TPU, and ``ssd_decode_step`` on the sliced row elsewhere. The
kernel runs where ``N`` fills the 128 lanes: at a narrower state (Zamba2's
64) the chip's compiler keeps the stack with the heads minor, and the
kernel's row-major tiles would cost a copy of the whole stack each way.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import ssd_decode_step

__all__ = ["ssd_decode_update", "ssd_decode_layer"]

#: Bytes of state per chunk: large enough that each DMA is long against
#: its start-up, small enough that ``SLOTS`` chunks fit in VMEM.
CHUNK_BYTES = 4 * 2**20
#: Chunk buffers, and how many of them are being read ahead of the chunk
#: being computed; the others hold writes still draining.
SLOTS = 4
READS_AHEAD = 2
#: The width of a vector register, in f32 lanes.
LANES = 128


def _kernel(layer_ref, dec_ref, xt_ref, b_ref, c_ref, s_hbm, yt_ref, s_out,
            buf, read_sem, write_sem, *, heads: int, groups: int, rows: int,
            chunks: int):
    rep = heads // groups
    p, n = buf.shape[-2:]
    c = pl.program_id(0)
    layer = layer_ref[0]

    def read(k):
        return pltpu.make_async_copy(s_hbm.at[layer, pl.ds(k * rows, rows)],
                                     buf.at[k % SLOTS],
                                     read_sem.at[k % SLOTS])

    def write(k):
        return pltpu.make_async_copy(buf.at[k % SLOTS],
                                     s_out.at[layer, pl.ds(k * rows, rows)],
                                     write_sem.at[k % SLOTS])

    @pl.when(c == 0)
    def _():
        for k in range(min(READS_AHEAD, chunks)):
            read(k).start()

    # the slot of chunk c + READS_AHEAD last held chunk c + READS_AHEAD -
    # SLOTS, whose write must land before the slot is read into again
    ahead = c + READS_AHEAD

    @pl.when(ahead < chunks)
    def _():
        @pl.when(ahead >= SLOTS)
        def _():
            write(ahead - SLOTS).wait()

        read(ahead).start()

    read(c).wait()
    s_ref = buf.at[c % SLOTS]
    first = c * rows

    def request(r, carry):
        xt = xt_ref[r]                            # (P, H): dt·x, by column
        bm = b_ref[r]                             # (G, N)
        ct = c_ref[r].T                           # (N, G)
        for h in range(heads):
            g = h // rep
            s_ref[r, h] = (s_ref[r, h] * dec_ref[(first + r) * heads + h]
                           + xt[:, h:h + 1] * bm[g:g + 1, :])    # (P, N)
        # y on the MXU: a group's new tiles as one (rep·P, N) matrix times
        # its C column repeated over 128 lanes, so every lane of a head's
        # (P, 128) rows holds that head's y; head h keeps lane h % 128.
        ys = []
        for g in range(groups):
            s = s_ref[r, g * rep:(g + 1) * rep].reshape(rep * p, n)
            prod = jnp.dot(s, jnp.broadcast_to(ct[:, g:g + 1], (n, LANES)),
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
            ys += [prod[k * p:(k + 1) * p] for k in range(rep)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (p, LANES), 1)
        for h0 in range(0, heads, LANES):
            width = min(LANES, heads - h0)
            acc = ys[h0]
            for k in range(1, width):
                acc = jnp.where(lane == k, ys[h0 + k], acc)
            yt_ref[r, :, h0:h0 + width] = acc[:, :width]
        return carry

    jax.lax.fori_loop(0, rows, request, 0)
    write(c).start()

    # writes of the last SLOTS chunks were never waited for above
    @pl.when(c == chunks - 1)
    def _():
        for k in range(max(0, chunks - SLOTS), chunks):
            write(k).wait()


def _rows_per_chunk(batch: int, row_bytes: int) -> int:
    """The most requests per chunk within ``CHUNK_BYTES`` that divide the
    batch (at least one)."""
    most = max(1, CHUNK_BYTES // row_bytes)
    return max(r for r in range(1, min(batch, most) + 1) if batch % r == 0)


def ssd_decode_update(
    x_t: jax.Array,      # (B, H, P)
    dt_t: jax.Array,     # (B, H)
    a: jax.Array,        # (H,)
    b_t: jax.Array,      # (B, G, N)
    c_t: jax.Array,      # (B, G, N)
    states: jax.Array,   # (L, B, H, P, N) f32
    layer: jax.Array,    # () int32
    d_skip: Optional[jax.Array] = None,   # (H,)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The decode step of layer ``layer``, in place in ``states``: ``y``
    (B, H, P) in ``x_t``'s dtype and the stack with that row updated."""
    _, bsz, h, p, n = states.shape
    g = b_t.shape[1]
    f32 = jnp.float32
    dt = dt_t.astype(f32)
    dec = jnp.exp(dt * a.astype(f32)).reshape(-1)                 # (B*H,)
    xt = jnp.swapaxes(dt[..., None] * x_t.astype(f32), 1, 2)     # (B,P,H)
    rows = _rows_per_chunk(bsz, h * p * n * 4)
    chunks = bsz // rows

    def by_request(*shape):
        return pl.BlockSpec((rows,) + shape,
                            lambda k, i: (k,) + (0,) * len(shape))

    yt, states = pl.pallas_call(
        functools.partial(_kernel, heads=h, groups=g, rows=rows,
                          chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),    # decay
                by_request(p, h),                         # dt·x, transposed
                by_request(g, n),                         # B
                by_request(g, n),                         # C
                pl.BlockSpec(memory_space=pl.ANY),        # the stack
            ],
            out_specs=[by_request(p, h), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((SLOTS, rows, h, p, n), f32),
                            pltpu.SemaphoreType.DMA((SLOTS,)),
                            pltpu.SemaphoreType.DMA((SLOTS,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, p, h), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SLOTS * rows * h * p * n * 4 + 16 * 2**20),
        interpret=interpret,
        name="ssd_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), dec, xt,
      b_t.astype(f32), c_t.astype(f32), states)
    y = jnp.swapaxes(yt, 1, 2)
    if d_skip is not None:
        y = y + d_skip.astype(f32)[None, :, None] * x_t.astype(f32)
    return y.astype(x_t.dtype), states


def _on_row(x_t, dt_t, a, b_t, c_t, states, layer, d_skip):
    """``ssd_decode_step`` on row ``layer``, written back into the stack."""
    row = jax.lax.dynamic_index_in_dim(states, layer, 0, keepdims=False)
    y, row = ssd_decode_step(x_t, dt_t, a, b_t, c_t, row, d_skip=d_skip)
    return y, jax.lax.dynamic_update_index_in_dim(states, row, layer, 0)


def ssd_decode_layer(x_t, dt_t, a, b_t, c_t, states, layer, d_skip=None):
    """One token's SSD step for layer ``layer`` of the stacked ``states``:
    the Pallas kernel where the step is lowered for TPU and the state
    fills the lanes, ``ssd_decode_step`` on the row elsewhere."""
    args = (x_t, dt_t, a, b_t, c_t, states, layer, d_skip)
    if states.shape[-1] % LANES:
        return _on_row(*args)
    return jax.lax.platform_dependent(
        *args, tpu=ssd_decode_update, default=_on_row)
