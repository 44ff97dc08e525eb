"""The one-pass SSD decode kernel (``kernels/ssd/decode.py``), run in
interpret mode on the CPU: one step equals ``ssd_decode_step`` on the row
it updates, sixteen steps equal the token-by-token recurrence, and every
other row of the stack is left bit for bit as it was."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import decode
from repro.kernels.ssd.decode import (
    _rows_per_chunk,
    ssd_decode_layer,
    ssd_decode_update,
)
from repro.kernels.ssd.ref import ssd_decode_step, ssd_sequential

TOL = dict(rtol=1e-5, atol=1e-5)
ROWS, B, H, P, L = 3, 2, 4, 8, 16

kernel = jax.jit(functools.partial(ssd_decode_update, interpret=True))


def _inputs(n, g, skip, seed=0, B=B, H=H):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    bm = jax.random.normal(ks[3], (B, L, g, n))
    cm = jax.random.normal(ks[4], (B, L, g, n))
    d = jax.random.normal(ks[5], (H,)) if skip else None
    states = jax.random.normal(ks[6], (ROWS, B, H, P, n))
    return x, dt, a, bm, cm, d, states


SHAPES = pytest.mark.parametrize("n,g", [(128, 1), (64, 2)])
SKIP = pytest.mark.parametrize("skip", [False, True])
LAYER = pytest.mark.parametrize("layer", [0, 2])


def _others_unchanged(new, old, layer):
    for r in range(ROWS):
        if r != layer:
            np.testing.assert_array_equal(np.asarray(new[r]),
                                          np.asarray(old[r]))


@SHAPES
@SKIP
@LAYER
def test_one_step_matches_the_jnp_step(n, g, skip, layer):
    x, dt, a, bm, cm, d, states = _inputs(n, g, skip)
    y, new = kernel(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], states,
                    jnp.int32(layer), d)
    y_ref, s_ref = ssd_decode_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                   states[layer], d_skip=d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(new[layer]), np.asarray(s_ref),
                               **TOL)
    _others_unchanged(new, states, layer)


@SHAPES
@SKIP
@LAYER
def test_sixteen_steps_match_the_sequential_recurrence(n, g, skip, layer):
    x, dt, a, bm, cm, d, states = _inputs(n, g, skip, seed=1)
    new, ys = states, []
    for t in range(L):
        y, new = kernel(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], new,
                        jnp.int32(layer), d)
        ys.append(y)
    y_ref, s_ref = ssd_sequential(x, dt, a, bm, cm, d_skip=d,
                                  initial_state=states[layer],
                                  return_final_state=True)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, axis=1)),
                               np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(new[layer]), np.asarray(s_ref),
                               **TOL)
    _others_unchanged(new, states, layer)


# chunks of one or two requests: fewer chunks than slots, as many, and
# more, so that slots are reused while earlier writes are still draining
@pytest.mark.parametrize("batch,rows", [(3, 1), (8, 2), (7, 1), (12, 2)])
def test_chunks_stream_through_the_slots(batch, rows, monkeypatch):
    x, dt, a, bm, cm, d, states = _inputs(128, 1, True, seed=2, B=batch)
    monkeypatch.setattr(decode, "CHUNK_BYTES", rows * H * P * 128 * 4)
    y, new = jax.jit(functools.partial(ssd_decode_update, interpret=True))(
        x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], states, jnp.int32(1), d)
    y_ref, s_ref = ssd_decode_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                   states[1], d_skip=d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(new[1]), np.asarray(s_ref), **TOL)
    _others_unchanged(new, states, 1)


def test_heads_past_one_register_of_lanes():
    """``y`` is gathered 128 heads to a register: 136 heads fill one and
    start a second."""
    x, dt, a, bm, cm, d, states = _inputs(128, 2, True, seed=3, B=1, H=136)
    y, new = kernel(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], states,
                    jnp.int32(2), d)
    y_ref, s_ref = ssd_decode_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                   states[2], d_skip=d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(new[2]), np.asarray(s_ref), **TOL)
    _others_unchanged(new, states, 2)


def test_the_cpu_lowering_takes_the_jnp_step():
    """Off the TPU the step is ``ssd_decode_step`` on the row: no kernel
    in the lowered module."""
    x, dt, a, bm, cm, d, states = _inputs(128, 1, True)
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], states, jnp.int32(1), d)
    assert "tpu_custom_call" not in jax.jit(ssd_decode_layer).lower(
        *args).as_text()
    y, new = jax.jit(ssd_decode_layer)(*args)
    y_ref, s_ref = ssd_decode_step(*args[:5], states[1], d_skip=d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(new[1]), np.asarray(s_ref), **TOL)
    _others_unchanged(new, states, 1)


@pytest.mark.parametrize("batch,row_bytes,rows", [
    (256, 24 * 64 * 128 * 4, 4),     # mamba2-130m: 3.1 MB chunks
    (16, 112 * 64 * 64 * 4, 2),      # zamba2-7b: 3.7 MB chunks
    (6, 2**20, 3),                   # the most rows that divide the batch
    (3, 8 * 2**20, 1),               # one request over the budget
])
def test_chunks_follow_from_the_shape(batch, row_bytes, rows):
    assert _rows_per_chunk(batch, row_bytes) == rows
