"""The decode step updates its stacked caches in place.

* ``lm.decode_step`` carries the stacked caches through the layer loop
  and writes each layer's row back; it must equal the layer scan that
  takes the caches as ``xs`` and returns them as ``ys`` (the oracle
  below), in logits and in every cache leaf, and its unrolled form must
  equal its looped one. A Zamba2 hybrid unrolls its layers and writes
  each invocation's token into the stacked hot rings; its oracle decodes
  layer by layer, each invocation's row as a cache of its own.
* ``ssd_decode_step`` forms ``y`` from the old state; stepped over a
  sequence it must equal the token-by-token and the chunked references.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels.ssd.ref import (
    ssd_decode_step,
    ssd_reference,
    ssd_sequential,
)
from repro.models import lm
from repro.models.attention import attn_decode
from repro.models.common import rms_norm
from repro.models.mlp import lora_mlp_forward
from repro.models.ssm import ssm_decode

ARCHS = ["mamba2-130m", "zamba2-2.7b", "zamba2-7b-l24", "gemma2-2b"]
BATCH, PROMPT, STEPS = 2, 8, 5


def _scan_decode(cfg, params, token, pos, caches):
    """Oracle: the layer loop as a scan over (parameter rows, cache rows),
    the new caches stacked as its outputs (for a hybrid, the layers one by
    one, each invocation's cache row decoded as a cache of its own)."""
    if cfg.hybrid_layer_ids:
        return _hybrid_decode(cfg, params, token, pos, caches)

    def body(x, xs):
        slot_rows, cache_rows = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"slot{i}"
            bp = slot_rows[key]
            if kind == "ssm":
                y, new_caches[key] = ssm_decode(
                    cfg, bp["ssm"], rms_norm(x, bp["ln"]), cache_rows[key])
                x = x + y
            else:
                y, new_caches[key] = attn_decode(
                    cfg, bp["attn"], rms_norm(x, bp["ln1"]), pos,
                    cache_rows[key], kind)
                x, _ = lm._ffn(cfg, bp, x + y, 0.0)
        return x, new_caches

    x = lm._embed(cfg, params, token)
    x, new_caches = jax.lax.scan(body, x, (params["slots"], caches))
    return lm._head(cfg, params, x)[:, 0], new_caches, pos + 1


def _hybrid_decode(cfg, params, token, pos, caches):
    """Zamba-2's layer equations, layer by layer, new cache rows stacked."""
    def row(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    eps, ids = cfg.norm_eps, list(cfg.hybrid_layer_ids)
    x = e = lm._embed(cfg, params, token)
    states, kvs = [], []
    for i in range(cfg.num_layers):
        h = x
        if i in ids:
            j = ids.index(i)
            sp = row(params["shared"], j % cfg.num_mem_blocks)
            hp = row(params["hybrid"], j)
            a, new = attn_decode(
                cfg, sp["attn"],
                rms_norm(jnp.concatenate([x, e], -1), sp["ln1"], eps), pos,
                row(caches["hybrid"], j))
            kvs.append(new)
            f = lora_mlp_forward(sp["mlp"], hp["lora_a"], hp["lora_b"],
                                 rms_norm(a, sp["ln2"], eps))
            h = x + f @ hp["proj"].astype(f.dtype)
        lp = row(params["slots"]["slot0"], i)
        y, new = ssm_decode(cfg, lp["ssm"], rms_norm(h, lp["ln"], eps),
                            row(caches["slot0"], i))
        states.append(new)
        x = x + y

    def stack(rows):
        return jax.tree.map(lambda *a: jnp.stack(a), *rows)

    return (lm._head(cfg, params, x)[:, 0],
            {"slot0": stack(states), "hybrid": stack(kvs)}, pos + 1)


def _run(cfg, step, params, prompts, donate):
    """Prefill, then ``STEPS`` greedy decode steps through ``step``; the
    logits of every step and the last caches."""
    logits, caches, pos = jax.jit(
        lambda p, t: lm.prefill(cfg, p, t))(params, prompts)
    caches = lm.grow_caches(cfg, caches, PROMPT + STEPS)
    fn = jax.jit(lambda p, t, q, c: step(cfg, p, t, q, c),
                 donate_argnums=3 if donate else ())
    out = []
    for _ in range(STEPS):
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1)[:, None]
        logits, caches, pos = fn(params, tok.astype(jnp.int32), pos, caches)
        out.append(np.asarray(logits))
    return np.stack(out), jax.tree.map(np.asarray, caches)


def _assert_same(got, want):
    (gl, gc), (wl, wc) = got, want
    np.testing.assert_allclose(gl, wl, rtol=1e-6, atol=1e-6)
    assert jax.tree.structure(gc) == jax.tree.structure(wc)

    def same(path, g, w):
        assert g.dtype == w.dtype, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g.astype(np.float32), w.astype(np.float32), rtol=1e-6, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))

    jax.tree_util.tree_map_with_path(same, gc, wc)


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    cfg = smoke_config(request.param)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PROMPT), 0,
                                 cfg.vocab_size, jnp.int32)
    return cfg, params, prompts, _run(cfg, lm.decode_step, params, prompts,
                                      donate=True)


def test_decode_step_matches_layer_scan(setup):
    cfg, params, prompts, got = setup
    _assert_same(got, _run(cfg, _scan_decode, params, prompts, donate=False))


def test_decode_step_unrolled_matches_loop(setup):
    """In float32 compute: in bfloat16 the compiler fuses an unrolled stack
    differently from a looped one, and the roundings differ between them
    whatever the layer loop does."""
    cfg, params, prompts, _ = setup
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    flat = dataclasses.replace(cfg, scan_layers=False)
    _assert_same(_run(flat, lm.decode_step, params, prompts, donate=True),
                 _run(cfg, lm.decode_step, params, prompts, donate=True))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("skip", [False, True])
def test_ssd_decode_step_matches_references(groups, skip):
    """16 single-token steps equal the token-by-token recurrence and the
    chunked whole-sequence form, in every output and the final state."""
    ks = jax.random.split(jax.random.PRNGKey(groups + 2 * skip), 7)
    b, l, h, p, n = 2, 16, 4, 8, 16
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, l, groups, n))
    cm = jax.random.normal(ks[4], (b, l, groups, n))
    d = jax.random.normal(ks[5], (h,)) if skip else None
    s0 = jax.random.normal(ks[6], (b, h, p, n))

    s, ys = s0, []
    for t in range(l):
        y_t, s = ssd_decode_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], s,
                                 d_skip=d)
        ys.append(y_t)
    y = jnp.stack(ys, axis=1)

    refs = {
        "sequential": ssd_sequential(x, dt, a, bm, cm, d_skip=d,
                                     initial_state=s0,
                                     return_final_state=True),
        "chunked": ssd_reference(x, dt, a, bm, cm, chunk=8, d_skip=d,
                                 initial_state=s0, return_final_state=True),
    }
    for name, (y_ref, s_ref) in refs.items():
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
