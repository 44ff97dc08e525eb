"""Zamba2 on the program's normal path, against the benchmark's plain float32
reference (``benchmarks/chip/reference/zamba2.py``), at smoke size on the
CPU with the program in float32 under the highest matmul precision, so that
only the algorithms differ (chunked against token-by-token SSD, chunked
against blocked softmax, cached decode against one forward pass):

* prefill logits, and the logits of 24 decode steps past a hot-ring flush,
  equal the reference's;
* perturbing a shared block, or one invocation's own weights, changes the
  output only from that invocation's layer on;
* the pure-SSM decode step is the program it was before the hybrid path
  (its lowered module, byte for byte);
* ``n_params`` counts the initialised tree.
"""

import dataclasses
import functools
import hashlib
import importlib
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.launch.steps import make_serve_step, serve_params_shapes
from repro.models import lm

REFERENCE = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "reference"
#: The widths the reference reads, as the benchmark's configuration states them.
SPEC_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
             "d_ff", "vocab_size", "ssm_state", "ssm_head_dim", "ssm_expand",
             "ssm_conv", "ssm_groups", "hybrid_layer_ids", "num_mem_blocks",
             "adapter_rank", "attn_scale", "norm_eps", "rope_theta")
SEED = 2**31 + 15
#: Largest logit difference over the largest reference logit. Rounding at
#: float32 reads about 1e-6 here; a shared block applied with the other
#: block's weights, or a hot ring that wraps without its flush, reads above
#: 1e-2.
TOL = 1e-4


def reference():
    """``reference/zamba2.py`` as a package module, without putting the
    benchmark's directory on ``sys.path``."""
    if "chip_reference.zamba2" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_reference", REFERENCE / "__init__.py",
            submodule_search_locations=[str(REFERENCE)])
        pkg = importlib.util.module_from_spec(spec)
        sys.modules["chip_reference"] = pkg
        spec.loader.exec_module(pkg)
    return importlib.import_module("chip_reference.zamba2")


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(smoke_config("zamba2-7b-l24"),
                              compute_dtype="float32")
    spec = {k: getattr(cfg, k) for k in SPEC_KEYS}
    ref = reference()
    params = jax.jit(functools.partial(ref.init_params, spec))(
        ref.seed_key(SEED))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0,
                                cfg.vocab_size, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(ref.logits, spec))(params, tokens)
    return cfg, spec, params, tokens, np.asarray(want)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_smoke_config_keeps_two_blocks_serving_several_invocations(setup):
    cfg = setup[0]
    assert cfg.hybrid_layer_ids == (1, 3, 4) and cfg.num_mem_blocks == 2
    assert cfg.ssm_groups == 2 and cfg.num_layers == 6


def test_program_tree_is_the_reference_tree(setup):
    cfg, _, params, _, _ = setup
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, shapes) == \
        jax.tree.map(lambda a: a.shape, params)


def test_prefill_logits_match_the_reference(setup):
    cfg, _, params, tokens, want = setup
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: lm.prefill(cfg, p, t)[0])(params, tokens)
    assert rel(got, want[:, -1]) < TOL


def test_decode_across_a_flush_matches_the_reference(setup):
    """Prefill 16 tokens, then 24 decode steps fed the given tokens, with
    the hot ring (16 slots) flushed where ``serve`` flushes it; every
    step's logits against the reference's at that position."""
    cfg, _, params, tokens, want = setup
    prompt, steps = 16, tokens.shape[1] - 16
    assert steps >= 20 and steps > cfg.decode_hot_len
    with jax.default_matmul_precision("highest"):
        _, caches, pos = jax.jit(lambda p, t: lm.prefill(cfg, p, t))(
            params, tokens[:, :prompt])
        caches = lm.grow_caches(cfg, caches, prompt + steps)
        step = jax.jit(lambda p, t, q, c: lm.decode_step(cfg, p, t, q, c),
                       donate_argnums=3)
        flush = jax.jit(functools.partial(lm.consolidate_caches, cfg),
                        donate_argnums=0)
        for t in range(steps):
            logits, caches, pos = step(params, tokens[:, prompt + t:prompt + t + 1],
                                       pos, caches)
            assert rel(logits, want[:, prompt + t]) < TOL, t
            if (t + 1) % cfg.decode_hot_len == 0:
                caches = flush(caches)
    assert np.all(np.asarray(caches["hybrid"]["h_pos"]) < prompt + steps)


def truncated(cfg, params, depth):
    """The model's first ``depth`` layers, with the invocations among them."""
    ids = tuple(i for i in cfg.hybrid_layer_ids if i < depth)
    sub = dataclasses.replace(cfg, num_layers=depth, hybrid_layer_ids=ids)
    cut = dict(params, slots={"slot0": jax.tree.map(
        lambda a: a[:depth], params["slots"]["slot0"])})
    cut["hybrid"] = jax.tree.map(lambda a: a[:max(len(ids), 1)],
                                 params["hybrid"])
    return sub, cut


def perturbed(params, path, index):
    """``params`` with row ``index`` of every leaf under ``path`` moved."""
    def move(p, a):
        if p[:len(path)] != path:
            return a
        noise = jax.random.normal(jax.random.PRNGKey(7), a.shape[1:], a.dtype)
        return a.at[index].add(0.5 * noise * (jnp.std(a[index]) + 0.1))

    return jax.tree_util.tree_map_with_path(
        lambda p, a: move(tuple(getattr(k, "key", k) for k in p), a), params)


@pytest.mark.parametrize("what, path, index, first_layer", [
    ("shared block B", ("shared",), 1, 3),          # invocation 1, layer 3
    ("A of invocation 2", ("hybrid", "lora_a"), 2, 4),
    ("L of invocation 0", ("hybrid", "proj"), 0, 1),
])
def test_a_perturbation_acts_from_its_invocation_on(setup, what, path, index,
                                                    first_layer):
    cfg, _, params, tokens, _ = setup
    moved = perturbed(params, path, index)
    with jax.default_matmul_precision("highest"):
        for depth in range(1, cfg.num_layers + 1):
            sub, base = truncated(cfg, params, depth)
            _, other = truncated(cfg, moved, depth)
            run = jax.jit(lambda p, t, c=sub: lm.prefill(c, p, t)[0])
            diff = rel(run(other, tokens[:, :16]), run(base, tokens[:, :16]))
            if depth <= first_layer:
                assert diff == 0.0, (what, depth)
            else:
                assert diff > 1e-3, (what, depth)


# sha256 of ``jax.jit(serve_step, donate_argnums=3).lower(...).as_text()``
# for mamba2-130m (jax 0.9.0, lowered for the CPU), with each layer's SSD
# state updated in the stack by ``ssd_decode_layer``: the lowered module is
# what XLA compiles, so an equal text is an equal step, logits and caches
# bit for bit. Zamba2's path must leave it as it is.
PURE_SSM_STEP = {
    "smoke": ("a7f10a428ae82048e9d2300e2454893bd68e06601f71747fcb0674490e19e095", 2),
    "full": ("b3fd29ac7b3392c47476f5cb2f27d49c9d17c37afe12f122658af13a2ba9b81e", 256),
}


@pytest.mark.parametrize("size", sorted(PURE_SSM_STEP))
def test_the_pure_ssm_decode_step_is_unchanged(size):
    digest, batch = PURE_SSM_STEP[size]
    cfg = smoke_config("mamba2-130m") if size == "smoke" else get_config("mamba2-130m")
    caches = jax.eval_shape(lambda: lm.init_decode_caches(cfg, batch, 2048))
    text = jax.jit(make_serve_step(cfg), donate_argnums=3).lower(
        serve_params_shapes(cfg), jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32), caches).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


@pytest.mark.parametrize("arch", ["zamba2-7b", "zamba2-7b-l24", "zamba2-2.7b"])
@pytest.mark.parametrize("smoke", [False, True])
def test_n_params_counts_the_initialised_tree(arch, smoke):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    assert cfg.n_params() == lm.param_count(shapes)


def test_published_layout_of_the_7b():
    full, cut = get_config("zamba2-7b"), get_config("zamba2-7b-l24")
    assert full.num_layers == 81 and len(full.hybrid_layer_ids) == 13
    assert cut.hybrid_layer_ids == (6, 11, 17, 23)
    assert full.attn_in_dim == 7168 and full.num_heads * full.head_dim == 7168
    assert full.ssm_heads == 112 and full.ssm_groups == 2
