"""The plain reference against the program at smoke size, with the
program in float32 at the highest matmul precision so that only the
algorithms differ (sequential recurrence against chunked SSD)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import harness
from conftest import CHIP
from reference import mamba2


def setup(name):
    from repro.configs import smoke_config

    config = harness.load_json(CHIP / "configs" / f"{name}.json")
    cfg = dataclasses.replace(smoke_config(name), compute_dtype="float32")
    spec = harness.spec_of(cfg, config)
    model = mamba2
    params = jax.jit(functools.partial(model.init_params, spec))(
        model.seed_key(2**31 + 7))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                                spec["vocab_size"])
    return cfg, spec, model, params, tokens


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_reference_logits_match_the_program_prefill():
    from repro.models import lm

    cfg, spec, model, params, tokens = setup("mamba2-130m")
    with jax.default_matmul_precision("highest"):
        prog = jax.jit(lambda p, t: lm.prefill(cfg, p, t)[0])(params, tokens)
        ref = jax.jit(functools.partial(model.logits, spec))(params, tokens)
    assert rel(prog, ref[:, -1]) < 1e-4


def test_reference_loss_and_gradient_match_the_program():
    from repro.models import lm

    cfg, spec, model, params, tokens = setup("mamba2-130m")
    batch = {"inputs": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    with jax.default_matmul_precision("highest"):
        pl, pg = jax.jit(jax.value_and_grad(
            lambda p: lm.train_loss(cfg, p, batch)[0]))(params)
        rl, rg = jax.jit(jax.value_and_grad(
            lambda p: model.loss(spec, p, batch, block=16)))(params)
    assert abs(float(pl) - float(rl)) < 1e-5 * abs(float(rl))
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(rg)):
        assert rel(a, b) < 1e-3


def test_blocked_recurrence_equals_the_plain_one():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (2, 64, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, 64, 4)))
    b = jax.random.normal(k[2], (2, 64, 1, 16))
    c = jax.random.normal(k[3], (2, 64, 1, 16))
    a = -jnp.exp(jax.random.normal(k[4], (4,)))
    d = jnp.ones(4)
    plain = mamba2.ssm_recurrence(x, dt, a, b, c, d)
    blocked = mamba2.ssm_recurrence(x, dt, a, b, c, d, block=16)
    np.testing.assert_allclose(plain, blocked, rtol=1e-5, atol=1e-5)


def test_fp8_rounds_operands_and_passes_gradients_through():
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
    q = mamba2._fp8(x, -1)
    err = jnp.abs(q - x) / jnp.max(jnp.abs(x), -1, keepdims=True)
    assert 0 < float(err.max()) <= 2.0 ** -4
    g = jax.grad(lambda v: jnp.sum(mamba2._fp8(v, -1) * 3.0))(x)
    np.testing.assert_array_equal(g, jnp.full_like(x, 3.0))
