"""A run with the timed path broken underneath must come out as not
correct. Each test skips the harness's look for a chip and drives the rest
of a run at smoke size, with one fault planted in the program's step:
a step that returns its state unchanged, half of the batch left out (the
mean taken over the rest), a token altered where it is produced. The
cells run on one chip, so there is no exchange between chips to leave
out. A sound run at the same size comes out as correct."""

import time

import jax
import jax.numpy as jnp
import pytest

import harness
import run
from conftest import SMOKE_TRAFFIC

DECODES = ["mamba2-130m.decode.b256-p512-g1536"]
SEED = 2**31 + 11


def run_smoke(cell):
    result, info = run.run_cell(cell, SEED, 0.2, 0, require_chip=False,
                                smoke=True, traffic=SMOKE_TRAFFIC[cell],
                                t_start=time.perf_counter())
    return result


def train_fault(kind):
    from repro.launch import train as train_mod

    make = train_mod.make_train_step

    def make_faulty(cfg, opt_cfg):
        step = make(cfg, opt_cfg)

        def faulty(state, batch):
            if kind == "half_batch":
                half = batch["labels"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()})
            _, metrics = step(state, batch)
            return state, metrics          # state returned unchanged

        return faulty

    return harness.replaced(train_mod, make_train_step=make_faulty)


def decode_fault(kind):
    from repro.launch import serve as serve_mod

    make = serve_mod.make_serve_step

    def make_faulty(cfg):
        step = make(cfg)

        def faulty(params, token, pos, caches):
            logits, new, pos1 = step(params, token, pos, caches)
            if kind == "state_unchanged":
                return logits, caches, pos1
            if kind == "half_batch":
                half = logits.shape[0] // 2
                return jnp.concatenate([logits[:half]] * 2), new, pos1
            # the token produced here becomes the least likely one
            worst = jnp.argmin(logits[:, :cfg.vocab_size], -1)
            return logits.at[jnp.arange(logits.shape[0]), worst].set(1e4), new, pos1

        return faulty

    return harness.replaced(serve_mod, make_serve_step=make_faulty)


@pytest.mark.parametrize("cell", DECODES)
def test_a_sound_run_is_correct(cell):
    assert run_smoke(cell)["correct"]


def test_a_sound_training_run_is_correct(training_cell):
    assert run_smoke(training_cell)["correct"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(kind, training_cell):
    with train_fault(kind):
        result = run_smoke(training_cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "token_altered"])
@pytest.mark.parametrize("cell", DECODES)
def test_a_broken_decode_step_is_not_correct(cell, kind):
    with decode_fault(kind):
        result = run_smoke(cell)
    assert not result["correct"], result["checks"]
    jax.clear_caches()
