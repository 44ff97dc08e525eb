"""The control, the plain reference put in the program's place with every
weight product rounded to fp8, must come out as not correct under the
decoding cell's limits, through the harness's own comparison
(``run.judge``), on three seeds, at a size a test run holds: the smoke
model widened to d_model 256 and an 8,192-token vocabulary, at full depth
(rounding grows with depth). The training cell has no such test yet: its
compared numbers do not separate the control (PERF.md, section 7)."""

import dataclasses
import functools

import jax
import numpy as np
import pytest

import checks
import harness
import run
from conftest import CHIP
from reference import mamba2

SEEDS = [2**31 + 101, 2**31 + 102, 2**31 + 103]
WIDE = {"d_model": 256, "ssm_head_dim": 32, "ssm_state": 32, "vocab_size": 8192}
DECODE = "mamba2-130m.decode.b256-p512-g1536"


def wide_spec():
    from repro.configs import smoke_config

    config = harness.load_json(CHIP / "configs" / "mamba2-130m.json")
    cfg = dataclasses.replace(smoke_config("mamba2-130m"), **WIDE,
                              num_layers=24)
    return harness.spec_of(cfg, config)


def correct(numbers, cell):
    return run.judge(numbers, harness.load_json(CHIP / "limits" / f"{cell}.json"))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_reference_fails_the_decoding_cell(seed):
    spec = wide_spec()
    params = jax.jit(functools.partial(mamba2.init_params, spec))(
        mamba2.seed_key(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, spec["vocab_size"], (2, 64), dtype=np.int32)
    served = rng.integers(0, spec["vocab_size"], (2, 128), dtype=np.int32)
    _, ctrl = checks.served_gaps(mamba2, spec, params, prompts, served,
                                 spec["vocab_size"], control=True)
    numbers = {"token_gap": float(ctrl.max()), "prompt_mismatch": 0.0,
               "nonfinite": 0.0}
    checked, ok = correct(numbers, DECODE)
    assert not ok, checked
