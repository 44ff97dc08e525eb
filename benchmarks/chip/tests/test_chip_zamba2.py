"""The Zamba2 cell's yardstick: its operation and byte counts against values
worked out by hand from the published widths, the driver's arithmetic on
the steps it counts, the per-layer metric that reads the shared blocks'
device time, the plain reference against the program at smoke size, the
cell run end to end at smoke size, traced and untraced, and the fp8
control failing the cell's limits at a CPU size."""

import dataclasses
import functools
import json

import jax
import numpy as np
import pytest

import counts
import counts_zamba2
import harness
import run
from conftest import CHIP
from reference import zamba2

CELL = "zamba2-7b-l24.decode.b16-p1024-g1024"
SMOKE_TRAFFIC = {"requests": 4, "prompt_len": 32, "gen_len": 40,
                 "warm_steps": 16, "check_requests": 2}


@pytest.fixture(scope="module")
def spec():
    return json.loads((CHIP / "configs" / "zamba2-7b-l24.json").read_text())["model"]


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_shared_block_and_invocation_products(spec):
    # q, k, v: 7168 -> 3 x 32 x 224; o: 7168 -> 3584; gate-up 3584 -> 2 x
    # 14,336; down 14,336 -> 3584
    assert counts_zamba2.shared_matrix_params(spec) == (
        7168 * 3 * 7168 + 7168 * 3584 + 3584 * 28_672 + 14_336 * 3584)
    assert counts_zamba2.shared_matrix_params(spec) == 333_971_456
    # LoRA A 3584 x 128, B 128 x 28,672, projection 3584 x 3584
    assert counts_zamba2.invocation_matrix_params(spec) == 16_973_824
    # mixer: 3584 x (7168 z + 7168 x + 128 B + 128 C + 112 dt) + 7168 x 3584
    assert counts.ssm_matrix_params(spec) == 78_389_248


def test_decode_operations_count_each_invocation(spec):
    n = 24 * 78_389_248 + 3584 * 32_000 + 4 * (333_971_456 + 16_973_824)
    assert counts_zamba2.matrix_params_per_token(spec) == n == 3_399_811_072
    # at position 1600: 2N per token plus 4 invocations x 4 x 32 x 224 per
    # visible key (1601 of them)
    assert counts_zamba2.decode_step_ops(spec, 16, 1600) == 16 * (
        2 * n + 4 * 4 * 32 * 224 * 1601)


def test_decode_bytes_read_each_shared_block_once(spec):
    # Mamba layers (products, conv 4 x 7424, 3 x 112, gated norm 7168, ln
    # 3584), head 3584 x 32,000 and the final norm, in bf16
    trunk = 2 * (24 * 78_430_032 + 3584 * 32_000 + 3584)
    assert counts.weight_bytes(spec) == trunk == 3_994_024_704
    # two shared blocks once (with their norms, 7168 and 3584 wide) and
    # four invocations' own products
    shared = 2 * (2 * (333_971_456 + 10_752) + 4 * 16_973_824)
    assert counts_zamba2.weight_bytes(spec) == trunk + shared == 5_465_744_128
    # per request: f32 state 112 x 64 x 64 and bf16 tails 3 x 7424, read
    # and written in 24 layers, one embedding row, and 4 invocations' keys
    # and values (32 x 224 bf16 each) for 1600 earlier positions and the new
    per_req = 24 * 2 * (112 * 64 * 64 * 4 + 3 * 7424 * 2) + 3584 * 2 \
        + 4 * 2 * 32 * 224 * 2 * 1601
    assert counts_zamba2.decode_step_bytes(spec, 16, 1600) == \
        5_465_744_128 + 16 * per_req == 9_847_202_560


def test_bytes_grow_with_position_through_the_caches_alone(spec):
    grow = counts_zamba2.decode_step_bytes(spec, 16, 2000) - \
        counts_zamba2.decode_step_bytes(spec, 16, 1000)
    assert grow == 16 * 4 * 2 * 32 * 224 * 2 * 1000


# ---------------------------------------------------------------------------
# the driver's arithmetic
# ---------------------------------------------------------------------------
def ctx_with(spec, calls, trace=True, warm=128):
    hooks = harness.Hooks("unused", 3.0)
    hooks.span = [0.0, 1.0] if trace else None
    hooks.span_calls = calls
    return {"hooks": hooks, "trace": trace, "spec": spec,
            "traffic": {"warm_steps": warm, "requests": 16, "prompt_len": 1024}}


def test_counted_steps_are_the_traced_launches(spec):
    drv = harness.load_module("drivers", "decode_zamba2")
    calls = [f"decode_{t}#0" for t in range(128, 131)]
    rec = {"span": {"steps": 3}, "window": {"steps": 896}}
    assert drv.counted_steps(ctx_with(spec, calls), rec) == [128, 129, 130]
    rec = {"span": {"steps": 896}, "window": {"steps": 896}}
    assert drv.counted_steps(ctx_with(spec, [], trace=False), rec) == \
        list(range(128, 1024))


@pytest.mark.parametrize("calls", [
    ["decode_128#0", "decode_130#0"],         # a gap
    ["decode_129#0", "decode_130#0"],         # not from the warm-up's end
])
def test_counted_steps_must_run_on_from_the_warm_up(spec, calls):
    drv = harness.load_module("drivers", "decode_zamba2")
    rec = {"span": {"steps": len(calls)}, "window": {"steps": 896}}
    with pytest.raises(ValueError):
        drv.counted_steps(ctx_with(spec, calls), rec)


def test_the_driver_counts_zamba2_and_keeps_the_reduction(spec, monkeypatch):
    drv = harness.load_module("drivers", "decode_zamba2")
    calls = [f"decode_{t}#0" for t in range(128, 132)]
    ctx = ctx_with(spec, calls)
    monkeypatch.setattr(drv.decode, "run", lambda ctx: {
        "span": {"steps": 4, "ops": 1, "bytes": 1}, "window": {"steps": 896}})
    monkeypatch.setattr(drv.spans, "reduce", lambda d, steps: {"steps": steps})
    rec = drv.run(ctx)
    assert rec["span"]["ops"] == sum(
        counts_zamba2.decode_step_ops(spec, 16, 1024 + t) for t in range(128, 132))
    assert rec["span"]["bytes"] == sum(
        counts_zamba2.decode_step_bytes(spec, 16, 1024 + t) for t in range(128, 132))
    assert rec["spans"] == {"steps": 4}
    ctx["trace"] = False
    rec = {"span": {"steps": 896}, "window": {"steps": 896}}
    monkeypatch.setattr(drv.decode, "run", lambda ctx: rec)
    assert "spans" not in drv.run(ctx)


# ---------------------------------------------------------------------------
# the metric
# ---------------------------------------------------------------------------
def test_shared_block_time_is_attention_and_mlp_scopes():
    metric = harness.load_module("metrics", "shared_block_ms_per_step")
    red = {"scoped": True, "device_ms_per_step": {
        "attn": 3.0, "attn/attn": 0.5, "ffn": 2.0, "ssm": 9.0,
        "ssm/state_update": 4.0, "unscoped": 1.0, "head": 0.2}}
    assert metric.read({"spans": red}) == pytest.approx(5.5)


@pytest.mark.parametrize("rec", [
    {},                                                   # untraced
    {"spans": None},                                      # no device op
    {"spans": {"scoped": False, "device_ms_per_step": {"unscoped": 2.0}}},
    {"spans": {"scoped": True, "device_ms_per_step": {"ssm": 2.0}}},
])
def test_shared_block_time_is_absent_where_nothing_reads_it(rec):
    metric = harness.load_module("metrics", "shared_block_ms_per_step")
    assert metric.read(rec) is None


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------
def test_reference_logits_match_the_program_prefill():
    from repro.configs import smoke_config
    from repro.models import lm

    config = harness.load_json(CHIP / "configs" / "zamba2-7b-l24.json")
    cfg = dataclasses.replace(smoke_config(config["name"]),
                              compute_dtype="float32")
    spec = harness.spec_of(cfg, config)
    params = jax.jit(functools.partial(zamba2.init_params, spec))(
        zamba2.seed_key(2**31 + 7))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                                spec["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog = jax.jit(lambda p, t: lm.prefill(cfg, p, t)[0])(params, tokens)
        ref = jax.jit(functools.partial(zamba2.logits, spec))(params, tokens)
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)[:, -1]
    assert np.max(np.abs(prog - ref)) / np.max(np.abs(ref)) < 1e-4


# ---------------------------------------------------------------------------
# the cell, end to end at smoke size
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_at_smoke_size(trace):
    result, info = run.run_cell(CELL, 2**33 + 5, 2.0, trace, require_chip=False,
                                smoke=True, traffic=SMOKE_TRAFFIC)
    assert result["correct"], result["checks"]
    want = ({"shared_block_ms_per_step.decode", "host_ms_per_step.decode",
             "talp_overhead_share.decode", "device_idle_share.decode"}
            if trace else {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"})
    assert set(result["metrics"]) == want     # no peak on the CPU: no mfu
    assert info["span"]["steps"] > 0 and info["span"]["bytes"] > 0


# ---------------------------------------------------------------------------
# the control
# ---------------------------------------------------------------------------
#: The smoke model widened (d_model 256, four 128-wide heads over the
#: 512-wide input, an 8,192-token vocabulary) at full depth with the
#: published hybrid positions: rounding grows with depth.
WIDE = {"d_model": 256, "num_heads": 4, "num_kv_heads": 4, "head_dim": 128,
        "d_ff": 1024, "ssm_head_dim": 32, "ssm_state": 32, "vocab_size": 8192,
        "adapter_rank": 16, "num_layers": 24,
        "hybrid_layer_ids": (6, 11, 17, 23)}


@pytest.mark.parametrize("seed", [2**31 + 101, 2**31 + 102, 2**31 + 103])
def test_the_fp8_reference_fails_the_cell(seed):
    """The reference with every weight product in fp8, in the program's
    place, comes out as not correct under the cell's limits."""
    import checks
    from repro.configs import smoke_config

    config = harness.load_json(CHIP / "configs" / "zamba2-7b-l24.json")
    spec = harness.spec_of(dataclasses.replace(
        smoke_config(config["name"]), **WIDE), config)
    params = jax.jit(functools.partial(zamba2.init_params, spec))(
        zamba2.seed_key(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, spec["vocab_size"], (2, 64), dtype=np.int32)
    served = rng.integers(0, spec["vocab_size"], (2, 128), dtype=np.int32)
    _, ctrl = checks.served_gaps(zamba2, spec, params, prompts, served,
                                 spec["vocab_size"], control=True)
    numbers = {"token_gap": float(ctrl.max()), "prompt_mismatch": 0.0,
               "nonfinite": 0.0}
    checked, ok = run.judge(numbers, harness.load_json(
        CHIP / "limits" / f"{CELL}.json"))
    assert not ok, checked
