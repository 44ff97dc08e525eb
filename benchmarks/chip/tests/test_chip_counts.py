"""Operation and byte counts against values worked out by hand from the
published widths."""

import json

import pytest

import counts
from conftest import CHIP


@pytest.fixture(scope="module")
def mamba():
    return json.loads((CHIP / "configs" / "mamba2-130m.json").read_text())["model"]


@pytest.fixture(scope="module")
def zamba():
    """A hybrid pattern for the attention counts: the program's own reading
    of Zamba2-2.7B (one shared attention + MLP block after every five
    Mamba-2 blocks, 80-wide heads), which no cell runs yet."""
    spec = json.loads((CHIP / "configs" / "mamba2-130m.json").read_text())["model"]
    return dict(spec, num_layers=54, d_model=2560, num_heads=32,
                num_kv_heads=32, d_ff=10240, vocab_size=32000,
                pattern=["ssm"] * 5 + ["shared_attn"], ssm_state=64)


def test_mamba2_130m_matrix_parameters_and_train_step(mamba):
    # one mixer: 768 x (1536 z + 1536 x + 128 B + 128 C + 24 dt) + 1536 x 768
    assert counts.ssm_matrix_params(mamba) == 3_753_984
    # 24 mixers and the head over the padded vocabulary (768 x 50,432)
    assert counts.matrix_params_per_token(mamba) == 128_827_392
    # 6 N per token, 8 x 2048 tokens
    assert counts.train_step_ops(mamba, 8, 2048) == 6 * 128_827_392 * 16_384


def test_a_hybrid_counts_the_shared_block_per_application(zamba):
    # mixer: 2560 x (5120 + 5120 + 64 + 64 + 80) + 5120 x 2560
    assert counts.ssm_matrix_params(zamba) == 39_854_080
    # q, k, v, o at 2560 x 2560 and SwiGLU 3 x 2560 x 10240
    assert counts.attn_block_matrix_params(zamba) == 104_857_600
    assert counts.layer_counts(zamba) == (45, 9)
    assert counts.matrix_params_per_token(zamba) == (
        45 * 39_854_080 + 9 * 104_857_600 + 2560 * 32_000)
    # decode at position 1224: 2N per token plus 9 x 4 x 32 x 80 x 1225
    assert counts.decode_step_ops(zamba, 16, 1224) == 16 * (
        2 * 2_819_072_000 + 9 * 4 * 32 * 80 * 1225)


def test_decode_bytes(mamba, zamba):
    # weights once in bf16, embedding aside: 24 mixers (products, conv
    # 4 x 1792, dt_bias/a_log/d_skip 3 x 24, gated norm 1536, ln 768),
    # head 768 x 50,432 and the final norm
    assert counts.weight_bytes(mamba) == 2 * (24 * 3_763_528 + 38_731_776 + 768)
    # per request: f32 state 24 x 64 x 128 and bf16 tails 3 x 1792, read
    # and written in 24 layers, plus one embedding row
    per_req = 24 * 2 * (24 * 64 * 128 * 4 + 3 * 1792 * 2) + 768 * 2
    assert counts.decode_step_bytes(mamba, 64, 600) == 258_114_432 + 64 * per_req
    assert counts.decode_step_bytes(mamba, 64, 600) == 2_707_161_984
    # zamba2 adds 9 x (k, v) x 32 x 80 bf16 per earlier position
    per_req = 45 * 2 * (80 * 64 * 64 * 4 + 3 * 5248 * 2) + 2560 * 2 \
        + 9 * 2 * 32 * 80 * 2 * 1225
    assert counts.weight_bytes(zamba) == 3_963_039_840
    assert counts.decode_step_bytes(zamba, 16, 1224) == 3_963_039_840 + 16 * per_req


def test_shares_cannot_pass_the_peak_on_counts_alone(mamba):
    # the bytes of a step grow with position only through attention
    assert counts.decode_step_bytes(mamba, 64, 600) == \
        counts.decode_step_bytes(mamba, 64, 4000)
