"""zamba2-2.7b — Mamba-2 trunk with two alternating shared attention blocks.

[hybrid] 54L d_model=2560, 32 heads of 160 (MHA) over [x, embedding]
(5120 wide), FFN 10240 GELU, Mamba-2 with 80 heads of 64, state 64,
vocab 32000 [arXiv:2411.15242; hf Zyphra/Zamba2-2.7B].

The same mechanism as ``zamba2-7b`` (see there). No file here holds the
2.7B's published config; assumed from the 7B's: the hybrid positions by
the 7B's rule (6, 11, 17, 23, ..., 53), 2 B/C groups,
RoPE (theta 10000) in the shared attention, 2 shared blocks, LoRA rank 128
on their MLP, scale (160/2)^-1/2 and RMSNorm eps 1e-5. Departures as the
7B's: split projections, no convolution bias, an untied head.
"""

from .base import ModelConfig, register_config


@register_config("zamba2-2.7b")
def zamba2_2_7b() -> ModelConfig:
    return ModelConfig(
        name="zamba2-2.7b",
        family="hybrid",
        source="arXiv:2411.15242",
        num_layers=54,
        d_model=2560,
        num_heads=32,
        num_kv_heads=32,       # MHA in the shared blocks
        head_dim=160,          # 2 x 2560 / 32
        d_ff=10240,
        vocab_size=32000,
        pattern=("ssm",),
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53),
        num_mem_blocks=2,
        adapter_rank=128,
        norm_eps=1e-5,
        rope_theta=10000.0,
        attn_kv_chunk=256,     # prefill scores (B, S, 32, 256) in f32
        ssm_state=64,
        ssm_head_dim=64,
        ssm_expand=2,          # d_inner = 5120, 80 SSD heads
        ssm_groups=2,
        long_context_ok=True,  # SSM + a few attn blocks → long_500k runs
    )
