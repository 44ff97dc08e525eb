"""zamba2-7b — Mamba-2 trunk with two alternating shared attention blocks.

[hybrid] 81L d_model=3584, 32 heads of 224 (MHA) over [x, embedding]
(7168 wide), FFN 14336 GELU, 2 shared blocks, LoRA rank 128 on their
MLP, Mamba-2 with 112 heads of 64, 2 groups, state 64 [arXiv:2411.15242;
https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json].

Before each Mamba layer in ``hybrid_layer_ids`` the j-th invocation runs
shared block j mod 2 over RMSNorm([x, embedding]) (attention with RoPE,
scale (224/2)^-1/2, its own KV cache), then its MLP with a per-invocation
LoRA, then a per-invocation 3584 x 3584 projection whose output is added
to that Mamba layer's input (not to its residual).

Departures, followed by the benchmark's reference: separate z/x/B/C/dt
projections (one fused in_proj in the published model); no convolution
bias (the published model has ``use_conv_bias``); an untied output head
(the published config does not state ``tie_word_embeddings``).

``zamba2-7b-l24`` is published layers 0-23 at the same widths (hybrid
invocations at 6, 11, 17 and 23: blocks A, B, A, B), one pipeline stage
of the 81-layer model on one v5e chip, with the embedding and the head.
"""

import dataclasses

from .base import ModelConfig, register_config

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


@register_config("zamba2-7b")
def zamba2_7b() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b",
        family="hybrid",
        source="arXiv:2411.15242",
        num_layers=81,
        d_model=3584,
        num_heads=32,
        num_kv_heads=32,
        head_dim=224,          # attention_head_dim: 2 x 3584 / 32
        d_ff=14336,
        vocab_size=32000,
        pattern=("ssm",),
        hybrid_layer_ids=HYBRID_LAYER_IDS,
        num_mem_blocks=2,
        adapter_rank=128,
        norm_eps=1e-5,
        rope_theta=10000.0,
        attn_kv_chunk=256,     # prefill scores (B, S, 32, 256) in f32
        ssm_state=64,
        ssm_head_dim=64,
        ssm_expand=2,          # d_inner = 7168, 112 SSD heads
        ssm_groups=2,
        ssm_chunk=256,
        ssm_conv=4,
    )


@register_config("zamba2-7b-l24")
def zamba2_7b_l24() -> ModelConfig:
    cfg = zamba2_7b()
    return dataclasses.replace(
        cfg, name="zamba2-7b-l24", num_layers=24,
        hybrid_layer_ids=tuple(i for i in cfg.hybrid_layer_ids if i < 24))
