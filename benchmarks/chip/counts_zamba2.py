"""Operations and bytes that a Zamba2 decode step needs, from the widths in
a configuration file, under ``counts.py``'s conventions. ``counts.py``
counts the Mamba-2 trunk (the configuration's ``pattern`` is all SSM), the
head and the per-request SSM state; this adds the shared blocks'
invocations (``hybrid_layer_ids``):

- a shared block's products count in the operations once per invocation
  (a token passes through each), and its weights in the bytes once per
  step (a step need read each shared block once, though it applies it at
  two layers);
- an invocation's own products (the LoRA's A and B, and the projection
  into the Mamba layer's input) count once per invocation in both;
- attention adds its score and value products over the positions each
  query may see, in every invocation; decode reads the keys and values of
  the earlier positions and writes one, per invocation (bf16, no padding).
"""

from __future__ import annotations

import counts


def invocations(spec):
    return len(spec["hybrid_layer_ids"])


def shared_matrix_params(spec):
    """Products of one shared block: q, k, v over [x, embedding] (2 x
    d_model wide), the output projection, the fused gate-up and down."""
    m, f, hd = spec["d_model"], spec["d_ff"], spec["head_dim"]
    q, kv = spec["num_heads"] * hd, spec["num_kv_heads"] * hd
    return 2 * m * (q + 2 * kv) + q * m + 2 * m * f + f * m


def invocation_matrix_params(spec):
    """One invocation's own products: LoRA A (M x r), B (r x 2F) and the
    projection (M x M)."""
    m, f, r = spec["d_model"], spec["d_ff"], spec["adapter_rank"]
    return m * r + r * 2 * f + m * m


def matrix_params_per_token(spec):
    """N of 2·N operations per token: every product a token passes through."""
    return counts.matrix_params_per_token(spec) + invocations(spec) * (
        shared_matrix_params(spec) + invocation_matrix_params(spec))


def attention_ops(spec, visible):
    """Score and value operations of one query over ``visible`` keys, summed
    over the invocations."""
    return invocations(spec) * 4 * spec["num_heads"] * spec["head_dim"] * visible


def decode_step_ops(spec, batch, pos):
    """Operations of one decode step whose new tokens sit at ``pos``."""
    return batch * (2 * matrix_params_per_token(spec)
                    + attention_ops(spec, pos + 1))


def shared_weight_bytes(spec, dtype_bytes=2):
    """The shared blocks' weights (with their two norms, 2 x d_model and
    d_model wide), once each, and every invocation's own."""
    m = spec["d_model"]
    block = shared_matrix_params(spec) + 2 * m + m
    return dtype_bytes * (spec["num_mem_blocks"] * block
                          + invocations(spec) * invocation_matrix_params(spec))


def weight_bytes(spec, dtype_bytes=2):
    """Every weight a decode step reads once, the input embedding aside."""
    return counts.weight_bytes(spec, dtype_bytes) + shared_weight_bytes(
        spec, dtype_bytes)


def kv_bytes(spec, pos):
    """One request's keys and values a step moves at position ``pos``."""
    kv = 2 * spec["num_kv_heads"] * spec["head_dim"] * 2
    return invocations(spec) * kv * (pos + 1)   # read pos earlier, write 1


def decode_step_bytes(spec, batch, pos):
    """The least bytes one decode step must move at position ``pos``."""
    return (counts.decode_step_bytes(spec, batch, pos)
            + shared_weight_bytes(spec) + batch * kv_bytes(spec, pos))
