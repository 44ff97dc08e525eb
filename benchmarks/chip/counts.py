"""Operations and bytes that the algorithm needs, from the widths in a
configuration file. These are the numerators of the benchmark's
utilisation metrics; they count what the model requires, never what one
implementation happens to move, so a share of a peak built on them cannot
pass 100%.

Conventions:
- a multiply-add is 2 operations;
- matrix parameters are counted once per application: the shared block of
  a hybrid model counts once for every position of the pattern it fills;
- the input embedding is a gather, not a product, and is left out of the
  operations; the output head spans the padded vocabulary, as the
  configuration states;
- attention adds its score and value products over the positions each
  query may see;
- decode bytes are the weights once (bf16), the rows of the embedding that
  are read, the SSM state (float32) and convolution tails read and
  written, and the keys and values of valid positions (no padding).
"""

from __future__ import annotations


def padded_vocab(spec):
    return -(-spec["vocab_size"] // 256) * 256


def _ssm(spec):
    m = spec["d_model"]
    d_in = spec["ssm_expand"] * m
    heads = d_in // spec["ssm_head_dim"]
    gn = spec["ssm_groups"] * spec["ssm_state"]
    return m, d_in, heads, gn


def ssm_matrix_params(spec):
    """Parameters of one Mamba-2 mixer's products: z, x, B, C, dt in, out."""
    m, d_in, heads, gn = _ssm(spec)
    return m * (2 * d_in + 2 * gn + heads) + d_in * m


def head_dim(spec):
    return spec["head_dim"] or spec["d_model"] // spec["num_heads"]


def attn_block_matrix_params(spec):
    """Parameters of one attention + SwiGLU block's products."""
    m, hd = spec["d_model"], head_dim(spec)
    q, kv = spec["num_heads"] * hd, spec["num_kv_heads"] * hd
    return m * q + 2 * m * kv + q * m + 3 * m * spec["d_ff"]


def layer_counts(spec):
    """(SSM applications, attention applications) per token."""
    pattern = spec["pattern"]
    repeats = spec["num_layers"] // len(pattern)
    n_ssm = repeats * sum(k == "ssm" for k in pattern)
    return n_ssm, spec["num_layers"] - n_ssm


def matrix_params_per_token(spec):
    """N of 2·N operations per token: every product a token passes through."""
    n_ssm, n_attn = layer_counts(spec)
    attn = n_attn * attn_block_matrix_params(spec) if n_attn else 0
    return (n_ssm * ssm_matrix_params(spec) + attn
            + spec["d_model"] * padded_vocab(spec))


def attention_ops(spec, visible):
    """Score and value operations of one query over ``visible`` keys, summed
    over the attention applications of one token."""
    _, n_attn = layer_counts(spec)
    if not n_attn:
        return 0
    return n_attn * 4 * spec["num_heads"] * head_dim(spec) * visible


def train_step_ops(spec, batch, seq):
    """Forward and backward operations of one training step (3x forward)."""
    fwd = 2 * matrix_params_per_token(spec) * batch * seq
    fwd += batch * attention_ops(spec, (seq + 1) / 2) * seq
    return 3 * fwd


def decode_step_ops(spec, batch, pos):
    """Operations of one decode step whose new tokens sit at ``pos``."""
    return batch * (2 * matrix_params_per_token(spec)
                    + attention_ops(spec, pos + 1))


def weight_bytes(spec, dtype_bytes=2):
    """Every weight a decode step reads once, the input embedding aside."""
    m = spec["d_model"]
    n_ssm, n_attn = layer_counts(spec)
    _, d_in, heads, gn = _ssm(spec)
    ssm = ssm_matrix_params(spec) + spec["ssm_conv"] * (d_in + 2 * gn) \
        + 3 * heads + d_in + m
    total = n_ssm * ssm + m * padded_vocab(spec) + m
    if n_attn:
        total += attn_block_matrix_params(spec) + 2 * m
    return total * dtype_bytes


def decode_step_bytes(spec, batch, pos):
    """The least bytes one decode step must move at position ``pos``."""
    m = spec["d_model"]
    n_ssm, n_attn = layer_counts(spec)
    _, d_in, heads, gn = _ssm(spec)
    state = heads * spec["ssm_head_dim"] * spec["ssm_state"] * 4
    tails = (spec["ssm_conv"] - 1) * (d_in + 2 * gn) * 2
    per_req = n_ssm * 2 * (state + tails) + m * 2
    if n_attn:
        kv = 2 * spec["num_kv_heads"] * head_dim(spec) * 2
        per_req += n_attn * kv * (pos + 1)   # read pos earlier, write 1
    return weight_bytes(spec) + batch * per_req
