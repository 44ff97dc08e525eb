"""Plain float32 reference of the Zamba2 language model as the benchmark's
configuration files state it (Zyphra, arXiv:2411.15242; HF
``modeling_zamba2.py``).

Nothing here imports the program. The weights come from :func:`init_params`,
the benchmark's own initialisation from the seed, in the tree layout the
program's entry points take, so that the same weights can be handed to both.

With ``e`` the embedding output, carried unchanged to every layer, and
``j`` counting the hybrid layers (``hybrid_layer_ids``):

    Mamba layer i:   x <- x + Mamba_i(RMSNorm(x))
    hybrid layer i (the j-th; shared block b = j mod num_mem_blocks):
        u = RMSNorm_b,1([x, e])                 (2 d_model wide)
        a = Attn_b(u)                           (RoPE; causal; scale attn_scale)
        [G | U] = u2 Wgu_b + (u2 A_j) B_j,  u2 = RMSNorm_b,2(a)
        T = (GELU(G) * U) Wdown_b L_j
        x <- x + Mamba_i(RMSNorm(x + T))        (the residual is x)
    Mamba_i: z, x, B, C, dt projections; causal conv of x, B, C; the SSD
        recurrence; RMSNorm of y * SiLU(z) over each of ssm_groups groups;
        output projection.

Departures from the published Zamba2, shared with the program under test
and stated in the configuration file: separate z/x/B/C/dt projections (one
fused in_proj in the published model), no convolution bias (published:
``use_conv_bias`` true), an untied output head (the published config does
not state ``tie_word_embeddings``), RMSNorm scales stored as ``1 + w``, and
a softmax over the vocabulary padded to a multiple of 256.

The SSM is the token-by-token recurrence of ``reference/mamba2.py``; the
attention runs in blocks of queries (and the MLP and projection with them),
so that no (queries x keys) score matrix of the whole sequence is held.
Every matrix product runs in float32 at the highest precision;
``quant="fp8"`` rounds both operands of every weight product to float8 e4m3
(``mamba2.matmul``): the control that must come out as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import mamba2

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: Queries per block of the attention (and of the MLP after it).
QUERY_BLOCK = 256

matmul = mamba2.matmul
seed_key = mamba2.seed_key
padded_vocab = mamba2.padded_vocab


def rms_norm(x, w, eps):
    """RMSNorm over the last axis with the ``1 + w`` scale."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


# ---------------------------------------------------------------------------
# shapes and initialisation
# ---------------------------------------------------------------------------
def attn_dims(spec):
    return {"in": 2 * spec["d_model"],
            "q": spec["num_heads"] * spec["head_dim"],
            "kv": spec["num_kv_heads"] * spec["head_dim"]}


def _uniform(key, shape, fan_in, scale=1.0):
    return mamba2._uniform(key, shape, scale / math.sqrt(fan_in))


def init_shared(spec, key):
    """One shared block: linear weights uniform in +-1/sqrt(fan_in), norm
    scales 1."""
    m, f = spec["d_model"], spec["d_ff"]
    d = attn_dims(spec)
    ks = jax.random.split(key, 6)
    return {
        "ln1": jnp.zeros((d["in"],), F32),
        "attn": {"wq": _uniform(ks[0], (d["in"], d["q"]), d["in"]),
                 "wk": _uniform(ks[1], (d["in"], d["kv"]), d["in"]),
                 "wv": _uniform(ks[2], (d["in"], d["kv"]), d["in"]),
                 "wo": _uniform(ks[3], (d["q"], m), d["q"])},
        "ln2": jnp.zeros((m,), F32),
        "mlp": {"w_gu": _uniform(ks[4], (m, 2 * f), m),
                "w_down": _uniform(ks[5], (f, m), f)},
    }


def init_invocation(spec, key):
    """One invocation's LoRA (A, B) and projection, uniform in
    +-1/sqrt(fan_in)."""
    m, f, r = spec["d_model"], spec["d_ff"], spec["adapter_rank"]
    ks = jax.random.split(key, 3)
    return {"lora_a": _uniform(ks[0], (m, r), m),
            "lora_b": _uniform(ks[1], (r, 2 * f), r),
            "proj": _uniform(ks[2], (m, m), m)}


def init_params(spec, key, dtype=F32):
    """The benchmark's weights for ``key`` (see :func:`seed_key`), in the
    program's tree layout: ``embed``, ``slots/slot0`` (``ln`` and ``ssm``,
    stacked over the layers), ``shared`` (stacked over the shared blocks),
    ``hybrid`` (stacked over the invocations), ``final_norm`` and
    ``unembed``. Call under ``jax.jit``."""
    k_emb, k_out, k_layers, k_shared, k_calls = jax.random.split(key, 5)
    v, m, n = padded_vocab(spec), spec["d_model"], spec["num_layers"]
    scale = 1.0 / math.sqrt(n)
    params = {
        "embed": 0.02 * jax.random.normal(k_emb, (v, m), F32),
        "slots": {"slot0": jax.vmap(
            lambda k: {"ln": jnp.zeros((m,), F32),
                       "ssm": mamba2.init_ssm(spec, k, scale)})(
            jax.random.split(k_layers, n))},
        "shared": jax.vmap(lambda k: init_shared(spec, k))(
            jax.random.split(k_shared, spec["num_mem_blocks"])),
        "hybrid": jax.vmap(lambda k: init_invocation(spec, k))(
            jax.random.split(k_calls, len(spec["hybrid_layer_ids"]))),
        "final_norm": jnp.zeros((m,), F32),
        "unembed": 0.02 * jax.random.normal(k_out, (m, v), F32),
    }
    return jax.tree.map(lambda a: a.astype(dtype), params)


# ---------------------------------------------------------------------------
# Mamba-2 mixer
# ---------------------------------------------------------------------------
def ssm_mixer(spec, p, h, quant=None):
    """``mamba2.ssm_mixer`` with the gated RMSNorm taken over each group
    of channels (one per B/C group)."""
    bsz, l, _ = h.shape
    d = mamba2.ssm_dims(spec)
    g, n = spec["ssm_groups"], spec["ssm_state"]
    z = matmul(h, p["wz"], quant)
    x = mamba2.causal_conv(matmul(h, p["wx"], quant), p["conv_x"])
    b = mamba2.causal_conv(matmul(h, p["wb"], quant), p["conv_b"])
    c = mamba2.causal_conv(matmul(h, p["wc"], quant), p["conv_c"])
    dt = jax.nn.softplus(matmul(h, p["wdt"], quant) + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    y = mamba2.ssm_recurrence(
        x.reshape(bsz, l, d["heads"], spec["ssm_head_dim"]), dt, a,
        b.reshape(bsz, l, g, n), c.reshape(bsz, l, g, n), p["d_skip"])
    y = y.reshape(bsz, l, g, d["d_in"] // g) \
        * jax.nn.silu(z).reshape(bsz, l, g, d["d_in"] // g)
    y = rms_norm(y, p["norm"].reshape(g, -1), spec["norm_eps"])
    return matmul(y.reshape(bsz, l, d["d_in"]), p["wo"], quant)


def mamba_layer(spec, lp, x, t=None, quant=None):
    h = x if t is None else x + t
    return x + ssm_mixer(spec, lp["ssm"], rms_norm(h, lp["ln"], spec["norm_eps"]),
                         quant)


# ---------------------------------------------------------------------------
# shared block
# ---------------------------------------------------------------------------
def rope(x, theta):
    """Rotary embedding by halves (HF ``rotate_half``). x: (B, L, H, D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs    # (L, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def shared_block(spec, sp, hp, x, e, quant=None):
    """The invocation's output T (B, L, M), computed in blocks of queries."""
    bsz, l, m = x.shape
    hd, nh, nk = spec["head_dim"], spec["num_heads"], spec["num_kv_heads"]
    grp = nh // nk
    eps = spec["norm_eps"]
    u = rms_norm(jnp.concatenate([x, e], -1), sp["ln1"], eps)
    p = sp["attn"]
    theta = spec["rope_theta"]
    q = rope(matmul(u, p["wq"], quant).reshape(bsz, l, nh, hd), theta)
    k = rope(matmul(u, p["wk"], quant).reshape(bsz, l, nk, hd), theta)
    v = matmul(u, p["wv"], quant).reshape(bsz, l, nk, hd)
    blk = math.gcd(l, QUERY_BLOCK)
    qb = jnp.moveaxis(q.reshape(bsz, l // blk, blk, nk, grp, hd), 1, 0)

    def rows(args):
        q_i, start = args
        s = jnp.einsum("bqkgd,btkd->bkgqt", q_i, k,
                       precision=HIGHEST) * spec["attn_scale"]
        causal = jnp.arange(l)[None, :] <= start + jnp.arange(blk)[:, None]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", w, v, precision=HIGHEST)
        a = matmul(o.reshape(bsz, blk, nh * hd), p["wo"], quant)
        u2 = rms_norm(a, sp["ln2"], eps)
        gu = matmul(u2, sp["mlp"]["w_gu"], quant) \
            + matmul(matmul(u2, hp["lora_a"], quant), hp["lora_b"], quant)
        g, up = jnp.split(gu, 2, axis=-1)
        f = matmul(jax.nn.gelu(g, approximate=False) * up,
                   sp["mlp"]["w_down"], quant)
        return matmul(f, hp["proj"], quant)

    t = jax.lax.map(rows, (qb, jnp.arange(0, l, blk)))       # (n, B, blk, M)
    return jnp.moveaxis(t, 0, 1).reshape(bsz, l, m)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def embed(params, tokens):
    return jnp.take(params["embed"].astype(F32), tokens, axis=0)


def _rows(tree, lo, hi=None):
    return jax.tree.map(lambda a: a[lo] if hi is None else a[lo:hi], tree)


def hidden(spec, params, tokens, quant=None):
    """Final-norm hidden states (B, L, M) of the whole model."""
    ids = list(spec["hybrid_layer_ids"])
    layers = params["slots"]["slot0"]
    e = x = embed(params, tokens)

    def scan_layers(x, lo, hi):
        def layer(x, lp):
            return mamba_layer(spec, lp, x, quant=quant), None

        return jax.lax.scan(layer, x, _rows(layers, lo, hi))[0] if hi > lo else x

    lo = 0
    for j, i in enumerate(ids):
        x = scan_layers(x, lo, i)
        b = j % spec["num_mem_blocks"]
        t = shared_block(spec, _rows(params["shared"], b),
                         _rows(params["hybrid"], j), x, e, quant)
        x = mamba_layer(spec, _rows(layers, i), x, t, quant)
        lo = i + 1
    x = scan_layers(x, lo, spec["num_layers"])
    return rms_norm(x, params["final_norm"], spec["norm_eps"])


def logits(spec, params, tokens, quant=None):
    """Logits over the padded vocabulary at every position, (B, L, V)."""
    return matmul(hidden(spec, params, tokens, quant), params["unembed"], quant)
