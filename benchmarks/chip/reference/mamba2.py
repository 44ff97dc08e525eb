"""Plain float32 reference of the Mamba-2 language model as the benchmark's
configuration files state it (Dao & Gu, arXiv:2405.21060).

Nothing here imports the program. The weights come from :func:`init_params`,
the benchmark's own initialisation from the seed, in the tree layout the
program's entry points take, so that the same weights can be handed to both.

Departures from the published Mamba-2, shared with the program under test
and stated in the configuration file: separate z/x/B/C/dt projections (one
fused projection in the paper), no convolution bias, an untied output head,
and a softmax over the vocabulary padded to a multiple of 256.

The SSM is the token-by-token recurrence

    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T,    y_t = s_t C_t + D x_t

and not the chunked dual form the program uses. Every matrix product runs
in float32 at the highest precision. ``quant="fp8"`` rounds both operands of
every weight product to float8 e4m3 (per-row scales for activations,
per-column scales for weights, gradients passed straight through): the
control that must come out as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8 e4m3fn value
NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``;
    the gradient passes through unchanged."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, quant=None):
    """``x @ w`` in float32 at the highest precision (fp8 operands with
    ``quant="fp8"``)."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quantisation {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w):
    """RMSNorm with the ``1 + w`` scale the configuration states."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) \
        * (1.0 + w.astype(F32))


# ---------------------------------------------------------------------------
# shapes and initialisation
# ---------------------------------------------------------------------------
def padded_vocab(spec):
    return -(-spec["vocab_size"] // 256) * 256


def ssm_dims(spec):
    d_in = spec["ssm_expand"] * spec["d_model"]
    return {
        "d_in": d_in,
        "heads": d_in // spec["ssm_head_dim"],
        "gn": spec["ssm_groups"] * spec["ssm_state"],
    }


def ssm_shapes(spec):
    m, k = spec["d_model"], spec["ssm_conv"]
    d = ssm_dims(spec)
    return {
        "wz": (m, d["d_in"]), "wx": (m, d["d_in"]), "wb": (m, d["gn"]),
        "wc": (m, d["gn"]), "wdt": (m, d["heads"]),
        "dt_bias": (d["heads"],), "a_log": (d["heads"],),
        "d_skip": (d["heads"],),
        "conv_x": (k, d["d_in"]), "conv_b": (k, d["gn"]),
        "conv_c": (k, d["gn"]),
        "norm": (d["d_in"],), "wo": (d["d_in"], spec["d_model"]),
    }


def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, F32, -bound, bound)


def init_ssm(spec, key, residual_scale):
    """One Mamba-2 mixer as ``mamba_ssm`` initialises it: linear and
    convolution weights uniform in +-1/sqrt(fan_in), the output projection
    scaled by ``residual_scale``, A in [1, 16], dt in [1e-3, 0.1], D = 1."""
    shapes = ssm_shapes(spec)
    ks = dict(zip(shapes, jax.random.split(key, len(shapes))))
    p = {}
    for name in ("wz", "wx", "wb", "wc", "wdt", "wo"):
        fan_in = shapes[name][0]
        p[name] = _uniform(ks[name], shapes[name], 1.0 / math.sqrt(fan_in))
    p["wo"] = p["wo"] * residual_scale
    for name in ("conv_x", "conv_b", "conv_c"):
        p[name] = _uniform(ks[name], shapes[name], 1.0 / math.sqrt(spec["ssm_conv"]))
    h = shapes["dt_bias"]
    dt = jnp.exp(jax.random.uniform(ks["dt_bias"], h, F32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    p["a_log"] = jnp.log(jax.random.uniform(ks["a_log"], h, F32, 1.0, 16.0))
    p["d_skip"] = jnp.ones(h, F32)
    p["norm"] = jnp.zeros(shapes["norm"], F32)          # scale 1 + 0
    return p


def init_params(spec, key, dtype=F32):
    """The benchmark's weights for ``key`` (see :func:`seed_key`), in the
    program's tree layout: ``embed``, ``slots/slot0`` (``ln`` and ``ssm``,
    stacked over the layers), ``final_norm`` and ``unembed``. Call under
    ``jax.jit``."""
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    v, m, n = padded_vocab(spec), spec["d_model"], spec["num_layers"]
    scale = 1.0 / math.sqrt(n)
    layers = jax.vmap(lambda k: {"ln": jnp.zeros((m,), F32),
                                 "ssm": init_ssm(spec, k, scale)})(
        jax.random.split(k_layers, n))
    params = {
        "embed": 0.02 * jax.random.normal(k_emb, (v, m), F32),
        "slots": {"slot0": layers},
        "final_norm": jnp.zeros((m,), F32),
        "unembed": 0.02 * jax.random.normal(k_out, (m, v), F32),
    }
    return jax.tree.map(lambda a: a.astype(dtype), params)


def seed_key(seed):
    """A PRNG key from a seed of up to 64 bits (high bits folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Mamba-2 mixer
# ---------------------------------------------------------------------------
def causal_conv(x, w):
    """Depthwise causal convolution then SiLU. x: (B, L, C); w: (K, C)."""
    k = w.shape[0]
    xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(F32) for i in range(k))
    return jax.nn.silu(out)


def ssm_recurrence(x, dt, a, b, c, d_skip, block=0):
    """Token-by-token SSM. x: (B, L, H, P); dt: (B, L, H); b, c:
    (B, L, G, N); a, d_skip: (H,). With ``block`` > 0 the scan runs in
    rematerialised blocks of that many tokens, so that its gradient keeps
    one state per block and not one per token."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t = jnp.repeat(b_t, rep, axis=1)                 # (B, H, N)
        c_t = jnp.repeat(c_t, rep, axis=1)
        s = s * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=HIGHEST)

    seq = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (x, dt, b, c))
    s0 = jnp.zeros((bsz, h, p, n), F32)
    if block and l % block == 0 and l > block:
        blocked = jax.tree.map(
            lambda t: t.reshape((l // block, block) + t.shape[1:]), seq)

        @jax.checkpoint
        def run_block(s, inp):
            return jax.lax.scan(step, s, inp)

        _, ys = jax.lax.scan(run_block, s0, blocked)
        ys = ys.reshape((l,) + ys.shape[2:])
    else:
        _, ys = jax.lax.scan(step, s0, seq, unroll=8)
    y = jnp.moveaxis(ys, 0, 1)
    return y + d_skip.astype(F32)[:, None] * x.astype(F32)


def ssm_mixer(spec, p, h, quant=None, block=0):
    """Mamba-2 mixer on a post-norm input h: (B, L, M)."""
    bsz, l, _ = h.shape
    d = ssm_dims(spec)
    z = matmul(h, p["wz"], quant)
    x = causal_conv(matmul(h, p["wx"], quant), p["conv_x"])
    b = causal_conv(matmul(h, p["wb"], quant), p["conv_b"])
    c = causal_conv(matmul(h, p["wc"], quant), p["conv_c"])
    dt = jax.nn.softplus(matmul(h, p["wdt"], quant) + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    g, n = spec["ssm_groups"], spec["ssm_state"]
    y = ssm_recurrence(
        x.reshape(bsz, l, d["heads"], spec["ssm_head_dim"]), dt, a,
        b.reshape(bsz, l, g, n), c.reshape(bsz, l, g, n), p["d_skip"],
        block=block)
    y = rms_norm(y.reshape(bsz, l, d["d_in"]) * jax.nn.silu(z), p["norm"])
    return matmul(y, p["wo"], quant)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def embed(params, tokens):
    return jnp.take(params["embed"].astype(F32), tokens, axis=0)


def hidden(spec, params, tokens, quant=None, block=0, remat=False):
    """Final-norm hidden states (B, L, M) of the whole model."""
    def layer(x, lp):
        return x + ssm_mixer(spec, lp["ssm"], rms_norm(x, lp["ln"]),
                             quant, block), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, embed(params, tokens),
                        params["slots"]["slot0"])
    return rms_norm(x, params["final_norm"])


def logits(spec, params, tokens, quant=None):
    """Logits over the padded vocabulary at every position, (B, L, V)."""
    return matmul(hidden(spec, params, tokens, quant), params["unembed"], quant)


def mean_xent(h, unembed, labels, quant=None, rows=2048):
    """Mean cross-entropy over the padded vocabulary, in blocks of rows."""
    m = h.shape[-1]
    h = h.reshape(-1, rows, m)
    labels = labels.reshape(-1, rows)

    @jax.checkpoint
    def block(hb, yb):
        lg = matmul(hb, unembed, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], -1)[:, 0])

    total = jnp.sum(jax.lax.map(lambda a: block(*a), (h, labels)))
    return total / labels.size


def loss(spec, params, batch, quant=None, block=64):
    """Training loss of one batch {"inputs", "labels"}: (B, S) int32."""
    h = hidden(spec, params, batch["inputs"], quant, block=block, remat=True)
    rows = math.gcd(h.shape[0] * h.shape[1], 2048)
    return mean_xent(h, params["unembed"], batch["labels"], quant, rows)
