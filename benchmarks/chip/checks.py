"""The comparisons that decide ``correct``: the program's timed path against
the plain float32 reference, with the reference's fp8 variant as the
control that must fail them.

Training compares, for the first three steps of the call that holds the
window: each step's loss; the first gradient as the optimizer got it,
leaf by leaf, by its norm and by its difference; and the parameters'
change after three steps, leaf by leaf.
A leaf's reading is the gap between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and of
the median leaf. Leaves whose reference gradient is under a thousandth of
the median leaf's are left out of the change.

Serving compares every token served to the sampled requests: the gap by
which the reference's logit of the served token lies below the
reference's best logit at that position, over the spread (standard
deviation) of the reference's logits there. The control reads the same
gap for the token the fp8 reference puts first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: Leaves whose reference gradient is under this share of the median
#: leaf's move by round-off alone and are left out of the change.
DEAD_LEAF = 1e-3


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------
def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def delta_norms(new, old):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
                      for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))])


def worst_leaf(gap, ref, names, keep=None):
    """Largest gap / max(ref, median ref) over the kept leaves, with
    ``gap`` and ``ref`` per leaf; returns (reading, leaf name)."""
    gap, ref = np.asarray(gap, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    floor = float(np.median(ref[keep]))
    gaps = gap / np.maximum(ref, floor)
    gaps = np.where(keep, gaps, -np.inf)
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def live_leaves(ref_grad_norms):
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= DEAD_LEAF * float(np.median(g))


# ---------------------------------------------------------------------------
# training reference
# ---------------------------------------------------------------------------
def lr_at(opt, count):
    """AdamW's learning rate before update ``count`` (0-based): linear
    warm-up to ``lr``, then cosine decay to ``min_lr_ratio * lr``."""
    warm = min(1.0, (count + 1) / max(1, opt["warmup_steps"]))
    span = max(1, opt["total_steps"] - opt["warmup_steps"])
    frac = min(max((count - opt["warmup_steps"]) / span, 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def reference_train(model, spec, seed, batches, opt, quant=None):
    """Three (or len(batches)) AdamW steps of the plain reference from the
    benchmark's initial weights. Returns per-step losses, the first clipped
    gradient's leaf norms, and the leaf norms of the change."""
    init = jax.jit(functools.partial(model.init_params, spec))
    key = model.seed_key(seed)
    params = init(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(params, mu, nu, batch, lr, count):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(spec, p, batch, quant))(params)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / gnorm)
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1 / (jnp.sqrt(v / c2) + opt["eps"])
                                      + opt["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, loss, grads

    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, mu, nu, loss, grads = step(params, mu, nu, batch,
                                               lr_at(opt, i), float(i + 1))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = grads
            del grads
        del mu, nu
        change = np.asarray(delta_norms(params, init(key)))
    return {"loss": losses, "grad": np.asarray(leaf_norms(first_grad)),
            "grad_tree": first_grad, "change": change,
            "names": leaf_names(params)}


def train_readings(prog, ref):
    """The training numbers from the program's and the reference's
    readings (dicts with ``loss``, ``grad``, ``grad_tree``, ``change``).

    ``grad_error`` is the first gradient's difference, leaf by leaf, over
    the larger of the reference's leaf norm and the median leaf's: rounding
    noise that averages out of a norm shows in it."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    names = ref["names"]
    grad, grad_leaf = worst_leaf(np.abs(prog["grad"] - ref["grad"]),
                                 ref["grad"], names)
    change, change_leaf = worst_leaf(np.abs(prog["change"] - ref["change"]),
                                     ref["change"], names,
                                     keep=live_leaves(ref["grad"]))
    diff = np.asarray(delta_norms(prog["grad_tree"], ref["grad_tree"]))
    error, error_leaf = worst_leaf(diff, ref["grad"], names)
    return {"loss_gap": loss, "grad_gap": grad, "grad_error": error,
            "change_gap": change, "grad_leaf": grad_leaf,
            "error_leaf": error_leaf, "change_leaf": change_leaf}


# ---------------------------------------------------------------------------
# serving reference
# ---------------------------------------------------------------------------
def served_gaps(model, spec, params, prompts, served, vocab, control=False):
    """Per served token, the reference's best logit minus its logit of the
    served token, over the real vocabulary, in units of the standard
    deviation of the reference's logits at that position (so that it reads
    alike at any width): (requests, gen_len). With ``control`` also the
    same gap for the fp8 reference's first token."""
    p = prompts.shape[1]
    g = served.shape[1]
    tokens = jnp.concatenate([jnp.asarray(prompts), jnp.asarray(served)], 1)
    hidden = jax.jit(functools.partial(model.hidden, spec),
                     static_argnames="quant")

    @jax.jit
    def gaps(h, unembed, tok, h8=None):
        lg = jnp.matmul(h, unembed[:, :vocab].astype(F32), precision=HIGHEST)
        best, spread = jnp.max(lg, -1), jnp.std(lg, -1)
        out = (best - jnp.take_along_axis(lg, tok[..., None], -1)[..., 0]) / spread
        if h8 is None:
            return out, None
        lg8 = model.matmul(h8, unembed[:, :vocab], "fp8")
        t8 = jnp.argmax(lg8, -1)
        return out, (best - jnp.take_along_axis(lg, t8[..., None], -1)[..., 0]) / spread

    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens)[:, p - 1:p + g - 1]
        h8 = (hidden(params, tokens, quant="fp8")[:, p - 1:p + g - 1]
              if control else None)
        prog, ctrl = [], []
        for r in range(tokens.shape[0]):
            a, b = gaps(h[r], params["unembed"], jnp.asarray(served[r]),
                        None if h8 is None else h8[r])
            prog.append(np.asarray(a))
            if b is not None:
                ctrl.append(np.asarray(b))
    return np.stack(prog), (np.stack(ctrl) if control else None)


def sample_requests(seed, requests, k):
    """``k`` request indices drawn from the seed, one from each of ``k``
    equal blocks of the batch, so that every part of the batch is seen."""
    rng = np.random.default_rng([int(seed), 1])
    edges = np.linspace(0, requests, k + 1).astype(int)
    return [int(rng.integers(lo, hi)) for lo, hi in zip(edges[:-1], edges[1:])]
