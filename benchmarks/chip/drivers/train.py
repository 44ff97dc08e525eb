"""Training cells: ``repro.launch.train.train`` driven as users call it,
with the benchmark's weights and batches.

One call is made. Its steps 0-2 are compared with the plain reference
(step 0 also loads the program) and count as set-up; the window is the
``n`` whole steps after them, with ``n`` sized from the traffic's
``step_s`` to last ``--seconds``; one more step closes the window's last
step on the host clock (the window runs from the start of its first
step's batch to the start of the batch after its last step).
"""

from __future__ import annotations

import functools
import math
import shutil
import tempfile
import time

import numpy as np

import checks
import counts
import harness

#: Steps before the window: 0 loads the program, 0-2 are compared.
FIRST = 3


def make_batch(seed, step, batch, seq, vocab):
    """Rows of uniformly drawn tokens, each row different, labels the next
    token: a pure function of (seed, step)."""
    rng = np.random.default_rng([int(seed), int(step)])
    tok = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"inputs": np.ascontiguousarray(tok[:, :-1]),
            "labels": np.ascontiguousarray(tok[:, 1:])}


def pipeline_class(seed, traffic, starts):
    """Stands in for the program's data pipeline: the benchmark's batches,
    and the host time at which each step asked for its batch."""

    class BenchPipeline:
        def __init__(self, cfg, process_index=None, process_count=None):
            want = (traffic["batch"], traffic["seq_len"])
            if (cfg.global_batch, cfg.seq_len) != want:
                raise harness.Refused(f"train() asked for batch "
                                      f"{cfg.global_batch}x{cfg.seq_len}, "
                                      f"traffic states {want}")
            self.cfg = cfg

        def batch_at(self, step):
            starts[step] = time.perf_counter()
            c = self.cfg
            return make_batch(seed, step, c.global_batch, c.seq_len,
                              c.vocab_size)

        def stop(self):
            pass

    return BenchPipeline


def layout(tree):
    """Paths, shapes and dtypes of a tree's leaves."""
    import jax

    return [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def init_fn(model, spec, key, program_init):
    """Stands in for the program's ``init_train_state``: the benchmark's
    float32 weights and a zero optimizer state, in one jitted call, in the
    layout the program's own initialiser gives."""
    import jax
    import jax.numpy as jnp

    def init_train_state(cfg, program_key):
        shapes = jax.eval_shape(functools.partial(program_init, cfg),
                                program_key)
        mine = jax.eval_shape(functools.partial(model.init_params, spec), key)
        if layout(mine) != layout(shapes["params"]):
            raise harness.Refused("the program's parameter layout differs "
                                  "from the reference's")

        @jax.jit
        def make(key):
            state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            state["params"] = model.init_params(spec, key)
            return state

        return make(key)

    return init_train_state


def run(ctx):
    import jax

    from repro.launch import train as train_mod
    from repro.optim.adamw import AdamWConfig

    tr, spec, seed, model = ctx["traffic"], ctx["spec"], ctx["seed"], ctx["model"]
    batch, seq = tr["batch"], tr["seq_len"]
    hooks, counter = ctx["hooks"], ctx["compiles"]
    key = model.seed_key(seed)
    starts = {}
    readings = {}
    b1 = tr["optimizer"]["b1"]

    def capture(call, out):
        state = out[0]
        if call == "train_step#0":
            mu = state["opt"]["mu"]
            readings["grad"] = np.asarray(checks.leaf_norms(mu)) / (1 - b1)
            readings["grad_tree"] = jax.tree.map(
                lambda m: np.asarray(m) / (1 - b1), mu)
        elif call == f"train_step#{FIRST - 1}":
            p0 = jax.jit(functools.partial(model.init_params, spec))(key)
            readings["change"] = np.asarray(checks.delta_norms(state["params"], p0))

    seconds = ctx["trace_seconds"] if ctx["trace"] else ctx["seconds"]
    n = max(1, math.ceil(1.03 * seconds / tr["step_s"]))
    n += 2 if ctx["trace"] else 0
    steps = FIRST + n + 1
    hooks.after_wait.append(capture)
    hooks.trace_from = f"train_step#{FIRST}"
    spool = tempfile.mkdtemp(prefix="chip_train_")
    try:
        with harness.replaced(
                train_mod,
                init_train_state=init_fn(model, spec, key,
                                         train_mod.init_train_state),
                SyntheticTokenPipeline=pipeline_class(seed, tr, starts),
                RuntimeBackend=hooks.backend(train_mod.RuntimeBackend)):
            state, hist, _ = train_mod.train(
                ctx["cfg"], steps=steps, global_batch=batch, seq_len=seq,
                seed=ctx["program_seed"], verbose=False,
                opt_cfg=AdamWConfig(**tr["optimizer"], total_steps=steps),
                talp_step_series=steps, talp_spool=spool,
                talp_json=f"{spool}/talp.json")
            hooks.stop_trace()
        del state
        ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
        talp = harness.load_json(f"{spool}/talp.json")
        rows, regions = harness.step_rows(spool)
    finally:
        shutil.rmtree(spool, ignore_errors=True)

    t0, t1 = starts[FIRST], starts[FIRST + n]
    window = {"steps": n, "seconds": t1 - t0, "start": t0, "end": t1,
              "compiles": counter.inside(t0, t1)}
    losses = [h["loss"] for h in hist]
    tokens = batch * seq
    step_ops = counts.train_step_ops(spec, batch, seq)
    if ctx["trace"] and hooks.span and hooks.span[1]:
        span_steps = [int(k.split("#")[1]) for k in hooks.span_calls]
        s0, s1 = hooks.span
    else:
        span_steps, s0, s1 = list(range(FIRST, FIRST + n)), t0, t1
    span = {"steps": len(span_steps), "seconds": s1 - s0,
            "ops": step_ops * len(span_steps), "bytes": None,
            "rows": harness.window_rows(rows, regions, "step", s0, s1)}

    # --- correctness: the first three steps against the reference --------
    opt = dict(tr["optimizer"], total_steps=steps)
    batches = [make_batch(seed, i, batch, seq, spec["vocab_size"])
               for i in range(FIRST)]
    ref = checks.reference_train(model, spec, seed, batches, opt)
    prog = {"loss": losses[:FIRST], **readings}
    numbers = checks.train_readings(prog, ref)
    control = None
    if ctx["control"]:
        half = [{k: v[:batch // 2] for k, v in b.items()} for b in batches]
        control = {
            "fp8": checks.train_readings(checks.reference_train(
                model, spec, seed, batches, opt, quant="fp8"), ref),
            "half_batch": checks.train_readings(checks.reference_train(
                model, spec, seed, half, opt), ref),
        }
    return {
        "setup_s": t0 - ctx["t_start"],
        "window": window,
        "span": span,
        "e2e": {"train_tokens_per_s": tokens * n / (t1 - t0)},
        "attempted": n,
        "failed": int(sum(not math.isfinite(x) for x in losses[FIRST:FIRST + n])),
        "numbers": numbers,
        "notes": {"losses": losses[:FIRST],
                  "reference_losses": ref["loss"],
                  "grad_leaf": numbers["grad_leaf"],
                  "error_leaf": numbers["error_leaf"],
                  "change_leaf": numbers["change_leaf"]},
        "talp": talp, "loop_region": "train_loop", "control": control,
    }
