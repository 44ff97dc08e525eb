"""Decoding cells: ``repro.launch.serve.serve`` driven as users call it,
with the benchmark's weights.

One call serves the traffic's static batch: prefill, cache growth, then
``gen_len`` decode steps. The decode steps up to the first flush of the
attention hot ring (``warm_steps``) count as set-up: the flush's program
is first built there. The window is the steady decode steps after them,
up to ``--seconds`` of cumulative step time; where steady decode ends
sooner, the window is all of it, and an earlier line says so.
"""

from __future__ import annotations

import functools
import shutil
import tempfile

import numpy as np

import checks
import counts
import harness


def expected_prompts(program_seed, requests, prompt_len, vocab):
    """The prompts ``serve`` draws from its seed: uniform token ids."""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(program_seed), (requests, prompt_len), 0, vocab,
        jnp.int32))


def run(ctx):
    import jax
    import jax.numpy as jnp

    from repro.launch import serve as serve_mod

    tr, spec, seed, model = ctx["traffic"], ctx["spec"], ctx["seed"], ctx["model"]
    req, plen, glen, warm = (tr["requests"], tr["prompt_len"], tr["gen_len"],
                             tr["warm_steps"])
    hooks, counter = ctx["hooks"], ctx["compiles"]
    key = model.seed_key(seed)

    def init_serve_params(cfg, program_key):
        return model.init_params(spec, key, jnp.bfloat16)

    hooks.trace_from = f"decode_{warm}#0"
    spool = tempfile.mkdtemp(prefix="chip_decode_")
    try:
        with harness.replaced(
                serve_mod, init_serve_params=init_serve_params,
                RuntimeBackend=hooks.backend(serve_mod.RuntimeBackend)):
            out = serve_mod.serve(ctx["cfg"], requests=req, prompt_len=plen,
                                  gen_len=glen, seed=ctx["program_seed"],
                                  verbose=False, talp_step_series=glen,
                                  talp_spool=spool,
                                  talp_json=f"{spool}/talp.json")
        hooks.stop_trace()
        ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
        served = np.asarray(out.tokens)
        prompts = np.asarray(out.prompts)
        token_s = np.asarray(out.token_s, np.float64)
        last_logits_finite = bool(np.all(np.isfinite(np.asarray(out.logits))))
        del out
        talp = harness.load_json(f"{spool}/talp.json")
        rows, regions = harness.step_rows(spool)
    finally:
        shutil.rmtree(spool, ignore_errors=True)

    steady = token_s[warm:]
    cum = np.cumsum(steady)
    n = int(np.searchsorted(cum, ctx["seconds"]) + 1) if len(steady) else 0
    short = n > len(steady)
    n = min(n, len(steady))
    gaps = steady[:n]
    launches = hooks.launches
    t0 = launches[f"decode_{warm}"][0]
    t1 = t0 + float(gaps.sum())
    window = {"steps": n, "seconds": float(gaps.sum()), "start": t0, "end": t1,
              "compiles": counter.inside(t0, t1),
              "short": bool(short)}
    if ctx["trace"] and hooks.span and hooks.span[1]:
        span_steps = [int(k.split("#")[0].split("_")[1]) for k in hooks.span_calls]
        s0, s1 = hooks.span
    else:
        span_steps, s0, s1 = list(range(warm, warm + n)), t0, t1
    span = {"steps": len(span_steps), "seconds": s1 - s0,
            "ops": sum(counts.decode_step_ops(spec, req, plen + t) for t in span_steps),
            "bytes": sum(counts.decode_step_bytes(spec, req, plen + t)
                         for t in span_steps),
            "rows": harness.window_rows(rows, regions, "decode_step", s0, s1)}

    # --- correctness: served tokens against the reference ----------------
    vocab = spec["vocab_size"]
    want = expected_prompts(ctx["program_seed"], req, plen, vocab)
    picks = checks.sample_requests(seed, req, tr["check_requests"])
    params = jax.jit(functools.partial(model.init_params, spec,
                                       dtype=jnp.bfloat16))(key)
    gap, ctrl = checks.served_gaps(model, spec, params, want[picks],
                                   served[picks], vocab, control=ctx["control"])
    del params
    control = None if ctrl is None else {"fp8": {"token_gap": float(ctrl.max())}}
    numbers = {"token_gap": float(gap.max()),
               "prompt_mismatch": float(np.sum(prompts != want)),
               "nonfinite": float(not last_logits_finite)}
    return {
        "setup_s": t0 - ctx["t_start"],
        "window": window,
        "span": span,
        "e2e": {"decode_tokens_per_s": req * n / window["seconds"],
                "token_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3},
        "attempted": req,
        "failed": 0,
        "numbers": numbers,
        "notes": {"checked_requests": picks,
                  "checked_tokens": int(gap.size),
                  "first_step_s": float(token_s[0]),
                  "warm_steps_s": float(token_s[:warm].sum()),
                  "steady_steps": len(steady),
                  "median_step_ms": float(np.median(gaps) * 1e3) if n else None},
        "talp": talp, "loop_region": "decode", "control": control,
    }
