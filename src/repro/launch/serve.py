"""Batched serving driver: prefill + decode loop under TALP monitoring.

Requests are prompt batches; the loop prefills the batch, grows the
caches, then decodes tokens autoregressively. Host/device states are
TALP-monitored exactly as in training — the serving profile typically
shows high Offload (host blocked on decode steps) and the per-step
Orchestration gap, which is the paper's framing for "the host cannot
feed the device." Each decode step's phases (``serve.fetch``,
``serve.feed``, ``serve.dispatch``, ``serve.sample``, ``serve.flush``)
and TALP's own scopes are profiler spans, so a ``jax.profiler`` trace
puts every idle gap of the device down to one of them.

Usage (CPU-sized):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --requests 4 --prompt-len 32 --gen-len 16
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ShapeConfig, get_config, list_configs, smoke_config
from ..core.backends import RuntimeBackend
from ..core.talp import TalpResult
from ..core.telemetry import spans
from ..models import lm
from .compile_cache import enable_compile_cache
from .session import TalpSession, add_arguments, options_from
from .steps import init_serve_params, make_prefill_step, make_serve_step

__all__ = ["ServeResult", "serve", "parser", "main"]


class ServeResult(NamedTuple):
    """What one :func:`serve` call produced."""

    tokens: np.ndarray      # (requests, gen_len) generated token ids
    talp: TalpResult
    params: Any             # the bf16 weights the batch was served with
    prompts: jax.Array      # (requests, prompt_len[, d_model]) inputs
    logits: jax.Array       # (requests, padded_vocab) last decode step
    token_s: np.ndarray     # (gen_len,) wall seconds of each decode step


def serve(
    cfg,
    requests: int = 4,
    prompt_len: int = 32,
    gen_len: int = 16,
    seed: int = 0,
    verbose: bool = True,
    **talp,
):
    """Serve a batch of requests.

    ``talp`` holds the TALP options of
    :class:`~repro.launch.session.TalpSession` (``rank``,
    ``world_size``, ``talp_*``), as for training. Each decode iteration
    runs in a nested ``decode_step`` region when a step series is kept,
    and the sample interval counts decoded tokens."""
    backend = RuntimeBackend()
    session = TalpSession(
        "serve", step_region="decode_step", unit="token", backend=backend,
        verbose=verbose,
        flop=(cfg, ShapeConfig(name="serve", seq_len=prompt_len + gen_len,
                               global_batch=requests, kind="decode")),
        **talp)
    mon = session.monitor
    key = jax.random.PRNGKey(seed)

    with mon.region("init"):
        params = jax.jit(functools.partial(init_serve_params, cfg))(key)
        params = jax.block_until_ready(params)

    prefill_fn = jax.jit(make_prefill_step(cfg))
    decode_fn = jax.jit(make_serve_step(cfg), donate_argnums=3)
    # Flushes the hot ring into the prefix cache before the ring wraps
    # (``attn_decode`` writes it at ``pos % decode_hot_len``).
    consolidate_fn = jax.jit(functools.partial(lm.consolidate_caches, cfg),
                             donate_argnums=0)

    if cfg.frontend == "token":
        prompts = jax.random.randint(
            key, (requests, prompt_len), 0, cfg.vocab_size, jnp.int32
        )
    else:
        prompts = jax.random.normal(
            key, (requests, prompt_len, cfg.d_model), jnp.bfloat16
        )

    tokens_out = []
    token_s = np.zeros(gen_len)
    with mon.region("prefill"):
        h = backend.launch(prefill_fn, params, prompts, name="prefill")
        with mon.offload():
            logits, caches, pos = backend.wait(h)
    with mon.region("grow_cache"):
        caches = lm.grow_caches(cfg, caches, prompt_len + gen_len)

    tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    with mon.region("decode"):
        for t in range(gen_len):
            t0 = time.perf_counter()
            with session.step():
                with spans.span("serve.fetch"):
                    tokens_out.append(np.asarray(tok))
                with spans.span("serve.feed"):
                    if cfg.frontend == "token":
                        inp = tok[:, None]
                    else:  # embed-frontend stub: feed a frame embedding
                        inp = jnp.zeros((requests, 1, cfg.d_model),
                                        jnp.bfloat16)
                with spans.span("serve.dispatch"):
                    h = backend.launch(decode_fn, params, inp, pos, caches,
                                       name=f"decode_{t}")
                with mon.offload():
                    logits, caches, pos = backend.wait(h)
                with spans.span("serve.sample"):
                    tok = jnp.argmax(
                        logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
                if (t + 1) % cfg.decode_hot_len == 0 and t + 1 < gen_len:
                    with spans.span("serve.flush"):
                        caches = consolidate_fn(caches)
            token_s[t] = time.perf_counter() - t0
            session.tick(t)

    result = session.finish()
    return ServeResult(np.stack(tokens_out, axis=1), result, params,
                       prompts, logits, token_s)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs(), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    add_arguments(ap)
    return ap


def main():
    args = parser().parse_args()
    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    t0 = time.time()
    tokens = serve(cfg, args.requests, args.prompt_len, args.gen_len,
                   **options_from(args)).tokens
    dt = time.time() - t0
    n = tokens.size
    print(f"generated {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s)")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
