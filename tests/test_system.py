"""End-to-end behaviour tests for the full system: training converges
under TALP monitoring with checkpointing; serving generates tokens; the
TALP reports produced by real runs satisfy the paper's invariants."""

import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.serve import serve
from repro.launch.train import train
from repro.models import lm
from repro.optim.adamw import AdamWConfig


@pytest.mark.slow
def test_train_loss_decreases_with_talp(tmp_path):
    cfg = smoke_config("gemma2-2b")
    state, history, talp = train(
        cfg,
        steps=30,
        global_batch=4,
        seq_len=64,
        ckpt_dir=str(tmp_path),
        ckpt_every=10,
        verbose=False,
        opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=30),
    )
    losses = [h["loss"] for h in history]
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]
    # TALP report exists and satisfies the multiplicative hierarchy
    loop = talp.regions["train_loop"]
    assert loop.host is not None and loop.device is not None
    loop.host.validate(tol=1e-6)
    loop.device.validate(tol=1e-6)
    assert loop.host_states[0]["useful"] > 0
    assert loop.host_states[0]["offload"] > 0
    assert loop.device_states[0]["kernel"] > 0


@pytest.mark.slow
def test_serve_generates_and_reports(tmp_path):
    cfg = smoke_config("h2o-danube-3-4b")   # SWA ring-cache path
    tokens, talp, *_ = serve(cfg, requests=2, prompt_len=16, gen_len=6,
                             verbose=False)
    assert tokens.shape == (2, 6)
    assert np.all(tokens >= 0) and np.all(tokens < cfg.vocab_size)
    dec = talp.regions["decode"]
    dec.host.validate(tol=1e-6)
    assert dec.device_states[0]["kernel"] > 0


@pytest.mark.slow
def test_embed_frontend_end_to_end():
    """VLM/audio stub frontends train and serve (backbone-only)."""
    cfg = smoke_config("musicgen-large")
    _, history, _ = train(cfg, steps=8, global_batch=2, seq_len=32,
                          verbose=False)
    assert np.isfinite(history[-1]["loss"])
    tokens = serve(cfg, requests=2, prompt_len=8, gen_len=3,
                   verbose=False).tokens
    assert tokens.shape == (2, 3)


def test_consolidate_caches_roundtrip():
    """Hot-ring flush: decode → consolidate → decode equals continuous
    decode (serving-layer contract)."""
    import jax
    import jax.numpy as jnp

    cfg = smoke_config("llama3.2-3b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size, jnp.int32)
    _, caches, pos = lm.prefill(cfg, params, toks[:, :16])
    caches = lm.grow_caches(cfg, caches, 24)

    # path 1: straight decode of 4 tokens
    c1, p1 = caches, pos
    for t in range(16, 20):
        l1, c1, p1 = lm.decode_step(cfg, params, toks[:, t:t+1], p1, c1)

    # path 2: decode 2, consolidate (flush hot ring), decode 2
    c2, p2 = caches, pos
    for t in range(16, 18):
        _, c2, p2 = lm.decode_step(cfg, params, toks[:, t:t+1], p2, c2)
    c2 = lm.consolidate_caches(cfg, c2)
    for t in range(18, 20):
        l2, c2, p2 = lm.decode_step(cfg, params, toks[:, t:t+1], p2, c2)

    np.testing.assert_allclose(
        np.asarray(l1, np.float32), np.asarray(l2, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("arch", ["llama3.2-3b", "zamba2-2.7b", "zamba2-7b-l24"])
def test_serve_past_hot_ring_matches_prefill(arch):
    """Decoding more tokens than the hot ring holds keeps every generated
    token in context: the last decode step's logits equal one prefill over
    the prompt plus the generated tokens, with the same weights.

    Tolerance: bf16 weights and activations, and decode and prefill reduce
    in different orders (recurrent vs chunked SSD, split vs chunked
    softmax); at these sizes that costs < 0.05, while a ring that wraps
    without a flush is off by > 0.2."""
    import jax
    import jax.numpy as jnp

    cfg = smoke_config(arch)
    gen_len = cfg.decode_hot_len + 8
    out = serve(cfg, requests=2, prompt_len=16, gen_len=gen_len,
                verbose=False)
    full = jnp.concatenate([out.prompts, jnp.asarray(out.tokens)], axis=1)
    ref, _, _ = jax.jit(lambda p, x: lm.prefill(cfg, p, x))(out.params, full)
    np.testing.assert_allclose(np.asarray(out.logits), np.asarray(ref),
                               rtol=0, atol=0.1)
