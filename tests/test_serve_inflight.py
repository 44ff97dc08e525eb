"""Serving with one decode step in flight: the served tokens are those of
a synchronous greedy loop, and TALP still reads the overlapping device
records of consecutive steps as it read the back-to-back ones."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TalpMonitor
from repro.core.backends import RuntimeBackend
from repro.launch.serve import serve
from repro.models import lm

PROMPT_LEN = 8


def _gen_len(cfg):
    return 2 * cfg.decode_hot_len + 4      # past two hot-ring flushes


def synchronous_oracle(cfg, params, prompts, gen_len):
    """Greedy decoding one step at a time: decode, argmax, flush when the
    hot ring is full. Returns (tokens (B, gen_len), last logits)."""
    decode = jax.jit(functools.partial(lm.decode_step, cfg))
    consolidate = jax.jit(functools.partial(lm.consolidate_caches, cfg))
    logits, caches, pos = jax.jit(functools.partial(lm.prefill, cfg))(
        params, prompts)
    caches = lm.grow_caches(cfg, caches, PROMPT_LEN + gen_len)
    tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    tokens = []
    for t in range(gen_len):
        tokens.append(np.asarray(tok))
        logits, caches, pos = decode(params, tok[:, None], pos, caches)
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        if (t + 1) % cfg.decode_hot_len == 0 and t + 1 < gen_len:
            caches = consolidate(caches)
    return np.stack(tokens, axis=1), np.asarray(logits)


@pytest.fixture(scope="module", params=["mamba2-130m", "zamba2-7b-l24"])
def served(request):
    cfg = smoke_config(request.param)
    out = serve(cfg, requests=2, prompt_len=PROMPT_LEN,
                gen_len=_gen_len(cfg), verbose=False,
                talp_step_series=_gen_len(cfg))
    return cfg, out


def test_tokens_match_a_synchronous_greedy_loop(served):
    cfg, out = served
    gen_len = _gen_len(cfg)
    want, last = synchronous_oracle(cfg, out.params, out.prompts, gen_len)
    assert out.tokens.shape == (2, gen_len)
    np.testing.assert_array_equal(out.tokens, want)
    np.testing.assert_array_equal(np.asarray(out.logits), last)
    assert 0 <= out.ahead_steps <= gen_len - 1


def test_decode_region_still_reports_offload(served):
    """The host still blocks on each step, one step later: TALP's Offload
    state holds that time."""
    _, out = served
    dec = out.talp.regions["decode"]
    assert dec.host_states[0]["offload"] > 0
    assert dec.device_states[0]["kernel"] > 0
    dec.host.validate(tol=1e-6)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_overlapping_step_records_flatten_into_one_busy_window():
    """Two steps launched before either is waited, then waited in order:
    two KERNEL records whose windows overlap, and a region whose device
    busy time is their union, no longer than the region."""
    clk = _Clock()
    be = RuntimeBackend(clock=clk)
    mon = TalpMonitor("inflight", clock=clk, backend=be)
    f = jax.jit(lambda x: x + 1)
    x = jnp.ones(8)
    with mon.region("decode"):
        h0 = be.launch(f, x, name="decode_0")
        clk.t = 1.0
        h1 = be.launch(f, x, name="decode_1")
        clk.t = 3.0
        with mon.offload():
            be.wait(h0)
        clk.t = 5.0
        with mon.offload():
            be.wait(h1)
        clk.t = 6.0
    assert (h0.launch_t, h0.done_t) == (0.0, 3.0)
    assert (h1.launch_t, h1.done_t) == (1.0, 5.0)
    assert h1.launch_t < h0.done_t          # the windows overlap
    res = mon.finalize()
    region = res["decode"]
    busy = region.device_states[0]["kernel"]
    assert busy == pytest.approx(5.0)       # the union of [0, 3] and [1, 5]
    assert busy <= region.elapsed
    host, dev = region.host, region.device
    for value in (host.parallel_efficiency, host.mpi_parallel_efficiency,
                  host.communication_efficiency, host.load_balance,
                  host.device_offload_efficiency, dev.parallel_efficiency,
                  dev.load_balance, dev.communication_efficiency,
                  dev.orchestration_efficiency):
        assert 0.0 <= value <= 1.0
