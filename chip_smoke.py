"""Bring-up smoke run on one TPU chip, through the normal entry points.

Four phases in one process, at published widths with random weights:

* train: ``repro.launch.train.train`` on mamba2-130m (all 24 layers,
  50,280-token vocabulary), batch 8 x 2048 tokens, 4 steps;
* serve: ``repro.launch.serve.serve`` on zamba2-2.7b (all 54 layers with
  its two shared attention blocks), 8 requests of 1024 prompt tokens and 160
  generated tokens, past the 128-slot hot ring, as deployed (bf16); then
  once more with float32 activations and highest-precision matmuls,
  where the last decode step must match one prefill over the prompt plus
  the generated tokens;
* kernels: the flash-attention and SSD Pallas kernels, compiled for the
  chip, against their plain references.

Each phase prints one JSON line of what it ran and measured. The last
line is ``{"ok": true, "device": {...}}``; it is printed only when every
phase passed, and the script exits non-zero when JAX finds no TPU.

    python chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from contextlib import nullcontext
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import attention_reference  # noqa: E402
from repro.kernels.ssd import ops as ssd_ops  # noqa: E402
from repro.kernels.ssd.kernel import ssd_pallas  # noqa: E402
from repro.kernels.ssd.ref import ssd_reference  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import lm  # noqa: E402

#: Last decode logits vs one prefill over the same tokens, as a share of
#: the largest reference logit, with float32 activations and
#: highest-precision matmuls (weights bf16 as served). The two paths
#: reduce in different orders (recurrent vs chunked SSD, split vs chunked
#: softmax): 7.7e-5 on one TPU v5e at the sizes of ``main``, where a hot
#: ring that wraps without a flush is off by 2.3e-2. In bf16 the
#: rounding alone moves the logits by about 0.2 of the largest through
#: the 54 layers, as much as that fault, so the bf16 run reports its
#: error without a limit.
SERVE_CHECK_TOL = 1e-3

#: Kernel vs reference, as a share of the largest reference value: the
#: kernels take bf16 inputs and write bf16 outputs (2^-8 relative
#: rounding), while a wrong tile or index map is off by O(1).
KERNEL_TOL = 2e-2

#: The model path's attention and SSD implementations: attention is the
#: chunked XLA scan of ``models/attention.py``; SSM prefill calls
#: ``ssd_reference`` and SSM decode ``ssd_decode_layer`` (the one-pass
#: Pallas kernel on TPU where the state is 128 wide, else
#: ``ssd_decode_step``).
MODEL_ATTENTION_IMPL = "xla chunked online softmax"
MODEL_SSD_PREFILL_IMPL = "xla ssd_reference"


class CompileCounter:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def _max_err(out, ref):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.all(np.isfinite(out)):
        raise AssertionError("non-finite output")
    err = float(np.max(np.abs(out - ref)))
    return err, err / float(np.max(np.abs(ref)))


def train_phase(cfg, steps: int, batch: int, seq: int) -> dict:
    """Train ``cfg`` for ``steps`` steps through ``train``; the loss must be
    finite on every step and the TALP report must hold host and device
    frames with kernel records on device 0."""
    _, history, talp = train(cfg, steps=steps, global_batch=batch,
                             seq_len=seq, talp_step_series=steps,
                             verbose=False)
    losses = [h["loss"] for h in history]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    loop = talp.regions["train_loop"]
    if loop.host is None or loop.device is None:
        raise AssertionError("TALP report lacks a host or device frame")
    if not loop.device_states.get(0, {}).get("kernel", 0.0) > 0:
        raise AssertionError("TALP report has no kernel time on device 0")
    times = [h["time_s"] for h in history]
    steady = float(np.median(times[1:])) if len(times) > 1 else None
    return {
        "config": cfg.name, "steps": steps, "batch": batch, "seq": seq,
        "loss": losses,
        "first_step_s": times[0],
        "steady_step_s": steady,
        "tokens_per_s": batch * seq / steady if steady else None,
        "talp_host_parallel_eff": loop.host.parallel_efficiency,
        "talp_device_parallel_eff": loop.device.parallel_efficiency,
        "talp_computational_eff": loop.device.computational_efficiency,
        "attention_impl": MODEL_ATTENTION_IMPL,
        "ssd_impl": f"ssd ops impl={ssd_ops._IMPL}",
    }


def serve_phase(cfg, requests: int, prompt_len: int, gen_len: int,
                tol: float = None, matmul_precision: str = None) -> dict:
    """Serve one batch through ``serve`` and compare the last decode
    step's logits with one prefill over the prompt plus the generated
    tokens, with the same weights; with ``tol`` the error, as a share of
    the largest reference logit, must stay within it."""
    with (jax.default_matmul_precision(matmul_precision)
          if matmul_precision else nullcontext()):
        out = serve(cfg, requests=requests, prompt_len=prompt_len,
                    gen_len=gen_len, verbose=False)
        full = jnp.concatenate([out.prompts, jnp.asarray(out.tokens)],
                               axis=1)
        ref = jax.jit(lambda p, x: lm.prefill(cfg, p, x)[0])(out.params,
                                                               full)
    decode = out.talp.regions["decode"]
    if decode.host is None or not decode.device_states.get(0, {}).get(
            "kernel", 0.0) > 0:
        raise AssertionError("TALP decode region has no device records")
    err, rel = _max_err(out.logits, ref)
    if tol is not None and not rel <= tol:
        raise AssertionError(
            f"decode logits off the prefill reference: max |d| {err} is "
            f"{rel:.3g} of the largest logit (limit {tol})")
    steady = out.token_s[1:] if gen_len > 1 else out.token_s
    return {
        "config": cfg.name, "requests": requests, "prompt_len": prompt_len,
        "gen_len": gen_len, "hot_ring": cfg.decode_hot_len,
        "compute_dtype": cfg.compute_dtype,
        "matmul_precision": matmul_precision or "default",
        "limit_rel": tol,
        "first_token_step_s": float(out.token_s[0]),
        "steady_token_step_s": float(np.median(steady)),
        "max_logit_err": err, "max_logit_err_rel": rel,
        "argmax_agree": float(np.mean(
            np.argmax(np.asarray(out.logits), -1)
            == np.argmax(np.asarray(ref), -1))),
        "talp_host_parallel_eff": decode.host.parallel_efficiency,
        "attention_impl": MODEL_ATTENTION_IMPL,
        "ssd_impl": MODEL_SSD_PREFILL_IMPL + " (prefill), "
                    "ssd_decode_layer (decode)",
    }


def kernel_phase(attn: tuple, ssd: tuple, interpret: bool = False,
                 seed: int = 0) -> dict:
    """Run both Pallas kernels once and compare each with its reference
    (run in float32 at the highest matmul precision).

    ``attn`` is (batch, seq, heads, head_dim); ``ssd`` is (batch, length,
    heads, head_dim, state, chunk) with one B/C group."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32, bf16 = jnp.float32, jnp.bfloat16
    b, s, h, d = attn
    q, k, v = (jax.random.normal(kk, (b, s, h, d), bf16) for kk in ks[:3])
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        flash_attention(q, k, v, causal=True, interpret=interpret))
    attn_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = attention_reference(q.astype(f32), k.astype(f32),
                                  v.astype(f32), causal=True)
    attn_err, attn_rel = _max_err(out, ref)

    b, l, h, p, n, chunk = ssd
    x = jax.random.normal(ks[3], (b, l, h, p), bf16)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, l, h), f32))
    a = -jnp.exp(jax.random.normal(ks[5], (h,), f32) * 0.3)
    bm = jax.random.normal(ks[6], (b, l, 1, n), bf16)
    cm = jax.random.normal(ks[7], (b, l, 1, n), bf16)
    d_skip = jnp.full((h,), 0.5, f32)
    t0 = time.perf_counter()
    y = jax.block_until_ready(ssd_pallas(x, dt, a, bm, cm, chunk=chunk,
                                         d_skip=d_skip, interpret=interpret))
    ssd_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        y_ref = ssd_reference(x.astype(f32), dt, a, bm.astype(f32),
                              cm.astype(f32), chunk=chunk, d_skip=d_skip)
    ssd_err, ssd_rel = _max_err(y, y_ref)
    for name, rel in (("flash_attention", attn_rel), ("ssd", ssd_rel)):
        if not rel <= KERNEL_TOL:
            raise AssertionError(f"{name} off its reference by {rel:.3g} "
                                 f"of the largest value (limit {KERNEL_TOL})")
    return {
        "interpret": interpret,
        "flash_attention": {"shape_bshd": list(attn), "first_call_s": attn_s,
                            "max_err": attn_err, "max_err_rel": attn_rel},
        "ssd": {"shape_blhpnq": list(ssd), "first_call_s": ssd_s,
                "max_err": ssd_err, "max_err_rel": ssd_rel},
    }


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    zamba = get_config("zamba2-2.7b")
    mamba = get_config("mamba2-130m")
    phases = [
        ("train", functools.partial(train_phase, mamba, 4, 8, 2048)),
        ("serve", functools.partial(serve_phase, zamba, 8, 1024, 160)),
        ("serve_check", functools.partial(
            serve_phase, dataclasses.replace(zamba, compute_dtype="float32"),
            8, 1024, 160, tol=SERVE_CHECK_TOL, matmul_precision="highest")),
        ("kernels", functools.partial(
            kernel_phase,
            (8, 1024, zamba.num_heads, zamba.resolved_head_dim),
            (8, 2048, mamba.ssm_heads, mamba.ssm_head_dim, mamba.ssm_state,
             mamba.ssm_chunk))),
    ]
    for name, run in phases:
        c0, h0, m0 = counter.snapshot()
        t0 = time.perf_counter()
        info = run()
        c1, h1, m1 = counter.snapshot()
        stats = dev.memory_stats() or {}
        print(json.dumps({
            "phase": name, **info,
            "wall_s": time.perf_counter() - t0,
            "compile_s": c1 - c0, "cache_hits": h1 - h0,
            "cache_misses": m1 - m0,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "compile_cache_dir": cache_dir,
        }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
