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
from contextlib import nullcontext
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ShapeConfig, get_config, list_configs, smoke_config
from ..core.backends import RuntimeBackend
from ..core.merge import FileSpoolTransport, emit_job_report
from ..core.report import render_tables, to_json
from ..core.talp import TalpMonitor, TalpResult
from ..core.telemetry import spans
from ..models import lm
from .compile_cache import enable_compile_cache
from .steps import (
    init_serve_params, make_prefill_step, make_serve_step, step_flop_model,
)

__all__ = ["ServeResult", "serve", "main"]


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
    talp_json: str = None,
    verbose: bool = True,
    rank: int = 0,
    world_size: int = 1,
    talp_spool: str = None,
    talp_sample_every: int = 0,
    talp_spool_format: str = "binary",
    talp_trace_out: str = None,
    talp_metrics_jsonl: str = None,
    talp_prometheus_port: int = None,
    talp_step_series: int = 0,
    talp_watchdog: bool = False,
    talp_anomaly_log: str = None,
    talp_fault_plan=None,
):
    """Serve a batch of requests. Multi-rank serving fleets: pass
    ``rank``/``world_size`` and a shared ``talp_spool`` dir to get one
    job-level TALP report across all serving processes.
    ``talp_sample_every=N`` publishes a mid-run snapshot every N decoded
    tokens (merged across ranks when a spool is given).

    Observability mirrors :func:`repro.launch.train.train`:
    ``talp_trace_out`` (Chrome/Perfetto trace at exit),
    ``talp_metrics_jsonl`` (snapshot stream), ``talp_prometheus_port``
    (opt-in ``/metrics`` endpoint — the natural fit for a long-lived
    serving process). ``talp_step_series``/``talp_watchdog``/
    ``talp_anomaly_log`` mirror the training driver at decode-token
    resolution: each decode iteration runs in a nested ``decode_step``
    region whose close feeds the per-step ring and the anomaly
    watchdog. On a TPU the decode step's model FLOPs over the device's
    published peak feed the measured Computational Efficiency
    annotation. ``talp_fault_plan`` injects
    deterministic collection faults for this rank (debug) — see
    :class:`repro.core.collect.FaultPlan`."""
    from ..core.collect import FaultPlan

    spans.install(jax.profiler.TraceAnnotation)
    fault_plan = (FaultPlan.from_spec(talp_fault_plan)
                  if talp_fault_plan is not None else None)
    clock = time.perf_counter
    if fault_plan is not None:
        skew = fault_plan.skew_s(rank)
        if skew:
            clock = lambda: time.perf_counter() + skew  # noqa: E731
        if verbose and fault_plan.touches(rank):
            print(f"[talp fault] rank {rank} plan: "
                  f"{fault_plan.describe(rank)}")
    backend = RuntimeBackend()
    want_steps = bool(talp_step_series or talp_watchdog or talp_anomaly_log)
    flop_model = step_flop_model(
        cfg, ShapeConfig(name="serve", seq_len=prompt_len + gen_len,
                         global_batch=requests, kind="decode"),
        world_size,
    ) if want_steps else None
    mon = TalpMonitor("serve", rank=rank, clock=clock, backend=backend,
                      overhead_report=True, flop_model=flop_model)
    step_recorder = step_watchdog = None
    if want_steps:
        from ..core.telemetry.stepseries import StepSeriesRecorder

        if talp_watchdog or talp_anomaly_log:
            from ..core.telemetry.watchdog import EfficiencyWatchdog

            step_watchdog = EfficiencyWatchdog(jsonl=talp_anomaly_log)
        step_recorder = StepSeriesRecorder(
            mon, capacity=talp_step_series or 4096,
            regions=("decode_step",), watchdog=step_watchdog,
        )
    sample_transport = (
        FileSpoolTransport(talp_spool, world_size=world_size,
                           payload=talp_spool_format)
        if talp_spool and talp_sample_every else None
    )
    telemetry = None
    if talp_metrics_jsonl or talp_prometheus_port is not None or talp_trace_out:
        from ..core.telemetry.exporter import TelemetryExporter

        telemetry = TelemetryExporter(mon, jsonl=talp_metrics_jsonl,
                                      watchdog=step_watchdog)
        if talp_prometheus_port is not None:
            port = telemetry.serve(port=talp_prometheus_port)
            if verbose:
                print(f"[talp] prometheus exposition on :{port}/metrics")

    def sample_snapshot(tag: str) -> None:
        snapshot = (
            telemetry.sample().result if telemetry is not None
            else mon.sample_result()
        )
        if sample_transport is not None:
            sample_transport.submit_sample(snapshot, rank=rank)
            job_snap = sample_transport.merge_samples(name=mon.name)
        else:
            job_snap = snapshot
        if verbose:
            g = job_snap.regions.get(TalpMonitor.GLOBAL)
            if g is not None and g.host is not None:
                print(f"[talp sample] {tag} "
                      f"ranks={g.n_ranks} devices={g.n_devices} "
                      f"PE_host={g.host.parallel_efficiency:.3f}")
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
            with (mon.region("decode_step") if step_recorder is not None
                  else nullcontext()):
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
            if talp_sample_every and (t + 1) % talp_sample_every == 0:
                sample_snapshot(f"token {t}")

    if telemetry is not None:
        telemetry.sample()  # last stream record covers the full window
    if step_recorder is not None:
        step_recorder.close()   # detach before finalize's Global close
    result = mon.finalize()
    if talp_trace_out:
        from ..core.telemetry.traceexport import export_monitor

        with open(talp_trace_out, "w") as f:
            f.write(export_monitor(
                mon, result=result,
                samples=telemetry.trace_samples() if telemetry else None,
                step_series=(step_recorder.series
                             if step_recorder is not None else None),
                anomalies=(step_watchdog.events
                           if step_watchdog is not None else None),
            ))
        if verbose:
            print(f"[talp] wrote Chrome trace: {talp_trace_out}")
    if telemetry is not None:
        telemetry.close()
    if verbose:
        print(render_tables(result))
        if step_watchdog is not None and step_watchdog.events:
            print(f"[talp watchdog] {len(step_watchdog.events)} anomaly "
                  f"event(s); first: {step_watchdog.events[0].as_dict()}")
    if talp_json:
        with open(talp_json, "w") as f:
            f.write(to_json(result))
    if talp_spool and step_recorder is not None:
        steps_transport = sample_transport or FileSpoolTransport(
            talp_spool, world_size=world_size, payload=talp_spool_format)
        steps_transport.submit_steps(step_recorder.series, rank=rank)
    if talp_spool:
        emit_job_report(result, talp_spool, rank, world_size, verbose=verbose,
                        payload=talp_spool_format, timelines=mon.devices,
                        fault_plan=fault_plan)
    if step_watchdog is not None:
        step_watchdog.close()
    return ServeResult(np.stack(tokens_out, axis=1), result, params,
                       prompts, logits, token_s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs(), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--talp-json", default=None)
    ap.add_argument("--talp-sample-every", type=int, default=0,
                    help="every N decoded tokens publish a mid-run snapshot "
                         "and (with --talp-spool) merge a job-level report")
    ap.add_argument("--talp-spool", default=None,
                    help="shared dir for per-rank reports + job-level merge")
    ap.add_argument("--talp-spool-format", choices=("binary", "json"),
                    default="binary",
                    help="spool payload: versioned binary .npz (default) "
                         "or legacy JSON")
    ap.add_argument("--talp-trace-out", default=None,
                    help="write a Chrome/Perfetto trace JSON at exit")
    ap.add_argument("--talp-metrics-jsonl", default=None,
                    help="stream every TALP snapshot as one JSON line")
    ap.add_argument("--talp-prometheus-port", type=int, default=None,
                    help="serve the latest snapshot as Prometheus text "
                         "(0 = ephemeral port)")
    ap.add_argument("--talp-step-series", type=int, default=0,
                    help="keep the last N per-decode-step metric rows "
                         "(columnar ring; spooled with --talp-spool)")
    ap.add_argument("--talp-watchdog", action="store_true",
                    help="run the online efficiency anomaly watchdog over "
                         "per-decode-step rows (implies a step series)")
    ap.add_argument("--talp-anomaly-log", default=None,
                    help="stream watchdog anomaly events as JSONL "
                         "(implies --talp-watchdog)")
    ap.add_argument("--talp-fault-plan", default=None, metavar="SPEC",
                    help="deterministic collection-fault injection for "
                         "this rank (debug): inline JSON or a JSON file "
                         "with drop/truncate/corrupt/delay/clock_skew "
                         "sections keyed by rank id")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world-size", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    t0 = time.time()
    tokens = serve(cfg, args.requests, args.prompt_len, args.gen_len,
                   talp_json=args.talp_json, rank=args.rank,
                   world_size=args.world_size, talp_spool=args.talp_spool,
                   talp_sample_every=args.talp_sample_every,
                   talp_spool_format=args.talp_spool_format,
                   talp_trace_out=args.talp_trace_out,
                   talp_metrics_jsonl=args.talp_metrics_jsonl,
                   talp_prometheus_port=args.talp_prometheus_port,
                   talp_step_series=args.talp_step_series,
                   talp_watchdog=args.talp_watchdog,
                   talp_anomaly_log=args.talp_anomaly_log,
                   talp_fault_plan=args.talp_fault_plan).tokens
    dt = time.time() - t0
    n = tokens.size
    print(f"generated {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s)")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
