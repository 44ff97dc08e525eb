"""End-to-end training driver with TALP monitoring as a first-class
feature.

Every step runs under TALP regions/states:
  * host *Useful*  — data synthesis + python control,
  * *Offload*      — device dispatch + blocked-on-device time (with a
                     device Kernel record via the runtime backend),
  * *MPI*          — cross-process control-plane waits (checkpoint
                     barrier in multi-process runs; ~0 single-process),
and the paper's text/JSON report is emitted at exit and every
``--talp-interval`` steps (TALP's online mode). The batch and the
dispatch (``train.batch``, ``train.dispatch``) and TALP's own scopes are
profiler spans, on the device trace's clock. Checkpoint/restart and
straggler detection are integrated (fault tolerance), and the data
pipeline prefetches in the background.

Usage (CPU-sized):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs import ShapeConfig, get_config, list_configs, smoke_config
from ..core.backends import RuntimeBackend
from ..core.merge import FileSpoolTransport, emit_job_report
from ..core.report import render_tables, to_json
from ..core.talp import TalpMonitor
from ..core.telemetry import spans
from ..data.pipeline import DataConfig, SyntheticTokenPipeline
from ..optim.adamw import AdamWConfig
from ..runtime.fault_tolerance import StragglerDetector
from .compile_cache import enable_compile_cache
from .steps import (
    init_train_state, make_train_step, step_flop_model, train_state_shapes,
)

__all__ = ["train", "main"]


def train(
    cfg,
    steps: int = 50,
    global_batch: int = 8,
    seq_len: int = 128,
    ckpt_dir: str = None,
    ckpt_every: int = 20,
    talp_interval: int = 0,
    talp_json: str = None,
    opt_cfg: AdamWConfig = None,
    fail_at_step: int = None,   # failure injection (tests)
    seed: int = 0,
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
    """Train a (usually reduced) config; returns (state, history, talp).

    Multi-rank jobs: give each process its ``rank``/``world_size`` and a
    shared ``talp_spool`` directory — every rank spools its per-process
    TALP report there, and whichever rank completes the spool last merges
    it into the job-level report (``talp_job.json``).

    ``talp_sample_every=N`` additionally takes a non-destructive
    all-regions snapshot every N steps (``TalpMonitor.sample_result``);
    with a ``talp_spool`` the snapshot is published to the spool and
    merged across whichever ranks have reported so far — a *job-level*
    mid-run TALP report, TALP's online mode at job scope.

    Observability: ``talp_trace_out`` writes a Chrome/Perfetto trace of
    this rank at exit; ``talp_metrics_jsonl`` streams every snapshot as
    one JSON line; ``talp_prometheus_port`` serves the latest snapshot
    as Prometheus text on ``/metrics`` (0 = ephemeral port). The report
    carries the measured ``talp_overhead`` annotation.

    Per-step attribution: ``talp_step_series=N`` keeps the last N
    per-step metric rows (a ``step`` region wraps each iteration and its
    close is captured into a columnar ring; with a ``talp_spool`` the
    ring is spooled and rank-aligned into a job-level per-step table).
    ``talp_watchdog`` runs the online anomaly watchdog over those rows;
    ``talp_anomaly_log`` streams its events as JSONL (either implies the
    step series). On a TPU the step's model FLOPs over the device's
    published peak feed the measured Computational Efficiency annotation;
    elsewhere that annotation is absent.

    Debugging the fault-tolerant collection path: ``talp_fault_plan`` (a
    :class:`~repro.core.collect.FaultPlan` spec — inline JSON or a file
    path) deterministically injects collection failures for this rank:
    drop/delay/corrupt the spool submit, or skew the monitor clock.
    """
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
    opt_cfg = opt_cfg or AdamWConfig(warmup_steps=10, total_steps=steps)
    backend = RuntimeBackend()
    want_steps = bool(talp_step_series or talp_watchdog or talp_anomaly_log)
    flop_model = step_flop_model(
        cfg, ShapeConfig(name="train", seq_len=seq_len,
                         global_batch=global_batch, kind="train"),
        world_size,
    ) if want_steps else None
    mon = TalpMonitor("train", rank=rank, clock=clock, backend=backend,
                      overhead_report=True, flop_model=flop_model)
    step_recorder = step_watchdog = None
    if want_steps:
        from ..core.telemetry.stepseries import StepSeriesRecorder

        if talp_watchdog or talp_anomaly_log:
            from ..core.telemetry.watchdog import EfficiencyWatchdog

            step_watchdog = EfficiencyWatchdog(jsonl=talp_anomaly_log)
        step_recorder = StepSeriesRecorder(
            mon, capacity=talp_step_series or 4096,
            regions=("step",), watchdog=step_watchdog,
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

    data = SyntheticTokenPipeline(
        DataConfig(
            global_batch=global_batch,
            seq_len=seq_len,
            vocab_size=cfg.vocab_size,
            embed_dim=cfg.d_model if cfg.frontend == "embed" else 0,
            seed=seed,
        ),
        process_index=rank,
        process_count=world_size,
    )

    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=0)
    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    detector = StragglerDetector()

    # --- init or resume ---------------------------------------------------
    start_step = 0
    state = None
    if manager is not None:
        state, start_step = manager.restore_latest(train_state_shapes(cfg))
    if state is None:
        with mon.region("init"):
            state = init_train_state(cfg, jax.random.PRNGKey(seed))
            state = jax.block_until_ready(state)
        start_step = 0

    history = []
    with mon.region("train_loop"):
        for step in range(start_step, steps):
            t0 = time.perf_counter()
            if fail_at_step is not None and step == fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            # A nested per-step region only when the step series is on:
            # its close is what the recorder/watchdog capture.
            with (mon.region("step") if step_recorder is not None
                  else nullcontext()):
                # host Useful: data synthesis (prefetch keeps this short)
                with spans.span("train.batch"):
                    batch = data.batch_at(step)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                # Offload: dispatch + block (async launch → kernel record)
                with spans.span("train.dispatch"):
                    handle = backend.launch(step_fn, state, batch,
                                            name="train_step")
                with mon.offload():
                    state, metrics = backend.wait(handle)
                if manager is not None and (step + 1) % ckpt_every == 0:
                    # snapshot is sync (short), file write is async
                    with mon.mpi():   # control-plane barrier analogue
                        manager.save(step, state)
            dt = time.perf_counter() - t0
            detector.observe(step, dt)
            history.append(
                {"step": step, "loss": float(metrics["loss"]),
                 "grad_norm": float(metrics["grad_norm"]), "time_s": dt}
            )
            if talp_interval and (step + 1) % talp_interval == 0 and verbose:
                snap = mon.sample("train_loop")
                print(f"[talp online] step {step} "
                      f"PE_host={snap.host.parallel_efficiency:.3f} "
                      f"OE={snap.host.device_offload_efficiency:.3f}")
            if talp_sample_every and (step + 1) % talp_sample_every == 0:
                # Through the telemetry exporter when one is attached,
                # so the snapshot also lands in the ring buffer and the
                # JSONL/Prometheus stream.
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
                        print(f"[talp sample] step {step} "
                              f"ranks={g.n_ranks} devices={g.n_devices} "
                              f"PE_host={g.host.parallel_efficiency:.3f}")
            if verbose and (step % 10 == 0 or step == steps - 1):
                print(f"step {step:5d} loss {history[-1]['loss']:.4f} "
                      f"({dt*1e3:.0f} ms)")
                sys.stdout.flush()

    if manager is not None:
        manager.save(steps - 1, state)
        manager.wait()
    data.stop()
    if telemetry is not None:
        # Final snapshot while the monitor still runs: the stream's last
        # record and the post-mortem report describe the same window.
        telemetry.sample()
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
        if detector.events:
            print(f"straggler events at steps: {detector.events}")
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
    return state, history, result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs(), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--talp-interval", type=int, default=0)
    ap.add_argument("--talp-sample-every", type=int, default=0,
                    help="every N steps publish a mid-run snapshot and "
                         "(with --talp-spool) merge a job-level report")
    ap.add_argument("--talp-json", default=None)
    ap.add_argument("--talp-spool", default=None,
                    help="shared dir for per-rank reports + job-level merge")
    ap.add_argument("--talp-spool-format", choices=("binary", "json"),
                    default="binary",
                    help="spool payload: versioned binary .npz (default) "
                         "or legacy JSON")
    ap.add_argument("--talp-trace-out", default=None,
                    help="write a Chrome/Perfetto trace JSON of this rank "
                         "at exit")
    ap.add_argument("--talp-metrics-jsonl", default=None,
                    help="stream every TALP snapshot as one JSON line to "
                         "this file")
    ap.add_argument("--talp-prometheus-port", type=int, default=None,
                    help="serve the latest snapshot as Prometheus text on "
                         "this port (0 = ephemeral)")
    ap.add_argument("--talp-step-series", type=int, default=0,
                    help="keep the last N per-step metric rows (columnar "
                         "ring; spooled + rank-aligned with --talp-spool)")
    ap.add_argument("--talp-watchdog", action="store_true",
                    help="run the online efficiency anomaly watchdog over "
                         "the per-step rows (implies a step series)")
    ap.add_argument("--talp-anomaly-log", default=None,
                    help="stream watchdog anomaly events as JSONL to this "
                         "file (implies --talp-watchdog)")
    ap.add_argument("--talp-fault-plan", default=None, metavar="SPEC",
                    help="deterministic collection-fault injection for "
                         "this rank (debug): inline JSON or a JSON file "
                         "with drop/truncate/corrupt/delay/clock_skew "
                         "sections keyed by rank id")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world-size", type=int, default=1)
    ap.add_argument("--history-json", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, history, _ = train(
        cfg,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        talp_interval=args.talp_interval,
        talp_json=args.talp_json,
        rank=args.rank,
        world_size=args.world_size,
        talp_spool=args.talp_spool,
        talp_sample_every=args.talp_sample_every,
        talp_spool_format=args.talp_spool_format,
        talp_trace_out=args.talp_trace_out,
        talp_metrics_jsonl=args.talp_metrics_jsonl,
        talp_prometheus_port=args.talp_prometheus_port,
        talp_step_series=args.talp_step_series,
        talp_watchdog=args.talp_watchdog,
        talp_anomaly_log=args.talp_anomaly_log,
        talp_fault_plan=args.talp_fault_plan,
    )
    if args.history_json:
        with open(args.history_json, "w") as f:
            json.dump(history, f)
    losses = [h["loss"] for h in history]
    if losses and not (np.isfinite(losses[-1]) and losses[-1] < losses[0]):
        print("WARNING: loss did not decrease")


if __name__ == "__main__":
    main()
