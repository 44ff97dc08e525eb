"""One TALP session for a driver loop.

:class:`TalpSession` holds the TALP work that the training and serving
drivers share: the monitor and what hangs off it, the mid-run samples,
and the teardown that writes the reports. The drivers keep their loops.
They open regions and host states on ``session.monitor`` themselves,
wrap each iteration in ``session.step()`` and call ``session.tick(i)``
after it.
"""

from __future__ import annotations

import argparse
import time
from contextlib import nullcontext

import jax

from ..core.collect import FaultPlan
from ..core.merge import FileSpoolTransport, emit_job_report
from ..core.report import render_tables, to_json
from ..core.talp import TalpMonitor, TalpResult
from ..core.telemetry import spans
from ..core.telemetry.exporter import TelemetryExporter
from ..core.telemetry.stepseries import StepSeriesRecorder
from ..core.telemetry.traceexport import export_monitor
from ..core.telemetry.watchdog import EfficiencyWatchdog
from .steps import step_flop_model

__all__ = ["TalpSession", "add_arguments", "options_from"]


class TalpSession:
    """The TALP monitor of one driver run, with its exporters.

    ``name`` names the monitor. ``step_region`` is the region that
    :meth:`step` opens around one iteration of the loop, and ``unit``
    names an iteration in the sample lines (``step`` or ``token``).
    ``backend`` is the driver's device backend. ``flop`` is the driver's
    ``(cfg, ShapeConfig)`` of one step; its flop model is built only when
    a step series is kept. ``verbose`` prints the report, the sample
    lines and what the options below write.

    Options (the drivers' ``--talp-*``, ``--rank`` and ``--world-size``
    flags, from :func:`add_arguments`):

    ``rank``, ``world_size``
        This process's place in a multi-process job.
    ``talp_json``
        Write this rank's report as JSON at exit.
    ``talp_spool``
        A directory that every rank of the job shares. Each rank spools
        its report there (binary ``.npz``), and whichever rank completes
        the spool last merges it into the job-level report
        (``talp_job.json``).
    ``talp_sample_every``
        Every N iterations take a non-destructive snapshot of all
        regions (TALP's online mode). With a ``talp_spool`` the snapshot
        is published to the spool and merged across the ranks that have
        reported so far: a job-level mid-run report.
    ``talp_trace_out``
        Write a Chrome/Perfetto trace of this rank at exit.
    ``talp_metrics_jsonl``
        Stream every snapshot as one JSON line to this file.
    ``talp_prometheus_port``
        Serve the latest snapshot as Prometheus text on ``/metrics``
        (0 = ephemeral port).
    ``talp_step_series``
        Keep the last N per-step metric rows: ``step_region`` wraps each
        iteration and its close is captured into a columnar ring. With a
        ``talp_spool`` the ring is spooled and rank-aligned into a
        job-level per-step table. On a TPU the step's model FLOPs over
        the device's published peak feed the measured Computational
        Efficiency annotation; elsewhere that annotation is absent.
    ``talp_watchdog``
        Run the online anomaly watchdog over those rows (implies a step
        series).
    ``talp_anomaly_log``
        Stream the watchdog's events as JSONL to this file (implies the
        watchdog).
    ``talp_fault_plan``
        A :class:`~repro.core.collect.FaultPlan` spec (inline JSON or a
        file path) that deterministically injects collection faults for
        this rank, for debugging the fault-tolerant collection path:
        drop, delay or corrupt the spool submit, or skew the monitor
        clock.
    """

    def __init__(
        self,
        name: str,
        *,
        step_region: str,
        backend,
        flop,
        unit: str = "step",
        verbose: bool = True,
        rank: int = 0,
        world_size: int = 1,
        talp_json: str = None,
        talp_spool: str = None,
        talp_sample_every: int = 0,
        talp_trace_out: str = None,
        talp_metrics_jsonl: str = None,
        talp_prometheus_port: int = None,
        talp_step_series: int = 0,
        talp_watchdog: bool = False,
        talp_anomaly_log: str = None,
        talp_fault_plan=None,
    ):
        spans.install(jax.profiler.TraceAnnotation)
        self.rank, self.world_size = rank, world_size
        self._step_region, self._unit = step_region, unit
        self._verbose = verbose
        self._json, self._trace_out = talp_json, talp_trace_out
        self._sample_every = talp_sample_every
        self._fault_plan = (FaultPlan.from_spec(talp_fault_plan)
                            if talp_fault_plan is not None else None)
        clock = time.perf_counter
        if self._fault_plan is not None:
            skew = self._fault_plan.skew_s(rank)
            if skew:
                clock = lambda: time.perf_counter() + skew  # noqa: E731
            if verbose and self._fault_plan.touches(rank):
                print(f"[talp fault] rank {rank} plan: "
                      f"{self._fault_plan.describe(rank)}")
        want_steps = bool(talp_step_series or talp_watchdog
                          or talp_anomaly_log)
        flop_model = (step_flop_model(*flop, world_size) if want_steps
                      else None)
        self.monitor = mon = TalpMonitor(
            name, rank=rank, clock=clock, backend=backend,
            overhead_report=True, flop_model=flop_model)
        self._watchdog = (EfficiencyWatchdog(jsonl=talp_anomaly_log)
                          if talp_watchdog or talp_anomaly_log else None)
        self._recorder = (
            StepSeriesRecorder(mon, capacity=talp_step_series or 4096,
                               regions=(step_region,),
                               watchdog=self._watchdog)
            if want_steps else None)
        self._spool = (FileSpoolTransport(talp_spool, world_size=world_size)
                       if talp_spool else None)
        self._telemetry = None
        if (talp_metrics_jsonl or talp_prometheus_port is not None
                or talp_trace_out):
            self._telemetry = TelemetryExporter(
                mon, jsonl=talp_metrics_jsonl, watchdog=self._watchdog)
            if talp_prometheus_port is not None:
                port = self._telemetry.serve(port=talp_prometheus_port)
                if verbose:
                    print(f"[talp] prometheus exposition on :{port}/metrics")

    def step(self):
        """The region around one iteration. It is opened only when a step
        series is kept: its close is what the recorder and the watchdog
        capture."""
        if self._recorder is None:
            return nullcontext()
        return self.monitor.region(self._step_region)

    def tick(self, i: int) -> None:
        """After iteration ``i``: take the mid-run sample when it is due."""
        if self._sample_every and (i + 1) % self._sample_every == 0:
            self._sample(i)

    def _sample(self, i: int) -> None:
        # Through the exporter when there is one, so that the snapshot
        # also lands in its ring and in the JSONL/Prometheus stream.
        snapshot = (self._telemetry.sample().result
                    if self._telemetry is not None
                    else self.monitor.sample_result())
        if self._spool is not None:
            self._spool.submit_sample(snapshot, rank=self.rank)
            snapshot = self._spool.merge_samples(name=self.monitor.name)
        if self._verbose:
            g = snapshot.regions.get(TalpMonitor.GLOBAL)
            if g is not None and g.host is not None:
                print(f"[talp sample] {self._unit} {i} "
                      f"ranks={g.n_ranks} devices={g.n_devices} "
                      f"PE_host={g.host.parallel_efficiency:.3f}")

    def finish(self) -> TalpResult:
        """Close the monitor, write every report the options ask for and
        return this rank's result."""
        mon, rec, dog, tel = (self.monitor, self._recorder, self._watchdog,
                              self._telemetry)
        if tel is not None:
            # Final snapshot while the monitor still runs: the stream's
            # last record and the post-mortem report describe the same
            # window.
            tel.sample()
        if rec is not None:
            rec.close()   # detach before finalize's Global close
        result = mon.finalize()
        if self._trace_out:
            with open(self._trace_out, "w") as f:
                f.write(export_monitor(
                    mon, result=result,
                    samples=tel.trace_samples() if tel else None,
                    step_series=rec.series if rec else None,
                    anomalies=dog.events if dog else None,
                ))
            if self._verbose:
                print(f"[talp] wrote Chrome trace: {self._trace_out}")
        if tel is not None:
            tel.close()
        if self._verbose:
            print(render_tables(result))
            if dog is not None and dog.events:
                print(f"[talp watchdog] {len(dog.events)} anomaly "
                      f"event(s); first: {dog.events[0].as_dict()}")
        if self._json:
            with open(self._json, "w") as f:
                f.write(to_json(result))
        if self._spool is not None:
            if rec is not None:
                self._spool.submit_steps(rec.series, rank=self.rank)
            emit_job_report(result, self._spool.spool_dir, self.rank,
                            self.world_size, verbose=self._verbose,
                            timelines=mon.devices, fault_plan=self._fault_plan)
        if dog is not None:
            dog.close()
        return result


# The flags of the session's options, described in its docstring; a
# flag left out takes the session's default.
_FLAGS = (
    ("--rank", dict(type=int)),
    ("--world-size", dict(type=int)),
    ("--talp-json", dict(metavar="FILE")),
    ("--talp-spool", dict(metavar="DIR")),
    ("--talp-sample-every", dict(type=int, metavar="N")),
    ("--talp-trace-out", dict(metavar="FILE")),
    ("--talp-metrics-jsonl", dict(metavar="FILE")),
    ("--talp-prometheus-port", dict(type=int, metavar="PORT")),
    ("--talp-step-series", dict(type=int, metavar="N")),
    ("--talp-watchdog", dict(action="store_true")),
    ("--talp-anomaly-log", dict(metavar="FILE")),
    ("--talp-fault-plan", dict(metavar="SPEC")),
)
_OPTIONS = tuple(flag[2:].replace("-", "_") for flag, _ in _FLAGS)


def add_arguments(ap: argparse.ArgumentParser) -> None:
    """Add the session's flags to a driver's parser."""
    group = ap.add_argument_group(
        "TALP", "options of repro.launch.session.TalpSession")
    for flag, kw in _FLAGS:
        group.add_argument(flag, default=argparse.SUPPRESS, **kw)


def options_from(args: argparse.Namespace) -> dict:
    """The session options among parsed arguments."""
    return {k: getattr(args, k) for k in _OPTIONS if hasattr(args, k)}
