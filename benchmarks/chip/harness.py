"""What every cell of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the guard on the program's widths, the
compile counter, the hooks through which the harness hands the program its
weights and data and reads its clock, and the device's own readings.

The harness drives the program's entry points as they are. It hands them
the benchmark's inputs by replacing, for the length of one call, the names
those entry points look up in their own modules (the weight initialiser,
the data pipeline, the runtime backend): the timed path itself is the
program's.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Refused(Exception):
    """The cell cannot be run as its files state it."""


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``<kind>/<name>.py`` under the benchmark's directory, as a module."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"chip_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload, bench=None):
    """The cell's entry, configuration file, traffic file and the metrics
    that it reports."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]

    return {
        "name": workload, "chips": cell["chips"], "config": config,
        "traffic": traffic, "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def _plain(v):
    return list(v) if isinstance(v, tuple) else v


def program_config(config, smoke=False):
    """The program's configuration of the cell's model, refused unless it
    holds every width the configuration file states. ``smoke`` returns the
    program's CPU-sized variant of the same family (tests only)."""
    from repro.configs import get_config, smoke_config

    cfg = get_config(config["name"])
    diff = {k: [v, _plain(getattr(cfg, k, None))]
            for k, v in config["model"].items()
            if _plain(getattr(cfg, k, None)) != v}
    if diff:
        raise Refused(f"program's {config['name']} differs from "
                      f"{config['name']}.json (file, program): {diff}")
    return smoke_config(config["name"]) if smoke else cfg


def spec_of(cfg, config):
    """The widths the reference reads, taken from ``cfg`` for the keys the
    configuration file states."""
    return {k: _plain(getattr(cfg, k)) for k in config["model"]}


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------
class CompileCounter:
    """Host times of backend compiles and persistent-cache loads, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compiles, self.loads = [], []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads.append(time.perf_counter())

    def inside(self, t0, t1):
        """Programs compiled or loaded from the cache within [t0, t1]."""
        return sum(t0 <= t <= t1 for t in self.compiles + self.loads)


_COUNTER = []


def compile_counter():
    """The process's one counter (JAX keeps its listeners for good)."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


# ---------------------------------------------------------------------------
# hooks into the program
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def replaced(module, **names):
    """Replace module attributes for the length of the block."""
    old = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


class Hooks:
    """The clock the harness keeps on the program's calls, and the traced
    span. ``launches[name]`` holds the host time at each dispatch of that
    name; ``after_wait`` callbacks see each completed call's output."""

    def __init__(self, trace_dir=None, trace_seconds=0.0):
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.trace_from = None          # "<name>#<count>" that starts the span
        self.span = None                # [t_start, t_stop] on the host clock
        self.span_calls = []            # launch keys inside the span
        self.launches = {}
        self.after_wait = []
        self._tracing = False

    def reset(self):
        self.launches = {}
        self.after_wait = []

    def _before_launch(self, name):
        import jax

        now = time.perf_counter()
        key = f"{name}#{len(self.launches.get(name, ()))}"
        if self.trace_dir and key == self.trace_from and not self._tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
            now = time.perf_counter()
            self.span = [now, None]
        elif self._tracing and now - self.span[0] >= self.trace_seconds:
            self.stop_trace()
            now = time.perf_counter()
        if self._tracing:
            self.span_calls.append(key)
        self.launches.setdefault(name, []).append(now)
        return key

    def stop_trace(self):
        import jax

        if self._tracing:
            self.span[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self._tracing = False

    def backend(self, base):
        """A subclass of the program's runtime backend that keeps this
        clock and annotates launch and wait in the profiler's trace."""
        hooks = self

        class Backend(base):
            def launch(self, fn, *args, name="", **kwargs):
                import jax

                key = hooks._before_launch(name)
                with jax.profiler.TraceAnnotation("bench.launch"):
                    handle = super().launch(fn, *args, name=name, **kwargs)
                handle.bench_key = key
                return handle

            def wait(self, handle):
                import jax

                with jax.profiler.TraceAnnotation("bench.wait"):
                    out = super().wait(handle)
                for fn in hooks.after_wait:
                    fn(handle.bench_key, out)
                return out

        return Backend


# ---------------------------------------------------------------------------
# device readings
# ---------------------------------------------------------------------------
def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def step_rows(spool_dir):
    """The TALP step series the program spooled: (rows, region names)."""
    paths = sorted(Path(spool_dir).glob("talp_steps_rank*.npz"))
    if not paths:
        return None, []
    with np.load(paths[0], allow_pickle=False) as npz:
        return npz["rows"], [str(r) for r in npz["regions"]]


def window_rows(rows, region_names, region, t0, t1):
    """Step rows of ``region`` that opened and closed within [t0, t1]."""
    if rows is None or region not in region_names:
        return None
    r = rows[rows["region"] == region_names.index(region)]
    return r[(r["t_open"] >= t0) & (r["t_close"] <= t1)]


def finite(x):
    return x is not None and math.isfinite(x)
