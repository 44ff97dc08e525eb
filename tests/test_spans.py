"""Profiler spans: the span path itself, TALP's scopes as spans (balanced,
nested as the regions/states/sections are, joined to the step series by
index), the drivers' loop phases and the model's named scopes in a real
``jax.profiler`` trace of a smoke-size ``serve()``."""

import glob
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.backends.runtime import RuntimeBackend
from repro.core.report import to_json
from repro.core.states import DeviceActivity
from repro.core.talp import TalpMonitor
from repro.core.telemetry import overhead as ovh
from repro.core.telemetry import spans
from repro.core.telemetry.stepseries import StepSeriesRecorder

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class RecordingSink:
    """A sink (``sink(name, **args)`` -> context manager) that records each
    span's begin and end, in order."""

    def __init__(self):
        self.events = []            # ("B", name, args) / ("E", name, args)

    def __call__(self, name, **args):
        events = self.events

        class Span:
            def __enter__(self):
                events.append(("B", name, args))

            def __exit__(self, *exc):
                events.append(("E", name, args))

        return Span()

    def spans(self):
        """``(name, args, depth)`` of each span, checking that every span
        closes after all spans opened inside it (proper nesting)."""
        stack, out = [], []
        for kind, name, args in self.events:
            if kind == "B":
                out.append((name, args, len(stack)))
                stack.append(name)
            else:
                assert stack and stack[-1] == name, (name, stack)
                stack.pop()
        assert not stack, f"spans left open: {stack}"
        return out


@pytest.fixture
def sink():
    s = RecordingSink()
    prev = spans.install(s)
    yield s
    spans.install(prev)


@pytest.fixture
def no_sink():
    prev = spans.install(None)
    yield
    spans.install(prev)


# ---------------------------------------------------------------------------
# the span path
# ---------------------------------------------------------------------------
def test_no_sink_spans_are_no_ops(no_sink):
    assert spans.current() is None
    assert spans.begin("serve.fetch", step=1) is None
    spans.end(None)
    with spans.span("serve.fetch"):
        pass


def test_span_context_and_tokens_close_out_of_order(sink):
    with spans.span("serve.dispatch", step=3):
        a = spans.begin("a")
        b = spans.begin("b")
        spans.end(a)                 # tokens, not a stack
        spans.end(b)
    assert sink.events == [("B", "serve.dispatch", {"step": 3}),
                           ("B", "a", {}), ("B", "b", {}), ("E", "a", {}),
                           ("E", "b", {}), ("E", "serve.dispatch", {"step": 3})]


def test_a_span_outlives_a_change_of_sink(sink):
    tok = spans.begin("talp.offload")
    spans.install(None)
    spans.end(tok)                   # closed by the sink that opened it
    assert sink.events[-1] == ("E", "talp.offload", {})


def test_profiler_annotations_are_a_sink(no_sink):
    import jax

    spans.install(jax.profiler.TraceAnnotation)
    with spans.span("serve.fetch"):
        spans.end(spans.begin("talp.region.decode_step", step=2))
    assert spans.current() is jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# TALP's scopes as spans
# ---------------------------------------------------------------------------
def _decode_like(mon, clk, steps=4):
    """A serving loop's shape on a fake clock: a decode region, one
    decode_step region per token with an Offload wait and a device record,
    and an MPI scope on the last step."""
    with mon.region("decode"):
        for i in range(steps):
            with mon.region("decode_step"):
                clk.advance(0.001)
                t0 = clk.t
                with mon.offload():
                    clk.advance(0.004)
                mon.add_device_record(0, DeviceActivity.KERNEL, t0,
                                      t0 + 0.003 + 0.0005 * i)
                if i == steps - 1:
                    with mon.mpi():
                        clk.advance(0.002)
                clk.advance(0.0005)


def test_talp_spans_balance_nest_and_join_step_rows_by_index(sink):
    clk = FakeClock()
    mon = TalpMonitor("serve", clock=clk)
    rec = StepSeriesRecorder(mon, capacity=2, regions=("decode_step",))
    _decode_like(mon, clk, steps=5)
    rec.close()
    mon.finalize()
    got = sink.spans()
    names = [n for n, _, _ in got]
    region = [(a, d) for n, a, d in got if n == "talp.region.decode_step"]
    # the step= index of each window is the row index the recorder writes
    assert [a["step"] for a, _ in region] == list(range(5))
    rows = rec.series.rows()
    assert len(rows) == 2 and rec.series.n_dropped == 3   # ring of 2
    assert list(rows["step"]) == [3, 4]
    # regions nest: decode_step inside decode inside Global
    depth = {n: d for n, _, d in got}
    assert depth["talp.region.Global"] == 0
    assert depth["talp.region.decode"] == 1
    assert {d for _, d in region} == {2}
    # host states nest in the step, once per scope
    assert names.count("talp.offload") == 5 and names.count("talp.mpi") == 1
    assert depth["talp.offload"] == depth["talp.mpi"] == 3
    # the recorder's capture runs after its window's span closed, with
    # the sections it triggers nested inside it
    assert names.count("talp.capture.step") == 5
    i = names.index("talp.capture.step")
    assert got[i][2] == 2
    assert names[i + 1] == "talp.capture.flatten" and got[i + 1][2] == 3
    assert all(n.startswith("talp.") for n in names)


def test_overhead_sections_are_capture_spans(sink):
    acc = ovh.OverheadAccumulator(clock=FakeClock())
    with acc.section("sample"):
        t0 = acc.begin("flatten")
        acc.end("flatten", t0)
    assert [e[:2] for e in sink.events] == [
        ("B", "talp.capture.sample"), ("B", "talp.capture.flatten"),
        ("E", "talp.capture.flatten"), ("E", "talp.capture.sample")]
    assert acc.counts == {"flatten": 1, "sample": 1}


def test_backend_flush_is_not_a_section_of_its_own():
    """The backend's drain runs inside TALP's ``ingest`` section; it
    charges nothing by itself."""
    acc = ovh.OverheadAccumulator(clock=FakeClock())
    prev = ovh.install(acc)
    try:
        be = RuntimeBackend()
        be.start()
        be._record(0, DeviceActivity.KERNEL, 0.0, 1.0)
        (dev, kinds, *_), = be.flush_arrays()
    finally:
        ovh.install(prev)
    assert dev == 0 and list(kinds) == [DeviceActivity.KERNEL.code]
    assert acc.counts == {}


def _run(with_sink):
    prev = spans.install(RecordingSink() if with_sink else None)
    try:
        clk = FakeClock()
        mon = TalpMonitor("serve", clock=clk)
        rec = StepSeriesRecorder(mon, capacity=8, regions=("decode_step",))
        _decode_like(mon, clk)
        rec.close()
        return to_json(mon.finalize()), rec.series.to_arrays()
    finally:
        spans.install(prev)


def test_spans_leave_results_and_step_series_bit_identical():
    res_a, ser_a = _run(with_sink=True)
    res_b, ser_b = _run(with_sink=False)
    assert res_a == res_b
    assert sorted(ser_a) == sorted(ser_b)
    for k in ser_a:
        a, b = np.asarray(ser_a[k]), np.asarray(ser_b[k])
        assert a.tobytes() == b.tobytes(), k


# ---------------------------------------------------------------------------
# a real trace of serve(): program spans and the model's scopes
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    import jax

    from repro.configs import smoke_config
    from repro.launch.serve import serve

    cfg = smoke_config("mamba2-130m")
    kw = dict(requests=2, prompt_len=8, gen_len=4, verbose=False,
              talp_step_series=4)
    prev = spans.current()
    serve(cfg, **kw)                      # compile outside the trace
    out = tmp_path_factory.mktemp("serve_trace")
    jax.profiler.start_trace(str(out))
    try:
        serve(cfg, **kw)
    finally:
        jax.profiler.stop_trace()
        spans.install(prev)
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return path


def test_traced_serve_holds_the_program_spans(serve_trace):
    from jax.profiler import ProfileData

    names = {e.name for p in ProfileData.from_file(serve_trace).planes
             for line in p.lines for e in line.events}
    for want in ("talp.region.decode_step", "talp.offload",
                 "talp.capture.step", "serve.fetch", "serve.dispatch",
                 "serve.feed", "serve.sample"):
        assert want in names, want
    steps = sorted(dict(e.stats)["step"]
                   for p in ProfileData.from_file(serve_trace).planes
                   for line in p.lines for e in line.events
                   if e.name == "talp.region.decode_step")
    assert steps == [0, 1, 2, 3]


def test_traced_step_ops_map_to_model_scopes(serve_trace):
    """Through the trace's metadata plane (the compiled modules' HLO), the
    decode step's ops carry the ``ssm`` and ``head`` scopes."""
    sys.path.append(str(CHIP))
    import spans as chip_spans

    with open(serve_trace, "rb") as f:
        modules = chip_spans.hlo_op_names(f.read()).values()
    step = [names for module, names in modules if "serve_step" in module]
    assert len(step) == 1, sorted(module for module, _ in modules)
    scopes = {chip_spans.scope_of(op) for op in step[0].values()}
    assert {"ssm", "ssm/state_update", "head"} <= scopes
    assert "embed" in scopes
