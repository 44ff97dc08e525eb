"""The reduction of a trace to the program's layers (``spans.py``) and the
four per-step figures it gives: the innermost-once rule for idle time on
hand-built events, scope attribution on a trace recorded here, what each
figure reads where the trace has nothing for it, and one cell traced with
its trace kept for the reduction while ``run.py`` reads as before."""

import time

import jax
import jax.numpy as jnp
import pytest

import harness
import spans
import xplane
from conftest import SMOKE_TRAFFIC

DECODE = "mamba2-130m.decode.b256-p512-g1536"
FIGURES = ("ssm_device", "unscoped_device", "loop_idle", "talp_idle")


# ---------------------------------------------------------------------------
# idle time under the innermost span
# ---------------------------------------------------------------------------
def test_an_idle_instant_counts_once_for_the_innermost_span():
    host = [("talp.region.decode_step", 0.0, 10.0),
            ("serve.fetch", 1.0, 3.0),
            ("talp.offload", 4.0, 8.0),
            ("talp.capture.step", 10.5, 11.5),
            ("talp.capture.flatten", 11.0, 11.2)]
    gaps = [(0.5, 2.0), (2.5, 4.5), (9.0, 12.0)]
    got = spans.idle_by_span(gaps, host)
    assert got == pytest.approx({
        "talp.region.decode_step": 0.5 + 1.0 + 1.0,
        "serve.fetch": 1.0 + 0.5,
        "talp.offload": 0.5,
        "talp.capture.step": 0.8,
        "talp.capture.flatten": 0.2,
        "no span": 0.5 + 0.5})
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))


def test_among_spans_that_begin_together_the_shortest_is_innermost():
    segs = spans.innermost_segments([("outer", 0.0, 4.0), ("inner", 0.0, 1.0)])
    assert segs == [(0.0, 1.0, "inner"), (1.0, 4.0, "outer")]
    assert spans.idle_by_span([(0.0, 4.0)], []) == {"no span": 4.0}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(serve_step)/while/body/ssm/state_update/mul", "ssm/state_update"),
    ("jit(step)/transpose(jvp(ssm))/state_update/dot_general", "ssm/state_update"),
    ("jit(step)/checkpoint/ffn/dot_general", "ffn"),
    ("jit(serve_step)/head/dot_general", "head"),
    ("jit(serve_step)/while/body/dynamic_update_slice", "unscoped"),
    ("", "unscoped"),
    ("jit(ssm_like)/ssmx/add", "unscoped")])
def test_scope_is_the_model_scopes_in_the_op_name(op_name, scope):
    assert spans.scope_of(op_name) == scope


# ---------------------------------------------------------------------------
# scope attribution on a trace recorded here
# ---------------------------------------------------------------------------
def _traced(tmp_path, f, *args):
    f(*args).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("serve.fetch"):
            f(*args).block_until_ready()
    jax.profiler.stop_trace()
    return spans.reduce(str(tmp_path), steps=2)


def test_ops_are_put_under_their_scopes_from_the_metadata_plane(tmp_path):
    def step(x):
        with jax.named_scope("ssm"):
            with jax.named_scope("state_update"):
                y = jnp.tanh(x @ x)
        with jax.named_scope("head"):
            return (y @ x).sum()

    out = _traced(tmp_path, jax.jit(step), jnp.ones((256, 256)))
    assert out["platform"] == "CPU" and out["scoped"]
    ms = out["device_ms_per_step"]
    assert ms["ssm/state_update"] > 0 and ms["head"] > 0
    assert out["spans"] == ["serve.fetch"]
    assert out["idle_in_spans_share"] is None or 0 <= out["idle_in_spans_share"] <= 100


def test_an_op_outside_every_scope_lands_in_unscoped(tmp_path):
    def step(x):
        with jax.named_scope("ssm"):
            y = jnp.tanh(x @ x)
        return jnp.sort(y, axis=0)          # no model scope

    out = _traced(tmp_path, jax.jit(step), jnp.ones((256, 256)))
    ms = out["device_ms_per_step"]
    assert ms["unscoped"] > 0 and ms["ssm"] > 0


def test_a_program_without_scopes_or_spans_reduces_all_the_same(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    out = spans.reduce(str(tmp_path), steps=1)
    assert not out["scoped"] and out["spans"] == []
    assert set(out["device_ms_per_step"]) == {"unscoped"}
    assert spans.figures(out) == dict.fromkeys(FIGURES)


def _message(*fields):
    """Protobuf wire bytes of ``(number, bytes)`` length-delimited fields."""
    out = b""
    for number, value in fields:
        out += bytes([number << 3 | 2]) + _varint(len(value)) + value
    return out


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _hlo(module, ops):
    """An HloProto: one computation of instructions with their op_names."""
    insts = [(2, _message((1, name.encode()), (7, _message((2, op.encode())))))
             for name, op in ops.items()]
    comp = _message((1, b"main"), *insts)
    return _message((1, _message((1, module.encode()), (3, comp))))


def test_a_tpu_trace_finds_each_ops_program_by_its_module_event(tmp_path):
    """A TPU op event names only its instruction; its program is the
    ``XLA Modules`` event running then, whose id keys the HLO in the
    metadata plane, or else whose module name does. Two programs with an
    instruction of the same name."""
    from jax.profiler import ProfileData

    hlo = {7: _hlo("jit_serve_step", {"fusion.1": "jit(serve_step)/ssm/state_update/mul",
                                      "copy.5": "jit(serve_step)/while/body/copy"}),
           9: _hlo("jit__argmax", {"fusion.1": "jit(_argmax)/argmax"})}
    esc = {k: "".join(f"\\{b:03o}" for b in v) for k, v in hlo.items()}
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }}
    events {{ metadata_id: 2 offset_ps: 12000000 duration_ps: 2000000 }}
    events {{ metadata_id: 5 offset_ps: 15000000 duration_ps: 1000000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 1000000 duration_ps: 3000000 }}
    events {{ metadata_id: 4 offset_ps: 5000000 duration_ps: 4000000 }}
    events {{ metadata_id: 3 offset_ps: 12000000 duration_ps: 2000000 }}
    events {{ metadata_id: 3 offset_ps: 15000000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_serve_step(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit__argmax(9)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.1 = f32[8]{{0}} fusion(...)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%copy.5 = f32[8]{{0}} copy(...)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_serve_step(8)" }} }} }}
planes {{ id: 2 name: "/host:metadata"
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_serve_step(7)"
    stats {{ metadata_id: 1 bytes_value: "{esc[7]}" }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit__argmax(9)"
    stats {{ metadata_id: 1 bytes_value: "{esc[9]}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 9000000 duration_ps: 4000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "serve.sample" }} }} }}
"""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    out = spans.reduce(str(tmp_path), steps=1)
    assert out["platform"] == "TPU"
    assert out["device_ms_per_step"] == pytest.approx(
        {"ssm/state_update": 0.003 + 0.001, "unscoped": 0.004 + 0.002})  # ms
    assert {(op, scope): ms for op, scope, ms in out["top_ops_ms_per_step"]} == \
        pytest.approx({("fusion.1", "ssm/state_update"): 0.004,
                       ("copy.5", "unscoped"): 0.004, ("fusion.1", "unscoped"): 0.002})
    # idle: 4-5, 9-12 and 14-15 us of the 1-16 us window; 9-12 under
    # serve.sample
    assert out["idle_ms_per_step"] == pytest.approx(0.005)
    assert out["idle_ms_per_step_by_span"] == pytest.approx(
        {"serve.sample": 0.003, "no span": 0.002})
    assert out["idle_in_spans_share"] == pytest.approx(60.0)


# ---------------------------------------------------------------------------
# the figures
# ---------------------------------------------------------------------------
def test_each_figure_reads_nothing_without_a_reduction():
    assert spans.figures(None) == dict.fromkeys(FIGURES)


def test_the_figures_read_their_scopes_and_spans():
    red = {"scoped": True,
           "spans": ["serve.fetch", "talp.capture.step", "talp.offload"],
           "device_ms_per_step": {"ssm": 1.0, "ssm/state_update": 2.0,
                                  "head": 4.0, "unscoped": 8.0},
           "idle_ms_per_step_by_span": {"serve.fetch": 0.5, "serve.sample": 0.25,
                                        "talp.capture.step": 0.125,
                                        "talp.capture.flatten": 0.0625,
                                        "talp.offload": 3.0, "no span": 1.0}}
    assert spans.figures(red) == {"ssm_device": 3.0, "unscoped_device": 8.0,
                                  "loop_idle": 0.75, "talp_idle": 0.1875}


def test_a_traced_smoke_cell_keeps_its_result_line_and_gives_the_figures():
    result, info, got = spans.traced_cell(
        DECODE, 2**31 + 5, 0.2, require_chip=False, smoke=True,
        traffic=SMOKE_TRAFFIC[DECODE], t_start=time.perf_counter())
    assert xplane.reduce.__name__ == "reduce"
    assert result["correct"] and "device_idle_share.decode" in result["metrics"]
    for name, value in spans.figures(got).items():
        assert harness.finite(value) and value >= 0, name
    assert got["steps"] == info["span"]["steps"]
    assert got["busy_ms_per_step"] > 0 and got["scopes_ms_per_step"] > 0
    assert 0 <= got["idle_in_spans_share"] <= 100
    assert {"serve.fetch", "serve.dispatch", "talp.offload",
            "talp.region.decode_step", "talp.capture.step"} <= set(got["spans"])


def test_the_cells_trace_reduction_is_restored_when_the_run_fails():
    base = xplane.reduce
    with pytest.raises(harness.Refused):
        spans.traced_cell("no.such.cell", 1, 0.1)
    assert xplane.reduce is base
