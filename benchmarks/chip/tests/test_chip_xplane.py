"""The trace reduction, on hand-built events and on a trace recorded here."""

import jax
import jax.numpy as jnp
import pytest

import xplane


def test_union_merges_overlapping_and_touching_intervals():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


@pytest.mark.parametrize("ops, busy", [
    ([(0, 1), (0.5, 2), (3, 4), (3.2, 3.4)], 3.0),
    ([(0, 1), (1, 2)], 2.0),
    ([], 0.0)])
def test_busy_is_the_length_of_the_union(ops, busy):
    assert xplane.busy_seconds(ops) == pytest.approx(busy)


def test_idle_gaps_cover_the_window_outside_the_ops():
    ops = [(1, 2), (1.5, 3), (5, 6)]
    assert xplane.idle_gaps(ops, 0, 7) == [(0, 1), (3, 5), (6, 7)]
    assert xplane.idle_gaps(ops, 1.5, 5.5) == [(3, 5)]


def test_a_gap_is_named_by_the_innermost_host_event_covering_it_most():
    host = [("outer", 0.0, 10.0), ("bench.wait", 2.0, 4.0),
            ("short", 3.9, 4.0)]
    events = xplane.HostEvents(host)
    assert events.name_gap((2.5, 3.5)) == "bench.wait"
    assert events.name_gap((5.0, 6.0)) == "outer"
    assert events.name_gap((11.0, 12.0)) == "no host event"
    assert xplane.HostEvents([]).name_gap((0, 1)) == "no host event"


def test_gaps_and_ops_are_summed_by_name():
    ops = [("fusion.1", 0, 1), ("dot", 1, 3), ("fusion.1", 4, 5)]
    assert dict(xplane.top_ops(ops)) == {"dot": 2, "fusion.1": 2}
    assert xplane.top_ops(ops, n=1)[0][1] == 2
    host = [("bench.launch", 3, 4), ("python", 0, 10)]
    gaps = xplane.idle_gaps([(s, e) for _, s, e in ops], 0, 6)
    assert dict(xplane.gaps_by_host(gaps, host)) == {
        "bench.launch": pytest.approx(1.0), "python": pytest.approx(1.0)}


def test_a_trace_recorded_on_the_cpu_reduces_to_busy_time_and_ops(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.wait"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    out = xplane.reduce(str(tmp_path), "CPU")
    assert out["n_ops"] >= 3
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
    with pytest.raises(ValueError):
        xplane.reduce(str(tmp_path), "TPU")


def test_nested_ops_count_once_by_their_self_time():
    ops = [("%while.1 = (s32[]) while(...)", 0, 10), ("%a = f32[] add()", 1, 3),
           ("%b = f32[] fusion()", 4, 5), ("%a = f32[] add()", 12, 13)]
    assert [t for _, t in xplane.self_times(ops)] == [7, 2, 1, 1]
    assert dict(xplane.top_ops(ops)) == {"%while.1": 7, "%a": 3, "%b": 1}
    assert xplane.busy_seconds([(s, e) for _, s, e in ops]) == 11
