"""Analytical backend: roofline-derived device states → paper metrics,
plus the beyond-paper Device Computational Efficiency branch."""

from types import SimpleNamespace

import pytest

from repro.core.analysis import analyze_trace
from repro.core.backends import (
    HardwareSpec, StepModel, TPU_V5E, device_peak, trace_from_step_model,
)
from repro.core.backends.analytical import AnalyticalBackend
from repro.core.report import node_scan_table


def test_step_model_terms():
    m = StepModel(flops=197e12, hbm_bytes=819e9, collective_bytes=200e9)
    assert m.compute_s == pytest.approx(1.0)
    assert m.hbm_s == pytest.approx(1.0)
    assert m.collective_s == pytest.approx(1.0)
    assert m.kernel_s == pytest.approx(1.0)   # max(compute, hbm)
    assert m.memory_s == pytest.approx(1.0)


def test_compute_bound_vs_memory_bound():
    hw = HardwareSpec()
    cb = StepModel(flops=2 * 197e12, hbm_bytes=819e9, collective_bytes=0, hw=hw)
    mb = StepModel(flops=197e12, hbm_bytes=4 * 819e9, collective_bytes=0, hw=hw)
    assert cb.kernel_s == pytest.approx(2.0)
    assert mb.kernel_s == pytest.approx(4.0)


def test_balanced_trace_metrics():
    m = StepModel(flops=197e12, hbm_bytes=0.5 * 819e9, collective_bytes=0.25 * 200e9)
    tr = trace_from_step_model([m, m], steps=3)
    a = analyze_trace(tr)
    a.validate()
    assert a.device.load_balance == pytest.approx(1.0)
    # kernel 1.0s, memory 0.25s per step → CE = 1/1.25
    assert a.device.communication_efficiency == pytest.approx(1 / 1.25)
    assert a.device.orchestration_efficiency == pytest.approx(1.0)


def test_imbalanced_devices():
    m_fast = StepModel(flops=0.5 * 197e12, hbm_bytes=0, collective_bytes=0)
    m_slow = StepModel(flops=1.0 * 197e12, hbm_bytes=0, collective_bytes=0)
    tr = trace_from_step_model([m_fast, m_slow], steps=2)
    a = analyze_trace(tr)
    assert a.device.load_balance == pytest.approx(0.75)


def test_host_gap_becomes_idle():
    m = StepModel(flops=197e12, hbm_bytes=0, collective_bytes=0, host_gap_s=1.0)
    tr = trace_from_step_model([m], steps=2, host_useful_s=0.0)
    a = analyze_trace(tr)
    # per step: 1s kernel + 1s gap → orchestration 50%
    assert a.device.orchestration_efficiency == pytest.approx(0.5)


def test_computational_efficiency_extension():
    """Paper's future-work branch: useful FLOPs / peak over kernel time."""
    m = StepModel(flops=2 * 197e12, hbm_bytes=0, collective_bytes=0,
                  model_flops=1 * 197e12)
    assert m.computational_efficiency == pytest.approx(0.5)
    be = AnalyticalBackend([m], steps=1)
    a = be.analyze()
    assert a.device.computational_efficiency == pytest.approx(0.5)
    trees = a.trees()
    node = trees["device"].find("Computational Eff. (ext)")
    assert node is not None and node.value == pytest.approx(0.5)
    trees["device"].validate()  # ext node is non-multiplicative


def test_collective_overlap_knob():
    m0 = StepModel(flops=197e12, hbm_bytes=0, collective_bytes=200e9)
    m1 = StepModel(flops=197e12, hbm_bytes=0, collective_bytes=200e9,
                   collective_overlap=0.75)
    assert m0.memory_s == pytest.approx(1.0)
    assert m1.memory_s == pytest.approx(0.25)
    assert m1.step_s < m0.step_s


def test_node_scan_table_renders():
    rows = []
    for nodes in (1, 2, 4, 8):
        m = StepModel(flops=197e12 / nodes, hbm_bytes=0,
                      collective_bytes=5e9 * nodes)
        rows.append(analyze_trace(trace_from_step_model([m] * 2, steps=1)))
    table = node_scan_table(rows, ["1", "2", "4", "8"], title="scan")
    assert "Orchestration Eff." in table
    assert table.count("\n") >= 8


def test_default_hw_is_v5e():
    assert TPU_V5E.peak_flops == pytest.approx(197e12)
    assert TPU_V5E.hbm_bw == pytest.approx(819e9)
    assert TPU_V5E.ici_bw == pytest.approx(1600e9 / 8)   # 1,600 Gbit/s


def test_device_peak_by_kind():
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peak(v5e) is TPU_V5E
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    assert device_peak(cpu) is None


def test_device_peak_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="TPU v99"):
        device_peak(SimpleNamespace(platform="tpu", device_kind="TPU v99"))
