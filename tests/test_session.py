"""The TALP session that both drivers share: a smoke-size run of each
driver with every output option on, and the one flag set of both
command lines."""

import argparse
import json

import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.telemetry.traceexport import validate_chrome_trace
from repro.launch import serve as serve_mod
from repro.launch import session
from repro.launch import train as train_mod

SAMPLE_EVERY = 2
# driver -> (call, iterations of its loop, step region, its own regions)
DRIVERS = {
    "serve": (lambda cfg, **kw: serve_mod.serve(
        cfg, requests=2, prompt_len=8, gen_len=6, **kw), 6, "decode_step",
        ("init", "prefill", "grow_cache", "decode", "decode_step")),
    "train": (lambda cfg, **kw: train_mod.train(
        cfg, steps=4, global_batch=2, seq_len=32, **kw), 4, "step",
        ("init", "train_loop", "step")),
}


@pytest.fixture(scope="module", params=sorted(DRIVERS))
def run(request, tmp_path_factory):
    call, iters, step_region, regions = DRIVERS[request.param]
    out = tmp_path_factory.mktemp(request.param)
    call(smoke_config("mamba2-130m"), verbose=False,
         talp_sample_every=SAMPLE_EVERY,
         talp_trace_out=str(out / "trace.json"),
         talp_metrics_jsonl=str(out / "metrics.jsonl"),
         talp_prometheus_port=0, talp_step_series=16, talp_watchdog=True,
         talp_anomaly_log=str(out / "anomalies.jsonl"),
         talp_spool=str(out / "spool"), talp_json=str(out / "talp.json"))
    return out, iters, step_region, regions


def test_trace_is_valid(run):
    out, *_ = run
    validate_chrome_trace((out / "trace.json").read_text())


def test_metrics_stream_has_each_sample_and_the_last(run):
    out, iters, *_ = run
    lines = (out / "metrics.jsonl").read_text().splitlines()
    assert len(lines) == iters // SAMPLE_EVERY + 1
    assert all(json.loads(line) for line in lines)


def test_spool_holds_report_sample_steps_and_job(run):
    out, iters, step_region, _ = run
    spool = out / "spool"
    assert {p.name for p in spool.iterdir()} >= {
        "talp_rank00000.npz", "talp_sample_rank00000.npz",
        "talp_steps_rank00000.npz", "talp_job.json"}
    with np.load(spool / "talp_steps_rank00000.npz") as npz:
        names = [str(r) for r in npz["regions"]]
        rows = npz["rows"]
    assert np.sum(rows["region"] == names.index(step_region)) == iters


def test_report_regions(run):
    out, _, _, regions = run
    report = json.loads((out / "talp.json").read_text())
    assert list(report["regions"]) == ["Global", *regions]


def test_drivers_share_the_session_flags():
    ap = argparse.ArgumentParser()
    session.add_arguments(ap)
    flags = {s for a in ap._actions for s in a.option_strings} - {
        "-h", "--help"}
    argv = ["--rank", "1", "--world-size", "2", "--talp-json", "j",
            "--talp-spool", "d", "--talp-sample-every", "3",
            "--talp-trace-out", "t", "--talp-metrics-jsonl", "m",
            "--talp-prometheus-port", "0", "--talp-step-series", "8",
            "--talp-watchdog", "--talp-anomaly-log", "a",
            "--talp-fault-plan", "{}"]
    assert {a for a in argv if a.startswith("--")} == flags
    want = dict(rank=1, world_size=2, talp_json="j", talp_spool="d",
                talp_sample_every=3, talp_trace_out="t",
                talp_metrics_jsonl="m", talp_prometheus_port=0,
                talp_step_series=8, talp_watchdog=True,
                talp_anomaly_log="a", talp_fault_plan="{}")
    for mod in (serve_mod, train_mod):
        p = mod.parser()
        driver_flags = {s for a in p._actions for s in a.option_strings
                        if s.startswith(("--talp", "--rank", "--world"))}
        assert driver_flags == flags
        args = p.parse_args(["--arch", "mamba2-130m", *argv])
        assert session.options_from(args) == want
        # a flag left out takes the session's default
        args = p.parse_args(["--arch", "mamba2-130m"])
        assert session.options_from(args) == {}
