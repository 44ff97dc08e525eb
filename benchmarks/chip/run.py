"""Run one cell of the on-chip benchmark and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic, limits, driver and per-layer metric
readers are found by the names in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json`` (whose ``driver``
names ``drivers/<driver>.py``), ``limits/<cell>.json``, and
``metrics/<metric>.py`` for each per-layer metric (the name before the
first ``.``).

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the first ``trace_seconds`` of the window. Earlier lines
give the device, the window and the compiles inside it; the last lines on
standard error, and the ``checks`` key that ends the result line, give
each number compared beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def judge(numbers, limits):
    """Each compared number beside its limit, and whether every number
    the limits file names is there, finite and within its limit."""
    checks = {name: {"value": value, "limit": limits[name]["limit"]}
              for name, value in numbers.items() if name in limits}
    ok = set(checks) == {k for k in limits if not k.startswith("_")} and \
        all(harness.finite(c["value"]) and c["value"] <= c["limit"]
            for c in checks.values())
    return checks, ok


def run_cell(workload, seed, seconds, trace, require_chip=True, smoke=False,
             traffic=None, t_start=None, control=False):
    """Run one cell; returns (result line, info line). ``smoke`` and
    ``traffic`` shrink the model and the traffic for tests on the CPU;
    ``control`` also reads the fp8 control's numbers (and, for training,
    the half-batch fault's) into the info line."""
    import importlib

    import jax

    cell = harness.load_cell(workload)
    chips = cell["chips"]
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise harness.Refused(f"{workload} needs {chips} TPU chip(s); JAX "
                              f"found {len(devices)} {devices[0].platform!r}")
    devices = devices[:chips]

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = harness.program_config(cell["config"], smoke=smoke)
    spec = harness.spec_of(cfg, cell["config"]) if smoke else cell["config"]["model"]
    tr = dict(cell["traffic"], **(traffic or {}))
    limits = harness.load_json(harness.HERE / "limits" / f"{workload}.json")
    trace_dir = tempfile.mkdtemp(prefix="chip_trace_") if trace else None
    ctx = {
        "traffic": tr, "spec": spec, "cfg": cfg, "seed": int(seed),
        "program_seed": int(seed) % 2 ** 32,
        "model": importlib.import_module(f"reference.{cell['config']['family']}"),
        "hooks": harness.Hooks(trace_dir, tr["trace_seconds"]),
        "compiles": harness.compile_counter(), "devices": devices,
        "t_start": T_START if t_start is None else t_start,
        "seconds": seconds, "trace": bool(trace),
        "trace_seconds": tr["trace_seconds"], "control": control,
    }
    driver = harness.load_module("drivers", tr["driver"])
    try:
        rec = driver.run(ctx)
        reduced = None
        if trace:
            import xplane

            reduced = xplane.reduce(trace_dir, devices[0].platform.upper())
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    from peaks import peak_of

    device = harness.device_info(devices)
    device["memory_peak_bytes"] = ctx.get("memory_peak_bytes")
    rec.update(spec=spec, traffic=tr, trace=reduced, peak=(
        peak_of(device["kind"]) if require_chip else None))
    if trace:
        rec["trace_window_s"] = rec["span"]["seconds"]
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = harness.load_module("metrics", m["name"].split(".")[0]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = rec["span"]["seconds"]
    else:
        values = dict(rec["e2e"], setup_s=rec["setup_s"])
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks, correct = judge(rec["numbers"], limits)
    correct = correct and all(harness.finite(m["value"])
                              for m in metrics.values())
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    info = {"workload": workload, "seed": int(seed), "device": device,
            "compile_cache": cache_dir,
            "window": {k: v for k, v in rec["window"].items()
                       if k not in ("start", "end")},
            "setup_s": rec["setup_s"], "span": {
                k: v for k, v in rec["span"].items() if k != "rows"},
            "notes": rec["notes"], "numbers": rec["numbers"]}
    if control:
        info["control"] = rec["control"]
    if reduced is not None:
        info["trace"] = {k: reduced[k] for k in ("devices", "busy_s", "n_ops")}
    return result, info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, info = run_cell(args.workload, args.seed, args.seconds,
                                args.trace)
    except harness.Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    w = info["window"]
    print(f"device {info['device']['kind']} x{info['device']['count']}; "
          f"window {w['seconds']:.4f} s, {w['steps']} steps; "
          f"compiles in window {w['compiles']}"
          + ("; steady decode ended before --seconds" if w.get("short") else ""),
          file=sys.stderr)
    print(json.dumps({"info": info}, default=float), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
