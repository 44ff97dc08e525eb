"""The harness refuses a program whose widths differ from the
configuration file, and ``run.py`` refuses to run without a TPU or
without the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import CHIP

ROOT = CHIP.parents[1]
CELL = "mamba2-130m.decode.b256-p512-g1536"


def test_the_program_holds_the_stated_widths():
    config = harness.load_json(CHIP / "configs" / "mamba2-130m.json")
    assert harness.program_config(config).name == "mamba2-130m"


@pytest.mark.parametrize("key, value", [("d_model", 512), ("num_layers", 12),
                                        ("ssm_state", 64)])
def test_a_program_with_other_widths_is_refused(key, value):
    config = harness.load_json(CHIP / "configs" / "mamba2-130m.json")
    config["model"][key] = value
    with pytest.raises(harness.Refused, match=key):
        harness.program_config(config)


def run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_run_exits_nonzero_without_a_tpu():
    out = run_py(ROOT, {})
    assert out.returncode != 0
    assert no_result(out.stdout)
    assert "TPU" in out.stderr


def test_run_exits_nonzero_with_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_py(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert no_result(out.stdout)
