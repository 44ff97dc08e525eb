"""CPU tests of the benchmark's yardstick. Run them by name:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import functools
import json
import os
import sys
import tempfile
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chip_tests_cache_"))

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(CHIP.parents[1] / "src"))

#: CPU-sized traffic per cell, for the smoke variants of its model.
SMOKE_TRAFFIC = {
    "mamba2-130m.train.b8-s2048": {"batch": 2, "seq_len": 64},
    "mamba2-130m.decode.b256-p512-g1536": {
        "requests": 4, "prompt_len": 32, "gen_len": 40, "warm_steps": 16,
        "check_requests": 2},
}

#: The training cell: its harness stays, but it is out of BENCHMARK.json
#: until its fp8 control has been read on the chip (PERF.md, section 7).
TRAINING_CELL = {"name": "mamba2-130m.train.b8-s2048", "config": "mamba2-130m",
                 "traffic": "train.b8-s2048", "chips": 1}


@pytest.fixture
def training_cell(monkeypatch):
    """The harness finds the training cell as if BENCHMARK.json named it."""
    import harness

    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench["workloads"].append(TRAINING_CELL)
    monkeypatch.setattr(harness, "load_cell",
                        functools.partial(harness.load_cell, bench=bench))
    return TRAINING_CELL["name"]
