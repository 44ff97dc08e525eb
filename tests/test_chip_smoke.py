"""``chip_smoke.py``: refuses to run without a TPU, and its phases run at
smoke size on the CPU (kernels in interpret mode)."""

import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs import smoke_config

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, SCRIPT], cwd=REPO_ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_train_phase(chip_smoke):
    info = chip_smoke.train_phase(smoke_config("mamba2-130m"), steps=2,
                                  batch=2, seq=64)
    assert len(info["loss"]) == 2
    assert info["steady_step_s"] > 0
    # no peak off the TPU: Computational Efficiency is not measured
    assert info["talp_computational_eff"] is None


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_chip_smoke_serve_phase(chip_smoke, dtype):
    cfg = dataclasses.replace(smoke_config("zamba2-2.7b"), compute_dtype=dtype)
    tol = chip_smoke.SERVE_CHECK_TOL if dtype == "float32" else None
    info = chip_smoke.serve_phase(cfg, requests=2, prompt_len=16,
                                  gen_len=cfg.decode_hot_len + 4, tol=tol,
                                  matmul_precision="highest")
    assert info["limit_rel"] == tol
    assert info["steady_token_step_s"] > 0


def test_chip_smoke_kernel_phase(chip_smoke):
    info = chip_smoke.kernel_phase(attn=(1, 128, 4, 80),
                                   ssd=(1, 256, 2, 64, 128, 128),
                                   interpret=True)
    assert info["flash_attention"]["max_err_rel"] <= chip_smoke.KERNEL_TOL
    assert info["ssd"]["max_err_rel"] <= chip_smoke.KERNEL_TOL
