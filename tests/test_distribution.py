"""Distribution tests that need >1 device: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process (and all smoke tests) keep seeing 1 device."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    """A real sharded train step on a 2x4 mesh produces the same loss as
    the unsharded step (SPMD correctness, not just compile)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.launch.steps import init_train_state, make_train_step
        from repro.launch.mesh import make_mesh
        from repro.sharding.partition import state_shardings, batch_pspec
        import repro.launch.steps as steps

        cfg = smoke_config("llama3.2-3b")
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        batch = {
            "inputs": jnp.zeros((8, 64), jnp.int32),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                         cfg.vocab_size, jnp.int32),
        }
        step = make_train_step(cfg)
        # single-device reference
        ref_state, ref_metrics = jax.jit(step)(state, batch)
        ref_loss = float(ref_metrics["loss"])

        mesh = make_mesh((2, 4), ("data", "model"))
        shapes = jax.eval_shape(lambda s: s, state)
        sh = state_shardings(shapes, mesh, cfg)
        state_sharded = jax.device_put(state, sh)
        bsh = jax.tree.map(
            lambda x: NamedSharding(mesh, batch_pspec(mesh, x.shape[0], x.ndim)),
            batch)
        batch_sharded = jax.device_put(batch, bsh)
        with mesh:
            new_state, metrics = jax.jit(
                step, in_shardings=(sh, bsh))(state_sharded, batch_sharded)
        loss = float(metrics["loss"])
        assert abs(loss - ref_loss) < 1e-3, (loss, ref_loss)
        print("OK", loss, ref_loss)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_reshard_checkpoint():
    """Save a sharded state on an 8-device mesh, restore onto a 4-device
    mesh (elastic downscale) — values identical."""
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.launch.steps import init_train_state, train_state_shapes
        from repro.launch.mesh import make_mesh
        from repro.sharding.partition import state_shardings
        from repro.checkpoint.checkpointer import save_checkpoint, restore_checkpoint

        cfg = smoke_config("gemma2-2b")
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        mesh8 = make_mesh((2, 4), ("data", "model"))
        sh8 = state_shardings(jax.eval_shape(lambda s: s, state), mesh8, cfg)
        sharded = jax.device_put(state, sh8)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 0, sharded)

        mesh4 = make_mesh((2, 2), ("data", "model"),
                          devices=jax.devices()[:4])
        shapes = train_state_shapes(cfg)
        sh4 = state_shardings(shapes, mesh4, cfg)
        restored = restore_checkpoint(d, 0, shapes, sh4)
        ref = jax.tree.leaves(state)
        got = jax.tree.leaves(restored)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored leaves actually live on the 4-device mesh
        leaf = jax.tree.leaves(restored)[0]
        assert len(leaf.sharding.device_set) <= 4
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_decode_step_sharded_runs():
    """serve_step executes (not just compiles) on a 2x2 mesh with sharded
    caches for a hybrid (zamba2) smoke config."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import smoke_config
        from repro.models import lm
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_serve_step
        from repro.sharding.partition import (cache_pspec, make_sharding_tree,
                                              param_pspec, batch_pspec)

        cfg = smoke_config("zamba2-2.7b")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        caches = lm.init_decode_caches(cfg, 4, 64, filled=True)
        tok = jnp.zeros((4, 1), jnp.int32)
        pos = jnp.full((4,), 64, jnp.int32)
        step = make_serve_step(cfg)
        ref_logits, _, _ = jax.jit(step)(params, tok, pos, caches)

        mesh = make_mesh((2, 2), ("data", "model"))
        psh = make_sharding_tree(params, mesh, cfg, param_pspec)
        csh = make_sharding_tree(caches, mesh, cfg, cache_pspec)
        params_s = jax.device_put(params, psh)
        caches_s = jax.device_put(caches, csh)
        bsh = NamedSharding(mesh, batch_pspec(mesh, 4, 2))
        possh = NamedSharding(mesh, batch_pspec(mesh, 4, 1))
        with mesh:
            logits, _, _ = jax.jit(
                step, in_shardings=(psh, bsh, possh, csh)
            )(params_s, jax.device_put(tok, bsh), jax.device_put(pos, possh),
              caches_s)
        # bf16 params + different reduction order across shards → ~5e-2
        np.testing.assert_allclose(np.asarray(ref_logits, np.float32),
                                   np.asarray(logits, np.float32),
                                   rtol=8e-2, atol=8e-2)
        print("OK")
    """)
    assert "OK" in out


def test_roofline_calibration_semantics():
    """Documents/verifies the two facts the roofline pipeline relies on:
    (1) cost_analysis counts a scan body once; (2) costs are per-device
    after SPMD partitioning."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        def costs(compiled):
            return compiled.cost_analysis()

        # large enough that XLA partitions instead of replicating
        x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        w = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        ws = jax.ShapeDtypeStruct((10, 1024, 1024), jnp.float32)
        dot_flops = 2 * 1024**3

        f = lambda a, b: a @ b
        c1 = costs(jax.jit(f).lower(x, w).compile())
        assert abs(c1["flops"] - dot_flops) / dot_flops < 0.05

        def g(a, bs):
            return jax.lax.scan(lambda h, b: (h @ b, None), a, bs)[0]
        c2 = costs(jax.jit(g).lower(x, ws).compile())
        # scan body counted ONCE, not x10:
        assert c2["flops"] < 2 * dot_flops, c2["flops"]

        # 2-D mesh with both operands sharded: partitioning is profitable
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
        with mesh:
            c3 = costs(jax.jit(
                f,
                in_shardings=(NamedSharding(mesh, P("a", "b")),
                              NamedSharding(mesh, P("b", None))),
            ).lower(x, w).compile())
        # per-device program: ~1/4 of the flops
        assert c3["flops"] < 0.5 * dot_flops, c3["flops"]
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_single_pod_small():
    """One full dry-run cell on a reduced mesh footprint via the module
    CLI (8 devices, overriding the mesh through make_mesh is covered
    elsewhere; here we exercise the real 256-chip path end to end)."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_cell
        res = run_cell("gemma2-2b", "decode_32k", multi_pod=False,
                       verbose=False, calibrate=False)
        assert res["status"] == "ok", res
        assert res["collective_bytes"] >= 0
        print("OK", res["dominant"])
    """, devices=512, timeout=560)
    assert "OK" in out
