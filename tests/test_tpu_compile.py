"""The main path's Pallas kernels compile for a TPU v5e at the widths the
models use. The chip is described, not attached: these compiles find
block layouts and memory use the chip's compiler refuses, which
interpret-mode tests cannot see. They run nothing and time nothing.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssd.kernel import ssd_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2-2.7b")
    qkv = (8, 1024, cfg.num_heads, cfg.resolved_head_dim)   # 32 x 80
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             [(qkv, jnp.bfloat16)] * 3, one_chip)


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-2.7b"])
def test_ssd_compiles_at_published_widths(arch, one_chip):
    cfg = get_config(arch)
    b, l, h = 8, 2048, cfg.ssm_heads
    g, n = cfg.ssm_groups, cfg.ssm_state
    _compile(
        lambda x, dt, a, bm, cm, d: ssd_pallas(x, dt, a, bm, cm,
                                               chunk=cfg.ssm_chunk, d_skip=d),
        [((b, l, h, cfg.ssm_head_dim), jnp.bfloat16),
         ((b, l, h), jnp.float32), ((h,), jnp.float32),
         ((b, l, g, n), jnp.bfloat16), ((b, l, g, n), jnp.bfloat16),
         ((h,), jnp.float32)],
        one_chip,
    )
