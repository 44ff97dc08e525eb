"""The main path's Pallas kernels compile for a TPU v5e at the widths the
models use, and the serving step updates its caches in place there. The
chip is described, not attached: these compiles find block layouts and
memory use the chip's compiler refuses, which interpret-mode tests cannot
see. They run nothing and time nothing.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssd.kernel import ssd_pallas
from repro.launch.steps import make_serve_step, serve_params_shapes
from repro.models import lm


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


# (arch, batch, most temporary bytes): one layer's f32 SSM state for
# mamba2-130m at batch 256 (256 x 24 x 64 x 128 x 4 B); a stacked cache
# threaded through the layer loop as its input and output would need a
# second whole stack (4.9 GB for mamba2-130m). The Zamba2 hybrids' stacks
# (state 705 MB, keys and values 1.9 GB each for zamba2-7b-l24) are laid
# out by the chip's compiler otherwise than their rows are read where the
# layer loop is split or nested, and are then copied whole.
@pytest.mark.parametrize("arch,batch,most", [
    ("mamba2-130m", 256, 201_326_592),
    ("zamba2-2.7b", 16, 100_000_000),
    ("zamba2-7b-l24", 16, 100_000_000),
])
def test_serve_step_updates_caches_in_place(arch, batch, most, one_chip):
    cfg = get_config(arch)
    caches = jax.eval_shape(
        lambda: lm.init_decode_caches(cfg, batch, cache_len=2048))

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    step = jax.jit(make_serve_step(cfg), donate_argnums=3).lower(
        spec(serve_params_shapes(cfg)),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        spec(caches)).compile()
    assert step.memory_analysis().temp_size_in_bytes < most
    stacks = {"%s[%s]" % (jnp.dtype(a.dtype).name.replace("float", "f")
                          .replace("int", "s"), ",".join(map(str, a.shape)))
              for a in jax.tree.leaves(caches)}
    # a copy into the chip's fast memory (layout ``...S(1)}``) is the
    # compiler's prefetch of a small stack, not a second one in HBM
    copied = re.findall(r"= (\S+?)\{[^}S]*\} copy\(", step.as_text())
    assert not stacks & set(copied)


#: What may hold or touch the SSD state stack, or a row of it, in the
#: compiled serve step when the one-pass kernel updates it: the step's
#: arguments and results, the layer loop's carry, and the kernel.
PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
                "custom-call"}
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*?)\s([a-z][\w-]*)\((.*?)\)(?:,|$)")


# (arch, batch, kernel calls per step): mamba2-130m's state (N 128) fills
# the lanes and every layer runs the kernel; Zamba2's (N 64) is kept with
# the heads minor by the chip's compiler, and its layers keep the jnp step.
@pytest.mark.parametrize("arch,batch,calls", [
    ("mamba2-130m", 256, 24),
    ("zamba2-7b-l24", 16, 0),
])
def test_serve_step_updates_the_ssd_state_in_one_pass(arch, batch, calls,
                                                      one_chip):
    """The kernel runs once per layer, and where it runs nothing else reads
    or writes the state: no reduction over a row, no second pass."""
    cfg = get_config(arch)
    caches = jax.eval_shape(
        lambda: lm.init_decode_caches(cfg, batch, cache_len=2048))

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    text = jax.jit(make_serve_step(cfg), donate_argnums=3).lower(
        spec(serve_params_shapes(cfg)),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        spec(caches)).compile().as_text()
    sites = [line for line in text.splitlines()
             if "custom-call(" in line and "ssd_decode_update" in line]
    # a call in the layer loop's body runs once per layer
    per_step = sum(cfg.repeats if "/while/body/" in line else 1
                   for line in sites)
    assert per_step == calls
    if not calls:
        return
    state = caches["slot0"]["state"].shape
    shapes = {"f32[%s]" % ",".join(map(str, s))
              for s in (state, state[1:], (1,) + state[1:])}
    instrs = [m.groups() for m in map(INSTRUCTION.match, text.splitlines())
              if m]
    held = {name for name, shape, _, _ in instrs
            if shape.split("{")[0] in shapes}
    touching = {op for name, _, op, operands in instrs
                if name in held or held & set(re.findall(r"%([\w.-]+)",
                                                         operands))}
    assert touching <= PASS_THROUGH, touching - PASS_THROUGH
