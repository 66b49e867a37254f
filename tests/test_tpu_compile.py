"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, rank-1 blocks, more VMEM than a kernel may use, programs
larger than the chip's memory.  These tests compile the Pallas kernels at
qwen2_0_5b / mamba2_1_3b / granite_4_0_h_micro widths and the served
qwen2_0_5b and granite_4_0_h_micro prefill and decode steps at full width,
for one chip of a described ``v5e:2x2`` host.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compile cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _kernel_case(name, spec):
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd_scan import ssd_scan
    from repro.kernels.ssd_state import ssd_state_update
    from repro.kernels.streamed_matmul import streamed_matmul

    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":     # qwen2_0_5b heads, 2k prefill
        return flash_attention, [spec((1, 14, 2048, 64), bf16)] * 3
    if name == "decode_attention":    # qwen2_0_5b, B=8, max_seq 1024
        return decode_attention, [spec((8, 14, 64), bf16),
                                  spec((8, 1024, 14, 64), bf16),
                                  spec((8, 1024, 14, 64), bf16),
                                  spec((), jnp.int32)]
    if name == "ssd_scan":            # mamba2_1_3b: 64 heads x 64, N=128
        return ssd_scan, [spec((1, 2048, 64, 64), bf16),
                          spec((1, 2048, 64), f32), spec((64,), f32),
                          spec((1, 2048, 128), bf16),
                          spec((1, 2048, 128), bf16)]
    if name == "ssd_state_update":    # granite_4_0_h_micro decode, batch 32
        return ssd_state_update, [spec((4, 32, 64, 64, 128), f32),
                                  spec((), jnp.int32),
                                  spec((32, 64, 64), f32), spec((32, 64), f32),
                                  spec((32, 128), f32), spec((32, 128), f32)]
    if name == "ssd_state_update_hymba":  # hymba_1_5b: P 50, N 16, batch 8
        return ssd_state_update, [spec((32, 8, 64, 50, 16), f32),
                                  spec((), jnp.int32),
                                  spec((8, 64, 50), f32), spec((8, 64), f32),
                                  spec((8, 16), f32), spec((8, 16), f32)]
    # qwen2_0_5b MLP up-projection: K = d_model = 896 = 7 x 128
    return streamed_matmul, [spec((4096, 896), bf16), spec((896, 4864), bf16)]


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "ssd_scan", "ssd_state_update",
                                  "ssd_state_update_hymba",
                                  "streamed_matmul"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(
        name, lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_0_5b_serving_steps_compile_for_v5e(one_chip):
    """Full-width prefill (8 x 512) and decode (8 x 1024 cache) of the
    served engine, from parameter shapes placed on the described chip."""
    from repro.models import build
    from repro.serve import EngineConfig, ServeEngine

    cfg = get_config("qwen2_0_5b")
    bundle = build(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    engine = ServeEngine(bundle, params,
                         EngineConfig(batch_size=8, max_seq=1024))
    seconds = engine.compile(prompt_len=512)
    assert seconds["prefill_s"] > 0 and seconds["decode_s"] > 0


def test_qwen2_0_5b_decode_writes_its_cache_in_place_for_v5e(one_chip):
    """The served decode step at the chat cell's shape (batch 128, max_seq
    1536) hands the whole donated cache back in place: the only output
    bytes not aliased to an input are those of a step that returns the
    token and the cache untouched, and its scratch holds no copy of the
    cache, nor of one layer's K and V."""
    from repro.models import build
    from repro.serve import EngineConfig, ServeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    bundle = build(get_config("qwen2_0_5b"))
    engine = ServeEngine(bundle, on_chip(jax.eval_shape(
        bundle.init, jax.random.PRNGKey(0))),
        EngineConfig(batch_size=128, max_seq=1536))
    engine.compile(prompt_len=16)
    caches = jax.eval_shape(lambda: bundle.init_cache(128, 1536))
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert engine.stats["decode_aliased_bytes"] == cache_bytes == 2_415_919_104

    tok = jax.ShapeDtypeStruct((128, 1), jnp.int32)
    step = engine._decode.lower(engine.params, caches, tok,
                                jax.ShapeDtypeStruct((), jnp.int32)
                                ).compile().memory_analysis()
    bare = jax.jit(lambda c, t: (t, c), donate_argnums=(0,)).lower(
        on_chip(caches), on_chip(tok)).compile().memory_analysis()
    assert bare.alias_size_in_bytes == cache_bytes
    assert step.output_size_in_bytes - step.alias_size_in_bytes == \
        bare.output_size_in_bytes - bare.alias_size_in_bytes
    assert step.temp_size_in_bytes < cache_bytes // 24


def test_granite_serving_steps_compile_for_v5e(one_chip):
    """granite_4_0_h_micro at every published width, at the chat cell's
    shape (batch 32, 1024-token prompts, max_seq 1536): prefill and decode
    fit one chip, and the decode step hands back its whole donated cache,
    the K/V of its 4 attention layers and the state and conv tails of its
    36 Mamba layers, in place.  Each Mamba layer's state is updated by the
    kernel where it lies: no instruction outside it outputs a whole layer's
    state, and the step's scratch is short of one layer's state, which the
    update fusion held before the kernel (552,727,552 bytes of temp)."""
    from repro.kernels.ssd_state import KERNEL
    from repro.models import build
    from repro.serve import EngineConfig, ServeEngine

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    bundle = build(get_config("granite_4_0_h_micro"))
    engine = ServeEngine(bundle, on_chip(jax.eval_shape(
        bundle.init, jax.random.PRNGKey(0))),
        EngineConfig(batch_size=32, max_seq=1536))
    engine.compile(prompt_len=1024)
    # K/V: 4 x 2 x 32 x 1536 x 512 x 2 bytes; state: 36 x 32 x (64 x 64 x
    # 128 x 4 + 3 x 4352 x 2) bytes
    assert engine.stats["cache_bytes"] == {"kv": 402_653_184,
                                           "state": 2_446_000_128}
    assert engine.stats["decode_aliased_bytes"] == 2_848_653_312
    assert engine.stats["state_inplace_layers"] == 36

    caches = jax.eval_shape(lambda: bundle.init_cache(32, 1536))
    step = engine._decode.lower(
        engine.params, on_chip(caches),
        on_chip(jax.ShapeDtypeStruct((32, 1), jnp.int32)),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    layer_state = 32 * 64 * 64 * 128 * 4
    assert step.memory_analysis().temp_size_in_bytes <= \
        552_727_552 - layer_state
    # each instruction's output type, between its name and its opcode
    outputs = [(m.group(1), line) for line in step.as_text().splitlines()
               if (m := re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ",
                                 line))]
    assert sum(KERNEL in line and "tpu_custom_call" in line
               for _, line in outputs) == 9   # per step of the layer scan
    assert not [line for out, line in outputs
                if "f32[32,64,64,128]" in out and KERNEL not in line]
