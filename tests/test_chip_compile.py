"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed beside the CPU backend, and compiles for a
v5e:2x2 topology that is described, not attached. These tests compile the
Pallas kernels with `interpret=False` at qwen3-1.7b widths, and the
engine's jitted prefill and decode programs at qwen3-1.7b's full width
(depth cut to 2 layers: the scanned layer body is the same program). They
catch what interpret mode cannot: illegal block tilings, VMEM overruns,
programs the chip's compiler refuses. Nothing runs, so they say nothing
about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so under several pytest workers only the
worker that runs this file may load it.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import models
from repro.configs import get_config
from repro.kernels import decode_attention, flash_attention, matmul, rmsnorm
from repro.serving.engine import jit_steps

CFG = get_config("qwen3-1.7b")
D, F = CFG.d_model, CFG.d_ff                       # 2048, 6144
HQ, HKV, DH = CFG.n_heads, CFG.n_kv_heads, CFG.d_head   # 16, 8, 128
B, T, S = 4, 1024, 1024       # serving slots, cache length, prefill length
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """shape, dtype -> ShapeDtypeStruct placed on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


KERNELS = {
    "matmul_up": (lambda a, b: matmul.matmul(a, b, interpret=False),
                  [((S, D),), ((D, F),)]),
    "matmul_down": (lambda a, b: matmul.matmul(a, b, interpret=False),
                    [((S, F),), ((F, D),)]),
    "flash_attention": (
        lambda q, k, v: flash_attention.flash_attention(
            q, k, v, causal=True, interpret=False),
        [((1, HQ, S, DH),), ((1, HKV, S, DH),), ((1, HKV, S, DH),)]),
    "decode_attention": (
        lambda q, k, v, n: decode_attention.decode_attention(
            q, k, v, n, interpret=False),
        [((B, HKV, HQ // HKV, DH),), ((B, T, HKV, DH),), ((B, T, HKV, DH),),
         ((B,), jnp.int32)]),
    "rmsnorm": (lambda x, g: rmsnorm.rmsnorm(x, g, interpret=False),
                [((S, D),), ((D,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(spec, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*[spec(*s) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel is there


@pytest.mark.parametrize("program", ["wave_prefill", "refill_prefill",
                                     "decode"])
def test_engine_program_compiles_for_v5e(spec, program):
    cfg = replace(CFG, n_layers=2)
    place = lambda tree: jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = place(models.abstract_params(cfg))
    prefill, decode = jit_steps(cfg)
    if program == "decode":
        args = (params, spec((B,), jnp.int32),
                place(models.abstract_cache(cfg, B, T)))
        compiled = decode.lower(*args).compile()
    else:
        b = B if program == "wave_prefill" else 1
        args = (params, spec((b, 11), jnp.int32),
                place(models.abstract_cache(cfg, b, T)),
                spec((b,), jnp.int32), None)
        compiled = prefill.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
