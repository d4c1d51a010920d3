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
import re
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


_INSTR = re.compile(r"(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def _materialised(hlo: str, shape: str):
    """(computation, instruction, opcode) of every value of `shape` that
    gets a buffer of its own: results of instructions outside the fused
    computations, less parameters, tuple reads and bitcasts."""
    comps, fused, cur = {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line.strip())
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    views = ("parameter", "get-tuple-element", "bitcast")
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if m and m.group(2) == shape and m.group(3) not in views:
                found.append((name, m.group(1), m.group(3)))
    return found


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "qwen2-0.5b"])
def test_decode_updates_its_cache_in_place_on_v5e(spec, arch):
    """The engine's decode program aliases its donated cache to its output,
    and no whole layer of K or V gets a buffer: the token is written into
    the stack in place and each layer is read where it lies."""
    cfg = replace(get_config(arch), n_layers=2)
    place = lambda tree: jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    _, decode = jit_steps(cfg)
    cache = place(models.abstract_cache(cfg, B, T))
    compiled = decode.lower(place(models.abstract_params(cfg)),
                            spec((B,), jnp.int32), cache).compile()
    kv = cache["units"]["u0"]
    layer = B * T * cfg.n_kv_heads * cfg.d_head        # one layer's K
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv["k"].size * 2 + kv["v"].size * 2
    if arch == "qwen3-1.7b":      # qwen2-0.5b's layer is 1 MB: the HLO says
        assert mem.temp_size_in_bytes < layer * 2
    whole = f"bf16[{B},{T},{cfg.n_kv_heads * cfg.d_head}]"
    assert _materialised(compiled.as_text(), whole) == []
