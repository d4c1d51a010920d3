"""Decode-time attention Pallas kernel — the narrow-M, IO-bound case the
paper highlights (Sec. IV-B: "matrix multiplications during decoding are
narrow (e.g. 16x12288)" and Sec. V-A: decode is bound by reading KV).

One query token per sequence; the kernel streams KV blocks from HBM through
VMEM exactly once per kv-head (GQA: the G query heads of a group ride the
same KV stream). q lives in VMEM for the whole sweep.

Layouts: q (B, Hkv, G, D); k/v fused (B, T, Hkv*D) — the serving cache's
own layout, so kv-head h is the lane-aligned column block h of width D and
each KV block is a (bk, D) tile (a (.., 1, D) block over a (Hkv, D) minor
pair is not a legal TPU tiling); lengths (B,) valid KV lengths
(ring-buffer caches pass full T). Grid (b, h, ki), ki innermost; running
(m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, n_k: int, bk: int, softcap: float, scale: float):
    ki = pl.program_id(2)
    b = pl.program_id(0)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
    k = k_ref[0].astype(jnp.float32)                 # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, bk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)                 # (bk, D)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, softcap: float = 0.0,
                            bk: int = 512, interpret: bool = False):
    """q: (B, Hkv, G, D); k, v: (B, T, Hkv*D); lengths: (B,) int32."""
    B, Hkv, G, D = q.shape
    T = k.shape[1]
    assert k.shape == v.shape == (B, T, Hkv * D), (q.shape, k.shape)
    bk = min(bk, T)
    grid = (B, Hkv, pl.cdiv(T, bk))
    kern = functools.partial(_decode_kernel, n_k=grid[2], bk=bk,
                             softcap=softcap, scale=1.0 / math.sqrt(D))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # lengths, whole array
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, j: (b, j, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)
