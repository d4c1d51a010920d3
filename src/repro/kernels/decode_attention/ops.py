"""Public decode-attention op."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import decode_attention_pallas
from .ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("softcap", "bk", "interpret"))
def decode_attention(q, k, v, lengths, *, softcap: float = 0.0,
                     bk: int = 512, interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, Hkv, D = k.shape
    bk_ = min(bk, T)
    pad = (-T) % bk_
    # fuse heads into the lane axis (the kernel's layout; a free reshape)
    k = k.reshape(B, T, Hkv * D)
    v = v.reshape(B, T, Hkv * D)
    if pad:   # zero-pad the KV axis; in-kernel length mask covers the rest
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    return decode_attention_pallas(q, k, v, lengths, softcap=softcap,
                                   bk=bk_, interpret=interpret)


reference = decode_attention_ref
