"""Plain float32 reference of the Qwen2 / Qwen3 dense decoder.

Follows the published description (Hugging Face `Qwen2ForCausalLM`,
`Qwen3ForCausalLM`): RMSNorm (eps from the config), grouped-query
attention with rotary embeddings applied to the two halves of each head
(`rotate_half`), Qwen2's q/k/v biases, Qwen3's per-head RMSNorm on q and
k before the rotation, a SwiGLU MLP, and an output head tied to the
embedding. Every matmul runs in float32 at `Precision.HIGHEST`, over the
whole sequence with a causal mask: no cache, no batching, no kernels.

It imports nothing of the program under test. Weights are made here from
a key, in the reference's own layout and in the types they are served in
(bfloat16 matrices and biases, float32 norm scales); the benchmark maps
them into the program's layout separately.

`quant` gives the control, the same model computed in a precision below
the bfloat16 it is served in: each linear layer's weights (per output
channel) and inputs (per token) quantized to int8 or float8 (e4m3) and
dequantized, with float32 accumulation; norms, rotary embeddings and
attention as above. `quant="bf16"` is a witness, not a control: the
activations rounded to bfloat16 wherever a bfloat16 program keeps them
(linear and attention outputs, norms, the residual stream, attention
probabilities), with float32 accumulation and float32 softmax, so that
its gap shows what rounding alone does at the served precision.

`work(conf)` counts the operations and bytes each prefill call and decode
round of this model needs, for the rooflines and `step_mfu`: one
description of the block, `dims(conf)`, serves the reference, the
program mapping (qwen_program.py) and the counts.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from work import causal_attention_flops

HIGHEST = jax.lax.Precision.HIGHEST
SCORE_STD = 1.0


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": conf["num_key_value_heads"],
            "dh": conf.get("head_dim") or d // hq,
            "ff": conf["intermediate_size"], "V": conf["vocab_size"],
            "bias": conf["model_type"] == "qwen2"
            or bool(conf.get("attention_bias")),
            "qk_norm": conf["model_type"] == "qwen3",
            "eps": conf["rms_norm_eps"], "theta": float(conf["rope_theta"])}


class Work:
    """Operations and bytes a call of the served model needs, from
    `dims(conf)` and the lengths alone (the rule is work.py's): every
    weight read once per call, the K/V of each sequence's actual context,
    K/V written once. The embedding lookup is free; the output head (tied
    to the embedding) is a matmul of d x V per token whose logits are
    needed: the last token of each prompt, each decoded token."""

    def __init__(self, conf: dict):
        m = dims(conf)
        L, d, hq, hkv, dh, ff, V = (m["L"], m["d"], m["hq"], m["hkv"],
                                    m["dh"], m["ff"], m["V"])
        layer_mat = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff
        layer_small_bytes = (2 * d * 4                      # two norm scales
                             + ((hq + 2 * hkv) * dh * 2 if m["bias"] else 0)
                             + (2 * dh * 4 if m["qk_norm"] else 0))
        self.matmul_params = L * layer_mat              # per token, no head
        self.weight_bytes = (L * (2 * layer_mat + layer_small_bytes)
                             + 2 * V * d + 4 * d)       # embedding, norm
        self.kv_bytes_per_token = L * 2 * hkv * dh * 2  # bf16 K and V
        self.head_flops = 2.0 * d * V
        self.attn_per_pair = 4.0 * L * hq * dh          # QK^T and PV

    def decode_round(self, ctx_lens: Sequence[int]) -> Tuple[float, float]:
        n = len(ctx_lens)
        flops = n * (2.0 * self.matmul_params + self.head_flops)
        flops += sum(self.attn_per_pair * c for c in ctx_lens)
        bytes_ = self.weight_bytes + sum(ctx_lens) * self.kv_bytes_per_token
        return flops, float(bytes_)

    def prefill(self, prompt_lens: Sequence[int]) -> Tuple[float, float]:
        flops = 0.0
        for p in prompt_lens:
            flops += 2.0 * self.matmul_params * p + self.head_flops
            flops += causal_attention_flops(self.attn_per_pair, p)
        bytes_ = (self.weight_bytes
                  + sum(prompt_lens) * self.kv_bytes_per_token)
        return flops, float(bytes_)


work = Work     # the entry point the harness looks up by name


def init_weights(conf: dict, key) -> dict:
    """Random weights from `key` (call under jit)."""
    m = dims(conf)
    L, d, hq, hkv, dh, ff = m["L"], m["d"], m["hq"], m["hkv"], m["dh"], m["ff"]
    bf = jnp.bfloat16
    out_scale = 0.02 / math.sqrt(2 * L)
    # Attention scores spread by about `spread` (q.k / sqrt(dh) has that
    # standard deviation), so what a position attends to depends on the
    # context, as in a trained model. Without q/k norms the spread is
    # std(wq) std(wk) d; with them it is the product of the norm scales.
    spread = conf.get("assumed", {}).get("attention_score_std", SCORE_STD)
    qk = 0.02 if m["qk_norm"] else math.sqrt(spread / d)
    shapes = {
        "wq": ((L, d, hq * dh), qk), "wk": ((L, d, hkv * dh), qk),
        "wv": ((L, d, hkv * dh), 0.02), "wo": ((L, hq * dh, d), out_scale),
        "w_gate": ((L, d, ff), 0.02), "w_up": ((L, d, ff), 0.02),
        "w_down": ((L, ff, d), out_scale),
    }
    keys = iter(jax.random.split(key, 16))
    layers = {name: (jax.random.normal(next(keys), shp, bf) * s).astype(bf)
              for name, (shp, s) in shapes.items()}

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    layers["ln1"] = scale((L, d))
    layers["ln2"] = scale((L, d))
    if m["bias"]:
        for name, width in (("bq", hq * dh), ("bk", hkv * dh),
                            ("bv", hkv * dh)):
            layers[name] = (jax.random.normal(next(keys), (L, width), bf)
                            * 0.02).astype(bf)
    if m["qk_norm"]:
        layers["q_norm"] = math.sqrt(spread) * scale((L, dh))
        layers["k_norm"] = math.sqrt(spread) * scale((L, dh))
    embed = (jax.random.normal(next(keys), (m["V"], d), bf) * 0.02).astype(bf)
    return {"embed": embed, "final_norm": scale((d,)), "layers": layers}


def _quantize(w, quant, axis):
    """Quantize-dequantize `w` per output channel (reduce over `axis`)."""
    if quant in (None, "bf16"):
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quantization {quant!r}")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (T, H, dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # (T, dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def logits(conf: dict, w: dict, tokens, want, quant=None):
    """Float32 logits (len(want), V) at positions `want` of the causal
    forward pass over `tokens` (T,). Positions after the real tokens may
    hold anything: the causal mask keeps them out of earlier positions."""
    m = dims(conf)
    hq, hkv, dh, eps = m["hq"], m["hkv"], m["dh"], m["eps"]
    G = hq // hkv
    T = tokens.shape[0]
    f32 = jnp.float32
    if quant == "bf16":
        def rb(a):
            return a.astype(jnp.bfloat16).astype(f32)
    else:
        def rb(a):
            return a
    emb = _quantize(w["embed"].astype(f32), quant, 1)
    x = emb[tokens]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    def mm(a, b):
        return jnp.matmul(a, b, precision=HIGHEST)

    def lin(a, b):
        """A linear layer; under `quant` its input is quantized per token
        (its weights were quantized per output channel)."""
        return rb(mm(_quantize(a, quant, -1), b))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        wmat = {k: _quantize(p[k], quant, 0)
                for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
        h = rb(_rms(x, p["ln1"], eps))
        q, k, v = lin(h, wmat["wq"]), lin(h, wmat["wk"]), lin(h, wmat["wv"])
        if m["bias"]:
            q, k, v = rb(q + p["bq"]), rb(k + p["bk"]), rb(v + p["bv"])
        q = q.reshape(T, hq, dh)
        k = k.reshape(T, hkv, dh)
        v = v.reshape(T, hkv, dh)
        if m["qk_norm"]:
            q = rb(_rms(q, p["q_norm"], eps))
            k = rb(_rms(k, p["k_norm"], eps))
        q = rb(_rope(q, pos, m["theta"]))
        k = rb(_rope(k, pos, m["theta"]))
        k = jnp.repeat(k, G, axis=1)                 # head h uses kv h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = rb(jax.nn.softmax(s, axis=-1))
        o = rb(jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST))
        x = rb(x + lin(o.reshape(T, hq * dh), wmat["wo"]))
        h = rb(_rms(x, p["ln2"], eps))
        x = rb(x + lin(rb(jax.nn.silu(lin(h, wmat["w_gate"]))
                          * lin(h, wmat["w_up"])), wmat["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = rb(_rms(x[want], w["final_norm"].astype(f32), eps))
    return mm(_quantize(x, quant, -1), emb.T)
