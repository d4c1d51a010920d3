"""Operations and bytes that the served algorithm needs, from shapes alone,
and the table of device peaks.

These count what the work needs, not what the program moves today: every
weight read once per program call, the K/V of each live sequence's actual
context (not the cache's `max_len`), K/V written once, and no copy of the
cache. A decode round or refill can therefore not read over 100% of its
roofline, and whatever today's code moves beyond this is headroom.

A multiply-add counts as 2 operations. The embedding lookup is free; the
output head (tied to the embedding) is a matmul of d x V per token whose
logits are needed.
"""
from __future__ import annotations

from typing import Sequence, Tuple

# Published peaks per chip, keyed by JAX's `device_kind`.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, 'TPU v5e': "
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def shape(conf: dict) -> dict:
    """Per-model counts from a Hugging Face style config."""
    d = conf["hidden_size"]
    hq = conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // hq
    ff = conf["intermediate_size"]
    L = conf["num_hidden_layers"]
    V = conf["vocab_size"]
    bias = conf["model_type"] == "qwen2" or bool(conf.get("attention_bias"))
    qk_norm = conf["model_type"] == "qwen3"
    layer_mat = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff
    layer_small_bytes = (2 * d * 4                      # two norm scales, f32
                         + ((hq + 2 * hkv) * dh * 2 if bias else 0)
                         + (2 * dh * 4 if qk_norm else 0))
    return {
        "L": L, "d": d, "hq": hq, "dh": dh, "V": V,
        "matmul_params": L * layer_mat,                 # per token, no head
        "weight_bytes": L * (2 * layer_mat + layer_small_bytes)
        + 2 * V * d + 4 * d,                            # embedding/head, norm
        "kv_bytes_per_token": L * 2 * hkv * dh * 2,     # bf16 K and V
    }


def _attn_flops(s: dict, q_tokens: int, ctx: int) -> float:
    """QK^T and PV for q_tokens queries over ctx keys, all layers."""
    return 4.0 * s["L"] * s["hq"] * s["dh"] * q_tokens * ctx


def decode_round(s: dict, ctx_lens: Sequence[int]) -> Tuple[float, float]:
    """One decode step of len(ctx_lens) live sequences; ctx_lens are the
    keys each attends over (its context including the new token)."""
    n = len(ctx_lens)
    flops = n * (2.0 * s["matmul_params"] + 2.0 * s["d"] * s["V"])
    flops += sum(_attn_flops(s, 1, c) for c in ctx_lens)
    bytes_ = (s["weight_bytes"] + sum(ctx_lens) * s["kv_bytes_per_token"])
    return flops, float(bytes_)


def prefill(s: dict, prompt_lens: Sequence[int]) -> Tuple[float, float]:
    """One prefill call over these prompts (a refill is one prompt, a wave
    is several): weights once, causal attention, logits of the last token
    of each prompt, and each prompt's K/V written once."""
    flops = 0.0
    for p in prompt_lens:
        flops += 2.0 * s["matmul_params"] * p + 2.0 * s["d"] * s["V"]
        flops += _attn_flops(s, 1, 1) * p * (p + 1) / 2.0
    bytes_ = s["weight_bytes"] + sum(prompt_lens) * s["kv_bytes_per_token"]
    return flops, float(bytes_)


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return max(flops / peaks["flops"], bytes_ / peaks["bytes_per_s"])


def bound(flops: float, bytes_: float, peaks: dict) -> str:
    return ("compute" if flops / peaks["flops"] >= bytes_ / peaks["bytes_per_s"]
            else "memory")
