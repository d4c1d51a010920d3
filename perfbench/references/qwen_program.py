"""Maps a Qwen2 / Qwen3 configuration and the reference's weights into
the program under test (`repro.configs.base.ModelConfig`, the parameter
tree of `repro.models.init_params`).

The program rotates interleaved pairs of each head, (2i, 2i+1), where the
published model rotates the two halves, (i, i + dh/2). The two agree once
the q and k output columns of each head (and Qwen3's q/k norm scales,
Qwen2's q/k biases) are permuted so that half-index i lands on 2i and
i + dh/2 on 2i + 1. Attention scores are then the same, since q and k are
permuted alike. A real checkpoint loader makes the same permutation.
"""
from __future__ import annotations

import jax.numpy as jnp

from references import qwen


def program_config(conf: dict):
    from repro.configs.base import ModelConfig
    m = qwen.dims(conf)
    if conf.get("hidden_act") != "silu" or not conf.get("tie_word_embeddings"):
        raise ValueError("only tied-embedding SiLU Qwen models are mapped")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["hq"], n_kv_heads=m["hkv"], d_head=m["dh"], d_ff=m["ff"],
        vocab_size=m["V"], qkv_bias=m["bias"], qk_norm=m["qk_norm"],
        mlp_gated=True, activation="silu", norm="rmsnorm",
        rope_theta=m["theta"], tie_embeddings=True, dtype="bfloat16",
        source=conf["source"])


def _interleave(a, dh: int):
    """Permute the last axis (heads x dh) from halves to interleaved."""
    half = jnp.arange(dh // 2)
    perm = jnp.stack([half, half + dh // 2], -1).reshape(dh)
    shp = a.shape
    return a.reshape(shp[:-1] + (shp[-1] // dh, dh))[..., perm].reshape(shp)


def program_params(conf: dict, w: dict, padded_vocab: int) -> dict:
    m = qwen.dims(conf)
    dh = m["dh"]
    lw = w["layers"]
    attn = {"wq": _interleave(lw["wq"], dh), "wk": _interleave(lw["wk"], dh),
            "wv": lw["wv"], "wo": lw["wo"]}
    if m["bias"]:
        attn.update(bq=_interleave(lw["bq"], dh),
                    bk=_interleave(lw["bk"], dh), bv=lw["bv"])
    if m["qk_norm"]:
        attn.update(q_norm=_interleave(lw["q_norm"], dh),
                    k_norm=_interleave(lw["k_norm"], dh))
    embed = jnp.pad(w["embed"], ((0, padded_vocab - m["V"]), (0, 0)))
    return {
        "embed": embed,
        "final_norm": {"scale": w["final_norm"]},
        "units": {"u0": {
            "ln1": {"scale": lw["ln1"]}, "ln2": {"scale": lw["ln2"]},
            "attn": attn,
            "mlp": {"w_up": lw["w_up"], "w_down": lw["w_down"],
                    "w_gate": lw["w_gate"]}}},
        "rem": {},
    }
