"""Decoder-only (and enc-dec) LM skeleton.

Layer stacks are grouped into repeating *units* (smallest period of the
layer-kind sequence) and scanned with stacked parameters — one unit of HLO
regardless of depth (compile time + HLO size stay constant as layers grow,
which is what makes the 512-device dry-run tractable). Non-uniform archs:

    dense/moe/rwkv      unit = 1 layer
    recurrentgemma      unit = (rglru, rglru, attn), 8 units + 2 remainder
    llama-3.2-vision    unit = (attn, attn, attn, xattn, attn), 8 units
    whisper             encoder scan + decoder scan (self+cross per layer)

Public entry points (all pure functions of (cfg, params, ...)):
    init_params, forward (teacher-forced logits), loss,
    init_cache, prefill, decode_step
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from . import layers as L
from . import recurrent as R

Params = dict

TRAIN_CHUNK_Q = 512
TRAIN_CHUNK_K = 1024
VOCAB_PAD = 256      # embeddings padded so the vocab axis shards under TP


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // VOCAB_PAD) * VOCAB_PAD


def _mask_pad_logits(cfg: ModelConfig, logits):
    """Padded vocab entries must never win: -inf them (sharding-friendly
    iota-compare on the vocab axis)."""
    if logits.shape[-1] == cfg.vocab_size:
        return logits
    idx = jnp.arange(logits.shape[-1])
    return jnp.where(idx < cfg.vocab_size, logits,
                     jnp.asarray(-1e30, logits.dtype))


def _activation_spec(x):
    """Sharding constraint for scan-carry residuals: batch over (pod,data),
    d_model over model — keeps the remat-saved unit boundaries sharded
    instead of replicated (a beyond-paper optimization, EXPERIMENTS §Perf).
    Applies only under an active mesh whose axes divide the dims."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.shape:
        return None
    from jax.sharding import PartitionSpec as P
    shape = dict(am.shape)
    batch_axes = tuple(a for a in ("pod", "data") if a in shape)
    bsz = 1
    for a in batch_axes:
        bsz *= shape[a]
    b_ok = batch_axes and x.shape[0] % bsz == 0 and x.shape[0] >= bsz
    tp_ok = "model" in shape and x.shape[-1] % shape["model"] == 0
    if not (b_ok or tp_ok):
        return None
    return P(batch_axes if b_ok else None, None,
             "model" if tp_ok else None)


ACTIVATION_SHARDING = False   # opt-in: forcing d-sharded scan carries makes
#                               XLA reshard around every matmul (measured
#                               regression, EXPERIMENTS.md §Perf iteration 2)


def _shard_activations(x):
    if not ACTIVATION_SHARDING:
        return x
    spec = _activation_spec(x)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# layer kinds / unit structure
# ---------------------------------------------------------------------------

def layer_kinds(cfg: ModelConfig) -> list:
    kinds = []
    for i in range(cfg.n_layers):
        k = cfg.block_kind(i)
        if k == "attn":
            if cfg.cross_attention:
                k = "encdec"                   # whisper decoder layer
            elif i in cfg.cross_attn_layers:
                k = "xattn"                    # vision cross-attn layer
        kinds.append(k)
    return kinds


def unit_structure(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """(unit kinds, n_units, remainder kinds)."""
    kinds = layer_kinds(cfg)
    n = len(kinds)
    for p in range(1, n + 1):
        reps = n // p
        if reps == 0:
            continue
        if all(kinds[i] == kinds[i % p] for i in range(reps * p)):
            return tuple(kinds[:p]), reps, tuple(kinds[reps * p:])
    return tuple(kinds), 1, ()


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(cfg: ModelConfig, kind: str, key) -> Params:
    ks = jax.random.split(key, 6)
    if kind == "rwkv":
        return {"ln1": L.norm_init(cfg), "tmix": R.rwkv_tmix_init(cfg, ks[0]),
                "ln2": L.norm_init(cfg), "cmix": R.rwkv_cmix_init(cfg, ks[1])}
    if kind == "rglru":
        return {"ln1": L.norm_init(cfg), "rec": R.rglru_init(cfg, ks[0]),
                "ln2": L.norm_init(cfg), "mlp": L.mlp_init(cfg, ks[1])}
    p = {"ln1": L.norm_init(cfg), "ln2": L.norm_init(cfg)}
    if kind == "xattn":
        p["xattn"] = L.attn_init(cfg, ks[0])
        p["xgate"] = jnp.zeros((1,), jnp.float32)
        p["mlp"] = L.mlp_init(cfg, ks[1])
        return p
    p["attn"] = L.attn_init(cfg, ks[0])
    if kind == "encdec":
        p["lnx"] = L.norm_init(cfg)
        p["xattn"] = L.attn_init(cfg, ks[2])
    if cfg.n_experts:
        p["moe"] = L.moe_init(cfg, ks[1])
    else:
        p["mlp"] = L.mlp_init(cfg, ks[1])
    return p


def _mlp_or_moe(cfg: ModelConfig, p: Params, h, aux):
    if cfg.n_experts:
        y, a = L.moe_apply(cfg, p["moe"], h)
        return y, aux + a
    return L.mlp_apply(cfg, p["mlp"], h), aux


def _apply_layer_full(cfg: ModelConfig, kind: str, p: Params, x, *,
                      positions, enc_out=None, frontend=None, aux=0.0,
                      static_attn: bool = True):
    """Full-sequence (training / prefill-without-cache) layer application."""
    if kind == "rwkv":
        B = x.shape[0]
        H, N = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
        prev = jnp.zeros((B, cfg.d_model), x.dtype)
        st0 = jnp.zeros((B, H, N, N), jnp.float32)
        h = L.apply_norm(cfg, p["ln1"], x)
        y, _ = R.rwkv_tmix_apply(cfg, p["tmix"], h, prev, st0)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        y, _ = R.rwkv_cmix_apply(cfg, p["cmix"], h, prev)
        return x + y, aux
    if kind == "rglru":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, _ = R.rglru_apply(cfg, p["rec"], h)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        y, aux = _mlp_or_moe(cfg, p, h, aux)
        return x + y, aux

    if kind == "xattn":   # vision cross-attention layer (gated)
        h = L.apply_norm(cfg, p["ln1"], x)
        q, k, v = L.attn_qkv(cfg, p["xattn"], h, kv_src=frontend)
        o = L.flash_attention(q, k, v, causal=False, static=static_attn,
                              chunk_q=TRAIN_CHUNK_Q, chunk_k=TRAIN_CHUNK_K)
        x = x + (jnp.tanh(p["xgate"])
                 * L.attn_out(p["xattn"], o)).astype(x.dtype)
        h = L.apply_norm(cfg, p["ln2"], x)
        y, aux = _mlp_or_moe(cfg, p, h, aux)
        return x + y, aux

    # self-attention (+ optional enc-dec cross attention)
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.attn_qkv(cfg, p["attn"], h, positions=positions)
    o = L.flash_attention(q, k, v, causal=True, window=cfg.attn_window,
                          logit_softcap=cfg.attn_logit_softcap,
                          static=static_attn,
                          chunk_q=TRAIN_CHUNK_Q, chunk_k=TRAIN_CHUNK_K)
    x = x + L.attn_out(p["attn"], o)
    if kind == "encdec":
        h = L.apply_norm(cfg, p["lnx"], x)
        q, k, v = L.attn_qkv(cfg, p["xattn"], h, kv_src=enc_out)
        o = L.flash_attention(q, k, v, causal=False, static=static_attn,
                              chunk_q=TRAIN_CHUNK_Q, chunk_k=TRAIN_CHUNK_K)
        x = x + L.attn_out(p["xattn"], o)
    h = L.apply_norm(cfg, p["ln2"], x)
    y, aux = _mlp_or_moe(cfg, p, h, aux)
    return x + y, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    unit, n_units, rem = unit_structure(cfg)
    keys = jax.random.split(key, 8)
    vpad = padded_vocab(cfg)
    params: Params = {
        "embed": L._init(keys[0], (vpad, cfg.d_model)),
        "final_norm": L.norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = L._init(keys[1], (cfg.d_model, vpad))

    def stack_init(kind, key, n):
        ks = jax.random.split(key, n)
        return jax.vmap(lambda k: _layer_init(cfg, kind, k))(ks)

    unit_keys = jax.random.split(keys[2], len(unit))
    params["units"] = {f"u{j}": stack_init(kind, unit_keys[j], n_units)
                       for j, kind in enumerate(unit)}
    rem_keys = jax.random.split(keys[3], max(len(rem), 1))
    params["rem"] = {f"r{j}": _layer_init(cfg, kind, rem_keys[j])
                     for j, kind in enumerate(rem)}
    if cfg.n_encoder_layers:
        ek = jax.random.split(keys[4], cfg.n_encoder_layers + 1)
        params["enc"] = {
            "layers": jax.vmap(lambda k: _layer_init(cfg, "attn", k))(
                jax.random.split(ek[0], cfg.n_encoder_layers)),
            "final_norm": L.norm_init(cfg),
        }
    return params


# ---------------------------------------------------------------------------
# forward (teacher-forced, training / eval)
# ---------------------------------------------------------------------------

def _encode(cfg: ModelConfig, params: Params, frontend):
    """Whisper encoder over stubbed frame embeddings (B, Nf, d)."""
    x = frontend + L.sinusoidal_positions(frontend.shape[1],
                                          cfg.d_model).astype(frontend.dtype)

    @jax.checkpoint
    def enc_layer(x, p):
        h = L.apply_norm(cfg, p["ln1"], x)
        q, k, v = L.attn_qkv(cfg, p["attn"], h)
        o = L.flash_attention(q, k, v, causal=False, static=True)
        x = x + L.attn_out(p["attn"], o)
        h = L.apply_norm(cfg, p["ln2"], x)
        x = x + L.mlp_apply(cfg, p["mlp"], h)
        return _shard_activations(x), None

    x, _ = lax.scan(enc_layer, _shard_activations(x), params["enc"]["layers"])
    return L.apply_norm(cfg, params["enc"]["final_norm"], x)


def forward(cfg: ModelConfig, params: Params, tokens, frontend=None,
            remat: bool = True):
    """tokens: (B, S) -> logits (B, S, V). frontend: stub modality embeds."""
    unit, n_units, rem = unit_structure(cfg)
    B, S = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.rope_fraction == 0.0 and not cfg.attention_free:
        x = x + L.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
    enc_out = _encode(cfg, params, frontend) if cfg.n_encoder_layers else None
    xattn_src = frontend if cfg.cross_attn_layers else None

    def unit_fn(carry, unit_params):
        x, aux = carry
        for j, kind in enumerate(unit):
            x, aux = _apply_layer_full(
                cfg, kind, unit_params[f"u{j}"], x, positions=positions,
                enc_out=enc_out, frontend=xattn_src, aux=aux)
        # remat saves the carry at unit boundaries: keep it sharded
        return (_shard_activations(x), aux), None

    scan_fn = jax.checkpoint(unit_fn) if remat else unit_fn
    (x, aux), _ = lax.scan(scan_fn, (_shard_activations(x), 0.0),
                           params["units"])
    for j, kind in enumerate(rem):
        x, aux = _apply_layer_full(cfg, kind, params["rem"][f"r{j}"], x,
                                   positions=positions, enc_out=enc_out,
                                   frontend=xattn_src, aux=aux)
    x = L.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = _mask_pad_logits(cfg, x @ head)
    return logits, aux


def loss_fn(cfg: ModelConfig, params: Params, tokens, targets, mask=None,
            frontend=None, aux_weight: float = 0.01, z_weight: float = 1e-4):
    logits, aux = forward(cfg, params, tokens, frontend=frontend)
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0] - logz
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = -(ll * mask).sum() / denom
    zl = z_weight * ((logz ** 2) * mask).sum() / denom
    total = ce + zl + aux_weight * aux
    return total, {"ce": ce, "z_loss": zl, "aux": aux,
                   "tokens": denom}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    dh, hkv = cfg.d_head, cfg.n_kv_heads
    if kind == "rwkv":
        H, N = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
        return {"state": jnp.zeros((batch, H, N, N), jnp.float32),
                "sx_t": jnp.zeros((batch, cfg.d_model), jnp.bfloat16),
                "sx_c": jnp.zeros((batch, cfg.d_model), jnp.bfloat16)}
    if kind == "rglru":
        return {"h": jnp.zeros((batch, cfg.d_model), jnp.float32),
                "conv": jnp.zeros((batch, cfg.rglru_conv_width - 1,
                                   cfg.d_model), jnp.bfloat16)}
    # KV caches are stored FUSED (B, T, Hkv*dh): the fused layout matches
    # the natural sharding of the kv projection output, so the cache
    # scatter needs no resharding under TP. Decode writes each token into
    # the unit's stacked cache in place and reads a layer in this layout as
    # it lies (`_decode_attend`); a per-head (B, T, Hkv, dh) view of it
    # would make XLA copy the layer out every round
    if kind == "xattn":
        nf = max(cfg.n_frontend_tokens, 1)
        return {"xk": jnp.zeros((batch, nf, hkv * dh), jnp.bfloat16),
                "xv": jnp.zeros((batch, nf, hkv * dh), jnp.bfloat16)}
    kv_len = min(max_len, cfg.attn_window) if cfg.attn_window else max_len
    c = {"k": jnp.zeros((batch, kv_len, hkv * dh), jnp.bfloat16),
         "v": jnp.zeros((batch, kv_len, hkv * dh), jnp.bfloat16)}
    if kind == "encdec":
        nf = max(cfg.n_frontend_tokens, 1)
        c["xk"] = jnp.zeros((batch, nf, hkv * dh), jnp.bfloat16)
        c["xv"] = jnp.zeros((batch, nf, hkv * dh), jnp.bfloat16)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    unit, n_units, rem = unit_structure(cfg)

    def stack(kind):
        one = _layer_cache(cfg, kind, batch, max_len)
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (n_units,) + a.shape), one)

    cache = {"units": {f"u{j}": stack(kind) for j, kind in enumerate(unit)},
             "rem": {f"r{j}": _layer_cache(cfg, kind, batch, max_len)
                     for j, kind in enumerate(rem)},
             # per-sequence decode positions (continuous batching)
             "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.n_encoder_layers:
        cache["enc_out"] = jnp.zeros(
            (batch, max(cfg.n_frontend_tokens, 1), cfg.d_model), jnp.bfloat16)
    return cache


def _cache_pos(cfg: ModelConfig, pos, max_len: int):
    """Ring-buffer write position for windowed caches."""
    if cfg.attn_window:
        return pos % min(cfg.attn_window, max_len)
    return pos


def _apply_layer_cached(cfg: ModelConfig, kind: str, p: Params, x, cache,
                        pos, *, layer=None, enc_out=None, frontend=None,
                        static_attn: bool = False):
    """Sequence chunk (prefill: pos scalar 0, `layer` None) or single step
    (decode: pos (B,) per-sequence positions — continuous batching) w/
    cache update.

    x: (B, S, d). In decode (`layer` given), self-attention k/v are the
    unit's whole stacks (U, B, T, Hkv*dh) and this layer is entry `layer`
    of them; every other leaf is this layer's own (see `_decode_layer`).
    """
    B, S, d = x.shape
    if kind == "rwkv":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, (sx, st) = R.rwkv_tmix_apply(cfg, p["tmix"], h, cache["sx_t"],
                                        cache["state"])
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        prev_c = cache["sx_c"]
        y, sxc = R.rwkv_cmix_apply(cfg, p["cmix"], h, prev_c)
        cache = {"state": st, "sx_t": sx.astype(jnp.bfloat16),
                 "sx_c": sxc.astype(jnp.bfloat16)}
        return x + y, cache
    if kind == "rglru":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, (hst, conv) = R.rglru_apply(cfg, p["rec"], h, h0=cache["h"],
                                       conv_carry=cache["conv"])
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        x = x + L.mlp_apply(cfg, p["mlp"], h)
        return x, {"h": hst, "conv": conv.astype(jnp.bfloat16)}
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    if kind == "xattn":
        h = L.apply_norm(cfg, p["ln1"], x)
        if frontend is not None:   # prefill: compute cross KV once
            _, xk, xv = L.attn_qkv(cfg, p["xattn"], h, kv_src=frontend)
            cache = {"xk": xk.reshape(B, -1, hkv * dh).astype(jnp.bfloat16),
                     "xv": xv.reshape(B, -1, hkv * dh).astype(jnp.bfloat16)}
        q = h @ p["xattn"]["wq"]
        if cfg.qkv_bias:
            q = q + p["xattn"]["bq"]
        q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
        if cfg.qk_norm:
            q = L.rms_norm(q, p["xattn"]["q_norm"])
        nf = cache["xk"].shape[1]
        o = L.flash_attention(q, cache["xk"].reshape(B, nf, hkv, dh),
                              cache["xv"].reshape(B, nf, hkv, dh),
                              causal=False, static=static_attn)
        x = x + (jnp.tanh(p["xgate"])
                 * L.attn_out(p["xattn"], o)).astype(x.dtype)
        h = L.apply_norm(cfg, p["ln2"], x)
        y, _ = _mlp_or_moe(cfg, p, h, 0.0)
        return x + y, cache

    # self-attention with KV cache (+ optional enc-dec cross)
    h = L.apply_norm(cfg, p["ln1"], x)
    if layer is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    else:
        positions = jnp.reshape(pos, (B, 1))
    q, k, v = L.attn_qkv(cfg, p["attn"], h, positions=positions)
    new_cache = dict(cache)
    kf = k.reshape(B, S, hkv * dh).astype(jnp.bfloat16)
    vf = v.reshape(B, S, hkv * dh).astype(jnp.bfloat16)
    if layer is None:
        # prefill from position 0 (right-padded prompts; pads are after the
        # valid tokens and get overwritten as decode advances per sequence)
        win = cache["k"].shape[1]
        if cfg.attn_window and S > win:
            slots = (jnp.arange(S - win, S)) % win
            ck = cache["k"].at[:, slots].set(kf[:, -win:])
            cv = cache["v"].at[:, slots].set(vf[:, -win:])
        else:
            ck = lax.dynamic_update_slice(cache["k"], kf, (0, 0, 0))
            cv = lax.dynamic_update_slice(cache["v"], vf, (0, 0, 0))
        new_cache["k"], new_cache["v"] = ck, cv
        o = L.flash_attention(q, k, v, causal=True, window=cfg.attn_window,
                              logit_softcap=cfg.attn_logit_softcap,
                              static=static_attn)
    else:
        # the token's k/v go in place at [layer, b, wpos[b]] of the stacks
        T = cache["k"].shape[2]
        at = (layer, jnp.arange(B), _cache_pos(cfg, pos, T))
        ck = cache["k"].at[at].set(kf[:, 0])
        cv = cache["v"].at[at].set(vf[:, 0])
        new_cache["k"], new_cache["v"] = ck, cv
        o = _decode_attend(cfg, q, ck, cv, layer, pos)
    x = x + L.attn_out(p["attn"], o)
    if kind == "encdec":
        h = L.apply_norm(cfg, p["lnx"], x)
        if enc_out is not None and frontend is not None:
            _, xk, xv = L.attn_qkv(cfg, p["xattn"], h, kv_src=enc_out)
            new_cache["xk"] = xk.reshape(B, -1, hkv * dh).astype(jnp.bfloat16)
            new_cache["xv"] = xv.reshape(B, -1, hkv * dh).astype(jnp.bfloat16)
        q = h @ p["xattn"]["wq"]
        if cfg.qkv_bias:
            q = q + p["xattn"]["bq"]
        q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
        nf = new_cache["xk"].shape[1]
        o = L.flash_attention(q, new_cache["xk"].reshape(B, nf, hkv, dh),
                              new_cache["xv"].reshape(B, nf, hkv, dh),
                              causal=False, static=static_attn)
        x = x + L.attn_out(p["xattn"], o)
    h = L.apply_norm(cfg, p["ln2"], x)
    y, _ = _mlp_or_moe(cfg, p, h, 0.0)
    return x + y, new_cache


def _decode_attend(cfg: ModelConfig, q, ks, vs, layer, pos):
    """Single-token attention over entry `layer` of the stacked caches,
    GQA-grouped (KV read once).

    q: (B,1,Hq,dh); ks/vs: (U,B,T,Hkv*dh); pos: (B,). Keys k < min(pos+1, T)
    are attended. Cache slot order may be a ring rotation — softmax is
    permutation invariant and RoPE was applied at write time, so ordering
    is irrelevant.

    The layer is read where it lies: both dots take it as the plain
    (B, T, Hkv*dh) operand it is stored as and contract over (or keep)
    Hkv*dh, so XLA fuses the slice of the stack into the dot. The query
    is held block-diagonally, (B, Hq, Hkv*dh) with head h's dh values in
    the columns of its KV head and zeros elsewhere; the output keeps each
    head's own columns. A per-head (B, T, Hkv, dh) view of the layer
    would be a different tiling on the chip: XLA copies the layer out of
    the stack, then again into a head-major layout, every round.
    """
    B, _, Hq, dh = q.shape
    Hkv = cfg.n_kv_heads
    G = max(1, Hq // Hkv)
    T = ks.shape[2]
    own = (jnp.arange(Hq)[:, None] // G
           == jnp.arange(Hkv * dh)[None, :] // dh)        # (Hq, Hkv*dh)
    qb = jnp.where(own, jnp.tile(q.reshape(B, Hq, dh), (1, 1, Hkv)), 0)
    s = jnp.einsum("bhc,btc->bht", qb, ks[layer],
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    if cfg.attn_logit_softcap:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    mask = jnp.arange(T)[None, :] < jnp.minimum(pos + 1, T)[:, None]
    s = jnp.where(mask[:, None, :], s, L.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,btc->bhc", p.astype(vs.dtype), vs[layer])
    o = jnp.where(own, o, 0).reshape(B, Hq, Hkv, dh).sum(2, dtype=o.dtype)
    return o.reshape(B, 1, Hq, dh)


def prefill(cfg: ModelConfig, params: Params, tokens, cache, frontend=None,
            prompt_lens=None):
    """Process right-padded prompts from position 0.

    prompt_lens: (B,) true prompt lengths (defaults to S). Returns
    (logits at each sequence's last real token, cache)."""
    unit, n_units, rem = unit_structure(cfg)
    B, S = tokens.shape
    if prompt_lens is None:
        prompt_lens = jnp.full((B,), S, jnp.int32)
    x = params["embed"][tokens]
    if cfg.rope_fraction == 0.0 and not cfg.attention_free:
        x = x + L.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = _encode(cfg, params, frontend)
        cache = dict(cache)
        cache["enc_out"] = enc_out.astype(jnp.bfloat16)
    xsrc = frontend if cfg.cross_attn_layers else None
    pos = jnp.zeros((), jnp.int32)

    def unit_fn(x, pc):
        unit_params, ucache = pc
        new_uc = {}
        for j, kind in enumerate(unit):
            x, new_uc[f"u{j}"] = _apply_layer_cached(
                cfg, kind, unit_params[f"u{j}"], x, ucache[f"u{j}"], pos,
                enc_out=enc_out, frontend=xsrc if xsrc is not None else frontend)
        return _shard_activations(x), new_uc

    x, new_units = lax.scan(unit_fn, x, (params["units"], cache["units"]))
    new_cache = dict(cache)
    new_cache["units"] = new_units
    new_rem = {}
    for j, kind in enumerate(rem):
        x, new_rem[f"r{j}"] = _apply_layer_cached(
            cfg, kind, params["rem"][f"r{j}"], x, cache["rem"][f"r{j}"], pos,
            enc_out=enc_out, frontend=xsrc if xsrc is not None else frontend)
    new_cache["rem"] = new_rem
    new_cache["pos"] = prompt_lens.astype(jnp.int32)
    # logits at each sequence's last real token
    last = jnp.clip(prompt_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32)
                                 .repeat(x.shape[-1], -1), axis=1)
    x_last = L.apply_norm(cfg, params["final_norm"], x_last)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return _mask_pad_logits(cfg, (x_last @ head)[:, 0]), new_cache


_SELF_KV = ("k", "v")      # self-attention K/V: written and read in place
_CROSS_KV = ("xk", "xv")   # cross-attention K/V: written by prefill only


def _decode_layer(cfg: ModelConfig, kind: str, p: Params, x, stack, i, pos):
    """One decode step of one layer on its unit position's cache `stack`,
    every leaf of which has a leading unit axis; this layer is entry `i`.

    What happens to a leaf follows from the leaf: self-attention k/v stay
    whole and get the token written in place and read where they lie;
    cross-attention xk/xv are read at `i`; any other leaf is a layer's
    small recurrent state (rglru h/conv, rwkv state/sx_*), read at `i`
    and written back whole at `i`.
    """
    one = {n: a if n in _SELF_KV else a[i] for n, a in stack.items()}
    x, new = _apply_layer_cached(cfg, kind, p, x, one, pos, layer=i)
    out = {}
    for n, a in stack.items():
        if n in _SELF_KV:
            out[n] = new[n]
        elif n in _CROSS_KV:
            out[n] = a
        else:
            out[n] = lax.dynamic_update_index_in_dim(
                a, new[n].astype(a.dtype), i, 0)
    return x, out


def decode_step(cfg: ModelConfig, params: Params, token, cache):
    """token: (B,) int32. Returns (logits (B,V), cache). Per-sequence
    positions in cache["pos"] (continuous batching).

    The layer scan carries the stacked per-unit cache, with the layer
    index as its input: each layer writes its token's k/v into the carried
    stack in place and attends over its entry where it lies
    (`_decode_layer`, `_decode_attend`). Under a jit that donates `cache`
    the step allocates no second cache and copies no layer of it."""
    unit, n_units, rem = unit_structure(cfg)
    x = params["embed"][token][:, None, :]
    pos = cache["pos"]                                   # (B,)
    if cfg.rope_fraction == 0.0 and not cfg.attention_free:
        # sinusoidal position of each sequence's current step
        d = cfg.d_model
        ang = pos[:, None].astype(jnp.float32) / jnp.power(
            10000.0, jnp.arange(0, d, 2, jnp.float32) / d)[None, :]
        pe = jnp.zeros((pos.shape[0], d), jnp.float32)
        pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(
            jnp.cos(ang[:, : (d - d // 2)]))
        x = x + pe[:, None, :].astype(x.dtype)

    def unit_fn(carry, xs):
        x, ucache = carry
        unit_params, i = xs
        ucache = dict(ucache)
        for j, kind in enumerate(unit):
            x, ucache[f"u{j}"] = _decode_layer(
                cfg, kind, unit_params[f"u{j}"], x, ucache[f"u{j}"], i, pos)
        return (x, ucache), None

    (x, new_units), _ = lax.scan(unit_fn, (x, cache["units"]),
                                 (params["units"], jnp.arange(n_units)))
    new_cache = dict(cache)
    new_cache["units"] = new_units
    new_rem = {}
    for j, kind in enumerate(rem):     # a stack of one layer each
        one = jax.tree.map(lambda a: a[None], cache["rem"][f"r{j}"])
        x, one = _decode_layer(cfg, kind, params["rem"][f"r{j}"], x, one, 0,
                               pos)
        new_rem[f"r{j}"] = jax.tree.map(lambda a: a[0], one)
    new_cache["rem"] = new_rem
    new_cache["pos"] = pos + 1
    x = L.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return _mask_pad_logits(cfg, (x @ head)[:, 0]), new_cache
