"""JAX backend for the mapper's chunk evaluation (ISSUE 6).

Host code: the kernel computes in int64/float64, which the TPU has no
native units for, so it is always placed on the host CPU
(`jax.devices("cpu")[0]`) and never takes an accelerator, even in a
process that holds one.

The compressed candidate search is embarrassingly data-parallel: every
feasible (tile, subtile, pipeline) row of a chunk is priced independently by
~30 elementwise int64/float64 ops. This module evaluates those rows with one
`jax.jit`-compiled XLA kernel instead of a numpy broadcast chain, which fuses
the whole table computation into a single pass over the rows (numpy
materializes ~25 intermediate arrays per chunk).

Padding buckets: jit recompiles per input shape, and chunk row counts vary
with every (device, shape) mix. Chunks are therefore padded up to the next
power-of-two bucket (min 4096 rows) with infeasible filler rows (`p_ok` all
False — they price to inf and belong to no pair's segment), so a handful of
traces serve every chunk the engine will ever build. The ISSUE 10 pruning
layer (mapper._prune_pairs) needs nothing special here: its seed-row
chunks and cutoff-filtered chunks are ordinary row sets that land in the
same buckets, and because no table op reduces across rows, dropping rows
cannot change any surviving row's total. Dtype mix (int64 byte
widths vs float64 sub-byte widths) keys its own trace, exactly mirroring the
numpy path's dtype promotion rule.

Numerics: the kernel runs under `jax.enable_x64` so every
intermediate matches the numpy path's dtype (int64 ceil-divisions are exact;
float64 elementwise ops are IEEE). There are no reductions anywhere in the
table computation, so XLA cannot reassociate sums; the one documented
divergence is FMA contraction of `a*b + c` patterns, which can move a
latency by its last ulp. Winners are therefore compared exactly and
latencies to 1e-12 relative in the equivalence gate
(tests/test_mapper_jax.py / benchmarks/mapper_speed.py); warm-cache reruns
are bit-identical to their own backend's cold path because the persistent
layer keys on the backend (mapper._pair_key).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from .systolic import gemm_cycles_array

#: smallest padding bucket — below this, trace count would grow while the
#: per-call win over numpy is already negligible
_MIN_BUCKET = 1 << 12

# pipeline options (db2, db1) — must match mapper._DB_OPTIONS order
_DB_OPTIONS = ((0, 0), (0, 1), (1, 0), (1, 1))

#: the gathered per-row columns the kernel consumes, in a fixed order
_INT_COLS = ("tm", "tk", "tn", "sm", "sk", "sn", "sa_rows", "sa_cols",
             "lanes", "cores", "gb_bw_cyc", "vec_tp", "m", "k", "n", "batch")
_FLT_COLS = ("freq", "mem_bw", "mac_scale")
_DYN_COLS = ("bytes_a", "bytes_b", "bytes_out", "bytes_acc")  # int OR float


@jax.jit
def _tables_kernel(g: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """One fused pass over a padded bucket of candidate rows. Mirrors
    mapper._chunk_tables_numpy statement for statement."""
    TM_, TK_, TN_ = g["tm"], g["tk"], g["tn"]
    SM_, SK_, SN_ = g["sm"], g["sk"], g["sn"]
    P_OK = g["p_ok"]
    sa_rows, sa_cols, lanes = g["sa_rows"], g["sa_cols"], g["lanes"]
    freq, cores, gb_bw_cyc = g["freq"], g["cores"], g["gb_bw_cyc"]
    mem_bw, vec_tp = g["mem_bw"], g["vec_tp"]
    m_v, k_v, n_v, batch_v = g["m"], g["k"], g["n"], g["batch"]
    bytes_a_v, bytes_b_v = g["bytes_a"], g["bytes_b"]
    bytes_out_v, bytes_acc_v = g["bytes_out"], g["bytes_acc"]
    bshared_v, mac_scale_v = g["b_shared"], g["mac_scale"]

    # ---------------- level 0: core compute time for one subtile ----------
    sn_lane = -(-SN_ // lanes)
    subtile_cyc = gemm_cycles_array(SM_, SK_, sn_lane, sa_rows, sa_cols,
                                    xp=jnp)
    subtile_cyc = jnp.ceil(subtile_cyc / mac_scale_v).astype(jnp.int64)

    # ---------------- level 1: schedule subtiles across cores -------------
    n_sub_m = -(-TM_ // SM_)
    n_sub_n = -(-TN_ // SN_)
    n_sub_k = -(-TK_ // SK_)

    out_subtiles = n_sub_m * n_sub_n
    waves = -(-out_subtiles // cores)
    w = jnp.minimum(out_subtiles, cores)
    gm = jnp.minimum(n_sub_m,
                     jnp.maximum(1, jnp.round(jnp.sqrt(w))).astype(jnp.int64))
    gn = jnp.minimum(n_sub_n, jnp.maximum(1, -(-w // gm)))
    wave_traffic = gm * SM_ * TK_ * bytes_a_v + gn * TK_ * SN_ * bytes_b_v \
        + gm * gn * SM_ * SN_ * bytes_out_v
    wave_mem_cyc = -(-wave_traffic // gb_bw_cyc)
    wave_cmp_cyc = n_sub_k * subtile_cyc
    s1_db0 = waves * (wave_mem_cyc + wave_cmp_cyc)
    s1_db1 = waves * jnp.maximum(wave_mem_cyc, wave_cmp_cyc) \
        + jnp.minimum(wave_mem_cyc, wave_cmp_cyc)

    ck = jnp.maximum(1, jnp.minimum(cores // jnp.maximum(out_subtiles, 1),
                                    n_sub_k))
    k_per_core = -(-n_sub_k // ck)
    s2_cmp_cyc = k_per_core * subtile_cyc
    red_traffic = (2 * (ck - 1)) * SM_ * SN_ * bytes_acc_v
    red_cyc = -(-red_traffic // gb_bw_cyc) + \
        -(-((ck - 1) * SM_ * SN_) // jnp.maximum(vec_tp * cores, 1))
    s2_waves = -(-(out_subtiles * ck) // cores)
    s2_traffic = SM_ * TK_ * bytes_a_v + TK_ * SN_ * bytes_b_v
    s2_mem_cyc = -(-(s2_traffic * out_subtiles
                     // jnp.maximum(s2_waves, 1)) // gb_bw_cyc)
    s2_db0 = s2_waves * (s2_mem_cyc + s2_cmp_cyc) + red_cyc
    s2_db1 = s2_waves * jnp.maximum(s2_mem_cyc, s2_cmp_cyc) + red_cyc

    use_s2 = (s2_db0 < s1_db0, s2_db1 < s1_db1)
    tile_time = (jnp.where(use_s2[0], s2_db0, s1_db0) / freq,
                 jnp.where(use_s2[1], s2_db1, s1_db1) / freq)

    # ---------------- level 2: main memory <-> global buffer --------------
    n_t_m = -(-m_v // jnp.minimum(TM_, m_v))
    n_t_n = -(-n_v // jnp.minimum(TN_, n_v))
    n_t_k = -(-k_v // jnp.minimum(TK_, k_v))
    steps = batch_v * n_t_m * n_t_n * n_t_k
    a_bytes_step = TM_ * TK_ * bytes_a_v
    b_bytes_step = TK_ * TN_ * bytes_b_v
    c_bytes_tile = TM_ * TN_ * bytes_out_v
    step_mem_t = jnp.where(bshared_v & (batch_v > 1),
                           (a_bytes_step + b_bytes_step / batch_v) / mem_bw,
                           (a_bytes_step + b_bytes_step) / mem_bw)
    c_mem_t = c_bytes_tile / mem_bw
    c_total_t = batch_v * n_t_m * n_t_n * c_mem_t

    cols = []
    for p, (db2, db1) in enumerate(_DB_OPTIONS):
        tt = tile_time[db1]
        if db2:
            tot = steps * jnp.maximum(step_mem_t, tt) + c_total_t \
                + jnp.minimum(step_mem_t, tt)
        else:
            tot = steps * (step_mem_t + tt) + c_total_t
        cols.append(jnp.where(P_OK[:, p], tot, jnp.inf))

    return {"totals": jnp.stack(cols, axis=1),
            "use_s2": jnp.stack(use_s2, axis=1),
            "tile_time": jnp.stack(tile_time, axis=1),
            "steps": steps, "step_mem_t": step_mem_t,
            "c_total_t": c_total_t,
            "n_t_m": n_t_m, "n_t_n": n_t_n, "n_t_k": n_t_k}


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def _pad_col(val, n: int, b: int, dtype, fill) -> np.ndarray:
    """Densify a (possibly scalar-collapsed) column to the bucket length."""
    out = np.full(b, fill, dtype=dtype)
    out[:n] = val
    return out


def chunk_tables(g: Dict) -> Dict:
    """Evaluate one gathered chunk's candidate tables on the JAX backend.

    Input/output contract is mapper._chunk_tables_numpy's: numpy arrays in,
    numpy arrays out. Filler rows above the real row count are infeasible
    (p_ok False) and sliced off before returning.
    """
    n = int(g["tm"].size)
    if n == 0:
        return _empty_tables()
    b = _bucket(n)

    padded = {}
    for c in _INT_COLS:
        padded[c] = _pad_col(g[c], n, b, np.int64, 1)
    for c in _FLT_COLS:
        padded[c] = _pad_col(g[c], n, b, np.float64, 1.0)
    for c in _DYN_COLS:
        # mirror the numpy path's promotion rule: int64 unless sub-byte
        # widths appeared in this chunk (the dtype keys the jit trace)
        v = np.asarray(g[c])
        dt = np.float64 if v.dtype == np.float64 else np.int64
        padded[c] = _pad_col(g[c], n, b, dt, 1)
    padded["b_shared"] = _pad_col(g["b_shared"], n, b, bool, False)
    p_ok = np.zeros((b, 4), dtype=bool)
    p_ok[:n] = g["p_ok"]
    padded["p_ok"] = p_ok

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        out = jax.device_get(_tables_kernel(padded))
    return {k: v[:n] for k, v in out.items()}


def _empty_tables() -> Dict:
    z = np.zeros(0)
    zi = np.zeros(0, dtype=np.int64)
    return {"totals": np.zeros((0, 4)),
            "use_s2": np.zeros((0, 2), bool),
            "tile_time": np.zeros((0, 2)),
            "steps": zi, "step_mem_t": z, "c_total_t": z,
            "n_t_m": zi, "n_t_n": zi, "n_t_k": zi}
