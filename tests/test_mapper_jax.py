"""ISSUE 6: JAX chunk backend equivalence against numpy and the dense oracle.

The JAX backend pads feasible candidate rows into power-of-two buckets and
prices them with one jitted XLA kernel. Nothing in the table computation
reduces across rows, so the ONLY numeric freedom XLA has is FMA contraction
of `a*b + c`, worth at most one float64 ulp. The gate therefore is:

  * the winning Mapping must be IDENTICAL to numpy's on every pair;
  * latencies agree to 1e-12 relative (bit-equal in almost every case);
  * flops / traffic / candidate counts are integers and must be bit-equal;
  * numpy stays bit-for-bit with the dense oracle (matmul_perf_reference),
    anchoring both backends to the frozen seed semantics.

The sweep below is a fixed grid (devices x shapes incl. mixed per-operand
widths, sub-byte weights, batched/b_shared and mac_scale), so it runs in
full without hypothesis; the property test on top re-draws random shapes
when hypothesis is installed.
"""
import os
import subprocess
import sys

import pytest

from _hypothesis_compat import given, settings, st

from repro.core import hardware as hw
from repro.core import result_cache
from repro.core.mapper import (clear_matmul_cache, get_mapper_backend,
                               matmul_perf_batch, matmul_perf_reference,
                               set_mapper_backend)

jax = pytest.importorskip("jax")

REL = 1e-12

DEVICES = [hw.nvidia_a100(), hw.google_tpu_v5e(), hw.amd_mi210(),
           hw.compute_design("C")]

# (m, k, n, batch, bytes_a, bytes_b, bytes_out, bytes_acc, b_shared,
#  mac_scale) — spans prefill/decode aspect ratios, batched + shared-B,
# mixed and sub-byte operand widths, and narrow-datatype MAC rates
SHAPES = [(1, 128, 128, 1, 2, 2, 2, 2, False, 1.0),
          (16, 12288, 12288, 1, 2, 2, 2, 2, False, 1.0),
          (4096, 12288, 3072, 1, 2, 2, 2, 2, False, 1.0),
          (2048, 128, 2048, 8, 2, 2, 2, 2, True, 1.0),
          (7, 64, 2048, 112, 2, 2, 2, 2, False, 1.0),
          (333, 777, 129, 3, 2, 2, 4, 2, False, 1.0),
          (16, 12288, 12288, 1, 2, 1, 2, 4, False, 1.0),   # int8 weights
          (512, 4096, 4096, 1, 1, 1, 1, 4, False, 2.0),    # w8a8
          (64, 8192, 8192, 1, 2, 0.5, 2, 4, False, 1.0)]   # int4 weights


@pytest.fixture(autouse=True)
def _numpy_backend_cold_cache():
    """Every test starts on the default backend with cold memos and no
    persistent layer, and restores the backend afterwards."""
    prev = get_mapper_backend()
    set_mapper_backend("numpy")
    clear_matmul_cache()
    with result_cache.disabled():
        yield
    set_mapper_backend(prev)
    clear_matmul_cache()


def _solve_with(backend, device, shapes):
    set_mapper_backend(backend)
    clear_matmul_cache()        # the memo key has no backend: clear between
    try:
        return matmul_perf_batch(device, shapes)
    finally:
        set_mapper_backend("numpy")


def _assert_equivalent(a, b, what):
    assert a.mapping == b.mapping, what          # the winner: exact
    assert a.flops == b.flops, what
    assert a.main_memory_bytes == b.main_memory_bytes, what
    assert a.candidates_searched == b.candidates_searched, what
    assert abs(a.latency - b.latency) <= REL * abs(b.latency), what


@pytest.mark.parametrize("device", DEVICES, ids=lambda d: d.name)
def test_jax_backend_matches_numpy(device):
    np_res = _solve_with("numpy", device, SHAPES)
    jx_res = _solve_with("jax", device, SHAPES)
    for s, a, b in zip(SHAPES, jx_res, np_res):
        _assert_equivalent(a, b, f"{device.name} {s}")


def test_numpy_backend_is_bitwise_the_dense_oracle():
    """Anchors the whole equivalence chain: the default backend IS the seed
    reference, so the JAX gate above transitively gates against it too."""
    dev = DEVICES[0]
    for s, r in zip(SHAPES, _solve_with("numpy", dev, SHAPES)):
        ref = matmul_perf_reference(dev, *s)
        assert r.mapping == ref.mapping
        assert r.latency == ref.latency          # bit-for-bit
        assert r.flops == ref.flops
        assert r.main_memory_bytes == ref.main_memory_bytes


def test_jax_single_vs_batched_chunking_identical():
    """Bucket padding must not leak filler rows into real segments: solving
    shapes one-by-one (small buckets) equals solving them stacked (large
    buckets spanning several pairs)."""
    dev = DEVICES[1]
    stacked = _solve_with("jax", dev, SHAPES)
    for s, r_stacked in zip(SHAPES, stacked):
        r_single = _solve_with("jax", dev, [s])[0]
        _assert_equivalent(r_single, r_stacked, s)


@given(m=st.integers(1, 4096), k=st.integers(1, 16384),
       n=st.integers(1, 4096), batch=st.sampled_from([1, 3, 8]),
       wa=st.sampled_from([0.5, 1, 2, 4]), wb=st.sampled_from([0.5, 1, 2]),
       b_shared=st.booleans(), mac=st.sampled_from([1.0, 2.0, 4.0]))
@settings(max_examples=40, deadline=None)
def test_jax_backend_matches_numpy_property(m, k, n, batch, wa, wb,
                                            b_shared, mac):
    shape = (m, k, n, batch, wa, wb, 2, 4, b_shared, mac)
    for dev in DEVICES[:2]:
        a = _solve_with("jax", dev, [shape])[0]
        b = _solve_with("numpy", dev, [shape])[0]
        _assert_equivalent(a, b, f"{dev.name} {shape}")


# ---------------------------------------------------------------------------
# backend selection API
# ---------------------------------------------------------------------------

def test_backend_switch_roundtrip():
    assert get_mapper_backend() == "numpy"
    prev = set_mapper_backend("jax")
    assert prev == "numpy"
    assert get_mapper_backend() == "jax"
    assert set_mapper_backend("numpy") == "jax"


def test_backend_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown mapper backend"):
        set_mapper_backend("cuda")
    assert get_mapper_backend() == "numpy"       # unchanged on error


def test_backend_env_var_selects_jax():
    env = dict(os.environ, REPRO_MAPPER_BACKEND="jax")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.core.mapper import get_mapper_backend;"
         "print(get_mapper_backend())"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "jax"


def test_backend_env_var_unknown_falls_back_to_numpy():
    env = dict(os.environ, REPRO_MAPPER_BACKEND="fortran")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.core.mapper import get_mapper_backend;"
         "print(get_mapper_backend())"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "numpy"


# ---------------------------------------------------------------------------
# padding buckets
# ---------------------------------------------------------------------------

def test_bucket_sizes_are_bounded_powers_of_two():
    from repro.core.mapper_jax import _MIN_BUCKET, _bucket
    assert _bucket(0) == _MIN_BUCKET
    assert _bucket(1) == _MIN_BUCKET
    assert _bucket(_MIN_BUCKET) == _MIN_BUCKET
    assert _bucket(_MIN_BUCKET + 1) == _MIN_BUCKET * 2
    for n in (5000, 70000, 130000):
        b = _bucket(n)
        assert b >= n and b & (b - 1) == 0
        assert b < 2 * max(n, _MIN_BUCKET)       # never over-pads 2x


def test_trace_reuse_across_chunk_sizes():
    """Different row counts inside one bucket reuse one jit trace — the
    whole point of padding (a trace per exact shape would recompile
    constantly)."""
    from repro.core import mapper_jax
    # warm one trace, then vary row counts within the same bucket
    _solve_with("jax", DEVICES[0], [SHAPES[0]])
    sizes = mapper_jax._tables_kernel._cache_size()
    _solve_with("jax", DEVICES[0], SHAPES[:3])
    _solve_with("jax", DEVICES[0], SHAPES[:5])
    assert mapper_jax._tables_kernel._cache_size() <= sizes + 2


def test_unimportable_jax_backend_raises_instead_of_degrading(monkeypatch):
    """REPRO_MAPPER_BACKEND=jax picks the backend at import without
    importing it; if mapper_jax then cannot be imported, the first chunk
    evaluation raises: no silent switch to numpy."""
    from repro import core
    from repro.core import mapper
    monkeypatch.setattr(mapper, "_BACKEND", "jax")      # as the env var does
    monkeypatch.setitem(sys.modules, "repro.core.mapper_jax", None)
    monkeypatch.delattr(core, "mapper_jax", raising=False)
    with pytest.raises(ImportError):
        matmul_perf_batch(DEVICES[0], SHAPES[:1])
    assert mapper.get_mapper_backend() == "jax"


def test_jax_backend_runs_on_the_host_cpu(monkeypatch):
    """The kernel computes in int64/float64: it is host code and is placed
    on the CPU even where an accelerator is the default device."""
    import numpy as np
    from repro.core import mapper_jax
    g = {c: np.ones(1, np.int64) for c in mapper_jax._INT_COLS}
    g.update({c: np.ones(1) for c in mapper_jax._FLT_COLS})
    g.update({c: np.full(1, 2, np.int64) for c in mapper_jax._DYN_COLS})
    g["b_shared"] = np.zeros(1, bool)
    g["p_ok"] = np.ones((1, 4), bool)
    seen = []
    kernel = mapper_jax._tables_kernel

    def spy(padded):
        seen.append(jax.config.jax_default_device)
        return kernel(padded)

    monkeypatch.setattr(mapper_jax, "_tables_kernel", spy)
    out = mapper_jax.chunk_tables(g)
    assert seen == [jax.devices("cpu")[0]]
    assert out["totals"].shape == (1, 4)
