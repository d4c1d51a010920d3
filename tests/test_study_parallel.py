"""ISSUE 10: parallel sharded Study execution — determinism and merging.

`Study.run(workers=N)` must be invisible in the results: `to_rows()` and
CSV output byte-identical to the serial path, identical persistent-cache
contents, and stats / EvalStats / MetricsRegistry counters that merge to
the serial totals (modulo wall-clock fields). Workers are real processes
(ProcessPoolExecutor), so these tests also pin the config plumbing: cache
root + enabled flag, mapper backend/prune mode and verify mode all travel
in the worker payload, never through inherited globals.
"""
import copy
import os
import tempfile

import pytest

from repro.core import hardware as hw
from repro.core import result_cache
from repro.core.evaluator import EvalStats
from repro.core.graph import Plan
from repro.core.mapper import MapperCacheStats, clear_matmul_cache
from repro.core.obs import MetricsRegistry, metrics
from repro.core.study import Study
from repro.core.workload import Trace, TrafficWorkload, Workload
from repro.configs import get_config

WORKLOADS = {"w256": Workload(2, 256, 32, samples=4),
             "w128": Workload(1, 128, 16, samples=2)}


def _grid_study(**kw):
    return Study(systems=[hw.dgx_a100(4)],
                 configs=[get_config("stablelm-1.6b"),
                          get_config("qwen2-0.5b")],
                 plans=[Plan(tp=2, dp=2)],
                 workloads=WORKLOADS, **kw)


def _run(workers, **kw):
    clear_matmul_cache()        # the serial side starts cold too
    return _grid_study(**kw).run(workers=workers)


def test_parallel_rows_and_csv_byte_identical():
    with result_cache.disabled():
        serial = _run(None)
        two = _run(2)
        eight = _run(8)         # clamps to len(cases)
    assert two.to_rows() == serial.to_rows()
    assert eight.to_rows() == serial.to_rows()
    assert two.to_csv() == serial.to_csv()
    assert eight.to_csv() == serial.to_csv()
    # merged sweep counters match the serial ones (wall-clock aside)
    assert two.stats.cases == serial.stats.cases
    assert two.stats.evaluated == serial.stats.evaluated
    assert two.stats.skipped_unfit == serial.stats.skipped_unfit
    assert two.stats.matmul_pairs_presolved \
        == serial.stats.matmul_pairs_presolved


def _tree(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in sorted(files):
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_parallel_disk_cache_contents_identical():
    """Cold serial and cold parallel runs persist the SAME entries, byte
    for byte — content-hashed keys + atomic writes make cross-process
    dedup safe, and merging changes nothing about what lands on disk."""
    with tempfile.TemporaryDirectory() as a, tempfile.TemporaryDirectory() as b:
        with result_cache.overridden(root=a, enabled=True):
            r_serial = _run(None)
        with result_cache.overridden(root=b, enabled=True):
            r_par = _run(2)
        assert r_par.to_rows() == r_serial.to_rows()
        ta, tb = _tree(a), _tree(b)
        assert sorted(ta) == sorted(tb)
        assert ta == tb


def test_parallel_warm_rerun_hits_case_cache():
    with tempfile.TemporaryDirectory() as root:
        with result_cache.overridden(root=root, enabled=True):
            cold = _run(2)
            warm = _run(2)
        assert warm.to_rows() == cold.to_rows()
        assert cold.stats.case_cache_hits == 0
        assert cold.stats.case_cache_misses == len(cold)
        assert warm.stats.case_cache_hits == len(warm)
        assert warm.stats.case_cache_misses == 0


def test_workers_zero_and_one_are_serial():
    with result_cache.disabled():
        assert _run(0).to_rows() == _run(1).to_rows() == _run(None).to_rows()


def test_jax_backend_through_spawned_workers():
    """Shards start by spawn with JAX pinned to the CPU; the mapper's JAX
    backend then runs inside them and rows match its serial run."""
    from repro.core.mapper import set_mapper_backend
    with result_cache.disabled():
        prev = set_mapper_backend("jax")
        try:
            serial = _run(None)
            par = _run(2)
        finally:
            set_mapper_backend(prev)
            clear_matmul_cache()    # the memo key has no backend
    assert par.to_rows() == serial.to_rows()


def test_worker_initializer_pins_jax_to_cpu(monkeypatch):
    import jax
    from repro.core.study import _host_only_worker
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    prev = jax.config.jax_platforms
    try:
        _host_only_worker()
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", prev)


def test_negative_workers_raises():
    with pytest.raises(ValueError):
        _grid_study().run(workers=-1)


def test_serve_stage_through_workers():
    trace = Trace.poisson(8, rate=20.0, in_len=(16, 64), out_len=8, seed=2)
    wls = [TrafficWorkload.from_trace(trace, slots=2, policy=p,
                                      kv_samples=4, seq_samples=4)
           for p in ("continuous", "static")]

    def study():
        clear_matmul_cache()
        return Study(systems=[hw.make_system(hw.nvidia_a100(), 1)],
                     configs=[get_config("qwen2-0.5b")], plans=[Plan()],
                     workloads=wls, stage="serve")

    with result_cache.disabled():
        serial = study().run()
        par = study().run(workers=2)
    assert len(par) == 2
    assert par.to_rows() == serial.to_rows()
    for r_s, r_p in zip(serial, par):
        assert r_p.sim is not None
        assert r_p.sim.goodput == r_s.sim.goodput
        assert r_p.sim.ttft(99) == r_s.sim.ttft(99)


# -- counter merging (satellite: merge-safe MapperCacheStats windows) -------

def test_merge_delta_counters_phases_gauges():
    reg = MetricsRegistry()
    reg.inc("mapper.misses", 3)
    reg.set_gauge("workers", 1.0)
    reg.merge_delta({"mapper.misses": 2.0, "mapper.rows_pruned": 7.0,
                     "gauge.workers": 4.0,
                     "phase.presolve.count": 2, "phase.presolve.seconds": 0.5})
    assert reg.counter("mapper.misses") == 5
    assert reg.counter("mapper.rows_pruned") == 7
    assert reg.gauge("workers") == 4.0            # gauges overwrite
    assert reg.phase_counts() == {"presolve": 2}  # phases add
    assert reg.phase_seconds() == {"presolve": 0.5}
    reg.merge_delta({"phase.presolve.count": 1,
                     "phase.presolve.seconds": 0.25})
    assert reg.phase_counts() == {"presolve": 3}
    assert reg.phase_seconds() == {"presolve": 0.75}


def test_mapper_cache_stats_window_sees_worker_activity():
    """Regression (ISSUE 10): a MapperCacheStats window constructed before
    a parallel run must report the workers' mapper activity after the
    join — per-worker registry deltas are summed into the parent registry,
    the single source of truth the window reads."""
    with result_cache.disabled():
        window = MapperCacheStats()
        before = window.misses
        _run(2)
        assert window.misses > before


def test_eval_stats_doc_roundtrip_and_merge():
    a = EvalStats(graphs=2, nodes=10, cache_hits=3, matmul_searches=4,
                  serial_seconds=0.5)
    doc = a.to_doc()
    assert doc["graphs"] == 2 and doc["serial_seconds"] == 0.5
    b = copy.deepcopy(a)
    b.merge(doc)
    assert b.graphs == 4 and b.nodes == 20 and b.cache_hits == 6
    assert b.serial_seconds == 1.0
    b.merge({"graphs": 0, "unknown_field": 9})    # zeros and strays ignored
    assert b.graphs == 4
    assert not hasattr(b, "unknown_field")


def test_parallel_merges_eval_stats():
    with result_cache.disabled():
        serial = _run(None)
        par = _run(2)
    s_ev = list(serial.evaluators.values())
    p_ev = list(par.evaluators.values())
    assert len(s_ev) == len(p_ev) == 1
    assert p_ev[0].stats.graphs == s_ev[0].stats.graphs
    assert p_ev[0].stats.matmul_searches == s_ev[0].stats.matmul_searches
    assert p_ev[0].stats.candidates_searched \
        == s_ev[0].stats.candidates_searched
