"""The program's `engine.*` spans: the readers built on them, on built
traces with hand-computed answers, and the span tree a real Engine writes
into a profile on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import harness
import program_spans as ps
import reduce_trace as rt
from metrics import (decode_host_ms, idle_engine_share, idle_share,
                     refill_host_ms, sample_ms)

READERS = (decode_host_ms, refill_host_ms, sample_ms, idle_engine_share)
M = 1e6          # trace nanoseconds per millisecond


def _ev(name, s, e, **meta):
    return (name, s * M, e * M, meta)


def _decode(s, e, *kids):
    return [_ev("engine.decode", s, e, live=2)] + [_ev(f"engine.{n}", a, b)
                                                   for n, a, b in kids]


# A 100 ms window. Two whole decode rounds, the first with two waits; a
# third round straddles the window's end, and a refill its start. One
# admit_wave call refills two slots: one refill waits, one does not.
EVENTS = (
    [_ev("engine.refill", -5, 5, uid=0, slot=0, prompt_len=4)]
    + _decode(10, 20, ("feed", 10, 11), ("step", 11, 12),
              ("sample", 12, 13.5), ("wait", 13.5, 16), ("commit", 16, 17),
              ("wait", 17, 19))
    + _decode(30, 36, ("feed", 30, 31), ("step", 31, 32),
              ("sample", 32, 32.5), ("wait", 32.5, 35), ("commit", 35, 36))
    + [_ev("engine.plan", 39.5, 40),
       _ev("engine.refill", 40, 50, uid=7, slot=1, prompt_len=9),
       _ev("engine.wait", 47, 49),
       _ev("engine.refill", 50, 53, uid=8, slot=3, prompt_len=5)]
    + _decode(95, 105, ("wait", 96, 104)))
CALLS = [("refill", 0, 1, -6, 6), ("decode", 1, 2, 9, 21),
         ("decode", 2, 2, 29, 37), ("refill", 3, 2, 39, 54),
         ("decode", 4, 2, 94, 106)]
OPS = [(2, 4), (11, 16), (31, 35), (41, 45), (96, 104)]


def _run(events=EVENTS, ops=OPS):
    run = harness.Run(B=4, open_loop=False, w0=0.0, w1=1.0, requests=[],
                      calls=[harness.Call(k, i, 0.0, 1.0, n, 0.0, 0.0)
                             for k, i, n, _, _ in CALLS],
                      setup={}, peaks=None)
    run.trace = rt.TraceData(
        device_ops=[[("x:op", s * M, e * M) for s, e in ops]],
        spans=[("pb.window", 0.0, 100 * M)]
        + [(f"pb.{k}#{i}", s * M, e * M) for k, i, _, s, e in CALLS])
    run.engine_spans = ps.build(events)
    return run


def test_a_round_less_its_waits_is_the_hosts_own_work():
    run = _run()
    # round one: 10 ms less waits of 2.5 and 2; round two: 6 less 2.5;
    # the round across the window's end is left out
    assert decode_host_ms.read(run) == pytest.approx((5.5 + 3.5) / 2)
    assert sample_ms.read(run) == pytest.approx((1.5 + 0.5) / 2)


def test_refill_host_time_is_per_slot_of_the_traced_refill_calls():
    run = _run()
    # 10 ms less a 2 ms wait, and 3 ms with no wait, over the call's two
    # slots; the refill across the window's start is left out
    assert refill_host_ms.read(run) == pytest.approx((8 + 3) / 2)


def test_idle_engine_share_counts_exposed_host_work_clipped_to_window():
    run = _run()
    # exposed (engine span, no wait), clipped to [0, 100], less busy:
    # [0,5] 3; [10,13.5] 1; [16,17] 1; [19,20] 1; [30,32.5] 1; [35,36] 1;
    # [39.5,47] 3.5; [49,53] 4; [95,96] 1
    assert idle_engine_share.read(run) == pytest.approx(16.5)
    assert idle_share.read(run) == pytest.approx(81.0)


def test_every_reader_gives_none_without_engine_spans(tmp_path):
    run = _run(events=())
    assert all(r.read(run) is None for r in READERS)
    # a profile written by a program without the spans reads as none
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("pb.window"):
        jnp.arange(4.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert ps.read_profile(tmp_path) == []
    assert ps.read_profile(tmp_path / "none") == []
    # a run without a device trace reads nothing from disk
    bare = harness.Run(B=1, open_loop=False, w0=0.0, w1=1.0, requests=[],
                       calls=[], setup={}, peaks=None)
    assert ps.spans(bare) == []
    assert all(r.read(bare) is None for r in READERS)


def test_interval_subtraction():
    assert ps.subtract([(0, 10), (20, 30)], [(2, 3), (5, 22), (29, 40)]) == \
        [(0, 2), (3, 5), (22, 29)]
    assert ps.subtract([(0, 1)], []) == [(0, 1)]


def test_an_engine_writes_its_span_tree_into_the_profile(tmp_path):
    from repro import models
    from repro.configs import ARCHS, smoke_config
    from repro.serving import Engine, Request

    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    eng = Engine(cfg, models.init_params(cfg, jax.random.PRNGKey(0)),
                 batch_size=2, max_len=64)

    def requests():          # two in a wave, then one refill
        return [Request(uid=i, prompt=[i + 1, 5, 7][:2 + i % 2],
                        max_new_tokens=[2, 4, 3][i]) for i in range(3)]

    eng.run(requests())      # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    done = eng.run(requests())
    jax.profiler.stop_trace()
    roots = ps.read_profile(tmp_path)
    names = [sp.name for sp in roots]
    assert set(names) == {"engine.plan", "engine.wave", "engine.refill",
                          "engine.decode"}
    assert names.count("engine.wave") == 1
    wave, = (sp for sp in roots if sp.name == "engine.wave")
    assert wave.meta == {"n": 2}
    assert [c.name for c in wave.children] == [
        "engine.feed", "engine.prefill", "engine.sample", "engine.wait"]

    refills = [sp for sp in roots if sp.name == "engine.refill"]
    assert len(refills) == 1
    for sp in refills:
        assert len(sp.kids("engine.wait")) == 1
        r = next(r for r in done if r.uid == sp.meta["uid"])
        assert sp.meta == {"uid": r.uid, "slot": sp.meta["slot"],
                           "prompt_len": len(r.prompt)}
        assert [c.name for c in sp.children] == [
            "engine.cache", "engine.feed", "engine.prefill",
            "engine.insert", "engine.sample", "engine.wait"]

    rounds = [sp for sp in roots if sp.name == "engine.decode"]
    assert len(rounds) == eng.stats["steps"] // 2 and rounds
    for sp in rounds:
        assert [c.name for c in sp.children] == [
            "engine.feed", "engine.step", "engine.sample", "engine.wait",
            "engine.commit"]
        assert 1 <= sp.meta["live"] <= 2

    for root in roots:
        waits = [w for w in root.walk() if w.name == "engine.wait"]
        assert not waits or root.name in ("engine.wave", "engine.refill",
                                          "engine.decode")
        assert all(c.start >= root.start and c.end <= root.end
                   for c in root.walk())


def test_the_engine_programs_are_named_after_functions():
    from repro import models
    from repro.configs import ARCHS, smoke_config
    from repro.serving.engine import jit_steps

    cfg = smoke_config(ARCHS["qwen1.5-0.5b"])
    params = models.abstract_params(cfg)
    prefill, decode = jit_steps(cfg)
    cache = models.abstract_cache(cfg, 2, 16)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    assert "@jit_decode_step" in decode.lower(params, tok, cache).as_text()
    assert "@jit_prefill_step" in prefill.lower(
        params, jax.ShapeDtypeStruct((2, 3), jnp.int32), cache, tok,
        None).as_text()
