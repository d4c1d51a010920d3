"""The reduction from a profiler trace and host spans to the per-layer
metrics, on built traces with hand-computed answers."""
import pytest

import harness
import reduce_trace as rt
import work
from references import qwen

from metrics import (decode_device_ms, decode_roofline, idle_share,
                     refill_device_ms, refill_roofline, step_mfu)

# A model small enough to count by hand: d 4, two q heads of 2, one kv
# head, ff 8, one layer, vocabulary 10.
CONF = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "intermediate_size": 8, "num_hidden_layers": 1,
        "vocab_size": 10, "model_type": "qwen3", "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0}
PEAKS = {"flops": 1000.0, "bytes_per_s": 100.0}
S = 1e9          # trace nanoseconds per second


def test_union_of_intervals():
    b = rt.Busy([(0, 2), (1, 3), (5, 6), (6, 7), (10, 10), (9, 9.5)])
    assert b.iv == [(0, 3), (5, 7), (9, 9.5)]
    assert b.within(0, 100) == pytest.approx(5.5)
    assert b.within(2, 6) == pytest.approx(2.0)
    assert b.within(3, 5) == 0.0
    assert b.gaps(1, 10) == [(3, 5), (7, 9), (9.5, 10)]


def test_idle_gaps_are_attributed_to_the_host_span_they_fell_in():
    b = rt.Busy([(1, 2), (6, 7)])
    spans = [("pb.refill#0", 0, 4), ("pb.decode#1", 5, 8)]
    assert rt.idle_by_span(b, spans, 0, 10) == {
        "refill": pytest.approx(3.0), "decode": pytest.approx(2.0),
        "between_calls": pytest.approx(3.0)}


def test_model_counts_by_hand():
    s = qwen.work(CONF)
    # per layer: q 4x4, k and v 4x2 each, o 4x4, three MLP mats 4x8
    assert s.matmul_params == 16 + 16 + 16 + 96
    # bf16 mats, two f32 norms of 4, two f32 q/k norms of 2, bf16
    # embedding 10x4, f32 final norm of 4
    assert s.weight_bytes == 2 * 144 + 32 + 16 + 80 + 16
    assert s.kv_bytes_per_token == 8
    f, b = s.decode_round([3, 5])
    assert f == 2 * (2 * 144 + 2 * 4 * 10) + 4 * 2 * 2 * (3 + 5)
    assert b == 432 + 8 * 8
    f, b = s.prefill([3])
    assert f == 2 * 144 * 3 + 2 * 4 * 10 + 4 * 2 * 2 * (1 + 2 + 3)
    assert b == 432 + 3 * 8


def _run(calls, spans, device_ops, window):
    run = harness.Run(B=2, open_loop=False, w0=0.0, w1=10.0, requests=[],
                      calls=calls, setup={}, peaks=PEAKS)
    run.trace = rt.TraceData(device_ops=[device_ops],
                             spans=[("pb.window",) + window] + spans)
    return run


def _call(kind, i, n, fb):
    return harness.Call(kind, i, 0.0, 1.0, n, fb[0], fb[1])


def test_device_time_goes_by_host_span_and_rooflines_by_hand():
    s = qwen.work(CONF)
    dec = s.decode_round([3, 5])                # 864 flops, 496 bytes
    ref = s.prefill([3])                        # 1040 flops, 456 bytes
    calls = [_call("decode", 0, 2, dec), _call("refill", 1, 1, ref),
             _call("decode", 2, 2, dec)]
    spans = [("pb.decode#0", 0 * S, 10 * S), ("pb.refill#1", 10 * S, 20 * S),
             ("pb.decode#2", 20 * S, 30 * S)]
    # names say nothing: the ops land where their host span was open
    ops = [("refill_prog:fusion", 1 * S, 7.2 * S),    # 6.2 s in decode#0
           ("x:op", 11 * S, 14 * S), ("x:op", 13 * S, 16.12 * S),
           ("decode_prog:fusion", 21 * S, 27.2 * S)]
    run = _run(calls, spans, ops, (0, 40 * S))
    assert decode_device_ms.read(run) == pytest.approx(6200.0)
    assert refill_device_ms.read(run) == pytest.approx(5120.0)
    # decode: memory bound, 496 B / 100 B/s = 4.96 s against 6.2 s
    assert decode_roofline.read(run) == pytest.approx(80.0)
    # refill: memory bound, 456 / 100 = 4.56 s against 5.12 s
    assert refill_roofline.read(run) == pytest.approx(100 * 4.56 / 5.12)
    # idle: 40 s window, 17.52 s busy
    assert idle_share.read(run) == pytest.approx(100 * (1 - 17.52 / 40))
    # model flops over summed span time times peak
    assert step_mfu.read(run) == pytest.approx(
        100 * (2 * 864 + 1040) / (30 * 1000.0))
    assert work.bound(*dec, PEAKS) == "memory"


def test_a_decode_reading_only_the_live_context_is_at_most_100_percent():
    f, b = qwen.work(CONF).decode_round([3, 5])
    need = b / PEAKS["bytes_per_s"]      # weights once + live K/V only
    calls = [_call("decode", 0, 2, (f, b))]
    spans = [("pb.decode#0", 0, 10 * S)]
    run = _run(calls, spans, [("k", 0, need * S)], (0, 10 * S))
    assert decode_roofline.read(run) == pytest.approx(100.0)
    # a cache copy or a max_len read takes longer and reads under 100%
    run = _run(calls, spans, [("k", 0, 2 * need * S)], (0, 10 * S))
    assert decode_roofline.read(run) == pytest.approx(50.0)


def test_readers_return_nothing_without_a_device_trace():
    run = harness.Run(B=2, open_loop=False, w0=0.0, w1=1.0, requests=[],
                      calls=[], setup={}, peaks=PEAKS)
    for reader in (decode_device_ms, refill_device_ms, decode_roofline,
                   refill_roofline, idle_share, step_mfu):
        assert reader.read(run) is None
    run.trace = rt.TraceData(device_ops=[[("k", 0, 1)]],
                             spans=[("pb.window", 0, 10)])
    assert refill_roofline.read(run) is None
    assert decode_device_ms.read(run) is None


def test_top_ops_sums_self_time_by_name_inside_the_window():
    ops = [("a", 0, 2), ("b", 2, 3), ("a", 3, 4), ("c", 50, 60),
           ("loop", 10, 20), ("a", 11, 13), ("b", 15, 16)]
    assert rt.top_ops(ops, 0, 30) == [("loop", 7), ("a", 5), ("b", 2)]
    assert rt.op_name("jit_f(12)", "%fusion.3 = bf16[2] fusion(%x)") == \
        "jit_f(12):fusion.3"


def test_peaks_table_refuses_an_unknown_device():
    assert work.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
