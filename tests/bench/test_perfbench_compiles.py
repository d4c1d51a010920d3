"""After the harness's warm-up, driving a mix through the program's Engine
compiles nothing: the rule every cell's window keeps. Also the run's
report: its result line and the numbers compared beside their limits."""
import io
import json

import jax
import pytest

import harness
import serving_stats
import tiny_cells


def test_the_compile_counter_sees_a_compile():
    counter = harness.CompileCounter(jax)
    before = counter.count
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert counter.count > before


@pytest.mark.parametrize("mix", ["backlog", "poisson"])
def test_driving_the_mix_after_warm_up_compiles_nothing(mix):
    c = tiny_cells.cell(mix=tiny_cells.MIX if mix == "backlog"
                        else tiny_cells.OPEN_MIX)
    out = tiny_cells.run(c, seed=2**33 + 11, seconds=1.0)
    run = out.run
    assert out.compiles_in_window == 0
    kinds = {c.kind for c in run.calls_in_window()}
    assert {"refill", "decode"} <= kinds
    assert serving_stats.window_tokens(run) > 0
    assert out.check["tokens"] > 0
    assert not any(r.failed for r in run.requests)
    if mix == "backlog":
        # every decode round of a backlog window has every slot live
        assert {c.n for c in run.calls_in_window("decode")} == {c.params
                                                                ["slots"]}


def test_report_prints_the_result_line_and_the_numbers_compared(capsys):
    import run as bench_run
    bench = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    c = tiny_cells.cell()
    c.end_to_end = bench["end_to_end"]
    out = tiny_cells.run(c, seed=5, seconds=1.0)
    err = io.StringIO()
    result = bench_run.report(c, out, False, err=err)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == result
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"tokens_per_s", "tpot_p95_ms",
                                      "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert err.getvalue().splitlines()[-2].startswith("check: logit_gap ")
    assert any("compiles in window 0" in ln for ln in lines)
