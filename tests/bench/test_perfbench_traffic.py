"""The benchmark's traffic generator: every block holds the same work in
the same order whatever the seed, and only the token ids change."""
import json
from collections import Counter
from pathlib import Path

import pytest

import tiny_cells
import traffic

MIXES = sorted((Path(__file__).resolve().parents[2] / "perfbench"
                / "traffic").glob("*.json"))


def _blocks(mix, seed, n_blocks, rate=None):
    n = mix["block"]
    reqs = traffic.take(mix, seed, 1000, n * n_blocks, rate)
    return [reqs[i * n:(i + 1) * n] for i in range(n_blocks)]


def _multiset(block):
    return Counter((len(r.prompt), r.out_len) for r in block)


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_same_work_in_the_same_order_for_every_seed(path):
    mix = json.loads(path.read_text())
    rate = 10.0 if mix["arrivals"] == "poisson" else None
    a = _blocks(mix, 1, 3, rate)
    b = _blocks(mix, 2**33 + 5, 3, rate)
    want = _multiset(a[0])
    for block in a + b:
        assert _multiset(block) == want
    order_a = [(len(r.prompt), r.out_len, r.due) for r in a[0] + a[1]]
    order_b = [(len(r.prompt), r.out_len, r.due) for r in b[0] + b[1]]
    assert order_a == order_b
    # blocks are shuffled, each in its own fixed order
    assert [len(r.prompt) for r in a[0]] != [len(r.prompt) for r in a[1]]
    assert a[0][0].prompt != b[0][0].prompt
    assert len({tuple(r.prompt) for r in a[0]}) == len(a[0])


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_prompt_lengths_stay_in_the_mix_set(path):
    mix = json.loads(path.read_text())
    rate = 10.0 if mix["arrivals"] == "poisson" else None
    reqs = traffic.take(mix, 7, 1000, 4 * mix["block"], rate)
    assert {len(r.prompt) for r in reqs} <= set(mix["prompt"]["buckets"])
    assert len(mix["prompt"]["buckets"]) <= 7
    assert all(mix["output"]["min"] <= r.out_len <= mix["output"]["max"]
               for r in reqs)
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


def test_poisson_schedule_repeats_and_keeps_its_gaps():
    mix = dict(tiny_cells.OPEN_MIX, block=64)
    n = mix["block"]
    one = [r.due for r in traffic.take(mix, 99, 1000, 2 * n, 8.0)]
    again = [r.due for r in traffic.take(mix, 99, 1000, 2 * n, 8.0)]
    other = [r.due for r in traffic.take(mix, 2**33 + 1, 1000, 2 * n, 8.0)]
    assert one == again == other
    assert one != [r.due for r in traffic.take(mix, 99, 1000, 2 * n, 9.0)]
    # every block holds the same gaps, so the same mean rate
    for dues in (one, other):
        gaps = [b - a for a, b in zip([0.0] + dues, dues)]
        assert sum(gaps[:n]) == pytest.approx(sum(gaps[n:]))
        assert sum(gaps[:n]) / n == pytest.approx(1 / 8.0, rel=0.05)
    assert all(b >= a for a, b in zip(one, one[1:]))


def test_backlog_requests_are_all_due_at_once():
    mix = json.loads((MIXES[0].parent / "short-backlog.json").read_text())
    assert {r.due for r in traffic.take(mix, 3, 1000, 100)} == {0.0}


def test_poisson_needs_a_rate():
    mix = tiny_cells.OPEN_MIX
    with pytest.raises(ValueError):
        next(traffic.stream(mix, 1, 1000, None))
