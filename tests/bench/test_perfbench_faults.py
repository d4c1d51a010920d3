"""With the timed path broken underneath, a run that skips only the
harness's look for a chip comes out not correct: once for each fault a
served cell on one chip can have (perfbench/faults.py). The same faults
are read at the cells' own sizes on the chip through
perfbench/calibrate.py --fault."""
import pytest

import check
import faults
import serving_stats
import tiny_cells


def _verdict(out):
    failed = sum(1 for r in serving_stats.attempted(out.run) if r.failed)
    return check.verdict(out.check, failed, tiny_cells.WIDE_LIMIT)


@pytest.mark.parametrize("fault", list(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    out = tiny_cells.run(tiny_cells.cell(tiny_cells.WIDE), seed=31)
    assert out.check["tokens"] > 0
    assert out.check["logit_gap"] > tiny_cells.WIDE_LIMIT
    assert not _verdict(out)


def test_the_same_run_unbroken_is_correct():
    out = tiny_cells.run(tiny_cells.cell(tiny_cells.WIDE), seed=31)
    assert out.check["logit_gap"] <= tiny_cells.WIDE_LIMIT
    assert _verdict(out)
