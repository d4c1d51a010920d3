"""The control of the correctness comparison, at a size a test run holds:
the plain reference computed in float8 in the program's place (the token
it puts first, read against the float32 reference) fails the limit that
the bf16 program meets, on three seeds, judged by the verdict that decides
`correct`. The same comparison at the cells' own sizes runs on the chip
through perfbench/calibrate.py."""
import pytest

import calibrate
import tiny_cells


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    # longer answers: enough served tokens to compare however slowly the
    # host runs
    mix = dict(tiny_cells.MIX, check_tokens=120,
               output={"median": 14, "sigma": 0.5, "min": 8, "max": 24})
    c = tiny_cells.cell(tiny_cells.WIDE, mix=mix, max_len=56)
    out = tiny_cells.run(c, seed=seed, seconds=5.0, controls=("fp8",))
    row = calibrate.summarize(c, out, seed=seed)
    assert row["tokens_compared"] >= 120
    assert row["program"]["correct"]
    assert row["program"]["logit_gap"] <= tiny_cells.WIDE_LIMIT
    assert not row["controls"]["fp8"]["correct"]
    assert row["controls"]["fp8"]["logit_gap"] > tiny_cells.WIDE_LIMIT
