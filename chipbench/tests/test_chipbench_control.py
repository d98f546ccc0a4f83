"""The control of each configuration, its reference in the next lower
precision, reads worse than the program on the same runs, and held to
the program's limit it comes out as not correct.  The limits themselves
were set from chip runs at the cells' sizes (PERF.md)."""

import pytest

from chipbench.tests import smoke


@pytest.mark.parametrize("seed", [11, 12])
def test_serving_control_reads_worse_than_the_program(seed):
    r = smoke.run(smoke.serve_cell(), seed=seed, control="fp8")
    got = r["readings"]
    assert got["control:fp8"] > 0
    assert got["control:fp8"] >= 3 * got["program"]


@pytest.mark.parametrize("seed", [11, 12])
def test_serving_control_is_not_correct(seed):
    cell = smoke.serve_cell()
    cell.workload["check"]["served_tokens"] = 96  # every served token
    r = smoke.run(cell, seconds=6.0, seed=seed, control="fp8")
    assert r["correct"], r["checks"]
    assert r["control_correct"] == {"control:fp8": False}, r["readings"]


def test_wordcount_control_fails_the_exact_comparison():
    r = smoke.run(smoke.wordcount_cell(), control="bfloat16")
    assert r["correct"]
    assert r["readings"]["program"] == 0
    assert r["readings"]["control:bfloat16"] > 0
    assert r["control_correct"] == {"control:bfloat16": False}
