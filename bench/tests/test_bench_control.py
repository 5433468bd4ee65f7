"""The control — the float64 references one precision below what the
configuration states, put in the program's place — must come out not
correct, where the program itself comes out correct (small CPU cells)."""

import pytest

from bench import compare
from bench.tests.bench_cells import drive, tiny_cell


@pytest.mark.parametrize("workload", ["gas16-w160k.slide", "gas16-w160k.query"])
def test_control_fails_where_the_program_passes(workload):
    cell = tiny_cell(workload, window=6000)
    out, exp = drive(cell, seed=2**32 + 3)
    limits = cell.config["limits"]
    ok, checks = compare.verdict(compare.numbers(cell.config, out, exp, 5), limits)
    assert ok, checks
    ctl = compare.control_outputs(cell.config, out, exp)
    ok, checks = compare.verdict(compare.numbers(cell.config, ctl, exp, 5), limits)
    assert not ok, checks
