"""The hierarchy kernel share's reader, on hand-built runs, and the cells
that read it."""

import pytest

from bench import harness
from bench.tests.bench_cells import ROOT

METRIC = "hierarchy_kernel_share"


def _read(stats0, stats1):
    return harness.load_reader(ROOT, METRIC)(harness.Run(stats0=stats0, stats1=stats1))


@pytest.mark.parametrize("kernel,passes,want", [
    ((3, 9), (3, 9), 1.0),  # every pass on the kernels
    ((0, 2), (5, 9), 0.5),  # half of them fell back to the scans
    ((4, 4), (1, 3), 0.0),
])
def test_share_of_passes(kernel, passes, want):
    got = _read({"hierarchy_kernel_passes_total": kernel[0], "recluster_count": passes[0]},
                {"hierarchy_kernel_passes_total": kernel[1], "recluster_count": passes[1]})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("stats0,stats1", [
    ({"recluster_count": 1}, {"recluster_count": 3}),  # a program without the counter
    ({"hierarchy_kernel_passes_total": 2, "recluster_count": 2},
     {"hierarchy_kernel_passes_total": 2, "recluster_count": 2}),  # no pass in the window
    ({}, {}),  # a run that recorded no stats
])
def test_reader_gives_none_without_counted_passes(stats0, stats1):
    assert _read(stats0, stats1) is None


@pytest.mark.parametrize("cell", ["gas16-w160k.slide", "gas16-w640k.slide"])
def test_slide_cells_read_it(cell):
    names = {m["name"] for m in harness.load_cell(ROOT, cell).per_layer}
    assert {METRIC, "epsilon_pass_ms"} <= names


def test_query_cell_does_not():
    names = {m["name"] for m in harness.load_cell(ROOT, "gas16-w160k.query").per_layer}
    assert METRIC not in names
