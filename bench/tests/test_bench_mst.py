"""The strip Borůvka's reader, on hand-built runs, and the cell that
reads it."""

import json

import pytest

from bench import harness
from bench.tests.bench_cells import ROOT

CELL = "gas16-w640k.slide"
METRIC = "mst_rounds_per_pass"


def _read(stats0, stats1):
    return harness.load_reader(ROOT, METRIC)(harness.Run(stats0=stats0, stats1=stats1))


@pytest.mark.parametrize("rounds,passes,want", [((10, 25), (3, 6), 5.0), ((0, 4), (0, 1), 4.0)])
def test_rounds_per_pass(rounds, passes, want):
    got = _read({"mst_rounds_total": rounds[0], "recluster_count": passes[0]},
                {"mst_rounds_total": rounds[1], "recluster_count": passes[1]})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("stats0,stats1", [
    ({"recluster_count": 1}, {"recluster_count": 3}),  # a program without the counter
    ({"mst_rounds_total": 4, "recluster_count": 1},
     {"mst_rounds_total": 4, "recluster_count": 1}),  # no pass in the window
])
def test_reader_gives_none_without_counted_passes(stats0, stats1):
    assert _read(stats0, stats1) is None


def test_cell_resolves_with_its_metrics():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic_name == "slide"
    assert cell.config["window"] == 640_000
    assert cell.config["engine"] == {"backend": "auto", "device_online": True,
                                     "spatial_index": True}
    assert {m["name"] for m in cell.end_to_end} == {
        "ingest_ops_per_s", "freshness_p95_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {METRIC, "epsilon_pass_ms"} <= names
    # it counts (Lp, Lp) calls, and this cell's kernel calls are strips
    assert "mutual_reach_roofline" not in names


def test_config_keys_match_the_w160k_config():
    """Same keys and guarantees; only sizes, engine path and precision move."""
    def load(name):
        return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())

    a, b = load("gas16-w160k"), load("gas16-w640k")
    assert set(a) == set(b)
    assert set(a["limits"]) == set(b["limits"])
    assert a["guarantees"] == b["guarantees"]
    assert a["stream"] == b["stream"]
    # one paper, but each source names the part that defines its deployment
    assert a["source"] != b["source"]
    assert a["source"].split()[0] == b["source"].split()[0]
    for k in ("min_pts", "compression", "epsilon", "max_block"):
        assert a[k] == b[k]
