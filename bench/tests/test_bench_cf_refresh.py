"""The reader of the Bubble-tree's CF-refresh counter, on hand-built runs."""

import json

import pytest

from bench import harness
from bench.tests.bench_cells import ROOT

METRIC = "cf_refresh_nodes_per_kop"


def _read(stats0, stats1, ops=20_000):
    run = harness.Run(ops_applied=ops, stats0=stats0, stats1=stats1)
    return harness.load_reader(ROOT, METRIC)(run)


@pytest.mark.parametrize("before,after,ops,want", [
    (1_000, 25_000, 20_000, 24_000 / 20),
    (0, 0, 20_000, 0.0),
    (5, 1_205, 1_000, 1_200.0),
])
def test_reader_gives_growth_per_kop(before, after, ops, want):
    got = _read({"tree_cf_refreshes": before}, {"tree_cf_refreshes": after}, ops)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("stats0,stats1", [
    ({"recluster_count": 3}, {"recluster_count": 5}),
    ({"recluster_count": 3}, {"recluster_count": 5, "tree_cf_refreshes": 9}),
])
def test_reader_gives_none_without_the_counter(stats0, stats1):
    """An engine that keeps no ``tree_cf_refreshes`` reads as nothing, not 0."""
    assert _read(stats0, stats1) is None


def test_reader_gives_none_for_an_empty_window():
    assert _read({"tree_cf_refreshes": 1}, {"tree_cf_refreshes": 1}, ops=0) is None


def test_metric_is_declared_for_the_slide_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = {x["name"]: x for x in spec["per_layer"]}[METRIC]
    assert m["source"] == "program_counter" and m["moves"] == "ingest_ops_per_s"
    assert m["workloads"] == ["gas16-w160k.slide"]
    assert METRIC in [x["name"] for x in harness.load_cell(ROOT, "gas16-w160k.slide").per_layer]
