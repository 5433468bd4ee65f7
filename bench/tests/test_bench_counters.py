"""Readers of the program's own counters, on hand-built runs."""

import json
from types import SimpleNamespace

import pytest

from bench import harness
from bench.tests.bench_cells import ROOT

NEW = ("ingest_assign_ms_per_kop", "ingest_cf_ms_per_kop", "ingest_maintain_ms_per_kop",
       "maintain_steps_per_kop", "ingest_cpu_pct.slide", "ingest_cpu_pct.query",
       "epsilon_host_ms", "query_program_ms_p50", "query_program_ms_p95")


def _stats(span_s, splits, dissolves, wall, cpu, passes):
    return {"span_s": span_s, "tree_splits": splits, "tree_dissolves": dissolves,
            "ingest_wall_s_total": wall, "ingest_cpu_s_total": cpu,
            "recluster_count": passes, "offline_seconds_total": 0.0}


def _query(program_s):
    return harness.QueryRecord(due=0.0, rows=None, result=SimpleNamespace(program_s=program_s))


@pytest.fixture
def run():
    """A window of 20,000 operations and 4 passes, and 5 served requests."""
    r = harness.Run(ops_applied=20_000)
    r.stats0 = _stats({"ingest.assign": 1.0, "ingest.cf": 2.0, "ingest.maintain": 3.0,
                       "offline.capture": 0.5, "offline.pass": 4.0, "offline.program": 3.0},
                      splits=100, dissolves=50, wall=10.0, cpu=9.0, passes=7)
    r.stats1 = _stats({"ingest.assign": 1.1, "ingest.cf": 2.4, "ingest.maintain": 3.6,
                       "offline.capture": 0.51, "offline.pass": 5.6, "offline.program": 4.2},
                      splits=160, dissolves=80, wall=12.0, cpu=10.5, passes=11)
    r.queries = [_query(s) for s in (0.001, 0.002, 0.003, 0.004, 0.005)]
    r.queries.append(harness.QueryRecord(due=0.0, rows=None))  # failed: no result
    return r


WANT = {
    "ingest_assign_ms_per_kop": 1e3 * 0.1 / 20,
    "ingest_cf_ms_per_kop": 1e3 * 0.4 / 20,
    "ingest_maintain_ms_per_kop": 1e3 * 0.6 / 20,
    "maintain_steps_per_kop": (60 + 30) / 20,
    "ingest_cpu_pct.slide": 100 * 1.5 / 2.0,
    "ingest_cpu_pct.query": 100 * 1.5 / 2.0,
    "epsilon_host_ms": 1e3 * (0.01 + 1.6 - 1.2) / 4,
    "query_program_ms_p50": 3.0,
    "query_program_ms_p95": 4.8,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_counters(run, metric):
    assert harness.load_reader(ROOT, metric)(run) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_none_without_the_counters(metric):
    """A program without spans or counters (the stats an older engine
    keeps, results without ``program_s``) reads as nothing, not 0."""
    old = {"recluster_count": 3, "offline_seconds_total": 1.0, "inserts": 5}
    r = harness.Run(ops_applied=20_000, stats0=dict(old), stats1=dict(old, recluster_count=5))
    r.queries = [harness.QueryRecord(due=0.0, rows=None, result=SimpleNamespace(labels=None))]
    assert harness.load_reader(ROOT, metric)(r) is None


def test_span_absent_from_the_window_start_counts_from_zero(run):
    run.stats0["span_s"] = {}
    read = harness.load_reader(ROOT, "ingest_cf_ms_per_kop")
    assert read(run) == pytest.approx(1e3 * 2.4 / 20)


def test_every_new_metric_is_declared_for_its_cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in NEW:
        m = per[name]
        assert m["source"] == "program_counter" and set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert name in [x["name"] for x in harness.load_cell(ROOT, cell).per_layer]
