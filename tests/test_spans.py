"""Program spans and counters (repro.spans), on the CPU.

A small engine — host tree with an ``assign_fn``, deletes that force
dissolves, ε-passes, a query — runs under a profiler session; the
``.xplane.pb`` it writes is read back with `jax.profiler.ProfileData`.
"""

import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.serving.stream import StreamingClusterEngine
from repro.spans import SPANS, STAGES, span

DIM = 4


def _host_spans(trace_dir) -> dict:
    """Span name -> [(start, end)] of every program span in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return out


def _ancestors(tree, leaves) -> int:
    """Distinct internal ancestors of ``leaves``, up the parent links."""
    seen = set()
    for nid in {int(x) for x in leaves}:
        nid = int(tree.parent[nid])
        while nid != -1 and nid not in seen:
            seen.add(nid)
            nid = int(tree.parent[nid])
    return len(seen)


def _session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Fill outside any session, then slide the window, query and flush
    inside one; the tree's split/dissolve calls are counted by spies, and
    each block path's CF refreshes are recorded beside the ancestors of
    the leaves it touched."""
    rng = np.random.default_rng(0)
    eng = StreamingClusterEngine(DIM, min_pts=5, compression=0.05, epsilon=0.1,
                                 backend="jnp", device_assign=True)
    tree = eng.tree
    made = collections.Counter()
    split, dissolve = tree._split_leaf, tree._dissolve_leaf

    def split_spy(leaf):
        sib = split(leaf)
        made["splits"] += sib is not None
        return sib

    def dissolve_spy(leaf):
        made["dissolves"] += 1
        return dissolve(leaf)

    apply, delete = tree.apply_assigned_block, tree.delete_block
    refreshes = {"insert": [], "delete": []}

    def apply_spy(X, leaf_per_row, overfull_hint=None):
        want, r0 = _ancestors(tree, leaf_per_row), tree.cf_refreshes
        pids = apply(X, leaf_per_row, overfull_hint)
        refreshes["insert"].append((want, tree.cf_refreshes - r0))
        return pids

    def delete_spy(pids):
        want, r0 = _ancestors(tree, tree.point_leaf[np.asarray(pids)]), tree.cf_refreshes
        delete(pids)
        refreshes["delete"].append((want, tree.cf_refreshes - r0))

    tree._split_leaf, tree._dissolve_leaf = split_spy, dissolve_spy
    tree.apply_assigned_block, tree.delete_block = apply_spy, delete_spy
    pids = list(eng.ingest(rng.normal(size=(2000, DIM))))  # outside the session
    before = dict(eng.stats)
    trace_dir = tmp_path_factory.mktemp("trace")
    _session(trace_dir)
    polls = 0
    try:
        for _ in range(4):
            t = eng.submit_insert(rng.normal(size=(256, DIM)))
            eng.submit_delete(pids[:400])
            pids = pids[400:]
            eng.poll()
            polls += 1
            pids += list(t.pids)
        result = eng.query_detailed(rng.normal(size=(37, DIM)))
    finally:
        jax.profiler.stop_trace()
    return {"eng": eng, "before": before, "spans": _host_spans(trace_dir), "polls": polls,
            "made": made, "refreshes": refreshes, "result": result}


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_recorded(traced, name):
    assert traced["spans"][name], f"no {name} span in the trace"


@pytest.mark.parametrize("child, parent", [
    ("ingest.assign", "stream.poll"), ("ingest.cf", "stream.poll"),
    ("ingest.maintain", "stream.poll"), ("offline.capture", "stream.poll"),
    ("offline.pass", "stream.poll"), ("offline.program", "offline.pass")])
def test_spans_nest(traced, child, parent):
    outer = traced["spans"][parent]
    for s, e in traced["spans"][child]:
        assert any(ps <= s and e <= pe for ps, pe in outer), (child, s, e)


def test_no_span_is_recorded_outside_a_session(traced, tmp_path):
    # the fill's poll ran before the session: only the session's polls count
    assert len(traced["spans"]["stream.poll"]) == traced["polls"]
    # a span entered after the session stops leaves nothing in the next one
    with span("stream.poll"):
        pass
    _session(tmp_path)
    try:
        with span("query.program"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = _host_spans(tmp_path)
    assert len(got["query.program"]) == 1 and not got["stream.poll"]


def test_tree_counters_match_the_tree(traced):
    eng, made = traced["eng"], traced["made"]
    assert made["splits"] > 0 and made["dissolves"] > 0
    assert eng.tree.splits == made["splits"]
    assert eng.tree.dissolves == made["dissolves"]
    assert eng.stats["tree_splits"] == made["splits"]
    assert eng.stats["tree_dissolves"] == made["dissolves"]


@pytest.mark.parametrize("path", ["insert", "delete"])
def test_cf_refreshes_count_the_touched_leaves_ancestors(traced, path):
    """Each block refreshes exactly the distinct internal ancestors of the
    leaves it touched, and poll mirrors the tree's counter."""
    eng, calls = traced["eng"], traced["refreshes"]
    assert calls[path] and all(got == want > 0 for want, got in calls[path])
    total = sum(got for c in calls.values() for _, got in c)
    assert eng.tree.cf_refreshes == total
    assert eng.stats["tree_cf_refreshes"] == total


def test_ingest_wall_and_cpu_counters_grow(traced):
    eng, before = traced["eng"], traced["before"]
    wall = eng.stats["ingest_wall_s_total"] - before["ingest_wall_s_total"]
    cpu = eng.stats["ingest_cpu_s_total"] - before["ingest_cpu_s_total"]
    assert wall > 0 and cpu > 0


def test_span_seconds_reach_the_stats(traced):
    eng, before = traced["eng"], traced["before"]
    s0, s1 = before["span_s"], eng.stats["span_s"]
    assert s1 is not s0  # a copy of the stats keeps its own reading
    grew = {k: s1[k] - s0.get(k, 0.0) for k in s1}
    for name in ("ingest.assign", "ingest.cf", "ingest.maintain", "offline.capture",
                 "offline.pass", "offline.program"):
        assert grew[name] > 0, name
    assert grew["offline.program"] <= grew["offline.pass"]


def test_query_result_carries_its_program_seconds(traced):
    r = traced["result"]
    assert r.program_s is not None and r.program_s > 0
    assert len(r) == 37


def test_span_adds_its_seconds_even_when_the_block_raises():
    acc = {}
    with pytest.raises(KeyError):
        with span("ingest.cf", acc):
            raise KeyError("x")
    with span("ingest.cf", acc) as sp:
        pass
    assert acc["ingest.cf"] >= sp.seconds >= 0.0
    assert len(set(SPANS)) == len(SPANS) and len(set(STAGES)) == len(STAGES)


@pytest.mark.parametrize("branch", ["dense", "grid", "mesh-dense", "mesh-grid"])
def test_offline_pipeline_names_its_stages(branch):
    """Every stage of `_offline_pipeline` carries its scope as op-name
    metadata on each branch (the grid and mesh-grid passes build no d_m
    matrix, so they have no ``mutual_reach`` stage)."""
    Lp = 64
    spatial = branch.endswith("grid")
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
            if branch.startswith("mesh") else None)
    args = (jnp.zeros((Lp, DIM)), jnp.ones(Lp), jnp.zeros(Lp), jnp.asarray(Lp, jnp.int32),
            jnp.asarray(5.0))
    lowered = ops._offline_pipeline.lower(*args, 5, True, spatial=spatial,
                                          with_w=mesh is None, mesh=mesh)
    text = lowered.as_text(debug_info=True)
    want = [s for s in STAGES if not (s == "mutual_reach" and branch == "mesh-grid")]
    assert all(f"/{s}/" in text for s in want), [s for s in want if f"/{s}/" not in text]
