"""The comparison that decides ``correct``.

`Outputs` holds what the timed path produced, read once the window has
closed: the engine's alive points and their leaves, the last published
snapshot (made by the engine's own ε-pass over the window's final
summary), and a seeded sample of the served queries.  `numbers` sets
each against the float64 references of `reference`, and `control_outputs`
makes the same `Outputs` from the references one precision lower, so
that the limits can be shown to separate the two.
"""

from __future__ import annotations

import dataclasses

import numpy as np
from scipy.spatial import cKDTree

from bench import reference as ref

# precision a configuration states for each layer -> the control's, one below
LOWER_SUMS = {"float64": "f32"}
LOWER_DOTS = {"float32-highest": "bf16x3"}


@dataclasses.dataclass
class Served:
    """One served query request: its rows and what came back (None when
    the call failed)."""

    rows: np.ndarray
    labels: np.ndarray | None
    bubble_index: np.ndarray | None
    distance: np.ndarray | None
    version: int


@dataclasses.dataclass
class Snapshot:
    version: int
    rep: np.ndarray  # (L, d) bubble representatives
    n: np.ndarray  # (L,) represented mass
    labels: np.ndarray  # (L,) flat labels
    mst_u: np.ndarray  # (L − 1,) tree edges: endpoints and weights
    mst_v: np.ndarray
    mst_w: np.ndarray
    center: np.ndarray  # (d,) the frame queries are centred in


@dataclasses.dataclass
class Outputs:
    alive_pids: np.ndarray  # pids the engine holds alive
    alive_X: np.ndarray  # the points it stores for them
    leaf: np.ndarray  # the leaf each alive point is summarised in
    final: Snapshot  # the snapshot over the final summary
    served: list  # [Served]
    snapshots: dict  # version -> Snapshot, every version served


@dataclasses.dataclass
class Expected:
    """What the benchmark itself knows: pid -> stream row of every point
    it inserted and did not expire, and the stream's rows."""

    pid_row: dict
    X: np.ndarray  # rows of the stream, indexed by stream position


def _rel(a, b, floor):
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor))) if len(b) else 0.0


def mislabelled(labels, want) -> int:
    """Rows whose label disagrees with ``want``: 0 exactly when the two
    are the same partition with noise as noise.  Each cluster maps to the
    ``want`` cluster most of its rows carry, at most one cluster to each."""
    labels, want = np.asarray(labels), np.asarray(want)
    wrong, used = 0, set()
    for lab in np.unique(labels):
        rows = want[labels == lab]
        if lab == -1:
            wrong += int(np.sum(rows != -1))
            continue
        vals, cnt = np.unique(rows[rows != -1], return_counts=True)
        if not len(vals) or int(vals[np.argmax(cnt)]) in used:
            wrong += int(rows.size)
            continue
        used.add(int(vals[np.argmax(cnt)]))
        wrong += int(rows.size - cnt.max())
    return wrong


def hierarchy_wrong(snap: Snapshot, mcs: float) -> int:
    """Bubbles the reference hierarchy (single linkage, weighted condensed
    tree, excess of mass) labels otherwise over the snapshot's own tree
    edges; every bubble counts when the edges do not span the table."""
    L = len(snap.n)
    u, v = np.asarray(snap.mst_u, np.int64), np.asarray(snap.mst_v, np.int64)
    if len(u) != L - 1 or len(v) != L - 1 or u.min(initial=0) < 0 or v.min(initial=0) < 0 \
            or max(u.max(initial=0), v.max(initial=0)) >= L:
        return L
    uf = list(range(L))
    for a, b in zip(u.tolist(), v.tolist()):
        ra, rb = ref._find(uf, a), ref._find(uf, b)
        if ra == rb:
            return L
        uf[ra] = rb
    merges = ref.single_linkage(u, v, np.asarray(snap.mst_w, np.float64), snap.n)
    want = ref.eom_labels(*ref.condense(merges, snap.n, mcs), L=L)
    return mislabelled(snap.labels, want)


def edge_gap(snap: Snapshot, rep, cd, scale: float) -> float:
    """Widest gap, over ``scale``, between a tree edge's weight and the
    mutual reachability d_m(u, v) = max(cd_u, cd_v, ‖rep_u − rep_v‖) of
    its two endpoints on the reference table (rows in snapshot order);
    infinite when an endpoint is not a row of the table."""
    u, v = np.asarray(snap.mst_u, np.int64), np.asarray(snap.mst_v, np.int64)
    w, L = np.asarray(snap.mst_w, np.float64), len(rep)
    if not (len(u) == len(v) == len(w)) or np.any((u < 0) | (u >= L) | (v < 0) | (v >= L)):
        return float("inf")
    dm = np.maximum(np.maximum(cd[u], cd[v]), np.linalg.norm(rep[u] - rep[v], axis=1))
    return float(np.max(np.abs(w - dm)) / scale) if len(w) else 0.0


def numbers(cfg: dict, out: Outputs, exp: Expected, sample_seed: int) -> dict:
    """Every number compared, by name."""
    min_pts, mcs = int(cfg["min_pts"]), float(cfg.get("min_cluster_size", cfg["min_pts"]))
    res = {}
    # 1. online summary: every insert and expiry applied exactly once, each
    #    stored point is the row inserted, and the summary's table is the
    #    clustering features of the window's alive points
    want = np.fromiter(exp.pid_row.keys(), np.int64, len(exp.pid_row))
    got = np.asarray(out.alive_pids, np.int64)
    missing = np.setdiff1d(want, got).size + np.setdiff1d(got, want).size
    both = np.intersect1d(want, got)
    if both.size < 2:  # nothing left to compare the summary with
        res["misapplied"] = int(max(missing, 1))
        res.update(dict.fromkeys(("rep_gap", "level_gap", "edge_gap"), float("inf")))
        res["hierarchy_wrong"] = len(out.final.n)
        return res
    pos = {int(p): i for i, p in enumerate(got)}
    sel = np.array([pos[int(p)] for p in both], np.int64)
    rows = np.array([exp.pid_row[int(p)] for p in both], np.int64)
    wrong_point = int(np.sum(np.any(out.alive_X[sel] != exp.X[rows], axis=1)))
    ids, LS, SS, N = ref.leaf_cfs(exp.X[rows], out.leaf[sel])
    rep_r, ext_r = ref.bubble_table(LS, SS, N)
    snap = out.final
    # snapshot rows follow the program's own order: pair each with the
    # reference bubble whose representative is nearest
    _, j = cKDTree(rep_r).query(snap.rep, k=1)
    j = np.asarray(j, np.int64)
    unpaired = len(rep_r) - np.unique(j).size + abs(len(rep_r) - len(snap.rep))
    wrong_n = int(np.sum(snap.n != N[j]))
    res["misapplied"] = int(missing + wrong_point + unpaired + wrong_n)
    scale = float(np.median(ext_r[ext_r > 0])) if np.any(ext_r > 0) else 1.0
    res["rep_gap"] = float(np.max(np.linalg.norm(snap.rep - rep_r[j], axis=1)) / scale)
    # 2. offline pass: HDBSCAN of the reference table, rows in snapshot order
    off = ref.offline_pass(rep_r[j], N[j], ext_r[j], min_pts, mcs)
    # the tree's levels, edge by edge in ascending weight (its total weight
    # averages rounding out and does not separate the control)
    w_r, w_p = np.sort(off.mst_w), np.sort(np.asarray(snap.mst_w, np.float64))
    res["level_gap"] = (_rel(w_p, w_r, float(np.median(w_r))) if len(w_p) == len(w_r)
                        else float("inf"))
    # ... each of its edges weighs what its endpoints' d_m does on the
    # reference table, so that the tree is a minimum spanning tree of the
    # reference's graph ...
    res["edge_gap"] = edge_gap(snap, rep_r[j], off.cd, float(np.median(off.mst_w)))
    # ... and the labels: exactly the HDBSCAN hierarchy of that tree
    res["hierarchy_wrong"] = hierarchy_wrong(snap, mcs)
    # 3. serve plane: a seeded sample of the served requests, replayed
    if out.served:
        rng = np.random.default_rng(sample_seed)
        k = min(len(out.served), int(cfg.get("query_sample", 64)))
        big = max(range(len(out.served)), key=lambda i: len(out.served[i].rows))
        pick = set(rng.choice(len(out.served), size=k, replace=False).tolist()) | {big}
        wrong, gap = 0, 0.0
        for i in sorted(pick):
            s = out.served[i]
            sn = out.snapshots.get(s.version)
            if s.labels is None or sn is None or len(s.labels) != len(s.rows):
                wrong += len(s.rows)
                continue
            _, best, d2 = ref.nearest(s.rows, sn.rep, sn.center)
            idx = s.bubble_index
            ok = (idx >= 0) & (idx < len(sn.labels))
            wrong += int(np.sum(~ok))
            idx, lab, dist = idx[ok], s.labels[ok], s.distance[ok]
            wrong += int(np.sum(lab != sn.labels[idx]))
            d_srv = np.sqrt(d2[np.nonzero(ok)[0], idx])
            typ = max(float(np.median(best)), 1e-12)
            gap = max(gap, _rel(d_srv, best[ok], typ), _rel(dist, d_srv, typ))
        res["query_wrong"] = int(wrong)
        res["query_gap"] = float(gap)
    return res


def control_outputs(cfg: dict, out: Outputs, exp: Expected) -> Outputs:
    """The references in the program's place, one precision below what
    the configuration states: the summary's sums, the ε-pass's and the
    serve plane's distance dots."""
    prec = cfg["precision"]
    sums, dots = LOWER_SUMS[prec["summary"]], LOWER_DOTS[prec["distances"]]
    min_pts, mcs = int(cfg["min_pts"]), float(cfg.get("min_cluster_size", cfg["min_pts"]))
    pids = np.fromiter(exp.pid_row.keys(), np.int64, len(exp.pid_row))
    rows = np.array([exp.pid_row[int(p)] for p in pids], np.int64)
    pos = {int(p): i for i, p in enumerate(out.alive_pids)}
    leaf = out.leaf[[pos.get(int(p), 0) for p in pids]]
    _, LS, SS, N = ref.leaf_cfs(exp.X[rows], leaf, sums)
    rep, ext = ref.bubble_table(LS, SS, N)
    off = ref.offline_pass(rep, N, ext, min_pts, mcs, dots)
    final = Snapshot(version=out.final.version, rep=rep, n=N, labels=off.labels,
                     mst_u=off.mst_u, mst_v=off.mst_v, mst_w=off.mst_w,
                     center=out.final.center)
    served = []
    for s in out.served:
        sn = out.snapshots.get(s.version)
        if sn is None:
            served.append(s)
            continue
        idx, dist, _ = ref.nearest(s.rows, sn.rep, sn.center, dots)
        served.append(Served(rows=s.rows, labels=sn.labels[idx], bubble_index=idx,
                             distance=dist, version=s.version))
    return Outputs(alive_pids=pids, alive_X=exp.X[rows], leaf=leaf, final=final,
                   served=served, snapshots=out.snapshots)


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Every number against its limit; a number without a limit fails."""
    checks = {k: [v, limits.get(k)] for k, v in nums.items()}
    ok = all(lim is not None and v <= lim for v, lim in checks.values())
    return ok, checks
