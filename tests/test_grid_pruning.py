"""Differential suite for the grid-pruned neighbor engine (kernels.grid —
DESIGN.md §10), held to the float64 oracle.

The `spatial_index=` opt-in promises EXACT results — it prunes only
what cannot win — at every layer the grid is wired into:

  * Eq. 6 core distances (`ops.bubble_core_distances`),
  * MST construction (`boruvka_grid_jax`, and the dense `boruvka_jax`
    it replaces),
  * query/ingest assignment (`ops.assign`, index AND distance),
  * the fused offline pipeline (`offline_recluster_from_table`) and the
    streaming serve plane end to end,

on blobs / uniform / duplicate-heavy / collinear data, d ∈ {2, 8, 16},
both ClusterBackend flavors, plus the two grid extremes: ALL points in
one cell (identical coordinates → zero quantization range) and one
point per cell (spread so far every Morton cell is a singleton).

Every program computes in float32, so the suite holds both the pruned
and the dense path to the same float64 answers rather than to each
other's bits (which follow the compiler's fusion choices and differ
between machines):

  * distance-valued outputs (core distances, assignment distances, MST
    weights, d_m matrices) lie within one stated f32 tolerance of the
    float64 value (`_delta`, `_tol`);
  * indices (nearest bubble, MST endpoints) are exact except at ties: a
    different index passes only if its float64 distance or weight is
    within that tolerance of the oracle's;
  * trees are spanning trees of the valid rows whose sorted weights are
    the float64 MST's levels and whose every edge weighs the float64 d_m
    of its own endpoints (the benchmark's `level_gap` and `edge_gap`);
  * labels are exactly the host HDBSCAN hierarchy (single linkage →
    condensed tree → excess of mass) over the pass's own tree (the
    benchmark's `hierarchy_wrong`).

Eq. 6 itself is tie-sensitive: where two neighbours sit within rounding
of each other, f32 decides their order, and the bubble C at which the
mass crosses min_pts — whose nnDist enters the value — can change by
O(1).  `_cd_values` therefore enumerates every value some such order
gives, and the tree bounds use its per-row extremes.

Property tests (via tests/_hypothesis_compat) cover the structural
invariants the exactness argument rests on: the Morton sort is a
bijection placing every valid rep in exactly one tile, tile lower
bounds never exceed any member distance (so candidate enumeration can
never prune a true nearest neighbor), and invalid/padded rows are
excluded from every candidate set — results stay on the oracle whatever
the CONTENTS of invalid rows and the amount of bucket padding.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bubbles import DataBubbles, bubble_core_distances
from repro.core.hdbscan import (
    _stabilities,
    condense_tree,
    extract_clusters,
    hdbscan_labels,
    single_linkage,
)
from repro.core.mst import boruvka_dense, boruvka_grid_jax, boruvka_jax
from repro.kernels import ops
from repro.kernels.grid import (
    _block_views,
    build_grid,
    grid_assign,
    grid_core_distances,
)

L = 120  # deliberately off-bucket: exercises the Lp = 128 padding
MIN_PTS = 5
DIMS = [2, 8, 16]
KINDS = ["blobs", "uniform", "dup", "collinear"]
BACKENDS = [True, False]  # use_ref: jnp reference / Pallas (interpret)


def _dataset(kind: str, d: int, seed: int, n: int = L) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "blobs":
        centers = rng.normal(0.0, 5.0, (4, d))
        X = centers[rng.integers(0, 4, n)] + rng.normal(0.0, 0.4, (n, d))
    elif kind == "uniform":
        X = rng.uniform(-4.0, 4.0, (n, d))
    elif kind == "dup":
        # heavy EXACT duplication: distance ties everywhere, so every
        # lowest-index tie-break in the engine is load-bearing
        base = rng.normal(0.0, 3.0, (max(n // 6, 1), d))
        X = base[rng.integers(0, base.shape[0], n)]
    elif kind == "collinear":
        # rank-1 data: most grid dims carry zero range (inv_w = 0)
        t = rng.uniform(-5.0, 5.0, (n, 1))
        X = t * rng.normal(0.0, 1.0, (1, d)) + rng.normal(0.0, 1.0, (1, d))
    else:  # pragma: no cover
        raise AssertionError(kind)
    return X.astype(np.float32)


def _table(kind: str, d: int, seed: int, n: int = L):
    rng = np.random.default_rng(seed + 1000)
    rep = _dataset(kind, d, seed, n)
    n_b = rng.integers(1, 8, n).astype(np.float32)  # integral masses
    extent = np.abs(rng.normal(0.2, 0.05, n)).astype(np.float32)
    return rep, n_b, extent


# ---------------------------------------------------------------------------
# the float64 oracle

TAU = 1e-6


def _delta(*tables) -> float:
    """The one tolerance the suite holds f32 results to, as the error
    budget δ = TAU·S² of a squared distance, S the largest row norm of
    the tables the program computed distances over.

    Why: every f32 distance comes from the expansion ‖x‖² + ‖y‖² − 2x·y
    at ``Precision.HIGHEST``, whose rounding error is a few ulps of S²,
    not of d² (eps = 2⁻²³ ≈ 1.2e-7, so TAU is eight of them; the widest
    gap seen on these tables is under one).  A distance d therefore
    carries at most min(√δ, δ/d) (`_tol`): 1e-3·S at d = 0, under
    1e-5·S beyond d = 0.1·S.  A wrong neighbour costs a neighbour
    spacing, which on these tables is far above that everywhere except
    at genuine near-ties, which the oracle admits explicitly."""
    S = max(float(np.linalg.norm(np.asarray(t, np.float64), axis=1).max()) for t in tables)
    return TAU * S * S


def _tol(d, delta):
    """What a float64 distance ``d`` may be off by in f32 (see `_delta`),
    plus TAU relative for the rounding of the stored value."""
    d = np.asarray(d, np.float64)
    return np.minimum(np.sqrt(delta), delta / np.maximum(d, 1e-300)) + TAU * d


def _dist64(x, y) -> np.ndarray:
    """Euclidean distances in float64 from coordinate differences
    (identical rows are exactly 0 apart)."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    return np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))


@functools.lru_cache(maxsize=None)
def _subset_sums(masses: tuple, cap: float) -> frozenset:
    """Every sum < ``cap`` of a subset of ``masses``."""
    sums = {0.0}
    for m in masses:
        sums |= {s + m for s in sums if s + m < cap}
    return frozenset(sums)


def _cd_values(rep, n_b, extent, min_pts: int, delta: float):
    """Every value float64 Eq. 6 takes on each row when neighbours whose
    squared distances lie within 2δ of each other (f32 cannot order
    them) may come in either order, each with what it may be off by.

    For a crossing bubble C, every bubble nearer than that band precedes
    it, every bubble beyond it follows, and any subset of the band may
    precede it; C's nnDist enters the value, so a tie can move it by
    O(1).  Returns (lo, hi, vals): the per-row bounds of every admissible
    value widened by its tolerance, and per row the (value, tolerance)
    pairs.  The untied value, `core.bubbles.bubble_core_distances`, is
    always among them (asserted)."""
    rep = np.asarray(rep, np.float64)
    n = np.asarray(n_b, np.float64)
    ext = np.asarray(extent, np.float64)
    Lr, dim = rep.shape
    mp = float(min_pts)
    D = _dist64(rep, rep)
    vals = []
    for b in range(Lr):
        d, d2 = D[b], D[b] ** 2
        order = np.argsort(d, kind="stable")
        reach = d2[order[min(int(np.searchsorted(np.cumsum(n[order]), mp)), Lr - 1)]]
        row = set()
        for c in np.flatnonzero(d2 <= reach + 2 * delta):
            base = float(n[d2 < d2[c] - 2 * delta].sum())
            if base >= mp:
                continue
            may = np.abs(d2 - d2[c]) <= 2 * delta
            may[c] = False
            nC = max(float(n[c]), 1.0)
            for s in _subset_sums(tuple(np.sort(n[may])), mp - base):
                if base + s + n[c] >= mp:
                    k = min(max(mp - base - s, 1.0), nC)
                    v = d[c] + (k / nC) ** (1.0 / dim) * ext[c]
                    row.add((float(v), float(_tol(d[c], delta) + TAU * v)))
        vals.append(np.array(sorted(row)))
    want = bubble_core_distances(DataBubbles(rep, n, ext, dim), min_pts)
    for b in range(Lr):
        v, t = vals[b].T
        assert (np.abs(v - want[b]) <= t).any(), ("oracle", b, want[b], vals[b])
    lo = np.array([(v[:, 0] - v[:, 1]).min() for v in vals])
    hi = np.array([(v[:, 0] + v[:, 1]).max() for v in vals])
    return lo, hi, vals


def _assert_cd(got, rep, n_b, extent, min_pts, delta, what):
    """Core distances on the float64 Eq. 6 (tie-aware); returns the
    oracle's per-row (lo, hi)."""
    lo, hi, vals = _cd_values(rep, n_b, extent, min_pts, delta)
    got = np.asarray(got, np.float64)
    assert got.shape == lo.shape, (what, got.shape, lo.shape)
    bad = [b for b in range(lo.shape[0])
           if not (np.abs(vals[b][:, 0] - got[b]) <= vals[b][:, 1]).any()]
    assert not bad, (what, "cd", bad[:10], got[bad[:5]], [vals[b] for b in bad[:5]])
    return lo, hi


def _assert_nearest(idx, dist, x, reps, delta, what, valid=None):
    """Nearest-bubble indices (and distances, unless ``dist`` is None)
    on the float64 nearest search: a row's index may differ from the
    oracle's only where the two squared distances are within the 2δ f32
    cannot resolve."""
    D = _dist64(x, reps)
    if valid is not None:
        D[:, ~np.asarray(valid)] = np.inf
    idx = np.asarray(idx, np.int64)
    assert idx.shape == (D.shape[0],), (what, idx.shape)
    assert ((idx >= 0) & (idx < D.shape[1])).all(), (what, "index range")
    got = D[np.arange(idx.shape[0]), idx]
    best = D.min(axis=1)
    bad = np.flatnonzero(got**2 > best**2 + 2 * delta)
    assert not bad.size, (what, "idx", bad[:10], idx[bad[:5]], D[bad[:5]].argmin(1))
    if dist is not None:
        dist = np.asarray(dist, np.float64)
        bad = np.flatnonzero(np.abs(dist - got) > _tol(got, delta))
        assert not bad.size, (what, "dist", bad[:10], dist[bad[:5]], got[bad[:5]])


def _levels(W) -> np.ndarray:
    """Sorted weights of the float64 minimum spanning tree of ``W``."""
    W = np.array(W, np.float64)
    np.fill_diagonal(W, np.inf)
    return np.sort(boruvka_dense(W)[2])


def _assert_mst(u, v, w, W_lo, W_hi, what):
    """(u, v, w) is a minimum spanning tree of some matrix between W_lo
    and W_hi: a spanning tree of their rows, each edge weighing its own
    endpoints' entry (the benchmark's ``edge_gap``), and its sorted
    weights between the two matrices' MST levels (``level_gap``; the
    k-th level of an MST only grows with the matrix)."""
    Lr = W_lo.shape[0]
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    w = np.asarray(w, np.float64)
    assert u.shape == v.shape == w.shape == (Lr - 1,), (what, u.shape, Lr)
    assert ((u >= 0) & (u < Lr) & (v >= 0) & (v < Lr)).all(), (what, "endpoint")
    root = list(range(Lr))

    def find(a):
        while root[a] != a:
            root[a] = root[root[a]]
            a = root[a]
        return a

    for a, b in zip(u.tolist(), v.tolist()):
        ra, rb = find(a), find(b)
        assert ra != rb, (what, "cycle", a, b)
        root[ra] = rb
    e_lo, e_hi = W_lo[u, v], W_hi[u, v]
    bad = np.flatnonzero((w < e_lo) | (w > e_hi))
    assert not bad.size, (what, "edge", bad[:10], w[bad[:5]], e_lo[bad[:5]], e_hi[bad[:5]])
    ws, l_lo, l_hi = np.sort(w), _levels(W_lo), _levels(W_hi)
    bad = np.flatnonzero((ws < l_lo) | (ws > l_hi))
    assert not bad.size, (what, "level", bad[:10], ws[bad[:5]], l_lo[bad[:5]], l_hi[bad[:5]])


def _dm_bounds(rep, lo, hi, delta):
    """Bounds on the f32 d_m = max(cd_i, cd_j, d_ij) matrix: core
    distances anywhere in the oracle's [lo, hi], distances within their
    tolerance."""
    D = _dist64(rep, rep)
    t = _tol(D, delta)
    W_lo = np.maximum(D - t, np.maximum(lo[:, None], lo[None, :]))
    W_hi = np.maximum(D + t, np.maximum(hi[:, None], hi[None, :]))
    return W_lo, W_hi


def _edges(eu, ev, ew, valid):
    keep = np.asarray(valid)
    return np.asarray(eu)[keep], np.asarray(ev)[keep], np.asarray(ew)[keep]


def _hierarchy(u, v, w, n_b, mcs):
    """Host HDBSCAN over a given tree: (labels, stability per label)."""
    n_b = np.asarray(n_b, np.float64)
    slt = single_linkage(
        np.asarray(u), np.asarray(v), np.asarray(w, np.float64), n_b.shape[0], weights=n_b
    )
    ct = condense_tree(slt, min_cluster_size=float(mcs))
    selected = extract_clusters(ct)
    stab = _stabilities(ct)
    return hdbscan_labels(ct, selected), np.array([stab[c] for c in selected])


def _assert_same_partition(a, b, what):
    """Equal up to the names of the clusters, noise as noise; returns the
    a-label → b-label map."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    assert a.shape == b.shape, (what, a.shape, b.shape)
    bad = np.flatnonzero((a == -1) != (b == -1))
    assert not bad.size, (what, "noise", bad[:10])
    pairs = set(zip(a[a >= 0].tolist(), b[b >= 0].tolist()))
    names = dict(pairs)
    assert len(pairs) == len(names) == len(set(names.values())), (what, "partition", pairs)
    return names


def _assert_labels(res, n_b, mcs, what):
    """A pass's labels and stabilities are exactly the host hierarchy of
    its own tree (stabilities to f32 summation error)."""
    u, v, w = res.mst
    want, stab = _hierarchy(u, v, w, n_b, mcs)
    names = _assert_same_partition(res.labels, want, what)
    got = np.asarray(res.stabilities, np.float64)
    assert got.shape == stab.shape, (what, got.shape, stab.shape)
    for k, j in names.items():
        np.testing.assert_allclose(got[k], stab[j], rtol=1e-4, err_msg=what)


def _centered(rep, n_b):
    """The frame `offline_recluster_from_table` computes in."""
    rep = np.asarray(rep, np.float64)
    n_b = np.asarray(n_b, np.float64)
    return rep - (n_b @ rep) / n_b.sum()


def _own_bounds(W, rep, delta):
    """A tree built from the d_m matrix ``W`` its pass computed holds to
    that W up to the tolerance of the distances alone: the core
    distances it was built from are the ones in W."""
    W = np.asarray(W, np.float64)
    t = _tol(_dist64(rep, rep), delta)
    return W - t, W + t


def _assert_pass(res, W, rep, n_b, extent, min_pts, what):
    """One offline pass on the oracle: the d_m matrix it returned (None
    when it built none) on the float64 one (tie-aware), its tree a
    minimum spanning tree of that matrix — or, without one, of some
    matrix the oracle admits — and its labels the hierarchy of its tree."""
    delta = _delta(_centered(rep, n_b))
    lo, hi, _ = _cd_values(rep, n_b, extent, min_pts, delta)
    W_lo, W_hi = _dm_bounds(rep, lo, hi, delta)
    if W is not None:
        W = np.asarray(W, np.float64)
        off = ~np.eye(W.shape[0], dtype=bool)
        bad = off & ((W < W_lo) | (W > W_hi))
        assert not bad.any(), (what, "W", np.argwhere(bad)[:5])
        assert (np.diag(W) == 0.0).all(), (what, "W diagonal")
        W_lo, W_hi = _own_bounds(W, rep, delta)
    _assert_mst(*res.mst, W_lo, W_hi, what)
    _assert_labels(res, n_b, min_pts, what)


# ---------------------------------------------------------------------------
# jitted wrappers: the paths under test are jitted programs, so they
# run as such here

_assign_dense = jax.jit(
    functools.partial(ops.assign, with_dist=True), static_argnames=("use_ref",)
)
_assign_grid = jax.jit(
    functools.partial(ops.assign, with_dist=True, spatial_index=True)
)


@functools.partial(jax.jit, static_argnames=("min_pts", "dim"))
def _mst_grid(repp, valid, nbp, extp, min_pts, dim):
    g = build_grid(repp, valid)
    cd = grid_core_distances(g, nbp, extp, min_pts, dim)
    return cd, boruvka_grid_jax(g, cd)


@functools.partial(jax.jit, static_argnames=("min_pts",))
def _mst_dense(repp, is_pad, nbp, extp, min_pts):
    W = ops.bubble_mutual_reachability(repp, nbp, extp, min_pts, use_ref=True)
    W = jnp.where(is_pad[:, None] | is_pad[None, :], jnp.inf, W)
    return W, boruvka_jax(W)


def _pad_table(rep, n_b, extent):
    n, d = rep.shape
    Lp = max(8, 1 << (max(n - 1, 1)).bit_length())
    repp = np.full((Lp, d), ops._PAD_COORD, np.float32)
    repp[:n] = rep
    nbp = np.zeros(Lp, np.float32)
    nbp[:n] = n_b
    extp = np.zeros(Lp, np.float32)
    extp[:n] = extent
    return repp, nbp, extp, np.arange(Lp) < n


# ---------------------------------------------------------------------------
# differential suite: core distances / assignment / MST / pipeline


class TestCoreDistanceParity:
    @pytest.mark.parametrize("d", DIMS)
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("use_ref", BACKENDS, ids=["jnp", "pallas"])
    def test_bitwise(self, kind, d, use_ref):
        """Dense and pruned core distances both on the float64 Eq. 6."""
        rep, n_b, extent = _table(kind, d, seed=d * 17 + len(kind))
        delta = _delta(rep)
        dense = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=use_ref)
        pruned = ops.bubble_core_distances(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, spatial_index=True
        )
        _assert_cd(dense, rep, n_b, extent, MIN_PTS, delta, f"cd dense {kind} d={d}")
        _assert_cd(pruned, rep, n_b, extent, MIN_PTS, delta, f"cd pruned {kind} d={d}")

    def test_min_pts_sweep(self):
        rep, n_b, extent = _table("blobs", 8, seed=3)
        delta = _delta(rep)
        for mp in (1, 2, 7, 30):
            dense = ops.bubble_core_distances(rep, n_b, extent, mp, use_ref=True)
            pruned = ops.bubble_core_distances(
                rep, n_b, extent, mp, spatial_index=True
            )
            _assert_cd(dense, rep, n_b, extent, mp, delta, f"cd dense min_pts={mp}")
            _assert_cd(pruned, rep, n_b, extent, mp, delta, f"cd pruned min_pts={mp}")


class TestAssignParity:
    @pytest.mark.parametrize("d", DIMS)
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("use_ref", BACKENDS, ids=["jnp", "pallas"])
    def test_index_and_distance(self, kind, d, use_ref):
        rep, _, _ = _table(kind, d, seed=d * 31 + len(kind))
        rng = np.random.default_rng(d * 7)
        x = np.concatenate(
            [
                _dataset(kind, d, seed=d * 5 + 1, n=48),
                rng.normal(0.0, 6.0, (29, d)).astype(np.float32),  # off-manifold
            ]
        )
        delta = _delta(x, rep)
        di, dd = _assign_dense(x, rep, use_ref=use_ref)
        gi, gd = _assign_grid(x, rep)
        _assert_nearest(di, dd, x, rep, delta, f"assign dense {kind} d={d}")
        _assert_nearest(gi, gd, x, rep, delta, f"assign pruned {kind} d={d}")

    def test_duplicate_tie_break_pinned(self):
        # every query equidistant to many identical reps, on coordinates
        # whose arithmetic is exact in f32: the winner must be the LOWEST
        # row index among the tied reps, on both paths
        rep = np.tile(np.array([[1.5, -2.0]], np.float32), (64, 1))
        rep[::7] += 4.0  # two duplicate clusters
        x = np.array([[1.5, -2.0], [5.5, 2.0], [3.0, 0.0]], np.float32)
        D = _dist64(x, rep)
        want = np.argmin(D, axis=1)  # first occurrence of the exact minimum
        for name, (i, dist) in (
            ("dense", _assign_dense(x, rep, use_ref=True)),
            ("pruned", _assign_grid(x, rep)),
        ):
            np.testing.assert_array_equal(np.asarray(i), want, err_msg=f"dup tie {name}")
            np.testing.assert_array_equal(
                np.asarray(dist, np.float64), D.min(axis=1), err_msg=f"dup tie {name}"
            )


class TestMstParity:
    @pytest.mark.parametrize("d", [2, 8, 16])
    @pytest.mark.parametrize("kind", KINDS)
    def test_full_edge_buffers(self, kind, d):
        """The grid Borůvka and the dense one it replaces: both spanning
        trees of the valid rows on the float64 MST, and the grid pass's
        own core distances on the float64 Eq. 6."""
        rep, n_b, extent = _table(kind, d, seed=d * 13 + len(kind))
        repp, nbp, extp, valid = _pad_table(rep, n_b, extent)
        delta = _delta(rep)
        cd, ge = _mst_grid(repp, jnp.asarray(valid), nbp, extp, MIN_PTS, d)
        W, de = _mst_dense(repp, jnp.asarray(~valid), nbp, extp, MIN_PTS)
        cd = np.asarray(cd, np.float64)[valid]
        W = np.asarray(W)[valid][:, valid]
        lo, hi = _assert_cd(cd, rep, n_b, extent, MIN_PTS, delta, f"mst cd {kind} d={d}")
        W_lo, W_hi = _dm_bounds(rep, lo, hi, delta)
        assert ((W >= W_lo) & (W <= W_hi) | np.eye(L, dtype=bool)).all(), f"W {kind} d={d}"
        # each tree against the d_m of the core distances it was built from
        _assert_mst(*_edges(*de), *_own_bounds(W, rep, delta), f"mst dense {kind} d={d}")
        D = _dist64(rep, rep)
        t = _tol(D, delta)
        cdm = np.maximum(cd[:, None], cd[None, :])
        _assert_mst(
            *_edges(*ge), np.maximum(D - t, cdm), np.maximum(D + t, cdm),
            f"mst pruned {kind} d={d}",
        )


class TestPipelineParity:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("use_ref", BACKENDS, ids=["jnp", "pallas"])
    def test_labels_mst_w(self, kind, use_ref):
        """Dense and spatial passes on the oracle: the d_m matrix each
        returns, its tree, labels and stabilities."""
        rep, n_b, extent = _table(kind, 8, seed=len(kind))
        Wd, rd = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, return_w=True
        )
        Ws, rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, return_w=True,
            spatial_index=True,
        )
        _assert_pass(rd, Wd, rep, n_b, extent, MIN_PTS, f"dense {kind} ref={use_ref}")
        _assert_pass(rs, Ws, rep, n_b, extent, MIN_PTS, f"spatial {kind} ref={use_ref}")

    @pytest.mark.parametrize("d", [2, 16])
    def test_labels_other_dims(self, d):
        rep, n_b, extent = _table("blobs", d, seed=d)
        Wd, rd = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, return_w=True
        )
        rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, spatial_index=True
        )
        _assert_pass(rd, Wd, rep, n_b, extent, MIN_PTS, f"dense d={d}")
        _assert_pass(rs, None, rep, n_b, extent, MIN_PTS, f"spatial d={d}")


class TestGridExtremes:
    """All points in ONE cell (zero quantization range) and one point
    per cell (every tile a spread-out singleton run)."""

    @pytest.mark.parametrize("d", DIMS)
    def test_all_points_one_cell(self, d):
        rep = np.tile(np.float32(1.25) * np.ones((1, d), np.float32), (L, 1))
        rng = np.random.default_rng(d)
        n_b = rng.integers(1, 5, L).astype(np.float32)
        extent = np.abs(rng.normal(0.1, 0.02, L)).astype(np.float32)
        self._check(rep, n_b, extent, np.concatenate([rep[:5], rep[:5] + 0.5]), d)

    @pytest.mark.parametrize("d", DIMS)
    def test_one_point_per_cell(self, d):
        rng = np.random.default_rng(d + 5)
        # spacing ≫ range/1024 cells: every occupied Morton cell is a
        # singleton, the opposite degenerate tiling
        rep = (rng.permutation(L)[:, None] * 500.0 + rng.normal(0, 1, (L, d))).astype(
            np.float32
        )
        n_b = rng.integers(1, 5, L).astype(np.float32)
        extent = np.abs(rng.normal(0.1, 0.02, L)).astype(np.float32)
        x = (rep[:32] + rng.normal(0, 20, (32, d))).astype(np.float32)
        self._check(rep, n_b, extent, x, d)

    @staticmethod
    def _check(rep, n_b, extent, x, d):
        delta = _delta(rep)
        dense = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=True)
        pruned = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, spatial_index=True)
        _assert_cd(dense, rep, n_b, extent, MIN_PTS, delta, f"extreme cd dense d={d}")
        _assert_cd(pruned, rep, n_b, extent, MIN_PTS, delta, f"extreme cd pruned d={d}")
        delta_q = _delta(x, rep)
        di, dd = _assign_dense(x, rep, use_ref=True)
        gi, gd = _assign_grid(x, rep)
        _assert_nearest(di, dd, x, rep, delta_q, f"extreme assign dense d={d}")
        _assert_nearest(gi, gd, x, rep, delta_q, f"extreme assign pruned d={d}")
        Wd, rd = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, return_w=True
        )
        rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, spatial_index=True
        )
        _assert_pass(rd, Wd, rep, n_b, extent, MIN_PTS, f"extreme dense d={d}")
        _assert_pass(rs, None, rep, n_b, extent, MIN_PTS, f"extreme spatial d={d}")


class TestServePlane:
    def test_streaming_engine_end_to_end(self):
        from repro.serving.stream import StreamingClusterEngine

        rng = np.random.default_rng(0)
        X = np.concatenate(
            [rng.normal(0, 0.4, (90, 3)) + c for c in ([0, 0, 0], [6, 6, 0], [-6, 5, 3])]
        )
        rng.shuffle(X)
        Q = np.random.default_rng(7).normal(0, 4, (37, 3))

        def run(spatial):
            eng = StreamingClusterEngine(
                dim=3, min_pts=5, backend="jnp", spatial_index=spatial
            )
            for i in range(0, len(X), 45):
                eng.submit_insert(X[i : i + 45])
                eng.poll()
            eng.flush()
            return eng.snapshot, eng.query_detailed(Q)

        s_d, r_d = run(False)
        s_s, r_s = run(True)
        # the online summary is host float64 and the same on both engines
        assert s_d.n_bubbles == s_s.n_bubbles
        np.testing.assert_array_equal(s_d.bubble_rep, s_s.bubble_rep)
        np.testing.assert_array_equal(s_d.bubble_n, s_s.bubble_n)
        for name, s, r in (("dense", s_d, r_d), ("spatial", s_s, r_s)):
            _assert_labels(s.result, s.bubble_n, 5, f"engine labels {name}")
            delta = _delta(Q - s.center, s.bubble_rep - s.center)
            _assert_nearest(r.bubble_index, r.distance, Q, s.bubble_rep, delta, f"query {name}")
            np.testing.assert_array_equal(
                r.labels, s.bubble_labels[r.bubble_index], err_msg=f"query labels {name}"
            )
        # three separated blobs: both passes find the same clusters, and
        # each query row is served the same cluster and strength
        names = _assert_same_partition(s_d.bubble_labels, s_s.bubble_labels, "engine")
        np.testing.assert_array_equal(
            [names.get(k, -1) for k in r_d.labels.tolist()], r_s.labels, "query labels"
        )
        np.testing.assert_allclose(r_d.strength, r_s.strength, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# property tests (tests/_hypothesis_compat): the structural invariants
# the exactness argument rests on

_PL = 64  # fixed shapes so the mini-engine's examples share compiles


def _draw_grid(seed, d, frac_invalid):
    rng = np.random.default_rng(seed)
    pts = rng.normal(0.0, 3.0, (_PL, d)).astype(np.float32)
    valid = rng.random(_PL) >= frac_invalid
    valid[rng.integers(0, _PL)] = True  # at least one valid row
    pts[~valid] = ops._PAD_COORD
    return pts, valid


class TestGridProperties:
    @given(
        st.integers(0, 10_000), st.sampled_from([2, 8]),
        st.sampled_from([0.0, 0.2, 0.6]),
    )
    @settings(max_examples=15, deadline=None)
    def test_every_rep_in_exactly_one_tile(self, seed, d, frac_invalid):
        pts, valid = _draw_grid(seed, d, frac_invalid)
        g = build_grid(jnp.asarray(pts), jnp.asarray(valid))
        orig = np.asarray(g.orig)
        # Morton sort is a bijection: each original row occupies exactly
        # one sorted slot, hence exactly one tile
        assert np.array_equal(np.sort(orig), np.arange(_PL))
        assert np.asarray(g.valid).sum() == valid.sum()
        # tile AABBs contain every valid member (the lower-bound proof
        # needs containment, not tightness)
        T = _PL // g.tile_lo.shape[0]
        p3 = np.asarray(g.pts).reshape(-1, T, d)
        v3 = np.asarray(g.valid).reshape(-1, T)
        tlo = np.asarray(g.tile_lo)
        thi = np.asarray(g.tile_hi)
        for t in range(p3.shape[0]):
            if v3[t].any():
                assert (p3[t][v3[t]] >= tlo[t] - 0).all()
                assert (p3[t][v3[t]] <= thi[t] + 0).all()

    @given(st.integers(0, 10_000), st.sampled_from([2, 8]))
    @settings(max_examples=10, deadline=None)
    def test_tile_lower_bounds_never_exceed_member_distances(self, seed, d):
        # if lb(block, tile) ≤ every true member distance, the ascending-
        # lb enumeration with a strict > cutoff can never prune a tile
        # holding a true nearest neighbor / true kNN member
        pts, valid = _draw_grid(seed, d, 0.2)
        g = build_grid(jnp.asarray(pts), jnp.asarray(valid))
        xb, xx, xv, xo, order, lbs = (np.asarray(a) for a in _block_views(g, 32))
        ps = np.asarray(g.pts, np.float64)
        vs = np.asarray(g.valid)
        T = _PL // g.tile_lo.shape[0]
        NB, bn, _ = xb.shape
        for b in range(NB):
            brows = ps[b * bn : (b + 1) * bn][xv[b]]
            if brows.shape[0] == 0:
                continue
            for r, t in enumerate(order[b]):
                trows = ps[t * T : (t + 1) * T][vs[t * T : (t + 1) * T]]
                if trows.shape[0] == 0:
                    assert not np.isfinite(lbs[b, r])
                    continue
                true_min = np.sqrt(
                    ((brows[:, None, :] - trows[None, :, :]) ** 2).sum(-1)
                ).min()
                assert lbs[b, r] <= true_min + 1e-3 * (1.0 + true_min)

    @given(st.integers(0, 10_000), st.sampled_from([2, 8]))
    @settings(max_examples=10, deadline=None)
    def test_candidates_contain_true_knn(self, seed, d):
        # end-to-end form of the no-pruned-neighbor property: the pruned
        # core distances and nearest bubbles are the float64 oracle's,
        # which they cannot be if a true neighbor were ever pruned
        pts, valid = _draw_grid(seed, d, 0.0)
        rng = np.random.default_rng(seed + 1)
        n_b = rng.integers(1, 6, _PL).astype(np.float32)
        extent = np.abs(rng.normal(0.2, 0.05, _PL)).astype(np.float32)
        pruned = ops.bubble_core_distances(pts, n_b, extent, MIN_PTS, spatial_index=True)
        _assert_cd(pruned, pts, n_b, extent, MIN_PTS, _delta(pts), f"prop cd seed={seed} d={d}")
        x = rng.normal(0.0, 3.5, (32, d)).astype(np.float32)
        gi, gd = _assign_grid(x, pts)
        _assert_nearest(gi, gd, x, pts, _delta(x, pts), f"prop assign seed={seed}")

    @given(st.integers(0, 10_000), st.sampled_from([0.3, 0.7]))
    @settings(max_examples=10, deadline=None)
    def test_invalid_rows_contribute_nothing(self, seed, frac_invalid):
        d = 8
        pts, valid = _draw_grid(seed, d, frac_invalid)
        g = build_grid(jnp.asarray(pts), jnp.asarray(valid))
        x = np.random.default_rng(seed + 2).normal(0, 3, (32, d)).astype(np.float32)
        idx, _ = grid_assign(g, jnp.asarray(x))
        idx = np.asarray(idx)
        assert valid[idx].all(), "assignment landed on an invalid row"
        # the CONTENTS of invalid rows are irrelevant: scribble garbage
        # into them and every result on valid rows stays on the oracle
        # over the valid rows alone
        pts2 = pts.copy()
        pts2[~valid] = (
            np.random.default_rng(seed + 3)
            .normal(3e5, 1e5, (int((~valid).sum()), d))
            .astype(np.float32)
        )
        g2 = build_grid(jnp.asarray(pts2), jnp.asarray(valid))
        idx2, _ = grid_assign(g2, jnp.asarray(x))
        delta_q = _delta(x, pts[valid])
        _assert_nearest(idx, None, x, pts, delta_q, "invalid-rows idx", valid=valid)
        _assert_nearest(idx2, None, x, pts, delta_q, "invalid-contents idx", valid=valid)
        rng = np.random.default_rng(seed + 4)
        n_b = np.where(valid, rng.integers(1, 6, _PL), 0).astype(np.float32)
        extent = np.abs(rng.normal(0.2, 0.05, _PL)).astype(np.float32)
        mp = min(MIN_PTS, int(n_b.sum()))
        delta = _delta(pts[valid])
        for name, grid in (("invalid-rows", g), ("invalid-contents", g2)):
            cd = np.asarray(grid_core_distances(grid, n_b, extent, mp, d))[valid]
            _assert_cd(cd, pts[valid], n_b[valid], extent[valid], mp, delta, f"{name} cd")

    @given(st.integers(0, 10_000))
    @settings(max_examples=8, deadline=None)
    def test_bucket_padding_invariance(self, seed):
        # doubling the padded bucket (extra all-invalid tiles) leaves the
        # real rows' results on the oracle
        d = 8
        rng = np.random.default_rng(seed)
        rep = rng.normal(0, 3, (_PL, d)).astype(np.float32)
        n_b = rng.integers(1, 6, _PL).astype(np.float32)
        extent = np.abs(rng.normal(0.2, 0.05, _PL)).astype(np.float32)
        x = rng.normal(0, 3.5, (32, d)).astype(np.float32)

        def at_bucket(Lp):
            repp = np.full((Lp, d), ops._PAD_COORD, np.float32)
            repp[:_PL] = rep
            nbp = np.zeros(Lp, np.float32)
            nbp[:_PL] = n_b
            extp = np.zeros(Lp, np.float32)
            extp[:_PL] = extent
            g = build_grid(jnp.asarray(repp), jnp.arange(Lp) < _PL)
            cd = grid_core_distances(g, nbp, extp, MIN_PTS, d)
            idx, m = grid_assign(g, jnp.asarray(x))
            return np.asarray(cd)[:_PL], np.asarray(idx), np.asarray(m)

        xx = np.sum(x.astype(np.float32) ** 2, axis=-1)
        for Lp in (_PL, 2 * _PL):
            cd, idx, m = at_bucket(Lp)
            _assert_cd(cd, rep, n_b, extent, MIN_PTS, _delta(rep), f"padding cd Lp={Lp}")
            dist = np.sqrt(np.maximum(xx + m, 0.0))
            _assert_nearest(idx, dist, x, rep, _delta(x, rep), f"padding assign Lp={Lp}")
