"""Differential exactness suite for the grid-pruned neighbor engine
(kernels.grid — DESIGN.md §10).

The `spatial_index=` opt-in promises BIT-EXACT results against the dense
paths, not approximate ones; this suite pins that promise at every layer
the grid is wired into:

  * Eq. 6 core distances (`ops.bubble_core_distances`),
  * MST construction (`boruvka_grid_jax` vs `boruvka_jax` on the dense
    mutual-reachability matrix — full edge buffers, not just weight),
  * query/ingest assignment (`ops.assign`, index AND distance level,
    pinning the lowest-index tie-break on duplicate-heavy data),
  * the fused offline pipeline (`offline_recluster_from_table`) and the
    streaming serve plane end to end,

on blobs / uniform / duplicate-heavy / collinear data, d ∈ {2, 8, 16},
both ClusterBackend flavors, plus the two grid extremes: ALL points in
one cell (identical coordinates → zero quantization range) and one
point per cell (spread so far every Morton cell is a singleton).

Comparator discipline (the suite's one subtle rule): every dense
comparator runs under jit.  Eager per-op dispatch picks different CPU
gemm paths than XLA codegen inside jit — up to ~1000 ulps apart after
catastrophic-cancellation amplification in ‖x‖²+‖y‖²−2xy — and the
REAL dense paths the grid replaces are all jitted programs.  Comparing
against an eager re-run would test the wrong bits.

Bit-exactness is anchored at the jnp reference (the repo's ground
truth): the grid layer is backend-independent jnp, so BOTH backends'
core distances and assignments carry the same reference bits.  The
spatial pass's Borůvka sweeps row strips of the backend's own
mutual-reachability kernel, so on the pallas backend its tree is held
bitwise to the dense Borůvka over the W that kernel returns.  The dense Pallas
interpret-mode kernels drift from that anchor by ulps in a few epilogue
ops (documented in kernels/grid.py), so on the pallas backend the suite
demands exact labels / indices / tie-breaks and reference-bit values,
with cross-checks against the pallas dense leg itself restricted to the
tie-free kinds (blobs/uniform): on dup/collinear tables exact distance
ties abound, and the pallas ulp drift flips WHICH tied neighbor wins
k-NN selection / argmin — an O(1) value change no tolerance can paper
over, and not a defect in either path.

Property tests (via tests/_hypothesis_compat) cover the structural
invariants the exactness argument rests on: the Morton sort is a
bijection placing every valid rep in exactly one tile, tile lower
bounds never exceed any member distance (so candidate enumeration can
never prune a true nearest neighbor), and invalid/padded rows are
excluded from every candidate set — results are invariant to both the
CONTENTS of invalid rows and the amount of bucket padding.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.mst import boruvka_grid_jax, boruvka_jax
from repro.kernels import ops
from repro.kernels import ref
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


def _bitwise(a, b, what=""):
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (what, a.shape, b.shape)
    assert a.tobytes() == b.tobytes(), (
        what,
        np.flatnonzero(a.reshape(-1) != b.reshape(-1))[:10],
    )


# ---------------------------------------------------------------------------
# jitted comparator wrappers (see module docstring: dense legs MUST be
# the jitted programs the grid actually replaces)

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
    return boruvka_grid_jax(g, cd)


@functools.partial(jax.jit, static_argnames=("min_pts",))
def _mst_dense(repp, is_pad, nbp, extp, min_pts):
    W = ops.bubble_mutual_reachability(repp, nbp, extp, min_pts, use_ref=True)
    W = jnp.where(is_pad[:, None] | is_pad[None, :], jnp.inf, W)
    return boruvka_jax(W)


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
        rep, n_b, extent = _table(kind, d, seed=d * 17 + len(kind))
        dense = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=use_ref)
        pruned = ops.bubble_core_distances(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, spatial_index=True
        )
        if use_ref:
            _bitwise(dense, pruned, f"cd {kind} d={d}")
        else:
            # the pallas strip kernel drifts by ulps from the reference
            # anchor; the spatial path must carry reference bits EXACTLY
            # on this backend too (it is the same jnp program)
            anchor = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=True)
            _bitwise(anchor, pruned, f"cd-vs-ref {kind} d={d}")
            if kind in ("blobs", "uniform"):
                # cross-check vs the drifting pallas dense leg only where
                # pairwise distances are tie-free: on dup/collinear tables
                # exact ties abound and ulp-level drift flips WHICH
                # neighbor is k-th, so the dense pallas value can differ
                # from the anchor by O(1), not O(eps) — the reference
                # bitwise check above is the contract there
                np.testing.assert_allclose(
                    np.asarray(dense), np.asarray(pruned), rtol=1e-3, atol=1e-5
                )

    def test_min_pts_sweep(self):
        rep, n_b, extent = _table("blobs", 8, seed=3)
        for mp in (1, 2, 7, 30):
            dense = ops.bubble_core_distances(rep, n_b, extent, mp, use_ref=True)
            pruned = ops.bubble_core_distances(
                rep, n_b, extent, mp, spatial_index=True
            )
            _bitwise(dense, pruned, f"cd min_pts={mp}")


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
        di, dd = _assign_dense(x, rep, use_ref=use_ref)
        gi, gd = _assign_grid(x, rep)
        # index-level parity pins the lowest-index tie-break; index and
        # distance bits are anchored at the jnp reference on BOTH backends
        if use_ref:
            _bitwise(di, gi, f"assign idx {kind} d={d}")
            _bitwise(dd, gd, f"assign dist {kind} d={d}")
        else:
            ri, rd_ = _assign_dense(x, rep, use_ref=True)
            _bitwise(ri, gi, f"assign idx-vs-ref {kind} d={d}")
            _bitwise(rd_, gd, f"assign dist-vs-ref {kind} d={d}")
            if kind in ("blobs", "uniform"):
                # vs the drifting pallas dense leg only on tie-free data:
                # dup/collinear queries sit equidistant to several reps,
                # where ulp drift legitimately flips the argmin winner —
                # the reference anchors above are the contract there
                _bitwise(di, gi, f"assign idx {kind} d={d} pallas")
                np.testing.assert_allclose(
                    np.asarray(dd), np.asarray(gd), rtol=1e-4, atol=1e-5
                )

    def test_duplicate_tie_break_pinned(self):
        # every query equidistant to many identical reps: the winner must
        # be the LOWEST original row index, exactly like the dense argmin
        rep = np.tile(np.array([[1.5, -2.0]], np.float32), (64, 1))
        rep[::7] += 4.0  # two duplicate clusters
        x = np.array([[1.5, -2.0], [5.5, 2.0], [3.0, 0.0]], np.float32)
        di, dd = _assign_dense(x, rep, use_ref=True)
        gi, gd = _assign_grid(x, rep)
        _bitwise(di, gi, "dup tie idx")
        _bitwise(dd, gd, "dup tie dist")


class TestMstParity:
    @pytest.mark.parametrize("d", [2, 8, 16])
    @pytest.mark.parametrize("kind", KINDS)
    def test_full_edge_buffers(self, kind, d):
        rep, n_b, extent = _table(kind, d, seed=d * 13 + len(kind))
        repp, nbp, extp, valid = _pad_table(rep, n_b, extent)
        ge = _mst_grid(repp, jnp.asarray(valid), nbp, extp, MIN_PTS, d)
        de = _mst_dense(repp, jnp.asarray(~valid), nbp, extp, MIN_PTS)
        for name, g, dn in zip(("eu", "ev", "ew", "valid"), ge, de):
            _bitwise(dn, g, f"mst {name} {kind} d={d}")
        gw = np.asarray(ge[2])[np.asarray(ge[3])]
        dw = np.asarray(de[2])[np.asarray(de[3])]
        _bitwise(dw.sum(), gw.sum(), f"mst total weight {kind} d={d}")


class TestPipelineParity:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("use_ref", BACKENDS, ids=["jnp", "pallas"])
    def test_labels_mst_w(self, kind, use_ref):
        rep, n_b, extent = _table(kind, 8, seed=len(kind))
        Wd, rd = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, return_w=True
        )
        Ws, rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=use_ref, return_w=True,
            spatial_index=True,
        )
        _bitwise(rd.labels, rs.labels, f"labels {kind} ref={use_ref}")
        if use_ref:
            for a, b, nm in zip(rd.mst, rs.mst, "uvw"):
                _bitwise(a, b, f"mst.{nm} {kind}")
            _bitwise(np.asarray(Wd), np.asarray(Ws), f"W {kind}")
            _bitwise(rd.stabilities, rs.stabilities, f"stabilities {kind}")
        else:
            np.testing.assert_allclose(rd.mst[2], rs.mst[2], rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(Wd), np.asarray(Ws), rtol=1e-4, atol=1e-6
            )
            # the spatial pass's Borůvka sweeps strips of this backend's
            # kernel: its tree is the dense Borůvka over the W it returns
            # (the same kernel over the grid's core distances), bitwise
            eu, ev, ew, valid = jax.jit(boruvka_jax)(jnp.asarray(Ws))
            keep = np.asarray(valid)
            for a, b, nm in zip(
                (np.asarray(eu)[keep].astype(np.int64),
                 np.asarray(ev)[keep].astype(np.int64),
                 np.asarray(ew)[keep].astype(np.float64)),
                rs.mst, "uvw",
            ):
                _bitwise(a, b, f"mst.{nm} vs own W {kind}")

    @pytest.mark.parametrize("d", [2, 16])
    def test_labels_other_dims(self, d):
        rep, n_b, extent = _table("blobs", d, seed=d)
        rd = ops.offline_recluster_from_table(rep, n_b, extent, MIN_PTS, use_ref=True)
        rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, spatial_index=True
        )
        _bitwise(rd.labels, rs.labels, f"labels d={d}")
        for a, b, nm in zip(rd.mst, rs.mst, "uvw"):
            _bitwise(a, b, f"mst.{nm} d={d}")


class TestGridExtremes:
    """All points in ONE cell (zero quantization range) and one point
    per cell (every tile a spread-out singleton run)."""

    @pytest.mark.parametrize("d", DIMS)
    def test_all_points_one_cell(self, d):
        rep = np.tile(np.float32(1.25) * np.ones((1, d), np.float32), (L, 1))
        rng = np.random.default_rng(d)
        n_b = rng.integers(1, 5, L).astype(np.float32)
        extent = np.abs(rng.normal(0.1, 0.02, L)).astype(np.float32)
        dense = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=True)
        pruned = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, spatial_index=True)
        _bitwise(dense, pruned, f"one-cell cd d={d}")
        x = np.concatenate([rep[:5], rep[:5] + 0.5])
        di, dd = _assign_dense(x, rep, use_ref=True)
        gi, gd = _assign_grid(x, rep)
        _bitwise(di, gi, f"one-cell assign idx d={d}")
        _bitwise(dd, gd, f"one-cell assign dist d={d}")
        rd = ops.offline_recluster_from_table(rep, n_b, extent, MIN_PTS, use_ref=True)
        rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, spatial_index=True
        )
        _bitwise(rd.labels, rs.labels, f"one-cell labels d={d}")

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
        dense = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, use_ref=True)
        pruned = ops.bubble_core_distances(rep, n_b, extent, MIN_PTS, spatial_index=True)
        _bitwise(dense, pruned, f"singleton cd d={d}")
        x = (rep[:32] + rng.normal(0, 20, (32, d))).astype(np.float32)
        di, dd = _assign_dense(x, rep, use_ref=True)
        gi, gd = _assign_grid(x, rep)
        _bitwise(di, gi, f"singleton assign idx d={d}")
        _bitwise(dd, gd, f"singleton assign dist d={d}")
        rd = ops.offline_recluster_from_table(rep, n_b, extent, MIN_PTS, use_ref=True)
        rs = ops.offline_recluster_from_table(
            rep, n_b, extent, MIN_PTS, use_ref=True, spatial_index=True
        )
        _bitwise(rd.labels, rs.labels, f"singleton labels d={d}")


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
        assert s_d.n_bubbles == s_s.n_bubbles
        _bitwise(s_d.bubble_labels, s_s.bubble_labels, "engine labels")
        _bitwise(r_d.labels, r_s.labels, "query labels")
        _bitwise(r_d.bubble_index, r_s.bubble_index, "query idx")
        _bitwise(r_d.distance, r_s.distance, "query dist")
        _bitwise(r_d.strength, r_s.strength, "query strength")


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
        # nearest/top-K results equal the jitted dense reference exactly,
        # which is impossible if any true neighbor were ever pruned
        pts, valid = _draw_grid(seed, d, 0.0)
        rng = np.random.default_rng(seed + 1)
        n_b = rng.integers(1, 6, _PL).astype(np.float32)
        extent = np.abs(rng.normal(0.2, 0.05, _PL)).astype(np.float32)
        dense = ops.bubble_core_distances(pts, n_b, extent, MIN_PTS, use_ref=True)
        pruned = ops.bubble_core_distances(pts, n_b, extent, MIN_PTS, spatial_index=True)
        _bitwise(dense, pruned, f"prop cd seed={seed} d={d}")
        x = rng.normal(0.0, 3.5, (32, d)).astype(np.float32)
        di, dd = _assign_dense(x, pts, use_ref=True)
        gi, gd = _assign_grid(x, pts)
        _bitwise(di, gi, f"prop assign idx seed={seed}")
        _bitwise(dd, gd, f"prop assign dist seed={seed}")

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
        # into them and every output bit on valid rows must be unchanged
        pts2 = pts.copy()
        pts2[~valid] = (
            np.random.default_rng(seed + 3)
            .normal(3e5, 1e5, (int((~valid).sum()), d))
            .astype(np.float32)
        )
        g2 = build_grid(jnp.asarray(pts2), jnp.asarray(valid))
        idx2, m2 = grid_assign(g2, jnp.asarray(x))
        _bitwise(idx, np.asarray(idx2), "invalid-contents idx")
        rng = np.random.default_rng(seed + 4)
        n_b = np.where(valid, rng.integers(1, 6, _PL), 0).astype(np.float32)
        extent = np.abs(rng.normal(0.2, 0.05, _PL)).astype(np.float32)
        mp = min(MIN_PTS, int(n_b.sum()))
        cd1 = grid_core_distances(g, n_b, extent, mp, d)
        cd2 = grid_core_distances(g2, n_b, extent, mp, d)
        _bitwise(
            np.asarray(cd1)[valid], np.asarray(cd2)[valid], "invalid-contents cd"
        )

    @given(st.integers(0, 10_000))
    @settings(max_examples=8, deadline=None)
    def test_bucket_padding_invariance(self, seed):
        # doubling the padded bucket (extra all-invalid tiles) must not
        # change a single output bit on the real rows
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

        cd1, i1, m1 = at_bucket(_PL)
        cd2, i2, m2 = at_bucket(2 * _PL)
        _bitwise(cd1, cd2, "padding cd")
        _bitwise(i1, i2, "padding idx")
        _bitwise(m1, m2, "padding m")
