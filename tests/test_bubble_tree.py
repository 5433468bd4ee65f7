"""Bubble-tree (paper §4.1, Algorithm 1) structural + behavioral tests."""

import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bubble_tree import BubbleTree
from repro.core.cf import cf_of_points


def _fill(bt, X):
    return [bt.insert(p) for p in X]


class TestInvariants:
    def test_invariants_after_inserts(self, rng):
        bt = BubbleTree(dim=3, compression=0.1)
        X = rng.normal(size=(300, 3))
        _fill(bt, X)
        bt.check_invariants()

    def test_invariants_after_mixed(self, rng):
        bt = BubbleTree(dim=2, compression=0.08)
        X = rng.normal(size=(250, 2))
        ids = _fill(bt, X)
        drop = rng.choice(ids, size=100, replace=False)
        for i in drop:
            bt.delete(int(i))
        bt.check_invariants()
        assert bt.n_points == 150

    @given(st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_invariants_random_workload(self, seed):
        rng = np.random.default_rng(seed)
        bt = BubbleTree(dim=2, compression=0.1)
        ids = []
        for _ in range(200):
            if ids and rng.random() < 0.3:
                j = rng.integers(len(ids))
                bt.delete(ids.pop(j))
            else:
                ids.append(bt.insert(rng.normal(size=2) * rng.choice([1.0, 5.0])))
        bt.check_invariants()

    def test_root_cf_represents_everything(self, rng):
        """Property 1: root CF == CF of the whole dataset."""
        bt = BubbleTree(dim=4, compression=0.1)
        X = rng.normal(size=(200, 4))
        _fill(bt, X)
        LS, SS, n = cf_of_points(X)
        np.testing.assert_allclose(bt.LS[bt.root], LS, rtol=1e-9, atol=1e-7)
        assert bt.SS[bt.root] == pytest.approx(SS, rel=1e-9)
        assert bt.N[bt.root] == n

    def test_exact_deletion_of_cf_stats(self, rng):
        """CF sums support exact removal: insert+delete == never inserted."""
        bt = BubbleTree(dim=3, compression=0.1)
        X = rng.normal(size=(100, 3))
        _fill(bt, X)
        extra = rng.normal(size=(30, 3)) + 10.0
        eids = _fill(bt, extra)
        for i in eids:
            bt.delete(i)
        LS, SS, n = cf_of_points(X)
        np.testing.assert_allclose(bt.LS[bt.root], LS, rtol=1e-8, atol=1e-6)
        assert bt.N[bt.root] == n


class TestCompressionSteering:
    @pytest.mark.parametrize("compression", [0.05, 0.1, 0.2])
    def test_leaf_count_tracks_target(self, rng, compression):
        """Property 4 / Algorithm 1: num_leaves steered to L = c*N."""
        bt = BubbleTree(dim=2, compression=compression)
        X = rng.normal(size=(400, 2))
        _fill(bt, X)
        target = max(bt.min_leaves, int(round(compression * 400)))
        assert abs(bt.num_leaves - target) <= max(2, 0.25 * target)

    def test_leaf_count_shrinks_on_delete(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        X = rng.normal(size=(300, 2))
        ids = _fill(bt, X)
        L_before = bt.num_leaves
        for i in ids[:200]:
            bt.delete(int(i))
        assert bt.num_leaves < L_before
        target = max(bt.min_leaves, int(round(0.1 * 100)))
        assert abs(bt.num_leaves - target) <= max(2, 0.3 * target)

    def test_to_bubbles_weights_sum_to_n(self, rng):
        bt = BubbleTree(dim=3, compression=0.1)
        X = rng.normal(size=(250, 3))
        _fill(bt, X)
        b = bt.to_bubbles()
        assert b.n.sum() == pytest.approx(250.0)
        assert b.size == bt.num_leaves


class TestBlockOps:
    def test_insert_block_matches_serial(self, rng):
        """Throughput path: block insert keeps the same root CF and
        steers to the same leaf count."""
        X = rng.normal(size=(300, 2))
        a = BubbleTree(dim=2, compression=0.1)
        _fill(a, X)
        b = BubbleTree(dim=2, compression=0.1)
        b.insert_block(X)
        np.testing.assert_allclose(a.LS[a.root], b.LS[b.root], rtol=1e-9)
        assert a.N[a.root] == b.N[b.root]
        assert abs(a.num_leaves - b.num_leaves) <= max(3, 0.3 * a.num_leaves)
        b.check_invariants()

    def test_delete_block(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        X = rng.normal(size=(200, 2))
        ids = bt.insert_block(X)
        bt.delete_block(ids[:80])
        assert bt.n_points == 120
        bt.check_invariants()

    def test_block_paths_keep_internal_cfs_fresh(self, rng):
        """Sliding stream off the origin, with a concentrated block that
        forces splits and its expiry that forces dissolves: after every
        block each alive internal node's CF is the sum of its children's,
        to 1e-12 of the summed magnitudes (exact sums taken here)."""
        d = 4
        bt = BubbleTree(dim=d, compression=0.05)
        fifo = bt.insert_block(rng.normal(size=(3000, d)) + 5.0)

        def check():
            bt.check_invariants()
            for nid in np.nonzero(bt.node_alive & ~bt.is_leaf)[0]:
                kids = [int(k) for k in bt.children[int(nid)]]
                for j in range(d):
                    col = [float(bt.LS[k, j]) for k in kids]
                    scale = math.fsum(abs(x) for x in col)
                    assert abs(bt.LS[nid, j] - math.fsum(col)) <= 1e-12 * scale, (nid, j)
                ss = math.fsum(float(bt.SS[k]) for k in kids)
                assert abs(bt.SS[nid] - ss) <= 1e-12 * ss, nid
                assert bt.N[nid] == sum(float(bt.N[k]) for k in kids), nid

        hot = None
        for step in range(60):
            if step == 20:
                s0 = bt.splits
                hot = bt.insert_block(rng.normal(size=(400, d)) * 0.01 + 7.0)
                assert bt.splits > s0
                check()
            if step == 40:
                d0 = bt.dissolves
                bt.delete_block(hot)
                assert bt.dissolves > d0
                check()
            fifo += bt.insert_block(rng.normal(size=(100, d)) + 5.0)
            check()
            bt.delete_block(fifo[:100])
            fifo = fifo[100:]
            check()


class TestMaintenanceFixpoint:
    """ISSUE 4 satellite: the old ``abs(target_L - num_leaves) + 2``
    deficit caps starved — a concentrated block landing in one leaf
    stayed arbitrarily overfull whenever the count deficit was ~0."""

    def test_concentrated_block_respects_leaf_cap(self, rng):
        """4096 near-duplicate points into one leaf while the min_leaves
        floor pins target_L (deficit ≈ 0 — the exact starvation regime):
        maintenance must still shatter the leaf to the size invariant."""
        bt = BubbleTree(dim=2, compression=0.001, min_leaves=256)
        bt.insert_block(rng.normal(size=(10_000, 2)) * 5.0)
        assert bt.num_leaves == bt.target_L == 256  # deficit loop would get +2
        bt.insert_block(rng.normal(size=(4096, 2)) * 0.01 + 2.0)
        bt.check_invariants()
        cap = bt.leaf_cap
        for leaf in bt.alive_leaf_ids():
            assert len(bt.leaf_points[int(leaf)]) <= cap

    def test_concentrated_block_no_leaf_exceeds_M(self, rng):
        """High-compression regime: after fixpoint maintenance every leaf
        sits below the split threshold, so none exceeds M."""
        bt = BubbleTree(dim=2, compression=0.5)
        bt.insert_block(rng.normal(size=(64, 2)) * 5.0)
        bt.insert_block(rng.normal(size=(4096, 2)) * 0.001)
        bt.check_invariants()
        assert max(len(bt.leaf_points[int(i)]) for i in bt.alive_leaf_ids()) <= bt.M

    def test_delete_block_rebalances_to_fixpoint(self, rng):
        """Mass deletion must dissolve all the way down to target, not
        stop at a deficit cap."""
        bt = BubbleTree(dim=2, compression=0.1)
        ids = bt.insert_block(rng.normal(size=(2000, 2)))
        bt.delete_block(ids[:1800])
        bt.check_invariants()
        assert abs(bt.num_leaves - bt.target_L) <= max(2, 0.3 * bt.target_L)

    def test_fixpoint_safety_cap_raises(self, rng, monkeypatch):
        """The safety cap must raise, not silently stop (a regression to
        the old behavior would return normally here)."""
        bt = BubbleTree(dim=2, compression=0.1)
        bt.insert_block(rng.normal(size=(300, 2)))
        monkeypatch.setattr(
            BubbleTree, "_maintain_step", lambda self: True  # never converges
        )
        with pytest.raises(RuntimeError, match="fixpoint"):
            bt._maintain_to_fixpoint()


class TestBootstrapGrowth:
    """ISSUE 4 satellite: insert_block's bootstrap used tail recursion,
    re-paying the structure check per M-chunk and overflowing the
    recursion limit on huge blocks over slow-to-split data."""

    def test_growth_sequence_0_to_M_plus_1_to_block(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        first = bt.insert_block(rng.normal(size=(bt.M + 1, 2)))
        assert len(first) == bt.M + 1
        bt.check_invariants()
        rest = bt.insert_block(rng.normal(size=(500, 2)))
        assert len(rest) == 500
        assert bt.n_points == 511
        bt.check_invariants()
        assert len(set(first + rest)) == 511  # pids unique across phases

    def test_big_block_on_empty_tree_is_iterative(self, rng):
        """The flattened bootstrap must not recurse per M-chunk: cap the
        recursion limit well below block_size / M and insert."""
        import sys

        bt = BubbleTree(dim=2, compression=0.05)
        X = rng.normal(size=(4096, 2))
        limit = sys.getrecursionlimit()
        try:
            sys.setrecursionlimit(120)
            pids = bt.insert_block(X)
        finally:
            sys.setrecursionlimit(limit)
        assert len(pids) == 4096
        bt.check_invariants()

    def test_block_pids_insertion_ordered_across_growth(self, rng):
        """On a fresh store, block-insert pids must come out in insertion
        order even when the point store grows mid-block (offline
        consumers map point_ids back to dataset rows through this)."""
        bt = BubbleTree(dim=2, compression=0.05)  # store starts at 1024
        pids = bt.insert_block(rng.normal(size=(3000, 2)))  # grows twice
        assert pids == list(range(3000))
        more = bt.insert_block(rng.normal(size=(2000, 2)))
        assert more == list(range(3000, 5000))

    def test_duplicate_heavy_bootstrap(self):
        """Exact duplicates keep num_leaves at 1 the longest; the loop
        must keep making progress without recursion or stalls."""
        X = np.zeros((600, 2))
        bt = BubbleTree(dim=2, compression=0.1)
        pids = bt.insert_block(X)
        assert len(pids) == 600
        bt.check_invariants()


class TestAssignmentCentering:
    """ISSUE 4 satellite: the numpy fallback computed raw off-origin
    squared distances while the engine's device assign_fn mean-centers —
    center both identically."""

    def test_fallback_matches_backend_far_from_origin(self, rng):
        from repro.kernels import ops

        off = np.array([1.0e8, -1.0e8, 5.0e7])
        reps = rng.normal(size=(24, 3)) * 4.0 + off
        X = reps[rng.integers(0, 24, size=256)] + rng.normal(size=(256, 3)) * 0.05
        # the fixed fallback: center then expand (f64)
        mu = reps.mean(axis=0)
        Xc, Rc = X - mu, reps - mu
        sq = (
            np.einsum("id,id->i", Xc, Xc)[:, None]
            + np.einsum("jd,jd->j", Rc, Rc)[None, :]
            - 2.0 * Xc @ Rc.T
        )
        fallback = np.argmin(sq, axis=1)
        # ground truth: direct f64 differences (no expansion at all)
        direct = np.argmin(
            np.einsum("ijd,ijd->ij", X[:, None] - reps[None], X[:, None] - reps[None]),
            axis=1,
        )
        np.testing.assert_array_equal(fallback, direct)
        # and the f32 device kernel path agrees once both are centered
        device = np.asarray(ops.assign(Xc, Rc, use_ref=True))
        np.testing.assert_array_equal(device, direct)

    def test_insert_block_assigns_correctly_off_origin(self, rng):
        """End to end: far-from-origin blocks must land in the nearest
        leaves (pre-fix, the raw f64 expansion loses the separations and
        scrambles assignment, bloating the summary extents)."""
        off = np.array([3.0e8, -3.0e8])
        centers = np.asarray([[0.0, 0.0], [40.0, 0.0], [0.0, 40.0]]) + off
        bt = BubbleTree(dim=2, compression=0.1)
        seed = np.concatenate(
            [rng.normal(size=(40, 2)) * 0.3 + c for c in centers]
        )
        bt.insert_block(rng.permutation(seed))
        bt.insert_block(rng.normal(size=(200, 2)) * 0.3 + centers[0])
        bt.check_invariants()
        # every leaf must be tight around ONE center, never straddling
        for leaf in bt.alive_leaf_ids():
            P = bt.PX[np.asarray(bt.leaf_points[int(leaf)], dtype=np.int64)]
            rep = P.mean(axis=0)
            d = np.sqrt(((centers - rep) ** 2).sum(axis=1))
            assert d.min() < 20.0, "leaf rep far from every true center"


class TestOrderIndependence:
    def test_summary_quality_insensitive_to_order(self, rng, blobs):
        """The §5.1 claim: unlike ClusTree, the summary does not depend on
        insertion order (up to small tolerance) — measured by how well leaf
        reps cover the true blob structure."""
        X, y = blobs
        reps = []
        for seed in (0, 1):
            order = np.random.default_rng(seed).permutation(X.shape[0])
            bt = BubbleTree(dim=2, compression=0.1)
            _fill(bt, X[order])
            b = bt.to_bubbles()
            reps.append(b)
        # compare total represented mass per true cluster
        for b in reps:
            assert b.n.sum() == X.shape[0]
        # coverage: every blob center has a nearby leaf rep in both runs
        centers = np.array([[0, 0], [6, 0], [0, 6.0]])
        for b in reps:
            d = np.sqrt(((centers[:, None] - b.rep[None]) ** 2).sum(-1)).min(axis=1)
            assert (d < 1.0).all()
