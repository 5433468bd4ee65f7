"""Streaming clustering engine (serving.stream) + batched tree ops.

Covers the three contract points of the online–offline service:
  * batched ingestion ≡ sequential updates (order-independence, paper §5.1),
  * the staleness policy fires exactly when dirty mass crosses ε,
  * backend parity: the jnp fallback and the Pallas path agree on the
    offline MST total weight (the hierarchy invariant).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import make_blobs
from repro.core.bubble_tree import BubbleTree
from repro.core.metrics import nmi
from repro.kernels import ops
from repro.serving.batcher import HostBatcher
from repro.serving.stream import StreamingClusterEngine


class TestHostBatcher:
    def test_fifo_across_kinds(self):
        b = HostBatcher(max_block=10)
        b.push(1, kind="a")
        b.push(2, kind="a")
        b.push(3, kind="b")
        b.push(4, kind="a")
        assert len(b) == 4
        assert b.next_block() == ("a", [1, 2])  # stops at the kind switch
        assert b.next_block() == ("b", [3])
        assert b.next_block() == ("a", [4])
        assert not b

    def test_block_cap(self):
        b = HostBatcher(max_block=3)
        for i in range(7):
            b.push(i)
        assert b.next_block() == ("default", [0, 1, 2])
        assert b.next_block(limit=1) == ("default", [3])
        assert b.next_block() == ("default", [4, 5, 6])

    def test_pop_one(self):
        b = HostBatcher()
        b.push("x", kind="req")
        assert b.pop_one() == "x"
        assert len(b) == 0


def test_serving_does_not_load_the_lm_stack():
    """The clustering service imports none of the language-model
    template (models, configs, train)."""
    code = (
        "import sys, repro.serving, repro.serving.stream, repro.serving.query\n"
        "print(sorted(m for m in sys.modules if m.split('.')[:2] in "
        "(['repro', 'models'], ['repro', 'configs'], ['repro', 'train'])))"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "[]", r.stdout


class TestBatchedDelete:
    def test_matches_sequential(self, rng):
        X = rng.normal(size=(400, 3))
        drop_rows = rng.choice(400, size=170, replace=False)

        seq = BubbleTree(dim=3, compression=0.08)
        seq_ids = [seq.insert(p) for p in X]
        for r in drop_rows:
            seq.delete(seq_ids[r])
        seq.check_invariants()

        bat = BubbleTree(dim=3, compression=0.08)
        bat_ids = bat.insert_block(X)
        bat.delete_block([bat_ids[r] for r in drop_rows])
        bat.check_invariants()

        # CF additivity: identical global statistics and steering state
        assert bat.n_points == seq.n_points == 230
        assert bat.num_leaves == seq.num_leaves
        np.testing.assert_allclose(bat.LS[bat.root], seq.LS[seq.root], atol=1e-8)
        np.testing.assert_allclose(bat.SS[bat.root], seq.SS[seq.root], atol=1e-6)

    def test_dirty_mass_accounting(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        ids = bt.insert_block(rng.normal(size=(100, 2)))
        assert bt.dirty_mass == 100.0
        bt.mark_clean()
        assert bt.dirty_fraction() == 0.0
        bt.delete_block(ids[:30])
        assert bt.dirty_mass == 30.0
        assert bt.dirty_fraction() == pytest.approx(30.0 / 70.0)

    def test_delete_everything_and_refill(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        ids = bt.insert_block(rng.normal(size=(120, 2)))
        bt.delete_block(ids)
        bt.check_invariants()
        assert bt.n_points == 0
        bt.insert_block(rng.normal(size=(50, 2)))
        bt.check_invariants()
        assert bt.n_points == 50

    def test_dead_pid_raises(self, rng):
        bt = BubbleTree(dim=2, compression=0.1)
        ids = bt.insert_block(rng.normal(size=(40, 2)))
        bt.delete(ids[0])
        with pytest.raises(KeyError):
            bt.delete_block([ids[0], ids[1]])

    def test_negative_pid_rejected(self, rng):
        """-1 must not resolve to the last point-store row via numpy
        negative indexing and silently delete an unrelated live point."""
        bt = BubbleTree(dim=2, compression=0.1)
        ids = bt.insert_block(rng.normal(size=(40, 2)))
        with pytest.raises(KeyError):
            bt.delete(-1)
        with pytest.raises(KeyError):
            bt.delete_block([-1, ids[0]])
        bt.check_invariants()
        assert bt.n_points == 40


class TestStreamingEngine:
    def test_batched_equals_sequential_labels(self, rng):
        X, _ = make_blobs(rng, n_per=80)
        drop = rng.choice(240, size=90, replace=False)

        def final_labels(block):
            eng = StreamingClusterEngine(
                dim=2, min_pts=8, compression=0.1, backend="jnp",
                max_block=block, min_offline_points=8,
            )
            if block == 1:
                tickets = [eng.submit_insert(p) for p in X]
                eng.poll()
                pids = [t.pids[0] for t in tickets]
            else:
                pids = eng.ingest(X)
            eng.retire([pids[r] for r in drop])
            eng.flush()
            keep = np.asarray(sorted(set(range(240)) - set(drop)))
            return eng.query(X[keep])

        a = final_labels(block=512)
        b = final_labels(block=1)
        assert (a >= 0).mean() > 0.9  # well-separated blobs: little noise
        assert nmi(a, b) > 0.95  # order/batching independence (§5.1)

    def test_block_cap_never_exceeded_by_coalescing(self, rng):
        eng = StreamingClusterEngine(
            dim=2, backend="jnp", max_block=512, min_offline_points=10_000,
        )
        eng.submit_insert(rng.normal(size=(511, 2)))
        eng.submit_insert(rng.normal(size=(511, 2)))
        eng.poll()
        # 1022 points would fit one run but exceed the cap: must be 2 blocks
        assert eng.stats["blocks_applied"] == 2
        assert eng.tree.n_points == 1022

    def test_ticket_lifecycle(self, rng):
        eng = StreamingClusterEngine(dim=2, backend="jnp", min_offline_points=8)
        t = eng.submit_insert(rng.normal(size=(20, 2)))
        assert not t.applied
        eng.poll()
        assert t.applied and len(t.pids) == 20
        assert eng.tree.n_points == 20

    def test_empty_insert_is_noop(self, rng):
        """submit_insert([]) must not crash the drain loop (a bare [] lands
        as shape (1, 0) from ndmin=2 and needs normalizing)."""
        eng = StreamingClusterEngine(dim=3, backend="jnp", min_offline_points=8)
        t0 = eng.submit_insert([])  # empty on an empty tree
        eng.poll()
        assert t0.applied and t0.pids == [] and eng.tree.n_points == 0
        t1 = eng.submit_insert(rng.normal(size=(10, 3)))
        t2 = eng.submit_insert([])  # empty coalesced with a real block
        eng.poll()
        assert t1.applied and len(t1.pids) == 10
        assert t2.applied and t2.pids == []
        assert eng.tree.n_points == 10

    def test_staleness_fires_exactly_at_epsilon(self, rng):
        eps = 0.1
        eng = StreamingClusterEngine(
            dim=2, min_pts=5, compression=0.2, backend="jnp",
            epsilon=eps, min_offline_points=10,
        )
        # below min_offline_points: no pass at all
        eng.ingest(rng.normal(size=(9, 2)))
        assert eng.snapshot is None
        # crossing the population floor: first pass fires (no snapshot yet)
        eng.ingest(rng.normal(size=(1, 2)))
        assert eng.snapshot is not None and eng.stats["recluster_count"] == 1
        assert eng.tree.dirty_mass == 0.0
        # one-point drip: the pass must fire exactly when dirty/total >= eps
        for _ in range(40):
            before = eng.stats["recluster_count"]
            expect = (eng.tree.dirty_mass + 1) / (eng.tree.n_points + 1) >= eps
            eng.ingest(rng.normal(size=(1, 2)))
            fired = eng.stats["recluster_count"] > before
            assert fired == expect
            if fired:
                assert eng.tree.dirty_mass == 0.0

    def test_query_off_origin_matches_f64_assignment(self, rng):
        """Serve-plane assignment must center before the f32 device kernel:
        off-origin coordinates otherwise cancel and scramble labels."""
        X, _ = make_blobs(rng, n_per=60)
        Xoff = X + 1e5
        eng = StreamingClusterEngine(
            dim=2, min_pts=8, compression=0.1, backend="jnp",
            min_offline_points=8,
        )
        eng.ingest(Xoff)
        snap = eng.flush()
        got = eng.query(Xoff)
        # exact f64 nearest-bubble assignment oracle
        sq = ((Xoff[:, None, :] - snap.bubble_rep[None, :, :]) ** 2).sum(-1)
        want = snap.bubble_labels[np.argmin(sq, axis=1)]
        assert (got == want).mean() > 0.99

    def test_query_before_first_pass_is_noise(self, rng):
        eng = StreamingClusterEngine(dim=2, backend="jnp", min_offline_points=1000)
        eng.ingest(rng.normal(size=(50, 2)))
        assert eng.snapshot is None
        assert (eng.query(rng.normal(size=(5, 2))) == -1).all()

    def test_async_offline_serves_during_pass(self, rng):
        X, _ = make_blobs(rng, n_per=60)
        eng = StreamingClusterEngine(
            dim=2, min_pts=8, compression=0.1, backend="jnp",
            async_offline=True, min_offline_points=8, epsilon=0.05,
        )
        eng.ingest(X)
        snap = eng.flush()
        assert snap is not None and snap.n_clusters >= 2
        labels = eng.query(X)
        assert (labels >= 0).mean() > 0.9

    def test_inflight_pass_discounts_pending_dirty_mass(self, rng):
        """While an async pass is running, the mass it captured must not
        re-trigger the policy (or inflate recluster_skipped_busy)."""
        eng = StreamingClusterEngine(
            dim=2, backend="jnp", async_offline=True,
            min_offline_points=8, epsilon=0.5,
        )
        eng.ingest(rng.normal(size=(100, 2)))  # first pass launches async
        for _ in range(10):
            eng.poll()  # nothing new: no trigger, busy or not
        assert eng.stats["recluster_skipped_busy"] == 0
        eng.flush()
        assert eng.tree.dirty_mass == 0.0

    def test_wrong_dim_rejected_at_submit(self, rng):
        eng = StreamingClusterEngine(dim=3, backend="jnp", min_offline_points=8)
        with pytest.raises(ValueError, match=r"expected \(n, 3\)"):
            eng.submit_insert(rng.normal(size=(5, 4)))
        ok = eng.submit_insert(rng.normal(size=(5, 3)))
        eng.poll()
        assert ok.applied and eng.tree.n_points == 5

    def test_bad_delete_does_not_take_down_coalesced_siblings(self, rng):
        """Batched must equal sequential on the error path too: a retried
        (now-dead) delete raises, but its coalesced sibling still applies."""
        eng = StreamingClusterEngine(dim=2, backend="jnp", min_offline_points=10_000)
        t = eng.submit_insert(rng.normal(size=(40, 2)))
        eng.poll()
        eng.submit_delete(t.pids[:10])
        eng.submit_delete(t.pids[:10])  # client retry of the same request
        with pytest.raises(KeyError):
            eng.poll()
        # the first (valid) request applied; only the retry failed
        assert eng.tree.n_points == 30
        eng.tree.check_invariants()
        # engine keeps working afterwards
        eng.submit_delete(t.pids[10:20])
        eng.poll()
        assert eng.tree.n_points == 20

    def test_submit_copies_caller_buffer(self, rng):
        """Producers may reuse a staging buffer between submit and poll."""
        eng = StreamingClusterEngine(dim=2, backend="jnp", min_offline_points=10_000)
        buf = rng.normal(size=(10, 2))
        want = buf.copy()
        eng.submit_insert(buf)
        buf[:] = 1e9  # clobber before the scheduler applies it
        t = eng.submit_insert(buf)
        eng.poll()
        _, X = eng.tree.alive_points()
        np.testing.assert_allclose(np.sort(X[:10], axis=0), np.sort(want, axis=0))
        assert (X[10:] == 1e9).all()
        assert t.applied

    def test_async_offline_failure_surfaces(self, rng):
        """A crashed background pass must raise on the main thread, not
        silently serve stale labels forever."""
        eng = StreamingClusterEngine(
            dim=2, backend="jnp", async_offline=True, min_offline_points=8,
        )

        def boom(*a, **k):
            raise ValueError("kernel exploded")

        eng.backend.offline_recluster_from_table = boom
        eng.submit_insert(rng.normal(size=(50, 2)))
        with pytest.raises(RuntimeError, match="offline re-cluster pass failed"):
            eng.poll()  # launches the pass...
            eng.join()  # ...and surfaces its failure
        assert eng.stats["recluster_failures"] == 1
        assert eng.snapshot is None
        # engine remains usable: restore the backend, force a pass
        del eng.backend.offline_recluster_from_table
        eng.maybe_recluster(force=True)
        eng.join()
        assert eng.snapshot is not None

    def test_mixed_interleaved_stream(self, rng):
        """Inserts and deletes interleaved in one queue drain in FIFO order."""
        eng = StreamingClusterEngine(
            dim=2, backend="jnp", min_offline_points=8, max_block=64,
        )
        t1 = eng.submit_insert(rng.normal(size=(30, 2)))
        eng.submit_insert(rng.normal(size=(30, 2)))
        eng.poll()
        eng.submit_delete(t1.pids)
        t3 = eng.submit_insert(rng.normal(size=(10, 2)))
        eng.poll()
        assert eng.tree.n_points == 40
        assert t3.applied
        eng.tree.check_invariants()


class TestBackendParity:
    def test_offline_mst_weight_jnp_vs_pallas(self, rng):
        """The jnp fallback and the Pallas (interpret on CPU) path must
        agree on the offline MST total weight — the hierarchy invariant."""
        bt = BubbleTree(dim=3, compression=0.15)
        bt.insert_block(rng.normal(size=(200, 3)))
        ids, LS, SS, N = bt.leaf_cf_buffers()
        res_ref = ops.offline_recluster(LS, SS, N, ids, 5, use_ref=True)
        res_pal = ops.offline_recluster(LS, SS, N, ids, 5, use_ref=False)
        w_ref, w_pal = res_ref.mst[2], res_pal.mst[2]
        assert len(w_ref) == len(ids) - 1  # spanning tree
        assert w_ref.sum() == pytest.approx(w_pal.sum(), rel=1e-5)
        # the fused pass returns labels too — the backends must agree
        assert res_ref.n_clusters == res_pal.n_clusters
        np.testing.assert_array_equal(res_ref.labels, res_pal.labels)

    def test_offline_matches_dense_oracle_off_origin(self, rng):
        """Off-origin data is where a low-precision extent computation
        would cancel catastrophically; the pipeline must match the host
        float64 oracle (bubbles_from_cf + boruvka_dense) there."""
        from repro.core.bubbles import bubble_mutual_reachability as np_bmr
        from repro.core.bubbles import bubbles_from_cf
        from repro.core.mst import boruvka_dense

        bt = BubbleTree(dim=3, compression=0.15)
        bt.insert_block(rng.normal(size=(200, 3)) + 1000.0)  # far from origin
        ids, LS, SS, N = bt.leaf_cf_buffers()
        w_jit = ops.offline_recluster(LS, SS, N, ids, 5, use_ref=True).mst[2]
        b = bubbles_from_cf(LS[ids], SS[ids], N[ids])
        assert b.extent.max() > 0  # the cancellation-prone quantity is live
        W, _ = np_bmr(b, 5)
        Wd = W.copy()
        np.fill_diagonal(Wd, np.inf)
        _, _, w_oracle = boruvka_dense(Wd)
        assert w_jit.sum() == pytest.approx(w_oracle.sum(), rel=1e-4)

    def test_min_pts_above_total_mass_stays_data_scale(self, rng):
        """min_pts larger than the represented mass must clamp, not fall
        back onto a padding bubble at _PAD_COORD distance."""
        bt = BubbleTree(dim=2, compression=0.2)
        bt.insert_block(rng.normal(size=(30, 2)))  # total mass 30
        ids, LS, SS, N = bt.leaf_cf_buffers()
        w = ops.offline_recluster(LS, SS, N, ids, min_pts=50, use_ref=True).mst[2]
        assert len(w) == len(ids) - 1
        assert w.max() < 100.0  # unit-scale data, not ~1e6 pad distance

    def test_return_w_roundtrip(self, rng):
        bt = BubbleTree(dim=2, compression=0.2)
        bt.insert_block(rng.normal(size=(80, 2)))
        ids, LS, SS, N = bt.leaf_cf_buffers()
        W, res = ops.offline_recluster(LS, SS, N, ids, 5, use_ref=True, return_w=True)
        u, v, w = res.mst
        L = len(ids)
        assert W.shape == (L, L)  # padding bucket sliced away
        np.testing.assert_allclose(W[u, v], w, rtol=1e-6)

    def test_engine_level_parity(self, rng):
        X, _ = make_blobs(rng, n_per=50)
        snaps = {}
        for name in ("jnp", "pallas"):
            eng = StreamingClusterEngine(
                dim=2, min_pts=8, compression=0.1, backend=name,
                min_offline_points=8, device_assign=False,
            )
            eng.ingest(X)
            snaps[name] = eng.flush()
        assert snaps["jnp"].total_mst_weight == pytest.approx(
            snaps["pallas"].total_mst_weight, rel=1e-5
        )
        assert snaps["jnp"].n_clusters == snaps["pallas"].n_clusters

    def test_summarizer_backend_off_origin(self, rng):
        """The summarizer's backend path must center before f32 device
        calls, matching the numpy f64 path on off-origin data."""
        from repro.core.summarizer import BubbleTreeSummarizer

        X, _ = make_blobs(rng, n_per=50)
        Xoff = X + 1e5
        outs = {}
        for backend in (None, "jnp"):
            s = BubbleTreeSummarizer(
                dim=2, min_pts=8, compression=0.1, backend=backend
            )
            s.insert_block(Xoff)
            outs[backend] = s.cluster().point_labels
        assert nmi(outs[None], outs["jnp"]) > 0.95

    def test_backend_resolution(self):
        assert ops.get_backend("jnp").use_ref
        assert ops.get_backend("ref").name == "jnp"
        assert not ops.get_backend("pallas").use_ref
        with pytest.raises(ValueError):
            ops.get_backend("cuda")
