"""Serve-plane query subsystem (ISSUE 5): versioned device cache, fused
query program, input validation, labels() memoization, QueryBatcher.

The differential contract: the device-cached path must agree with a
pure-host f64 nearest-bubble replay — up to genuine argmin ties, which
are accepted via a near-nearest distance check.
"""

import threading

import numpy as np
import pytest

from conftest import make_blobs
from repro.serving import QueryBatcher, StreamingClusterEngine
from repro.serving.query import validate_query

BACKENDS = pytest.mark.parametrize(
    "backend", ["jnp", "pallas"], ids=["jnp", "pallas"]
)


def _engine(backend, rng, n_per=60, **kw):
    X, _ = make_blobs(rng, n_per=n_per)
    eng = StreamingClusterEngine(
        dim=2, min_pts=8, compression=0.1, backend=backend,
        min_offline_points=8, **kw,
    )
    eng.ingest(X)
    eng.flush()
    return eng, X


def _build_query_snapshot(L: int, d: int, seed: int):
    """Serving-scale `ClusterSnapshot` straight from a synthetic bubble
    table through the real fused offline pass — the query benches need a
    published snapshot, not a whole ingestion run."""
    from repro.kernels import ops as kops
    from repro.serving.stream import ClusterSnapshot

    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)) * 10.0
    rep = centers[rng.integers(0, 8, size=L)] + rng.normal(size=(L, d)) * 0.5
    n_b = rng.integers(5, 50, size=L).astype(np.float64)
    extent = np.abs(rng.normal(size=L)) * 0.3
    res = kops.offline_recluster_from_table(
        rep, n_b, extent, min_pts=10, min_cluster_size=10.0, use_ref=True
    )
    center = (n_b @ rep) / n_b.sum()
    return ClusterSnapshot(
        version=1, n_points=int(n_b.sum()), bubble_rep=rep, bubble_n=n_b,
        center=center, result=res, wall_seconds=0.0,
    )


def _host_nearest(snap, X):
    """f64 nearest-bubble replay in the snapshot's centered frame."""
    Xc = X - snap.center[None, :]
    Rc = snap.bubble_rep - snap.center[None, :]
    sq = ((Xc[:, None, :] - Rc[None, :, :]) ** 2).sum(-1)
    return np.argmin(sq, axis=1), sq


def assert_replay_matches(snap, X, res):
    """Device result vs host replay, tie-tolerant: the chosen bubble must
    be (near-)nearest in f64, and the label must be ITS label."""
    idx_host, sq = _host_nearest(snap, X)
    np.testing.assert_array_equal(res.labels, snap.bubble_labels[res.bubble_index])
    chosen = sq[np.arange(X.shape[0]), res.bubble_index]
    best = sq.min(axis=1)
    assert (chosen <= best * (1 + 1e-4) + 1e-8).all(), (
        "device path picked a bubble that is not (near-)nearest"
    )


class TestValidation:
    """Pinned regressions: empty / 1-D / wrong-dim inputs (both backends).

    Pre-fix, ``np.atleast_2d(np.asarray([]))`` became shape (1, 0) and
    query() returned ONE garbage label for zero points."""

    @BACKENDS
    def test_empty_inputs_return_empty_int64(self, backend, rng):
        eng, _ = _engine(backend, rng, n_per=40)
        for empty in ([], np.asarray([]), np.zeros((0, 2)), np.zeros((0, 5))):
            out = eng.query(empty)
            assert out.shape == (0,) and out.dtype == np.int64
            det = eng.query_detailed(empty)
            assert len(det) == 0
            assert det.version == eng.snapshot.version

    @BACKENDS
    def test_single_1d_point_is_one_row(self, backend, rng):
        eng, X = _engine(backend, rng, n_per=40)
        one = eng.query(X[0])
        assert one.shape == (1,)
        np.testing.assert_array_equal(one, eng.query(X[:1]))

    @BACKENDS
    def test_wrong_dim_raises_value_error(self, backend, rng):
        eng, _ = _engine(backend, rng, n_per=40)
        with pytest.raises(ValueError, match=r"\(n, 2\)"):
            eng.query(np.zeros((3, 5)))
        with pytest.raises(ValueError, match=r"\(n, 2\)"):
            eng.query([1.0, 2.0, 3.0])  # 1-D but not dim-sized
        with pytest.raises(ValueError):
            eng.query(np.zeros((2, 2, 2)))
        # n rows of 0 features carry n real rows the caller expects
        # answers for — they must raise, never silently become 0 points
        with pytest.raises(ValueError, match=r"\(n, 2\)"):
            eng.query(np.zeros((5, 0)))
        with pytest.raises(ValueError):
            eng.query([[]])  # shape (1, 0): one wrong-dim row

    def test_empty_before_first_snapshot(self, rng):
        eng = StreamingClusterEngine(dim=2, backend="jnp", min_offline_points=1000)
        eng.ingest(rng.normal(size=(20, 2)))
        assert eng.snapshot is None
        assert eng.query([]).shape == (0,)
        det = eng.query_detailed(rng.normal(size=(3, 2)))
        assert (det.labels == -1).all() and det.version == 0
        assert (det.strength == 0.0).all()

    def test_validate_query_helper(self):
        assert validate_query([], 3).shape == (0, 3)
        assert validate_query([1.0, 2.0, 3.0], 3).shape == (1, 3)
        with pytest.raises(ValueError):
            validate_query(np.ones((4, 2)), 3)


class TestParity:
    @BACKENDS
    def test_cached_matches_host_replay(self, backend, rng):
        eng, X = _engine(backend, rng)
        snap = eng.snapshot
        Q = np.concatenate([X, rng.normal(size=(40, 2)) * 3.0])
        res = eng.query_detailed(Q)
        assert_replay_matches(snap, Q, res)
        # distance parity vs f64 replay (f32 expansion tolerance)
        _, sq = _host_nearest(snap, Q)
        want = np.sqrt(sq[np.arange(Q.shape[0]), res.bubble_index])
        np.testing.assert_allclose(res.distance, want, rtol=1e-3, atol=1e-3)

    @BACKENDS
    def test_off_origin_centering(self, backend, rng):
        """The cached entry must center before f32, like every other
        device call site (off-origin cancellation)."""
        X, _ = make_blobs(rng, n_per=50)
        eng = StreamingClusterEngine(
            dim=2, min_pts=8, compression=0.1, backend=backend,
            min_offline_points=8,
        )
        eng.ingest(X + 1e5)
        snap = eng.flush()
        res = eng.query_detailed(X + 1e5)
        idx_host, _ = _host_nearest(snap, X + 1e5)
        want = snap.bubble_labels[idx_host]
        assert (res.labels == want).mean() > 0.99

    def test_strength_properties(self, rng):
        eng, X = _engine("jnp", rng)
        snap = eng.snapshot
        res = eng.query_detailed(X)
        assert ((res.strength >= 0.0) & (res.strength <= 1.0)).all()
        # noise points carry exactly zero strength
        assert (res.strength[res.labels == -1] == 0.0).all()
        # querying AT a clustered representative returns that bubble's own
        # membership probability λ_b / λ_max(c)
        lbl = snap.bubble_labels
        k = int(np.flatnonzero(lbl >= 0)[0])
        lam = np.asarray(snap.result.point_lambda, dtype=np.float64)
        lam_max = lam[lbl == lbl[k]].max()
        at_rep = eng.query_detailed(snap.bubble_rep[k])
        assert at_rep.labels[0] == lbl[k]
        np.testing.assert_allclose(
            at_rep.strength[0], min(lam[k] / lam_max, 1.0), rtol=1e-4
        )
        # strength decays with distance along a ray out of the cluster
        far = eng.query_detailed(snap.bubble_rep[k] + 50.0)
        assert far.strength[0] <= at_rep.strength[0]

    @BACKENDS
    def test_far_query_never_surfaces_a_pad_row(self, backend, rng):
        """A query out past the L-bucket padding coordinate must serve
        'no bubble' (-1/inf/0), never a fictitious row ≥ n_bubbles."""
        eng, _ = _engine(backend, rng, n_per=40)
        snap = eng.snapshot
        far = snap.center[None, :] + 5e6  # beyond _PAD_COORD's 1e6 frame
        res = eng.query_detailed(far)
        assert res.bubble_index[0] in (-1, *range(snap.n_bubbles))
        if res.bubble_index[0] == -1:
            assert res.labels[0] == -1 and np.isinf(res.distance[0])
            assert res.strength[0] == 0.0

    def test_infinite_lambda_does_not_poison_cluster_strength(self, rng):
        """λ_b = ∞ (duplicate-heavy bubble that never leaves before its
        cluster dies) means membership probability 1 — it must not blow
        up λ_max and collapse every sibling's strength to ~0."""
        import dataclasses as dc

        from repro.serving.query import QueryEngine

        snap = _build_query_snapshot(64, 4, seed=3)
        lbl = snap.bubble_labels
        k = int(np.flatnonzero(lbl >= 0)[0])
        lam = np.asarray(snap.result.point_lambda, dtype=np.float64).copy()
        lam[k] = np.inf  # inject the duplicate-bubble case
        snap = dc.replace(snap, result=dc.replace(snap.result, point_lambda=lam))
        from repro.kernels import ops as kops

        qe = QueryEngine(kops.get_backend("jnp"), 4)
        sibs = np.flatnonzero((lbl == lbl[k]) & np.isfinite(lam))
        # the ∞-λ bubble itself serves probability ~1 at its rep
        at_inf = qe.query_detailed(snap, snap.bubble_rep[k])
        np.testing.assert_allclose(at_inf.strength[0], 1.0, atol=1e-5)
        if sibs.size:  # finite siblings keep λ_b / λ_max(finite), not ~0
            s = int(sibs[0])
            res = qe.query_detailed(snap, snap.bubble_rep[s])
            want = min(lam[s] / lam[sibs].max(), 1.0)
            np.testing.assert_allclose(res.strength[0], want, rtol=1e-4)
            assert res.strength[0] > 1e-6

    def test_large_batch_chunks_match_small(self, rng):
        """Chunked (> _MAX_CHUNK) batches agree row-for-row with
        row-at-a-time queries (bucket padding never leaks)."""
        from repro.serving import query as qmod

        eng, X = _engine("jnp", rng)
        old = qmod._MAX_CHUNK
        qmod._MAX_CHUNK = 64
        try:
            Q = rng.normal(size=(150, 2)) * 3.0
            big = eng.query_detailed(Q)
        finally:
            qmod._MAX_CHUNK = old
        ref = eng.query_detailed(Q)
        np.testing.assert_array_equal(big.labels, ref.labels)
        np.testing.assert_allclose(big.distance, ref.distance, rtol=1e-6)


class TestSnapshotCache:
    def test_one_build_per_version_and_no_inplace_patch(self, rng):
        eng, X = _engine("jnp", rng)
        snap1 = eng.snapshot
        r1 = eng.query_detailed(X[:20])
        builds1 = eng._query_engine.cache.builds
        eng.query(X[:20])
        eng.query(X[20:40])
        assert eng._query_engine.cache.builds == builds1  # warm hits
        # publish a new version with genuinely different data
        eng.ingest(rng.normal(size=(120, 2)) + 12.0)
        eng.flush()
        snap2 = eng.snapshot
        assert snap2.version > snap1.version
        r2 = eng.query_detailed(X[:20])
        assert r2.version == snap2.version
        assert eng._query_engine.cache.builds == builds1 + 1
        # the old version's entry was never patched: pinning the query to
        # snap1 reproduces the pre-swap answer bit for bit
        r1_again = eng.query_detailed(X[:20], snapshot=snap1)
        assert r1_again.version == snap1.version
        np.testing.assert_array_equal(r1_again.labels, r1.labels)
        np.testing.assert_allclose(r1_again.distance, r1.distance)

    def test_single_flight_one_build_per_version(self, rng, monkeypatch):
        """Satellite regression: readers racing on the same cold version
        used to EACH pay the O(L·d) build + device upload.  With
        single-flight, one thread builds while the rest wait on its
        event and hit the installed entry."""
        from repro.serving import query as qmod

        eng, X = _engine("jnp", rng)
        snap = eng.snapshot
        cache = qmod.SnapshotDeviceCache(keep=4)
        real_build = qmod._build_entry
        started = threading.Barrier(8 + 1, timeout=30)

        def slow_build(s, spatial=False):
            import time

            time.sleep(0.05)  # hold the build open so the race is real
            return real_build(s, spatial)

        monkeypatch.setattr(qmod, "_build_entry", slow_build)
        got = [None] * 8

        def worker(i):
            started.wait()
            got[i] = cache.entry(snap)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        started.wait()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert cache.builds == 1
        assert cache.hits == 7
        assert all(g is got[0] for g in got)  # same installed entry

    def test_failed_build_wakes_followers_and_frees_key(self, rng, monkeypatch):
        from repro.serving import query as qmod

        eng, X = _engine("jnp", rng)
        snap = eng.snapshot
        cache = qmod.SnapshotDeviceCache(keep=4)
        real_build = qmod._build_entry
        fail_once = [True]

        def flaky_build(s, spatial=False):
            if fail_once[0]:
                fail_once[0] = False
                raise RuntimeError("device OOM")
            return real_build(s, spatial)

        monkeypatch.setattr(qmod, "_build_entry", flaky_build)
        with pytest.raises(RuntimeError, match="device OOM"):
            cache.entry(snap)
        assert cache._building == {}  # key freed: next caller retries
        e = cache.entry(snap)
        assert e is cache.entry(snap) and cache.builds == 1

    def test_eviction_is_lru_on_access(self, rng):
        """Satellite regression: eviction was insertion-ordered, so a
        version still actively served was evicted and rebuilt on every
        call once `keep` newer versions existed."""
        from repro.serving.query import SnapshotDeviceCache

        eng, X = _engine("jnp", rng)
        snaps = [eng.snapshot]
        for i in range(2):  # publish two more genuine versions
            eng.ingest(rng.normal(size=(80, 2)) + 9.0 * (i + 1))
            eng.maybe_recluster(force=True)
            snaps.append(eng.snapshot)
        assert len({s.version for s in snaps}) == 3
        cache = SnapshotDeviceCache(keep=2)
        cache.entry(snaps[0])
        cache.entry(snaps[1])
        cache.entry(snaps[0])  # touch v0: now v1 is the LRU victim
        cache.entry(snaps[2])  # evicts v1, NOT the just-touched v0
        assert cache.builds == 3
        cache.entry(snaps[0])  # still resident
        assert cache.builds == 3
        cache.entry(snaps[1])  # was evicted: rebuilt
        assert cache.builds == 4

    def test_swap_under_load_serves_single_version(self, rng):
        """Satellite regression: labels are gathered from the SAME
        snapshot the assignment ran against, even while the main thread
        publishes new versions as fast as it can."""
        eng, X = _engine("jnp", rng)
        history = {eng.snapshot.version: eng.snapshot}
        stop = threading.Event()
        errors = []
        checked = [0]

        def reader():
            rlocal = np.random.default_rng(123)
            while not stop.is_set():
                q = rlocal.normal(size=(8, 2)) * 4.0
                snap = eng.snapshot  # the version this reader observed
                try:
                    res = eng.query_detailed(q, snapshot=snap)
                    assert res.version == snap.version
                    assert_replay_matches(snap, q, res)
                    checked[0] += 1
                except BaseException as e:  # noqa: BLE001 — surfaced in main
                    errors.append(e)
                    return

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for i in range(12):  # publish a stream of versions
                eng.ingest(rng.normal(size=(30, 2)) + 3.0 * (i % 4))
                eng.maybe_recluster(force=True)
                history[eng.snapshot.version] = eng.snapshot
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors[0]
        assert len(history) >= 10 and checked[0] >= 20


class TestLabelsCache:
    def test_hit_and_invalidation(self, rng):
        eng, X = _engine("jnp", rng)
        pids1, lab1 = eng.labels()
        assert eng.stats["label_cache_hits"] == 0
        pids2, lab2 = eng.labels()
        assert eng.stats["label_cache_hits"] == 1
        np.testing.assert_array_equal(pids1, pids2)
        np.testing.assert_array_equal(lab1, lab2)
        # ingest invalidates (mutation counter moved)
        new = eng.ingest(rng.normal(size=(4, 2)))
        pids3, lab3 = eng.labels()
        assert eng.stats["label_cache_hits"] == 1
        assert set(new) <= set(pids3.tolist())
        # retire invalidates too
        eng.retire(new)
        pids4, _ = eng.labels()
        assert eng.stats["label_cache_hits"] == 1
        assert not (set(new) & set(pids4.tolist()))
        # and a cached return is a COPY — mutating it can't poison the cache
        pids5, lab5 = eng.labels()
        lab5[:] = -77
        _, lab6 = eng.labels()
        assert not (lab6 == -77).all()

    def test_cached_equals_fresh(self, rng):
        eng, X = _engine("jnp", rng)
        pids, lab = eng.labels()
        _, lab_cached = eng.labels()
        # fresh recomputation (bypassing the cache) must agree
        pids_f, Xf = eng.tree.alive_points()
        np.testing.assert_array_equal(pids, pids_f)
        np.testing.assert_array_equal(lab_cached, eng.query(Xf))


class TestQueryBatcher:
    def test_concurrent_callers_fan_out_correctly(self, rng):
        eng, X = _engine("jnp", rng)
        qb = QueryBatcher(eng, max_batch=256)
        chunks = [rng.normal(size=(int(rng.integers(1, 20)), 2)) * 3.0 for _ in range(16)]
        want = [eng.query(c) for c in chunks]
        got = [None] * len(chunks)
        errors = []

        def worker(i):
            try:
                got[i] = qb.query(chunks[i])
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(chunks))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert qb.fanned_out == len(chunks)
        assert 1 <= qb.batches <= len(chunks)

    def test_bad_input_raises_in_caller_only(self, rng):
        eng, X = _engine("jnp", rng)
        qb = QueryBatcher(eng)
        with pytest.raises(ValueError):
            qb.query(np.zeros((2, 9)))
        # the queue stays serviceable afterwards
        np.testing.assert_array_equal(qb.query(X[:3]), eng.query(X[:3]))
        assert qb.query([]).shape == (0,)

    def test_leader_death_fans_exception_to_whole_block(self, rng):
        """Satellite regression: a poisoned batch raising inside the
        leader's fused call left follower tickets in the same drained
        block uncompleted — their callers spun forever.  The leader's
        exception must reach EVERY caller of the failed block, and the
        batcher must keep serving afterwards."""
        eng, X = _engine("jnp", rng)
        qb = QueryBatcher(eng, max_batch=256)
        real_qd = eng.query_detailed
        poisoned = threading.Event()
        poisoned.set()

        def poison_qd(Xq, **kw):
            if poisoned.is_set():
                raise RuntimeError("poisoned batch")
            return real_qd(Xq, **kw)

        eng.query_detailed = poison_qd
        try:
            outcomes = [None] * 8

            def worker(i):
                try:
                    qb.query(rng.normal(size=(3, 2)))
                    outcomes[i] = "ok"
                except RuntimeError as e:
                    outcomes[i] = str(e)

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            # nobody hangs — leader AND followers all complete…
            assert not any(t.is_alive() for t in threads)
            # …and every caller saw the leader's exception
            assert outcomes == ["poisoned batch"] * 8
        finally:
            eng.query_detailed = real_qd
            poisoned.clear()
        # the dispatch loop survived the dead leader
        np.testing.assert_array_equal(qb.query(X[:5]), eng.query(X[:5]))
