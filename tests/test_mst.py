"""MST substrate: vectorized Borůvka / Kruskal vs networkx + scipy oracles."""

import functools

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from scipy.sparse.csgraph import minimum_spanning_tree as scipy_mst

from repro.core.mst import (
    UnionFind,
    boruvka_dense,
    boruvka_jax,
    boruvka_strips_jax,
    kruskal_edges,
)
from repro.kernels import ops


def _oracle_weight(W):
    return scipy_mst(np.where(np.isfinite(W), W, 0.0)).sum()


def _random_metric_matrix(rng, n):
    X = rng.normal(size=(n, 3))
    d = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1))
    np.fill_diagonal(d, np.inf)
    return d


class TestUnionFind:
    def test_basic(self):
        uf = UnionFind(5)
        assert uf.union(0, 1)
        assert not uf.union(1, 0)
        assert uf.union(2, 3)
        assert uf.n_components == 3
        uf.union(0, 2)
        labels = uf.labels()
        assert labels[0] == labels[3]
        assert labels[0] != labels[4]


class TestBoruvkaDense:
    @pytest.mark.parametrize("n", [2, 3, 17, 64, 150])
    def test_weight_matches_scipy(self, rng, n):
        W = _random_metric_matrix(rng, n)
        u, v, w = boruvka_dense(W)
        assert len(w) == n - 1
        assert np.isclose(w.sum(), _oracle_weight(W))

    def test_respects_initial_forest(self, rng):
        """Contraction-rule entry point: pre-seeded forest edges survive."""
        W = _random_metric_matrix(rng, 30)
        u0, v0, w0 = boruvka_dense(W)
        # remove 5 edges, reconnect starting from the partial forest
        keep = np.argsort(w0)[:-5]
        u, v, w = boruvka_dense(W, forest=(u0[keep], v0[keep], w0[keep]))
        assert np.isclose(w.sum(), w0.sum())

    def test_tied_weights_still_tree(self):
        W = np.ones((6, 6))
        np.fill_diagonal(W, np.inf)
        u, v, w = boruvka_dense(W)
        assert len(w) == 5
        uf = UnionFind(6)
        for a, b in zip(u, v):
            assert uf.union(int(a), int(b)), "cycle in claimed MST"


class TestKruskal:
    @given(st.integers(5, 40), st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_matches_networkx_on_random_graphs(self, n, seed):
        rng = np.random.default_rng(seed)
        W = _random_metric_matrix(rng, n)
        iu, iv = np.triu_indices(n, k=1)
        w = W[iu, iv]
        mu, mv, mw = kruskal_edges(iu, iv, w, n)
        g = nx.Graph()
        g.add_weighted_edges_from(zip(iu.tolist(), iv.tolist(), w.tolist()))
        t = nx.minimum_spanning_tree(g)
        assert np.isclose(mw.sum(), t.size(weight="weight"))

    def test_sparse_edge_list_forest(self):
        """Disconnected input -> spanning forest, not crash."""
        u = np.array([0, 1, 3])
        v = np.array([1, 2, 4])
        w = np.array([1.0, 2.0, 3.0])
        mu, mv, mw = kruskal_edges(u, v, w, 5)
        assert len(mw) == 3  # two components


class TestBoruvkaJax:
    @pytest.mark.parametrize("n", [8, 33, 100])
    def test_matches_scipy(self, rng, n):
        W = _random_metric_matrix(rng, n)
        eu, ev, ew, valid = boruvka_jax(W)
        assert int(np.sum(valid)) == n - 1
        assert np.isclose(float(np.sum(np.where(valid, ew, 0.0))), _oracle_weight(W), rtol=1e-5)

    def test_tied_weights_valid_tree(self):
        W = np.ones((16, 16))
        np.fill_diagonal(W, np.inf)
        eu, ev, ew, valid = boruvka_jax(W)
        eu, ev = np.asarray(eu)[np.asarray(valid)], np.asarray(ev)[np.asarray(valid)]
        assert len(eu) == 15
        uf = UnionFind(16)
        for a, b in zip(eu, ev):
            assert uf.union(int(a), int(b)), "cycle in claimed MST"

    @given(st.integers(4, 60), st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_random_property(self, n, seed):
        rng = np.random.default_rng(seed)
        W = _random_metric_matrix(rng, n)
        eu, ev, ew, valid = boruvka_jax(W)
        assert np.isclose(float(np.sum(np.where(valid, ew, 0.0))), _oracle_weight(W), rtol=1e-5)


# --------------------------------------------------------------------------
# strip Borůvka: the W-free engine of the spatial pass


def _strip_table(n, d, n_valid, seed):
    """Seeded reps with duplicate rows, tied core distances and pad rows
    parked where the pipeline parks them."""
    rng = np.random.default_rng(seed)
    rep = rng.normal(0.0, 2.0, (n, d)).astype(np.float32)
    rep[1:n_valid:7] = rep[0]  # duplicate reps: d = 0 between them
    cd = np.abs(rng.normal(0.5, 0.2, n)).astype(np.float32)
    cd[2:n_valid:5] = cd[3]  # tied core distances
    rep[n_valid:] = ops._PAD_COORD
    return rep, cd, np.arange(n) >= n_valid


@functools.partial(jax.jit, static_argnames=("use_ref", "rows", "max_rounds"))
def _strips(rep, cd, is_pad, use_ref, rows, max_rounds=None):
    return boruvka_strips_jax(rep, cd, is_pad, use_ref=use_ref, rows=rows,
                              max_rounds=max_rounds)


@functools.partial(jax.jit, static_argnames=("use_ref",))
def _dense_w(rep, cd, is_pad, use_ref):
    """The matrix the dense pass builds from the same reps and cd."""
    W = ops.mutual_reachability(rep, rep, cd, cd, zero_diag=True, use_ref=use_ref)
    return jnp.where(is_pad[:, None] | is_pad[None, :], jnp.inf, W)


STRIP_CASES = [
    # (n, n_valid, rows, use_ref): several strips in every case
    (64, 64, 16, True),
    (64, 50, 16, False),
    (256, 230, 64, True),
    (512, 500, 256, False),
    (1024, 1000, 256, True),
    (1024, 1024, 128, True),
]


class TestBoruvkaStrips:
    @pytest.mark.parametrize("d", [4, 16])
    @pytest.mark.parametrize("n,n_valid,rows,use_ref", STRIP_CASES)
    def test_bit_identical_to_dense(self, n, n_valid, rows, use_ref, d):
        """Edges, weights and validity equal ``boruvka_jax`` on the matrix
        the same kernel builds, bit for bit."""
        rep, cd, is_pad = _strip_table(n, d, n_valid, seed=n + d)
        got = _strips(rep, cd, is_pad, use_ref, rows)
        want = jax.jit(boruvka_jax)(_dense_w(rep, cd, is_pad, use_ref))
        for name, g, w in zip(("eu", "ev", "ew", "valid"), got[:4], want):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes(), (
                name, np.flatnonzero(g != w)[:10])
        assert int(np.sum(np.asarray(got[3]))) == n_valid - 1
        assert int(got[5]) == int(got[4]) * (n // rows)

    @pytest.mark.parametrize("d", [4, 16])
    def test_weights_match_kruskal_in_float64(self, d):
        n, n_valid = 256, 240
        rep, cd, is_pad = _strip_table(n, d, n_valid, seed=d)
        eu, ev, ew, valid, _, _ = _strips(rep, cd, is_pad, True, 64)
        W = np.asarray(_dense_w(rep, cd, is_pad, True), np.float64)[:n_valid, :n_valid]
        iu, iv = np.triu_indices(n_valid, k=1)
        _, _, kw = kruskal_edges(iu, iv, W[iu, iv], n_valid)
        sw = np.sort(np.asarray(ew, np.float64)[np.asarray(valid)])
        np.testing.assert_array_equal(sw, np.sort(kw))
        assert sw.sum() == np.sort(kw).sum()

    def test_rounds_are_the_rounds_that_added_edges(self):
        """Capping the loop at r rounds keeps the first r rounds: each
        counted round adds edges, and the last one completes the tree."""
        n, n_valid, rows = 256, 230, 64
        rep, cd, is_pad = _strip_table(n, 8, n_valid, seed=5)
        full = _strips(rep, cd, is_pad, True, rows)
        k = int(full[4])
        assert k >= 2
        counts = []
        for r in range(1, k + 1):
            cut = _strips(rep, cd, is_pad, True, rows, max_rounds=r)
            assert int(cut[4]) == r and int(cut[5]) == r * (n // rows)
            counts.append(int(np.sum(np.asarray(cut[3]))))
        assert all(b > a for a, b in zip([0] + counts, counts))
        assert counts[-1] == n_valid - 1

    def test_strip_height_is_capped_below_n(self):
        """A strip is never the whole (n, n) matrix: rows cap at n // 2."""
        rep, cd, is_pad = _strip_table(64, 4, 60, seed=1)
        out = _strips(rep, cd, is_pad, True, 1024)
        assert int(out[5]) == 2 * int(out[4])


class TestStripPass:
    """The spatial ε-pass runs the strip Borůvka and reports its counts."""

    @pytest.mark.parametrize("d", [4, 16])
    def test_spatial_labels_equal_dense(self, d):
        rng = np.random.default_rng(d)
        centers = rng.normal(0.0, 6.0, (5, d))
        L = 500
        rep = centers[rng.integers(0, 5, L)] + rng.normal(0.0, 0.5, (L, d))
        n_b = rng.integers(1, 30, L).astype(np.float64)
        extent = np.abs(rng.normal(0.3, 0.05, L))
        dense = ops.offline_recluster_from_table(rep, n_b, extent, 10, use_ref=True)
        strip = ops.offline_recluster_from_table(rep, n_b, extent, 10, use_ref=True,
                                                 spatial_index=True)
        np.testing.assert_array_equal(strip.labels, dense.labels)
        # the two branches take core distances from different programs
        # (grid search vs dense strips), whose f32 bits depend on the host
        # CPU's codegen; the tree given the same cd is pinned bitwise above
        np.testing.assert_allclose(np.sort(strip.mst[2]), np.sort(dense.mst[2]),
                                   rtol=1e-3)
        assert strip.stages["mst"] == "boruvka-strips-jnp"
        assert dense.mst_rounds == dense.mst_strip_calls == 0
        # Lp 512 sweeps strips of Lp // 2 rows: two kernel calls a round
        assert strip.mst_rounds >= 1 and strip.mst_strip_calls == 2 * strip.mst_rounds

    def test_engine_stats_grow_by_the_pass_counts(self):
        from repro.serving.stream import StreamingClusterEngine

        rng = np.random.default_rng(3)
        eng = StreamingClusterEngine(dim=3, min_pts=5, backend="jnp", spatial_index=True,
                                     compression=0.1, min_offline_points=10)
        eng.submit_insert(rng.normal(size=(400, 3)))
        eng.poll()
        assert eng.stats["recluster_count"] == 1
        res = eng.snapshot.result
        assert res.mst_rounds >= 1
        assert eng.stats["mst_rounds_total"] == res.mst_rounds
        assert eng.stats["mst_strip_calls_total"] == res.mst_strip_calls
        before = dict(eng.stats)
        eng.submit_insert(rng.normal(size=(400, 3)) + 4.0)
        eng.poll()
        assert eng.stats["recluster_count"] == before["recluster_count"] + 1
        res = eng.snapshot.result
        assert eng.stats["mst_rounds_total"] == before["mst_rounds_total"] + res.mst_rounds
        assert (eng.stats["mst_strip_calls_total"]
                == before["mst_strip_calls_total"] + res.mst_strip_calls)
