"""The float64 references against the program's own numpy oracle, and
the control's arithmetic against JAX's bfloat16."""

import numpy as np
import pytest

from bench import compare, reference as ref


def _table(seed, L=300, d=16):
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=6.0, size=(5, d))
    X = centers[rng.integers(0, 5, size=L * 20)] + rng.normal(size=(L * 20, d))
    leaf = rng.integers(0, L, size=X.shape[0])
    _, LS, SS, N = ref.leaf_cfs(X, leaf)
    rep, ext = ref.bubble_table(LS, SS, N)
    return X, leaf, LS, SS, N, rep, ext


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_offline_pass_matches_program_oracle(seed):
    from repro.core.bubbles import DataBubbles
    from repro.core.summarizer import cluster_bubbles

    _, _, LS, SS, N, rep, ext = _table(seed)
    got = ref.offline_pass(rep, N, ext, min_pts=15, mcs=15.0)
    want = cluster_bubbles(DataBubbles(rep=rep, n=N, extent=ext, dim=16), 15,
                           min_cluster_size=15.0, backend=None)
    assert compare.mislabelled(got.labels, want.labels) == 0
    np.testing.assert_allclose(np.sort(got.mst_w), np.sort(want.mst[2]), rtol=1e-9)


@pytest.mark.parametrize("seed", [5, 6])
def test_edge_gap_holds_edges_to_their_endpoints(seed):
    _, _, _, _, N, rep, ext = _table(seed)
    off = ref.offline_pass(rep, N, ext, min_pts=15, mcs=15.0)

    def gap(u, v, w):
        snap = compare.Snapshot(version=0, rep=rep, n=N, labels=off.labels, mst_u=u,
                                mst_v=v, mst_w=w, center=np.zeros(rep.shape[1]))
        return compare.edge_gap(snap, rep, off.cd, float(np.median(off.mst_w)))

    assert gap(off.mst_u, off.mst_v, off.mst_w) < 1e-12
    # the same weights on shuffled endpoints, and an endpoint off the table
    shuffled = np.random.default_rng(seed).permutation(off.mst_u)
    assert gap(shuffled, off.mst_v, off.mst_w) > 1e-2
    assert gap(off.mst_u, off.mst_v + len(N), off.mst_w) == float("inf")


def test_cfs_match_program_cf_arithmetic():
    from repro.core.cf import cf_extent, cf_rep

    X, leaf, LS, SS, N, rep, ext = _table(3, L=50)
    for j in range(5):
        P = X[leaf == j]
        np.testing.assert_allclose(LS[j], P.sum(axis=0), rtol=1e-12)
        np.testing.assert_allclose(SS[j], np.einsum("nd,nd->", P, P), rtol=1e-12)
    np.testing.assert_allclose(rep, cf_rep(LS, N), rtol=1e-12)
    np.testing.assert_allclose(ext, cf_extent(LS, SS, N), rtol=1e-9)


def test_bf16_rounding_matches_jax():
    import jax.numpy as jnp

    x = np.random.default_rng(0).normal(scale=100.0, size=4096).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(ref.to_bf16(x), want)


def test_lower_precision_distances_are_coarser():
    rng = np.random.default_rng(4)
    a, b = rng.normal(size=(64, 16)) * 5, rng.normal(size=(80, 16)) * 5
    exact = ref.sqdist(a, b)
    err = np.abs(ref.sqdist(a, b, "bf16x3") - exact) / exact
    assert 1e-7 < err.max() < 1e-2


def test_mislabelled_is_zero_only_for_the_same_partition():
    a = np.array([0, 0, 1, 1, -1, 2])
    assert compare.mislabelled(a, np.array([5, 5, 3, 3, -1, 7])) == 0
    assert compare.mislabelled(a, np.array([5, 5, 5, 5, -1, 7])) == 2
    assert compare.mislabelled(a, np.array([5, 5, 3, 3, 3, 7])) == 1
    assert compare.mislabelled(a, np.array([5, 5, 3, -1, -1, 7])) == 1
