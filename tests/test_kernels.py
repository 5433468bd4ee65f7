"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Per the assignment: for each kernel, sweep shapes/dtypes and
assert_allclose against the ref.py oracle; hypothesis drives random
geometry."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels import pairwise as pw_k

SHAPES = [(8, 8, 2), (100, 64, 3), (256, 256, 16), (130, 70, 34), (1, 5, 4), (257, 129, 7)]
DTYPES = [np.float32, np.float64]


def _data(rng, n, m, d, dtype):
    X = rng.normal(size=(n, d)).astype(dtype) * 3
    Y = rng.normal(size=(m, d)).astype(dtype) * 3
    return X, Y


class TestPairwise:
    @pytest.mark.parametrize("n,m,d", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, rng, n, m, d, dtype):
        X, Y = _data(rng, n, m, d, dtype)
        got = ops.pairwise_sqdist(X, Y)
        want = ref.pairwise_sqdist(jnp.asarray(X), jnp.asarray(Y))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    def test_direct_kernel_blockspec(self, rng):
        """Raw pallas_call path with explicit block sizes."""
        X = rng.normal(size=(512, 128)).astype(np.float32)
        got = pw_k.pairwise_sqdist(
            jnp.asarray(X), jnp.asarray(X), bn=128, bm=256, interpret=True
        )
        want = ref.pairwise_sqdist(jnp.asarray(X), jnp.asarray(X))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    @given(st.integers(1, 80), st.integers(1, 80), st.integers(1, 10), st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_property_nonneg_symmetric(self, n, m, d, seed):
        rng = np.random.default_rng(seed)
        X, Y = _data(rng, n, m, d, np.float32)
        D = np.asarray(ops.pairwise_sqdist(X, Y))
        assert (D >= 0).all()
        DT = np.asarray(ops.pairwise_sqdist(Y, X))
        np.testing.assert_allclose(D, DT.T, rtol=1e-4, atol=1e-4)
        Dxx = np.asarray(ops.pairwise_sqdist(X, X))
        assert np.allclose(np.diag(Dxx), 0.0, atol=1e-3)


class TestMutualReach:
    @pytest.mark.parametrize("n,m,d", SHAPES)
    def test_matches_ref(self, rng, n, m, d):
        X, Y = _data(rng, n, m, d, np.float32)
        cdx = np.abs(rng.normal(size=n)).astype(np.float32)
        cdy = np.abs(rng.normal(size=m)).astype(np.float32)
        got = ops.mutual_reachability(X, Y, cdx, cdy)
        want = ref.mutual_reachability(
            jnp.asarray(X), jnp.asarray(Y), jnp.asarray(cdx), jnp.asarray(cdy)
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    def test_zero_diag_flag(self, rng):
        X, _ = _data(rng, 32, 32, 4, np.float32)
        cd = np.abs(rng.normal(size=32)).astype(np.float32)
        w_on = np.asarray(ops.mutual_reachability(X, X, cd, cd, zero_diag=True))
        w_off = np.asarray(ops.mutual_reachability(X, X, cd, cd, zero_diag=False))
        assert np.allclose(np.diag(w_on), 0.0)
        assert (np.diag(w_off) >= cd - 1e-6).all()

    def test_matches_numpy_core_pipeline(self, rng):
        """Kernel d_m == hdbscan.py numpy d_m (the oracle the MST uses)."""
        from repro.core.hdbscan import core_distances as np_cd, mutual_reachability as np_mr

        X = rng.normal(size=(90, 6))
        cd = np_cd(X, 5)
        want = np_mr(X, cd)
        got = np.asarray(ops.mutual_reachability(X.astype(np.float32), X.astype(np.float32),
                                                 cd.astype(np.float32), cd.astype(np.float32)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestKnn:
    @pytest.mark.parametrize("n,m,d", [(16, 16, 2), (100, 64, 3), (130, 257, 8)])
    @pytest.mark.parametrize("k", [1, 5, 16])
    def test_matches_ref(self, rng, n, m, d, k):
        X, Y = _data(rng, n, m, d, np.float32)
        gd, gi = ops.knn(X, Y, k)
        wd, wi = ref.knn(jnp.asarray(X), jnp.asarray(Y), min(k, m))
        np.testing.assert_allclose(gd, wd, rtol=1e-4, atol=1e-4)
        # indices may differ on exact ties; distances through indices agree
        D = np.sqrt(np.asarray(ref.pairwise_sqdist(jnp.asarray(X), jnp.asarray(Y))))
        np.testing.assert_allclose(
            np.take_along_axis(D, np.asarray(gi), axis=1), wd, rtol=1e-4, atol=1e-4
        )

    def test_core_distances_match_numpy(self, rng):
        from repro.core.hdbscan import core_distances as np_cd

        X = rng.normal(size=(200, 5)).astype(np.float32)
        got = np.asarray(ops.core_distances(X, 7))
        want = np_cd(X.astype(np.float64), 7)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_duplicate_points_tie_break(self):
        X = np.zeros((12, 3), dtype=np.float32)
        d, i = ops.knn(X, X, 4)
        assert np.allclose(d, 0.0)
        # min-index tie-break: first k columns
        np.testing.assert_array_equal(np.asarray(i)[0], np.arange(4))

    def test_large_m_fallback(self, rng):
        """m > VMEM limit routes through the two-stage jnp path."""
        X = rng.normal(size=(8, 4)).astype(np.float32)
        Y = rng.normal(size=((1 << 14) + 64, 4)).astype(np.float32)
        d, i = ops.knn(X, Y, 3)
        wd, wi = ref.knn(jnp.asarray(X), jnp.asarray(Y), 3)
        np.testing.assert_allclose(d, wd, rtol=1e-4, atol=1e-4)


class TestAssign:
    @pytest.mark.parametrize("n,L,d", [(64, 8, 2), (200, 33, 5), (31, 100, 16)])
    def test_matches_ref(self, rng, n, L, d):
        X = rng.normal(size=(n, d)).astype(np.float32)
        R = rng.normal(size=(L, d)).astype(np.float32)
        got = np.asarray(ops.assign(X, R))
        want = np.asarray(ref.assign(jnp.asarray(X), jnp.asarray(R)))
        # ties can differ only when two reps are equidistant; compare dists
        D = np.asarray(ref.pairwise_sqdist(jnp.asarray(X), jnp.asarray(R)))
        np.testing.assert_allclose(D[np.arange(n), got], D[np.arange(n), want], atol=1e-4)

    def test_exact_on_separated_reps(self, rng, blobs):
        X, y = blobs
        centers = np.array([[0, 0], [6, 0], [0, 6.0]], dtype=np.float32)
        got = np.asarray(ops.assign(X.astype(np.float32), centers))
        assert (got == y).mean() > 0.99


class TestBubbleMutualReach:
    def test_matches_numpy_bubbles(self, rng):
        from repro.core.bubbles import DataBubbles, bubble_mutual_reachability as np_bmr
        from repro.core.cf import cf_of_points

        X = rng.normal(size=(300, 4))
        splits = np.array_split(rng.permutation(300), 24)
        LS = np.stack([cf_of_points(X[s])[0] for s in splits])
        SS = np.array([cf_of_points(X[s])[1] for s in splits])
        n = np.array([cf_of_points(X[s])[2] for s in splits])
        b = DataBubbles(rep=LS / n[:, None], n=n,
                        extent=np.sqrt(np.maximum((2 * n * SS - 2 * (LS ** 2).sum(1)) / (n * (n - 1)), 0)),
                        dim=4)
        want, _ = np_bmr(b, min_pts=10)
        got = np.asarray(ops.bubble_mutual_reachability(
            b.rep.astype(np.float32), b.n.astype(np.float32), b.extent.astype(np.float32), 10))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestShardedOfflinePass:
    def test_matches_single_device(self, rng):
        """Row-sharded d_m strip computation == single-device kernel (the
        distributed offline pass; multi-device equivalence is exercised by
        the 1/2/8-device subprocesses in tests/test_mesh_sharding.py)."""
        L, d = 23, 4
        rep = rng.normal(size=(L, d)).astype(np.float32)
        nb = (np.abs(rng.normal(size=L)) * 10 + 1).astype(np.float32)
        ext = np.abs(rng.normal(size=L)).astype(np.float32)
        want = np.asarray(ops.bubble_mutual_reachability(rep, nb, ext, 8))
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        got = np.asarray(ops.bubble_mutual_reachability_sharded(rep, nb, ext, 8, mesh))
        np.testing.assert_allclose(got, want, atol=1e-4)


class TestForceRef:
    def test_env_switch(self, rng, monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_REF", "1")
        X = rng.normal(size=(17, 3)).astype(np.float32)
        got = np.asarray(ops.pairwise_sqdist(X, X))
        want = np.asarray(ref.pairwise_sqdist(jnp.asarray(X), jnp.asarray(X)))
        np.testing.assert_allclose(got, want, rtol=1e-6)
