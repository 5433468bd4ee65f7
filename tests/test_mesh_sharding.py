"""Mesh-sharded offline pass: bit-parity + protocol contracts (DESIGN.md §12).

Two layers of guarantees:

  * in-process — the sharded fused pass (`mesh=`) must be BITWISE
    invariant across every mesh shape this process can build, and an
    equivalent clustering at ulp-level numeric agreement versus the
    unsharded path (submeshes of the visible devices — under plain
    tier-1 that is one device; the `tier1-multidevice` CI leg re-runs
    this file under XLA_FLAGS=--xla_force_host_platform_device_count=8
    where the same loops cover 1/2/3/4/8-way row blocking, including
    the non-divisible lift);

  * subprocess — the acceptance contract: SEPARATE processes forced to
    1, 2, and 8 simulated devices run the identical scenario suite
    (fused dense + spatial, device-table path, streaming engine end to
    end) and their result digests must be identical byte for byte
    (in subprocesses, so the parent process's jax device count is never
    polluted).

Run `python tests/test_mesh_sharding.py --digest` to print one
process's digests (the worker mode the subprocess test drives).
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import assert_same_partition

from repro.core.bubble_flat import BubbleFlat
from repro.core.device_table import (
    DeviceTableProtocol,
    FlatTableCapture,
    HostTableCapture,
    SnapshotDeviceTable,
)
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh, resolve_mesh
from repro.launch.sharding import leaf_row_owner, leaf_table_sharding

MIN_PTS = 5
MCS = 2.0


def _table(L, d, seed=0):
    rng = np.random.default_rng(seed)
    rep = rng.normal(size=(L, d)) * 3.0
    n_b = rng.integers(1, 9, size=L).astype(np.float64)
    extent = rng.uniform(0.1, 1.0, size=L)
    return rep, n_b, extent


def _digest_result(res):
    h = hashlib.sha256()
    for a in (res.labels, np.sort(res.mst[2]), res.stabilities,
              res.point_lambda, res.all_stabilities):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _feasible_ks():
    """Submesh sizes this process can build — includes a non-power-of-two
    (3) when enough devices exist, which exercises the padded lift of
    the materialized distance matrix."""
    n = len(jax.devices())
    return [k for k in (1, 2, 3, 4, 8) if k <= n]


def _submesh(k):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:k]), ("data",))


class TestResolveMesh:
    def test_none_and_false_pass_through(self):
        assert resolve_mesh(None) is None
        assert resolve_mesh(False) is None

    def test_true_builds_host_mesh(self):
        m = resolve_mesh(True)
        assert m is not None and "data" in m.shape

    def test_mesh_passes_through(self):
        m = make_host_mesh()
        assert resolve_mesh(m) is m


class TestProtocolAdoption:
    """The DeviceTableProtocol must cover the flat-table AND snapshot
    paths (the two offline sources the streaming engine switches on)."""

    def test_bubble_flat_conforms(self):
        flat = BubbleFlat(3, mesh=None)
        assert isinstance(flat, DeviceTableProtocol)
        assert flat.ready is False  # stale until the first load

    def test_snapshot_table_conforms(self):
        from repro.core.bubble_tree import BubbleTree

        t = BubbleTree(dim=3)
        s = SnapshotDeviceTable(t)
        assert isinstance(s, DeviceTableProtocol)
        assert s.ready is True
        assert isinstance(s.capture(0), HostTableCapture)

    def test_flat_capture_carries_mesh(self):
        mesh = _submesh(1)
        flat = BubbleFlat(2, mesh=mesh, mesh_axis="data")
        cap = flat.capture(7)
        assert isinstance(cap, FlatTableCapture)
        assert cap.mesh is mesh and cap.n_points == 7

    def test_host_capture_matches_unsharded_pass(self):
        rep, n_b, extent = _table(33, 3)
        # synthesize CF rows whose bubble_table derivation returns them
        LS = rep * n_b[:, None]
        SS = np.sum(rep * rep, axis=-1) * n_b + extent**2 * n_b  # arbitrary
        cap = HostTableCapture(
            ids=np.arange(33), LS=LS, SS=SS, N=n_b)
        backend = ops.get_backend("jnp")
        res, rep_out, nb_out, center = cap.recluster(
            backend, min_pts=MIN_PTS, min_cluster_size=MCS)
        rep2, extent2, nb2, center2 = ops.bubble_table(
            LS, SS, n_b, np.arange(33))
        ref = backend.offline_recluster_from_table(
            rep2, nb2, extent2, MIN_PTS, min_cluster_size=MCS)
        np.testing.assert_array_equal(res.labels, ref.labels)
        np.testing.assert_array_equal(center, center2)


class TestLeafRowLayout:
    def test_table_sharding_row_blocks_when_divisible(self):
        mesh = _submesh(1)
        s = leaf_table_sharding(mesh, (64, 3))
        assert s.mesh is mesh

    def test_row_owner_matches_block_layout(self):
        mesh = _submesh(len(jax.devices()))
        k = mesh.shape["data"]
        Lp = 64
        owners = leaf_row_owner(np.arange(Lp), Lp, mesh)
        assert owners.min() == 0 and owners.max() == (k - 1 if k > 1 else 0)
        if k > 1:
            m = Lp // k
            # shard i owns exactly rows [i*m, (i+1)*m)
            for i in range(k):
                assert (owners[i * m:(i + 1) * m] == i).all()

    def test_row_owner_replicated_fallback(self):
        # a bucket count no mesh >1 divides → replicated fallback, all zeros
        mesh = _submesh(len(jax.devices()))
        owners = leaf_row_owner(np.arange(13), 13, mesh)
        if mesh.shape["data"] > 1:
            assert (owners == 0).all()


class TestStandaloneSharded:
    """`bubble_mutual_reachability_sharded`: allclose to the dense d_m
    matrix and BITWISE identical on every mesh shape (the strips are
    slices of one pinned replicated distance matrix)."""

    @pytest.mark.parametrize("L,d", [(37, 4), (64, 8), (129, 2)])
    def test_allclose_and_mesh_invariant(self, L, d):
        rep, n_b, extent = _table(L, d, seed=L)
        W_d = np.asarray(ops.bubble_mutual_reachability(
            rep, n_b, extent, MIN_PTS, use_ref=True))
        outs = []
        for k in _feasible_ks():
            W_s = np.asarray(ops.bubble_mutual_reachability_sharded(
                jnp.asarray(rep, jnp.float32), jnp.asarray(n_b, jnp.float32),
                jnp.asarray(extent, jnp.float32), MIN_PTS, _submesh(k)))
            assert W_s.shape == (L, L)
            np.testing.assert_allclose(W_s, W_d, rtol=1e-5, atol=1e-5)
            outs.append(W_s)
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)


class TestFusedShardedParity:
    """The acceptance contract, in-process: the fused offline pass with
    mesh= is BITWISE invariant across every feasible mesh shape (its
    distance chain is pinned — ref.pairwise_dist_pinned — so XLA cannot
    re-fuse it differently per shard count), for the dense AND
    grid-pruned (spatial_index) stages, including a non-pow2-divisible
    live count.  Versus the unsharded (mesh=None) pass the pinning
    forbids the FMA contractions XLA picks inside the big fused jit, so
    the contract there is equivalent clustering at ulp-level numeric
    agreement, not bit equality."""

    @pytest.mark.parametrize("L,d,spatial", [
        (37, 4, False), (129, 2, False), (300, 3, True), (129, 2, True),
    ])
    def test_mesh_invariant_and_matches_unsharded(self, L, d, spatial):
        rep, n_b, extent = _table(L, d, seed=7 * L + d)
        kw = dict(min_pts=MIN_PTS, min_cluster_size=MCS,
                  use_ref=True, spatial_index=spatial)
        ref = ops.offline_recluster_from_table(rep, n_b, extent, **kw)
        first = None
        for k in _feasible_ks():
            res = ops.offline_recluster_from_table(
                rep, n_b, extent, mesh=_submesh(k), **kw)
            if first is None:
                first = res
                assert_same_partition(res.labels, ref.labels, f"k={k}")
                np.testing.assert_allclose(
                    np.sort(res.mst[2]), np.sort(ref.mst[2]),
                    rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(
                    res.stabilities, ref.stabilities, rtol=1e-3, atol=1e-4)
            else:
                np.testing.assert_array_equal(res.labels, first.labels)
                np.testing.assert_array_equal(
                    np.sort(res.mst[2]), np.sort(first.mst[2]))
                np.testing.assert_array_equal(
                    res.stabilities, first.stabilities)
                np.testing.assert_array_equal(
                    res.point_lambda, first.point_lambda)

    def test_return_w_rejected_on_mesh(self):
        rep, n_b, extent = _table(16, 2)
        with pytest.raises(ValueError, match="return_w"):
            ops.offline_recluster_from_table(
                rep, n_b, extent, MIN_PTS, return_w=True, mesh=_submesh(1))


class TestDeviceTableSharded:
    """`offline_recluster_from_device_table` (the BubbleFlat zero-copy
    path) with mesh= vs without: same bits, any mesh shape."""

    def _flat_state(self, L=23, d=3, Lp=32, seed=11):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(L, d)) * 2.0
        n = rng.integers(1, 6, size=L).astype(np.float64)
        LS = np.zeros((Lp, d), np.float32)
        SS = np.zeros(Lp, np.float32)
        N = np.zeros(Lp, np.float32)
        alive = np.zeros(Lp, bool)
        LS[:L] = (X * n[:, None]).astype(np.float32)
        SS[:L] = (np.sum(X * X, -1) * n + rng.uniform(0, 1, L)).astype(np.float32)
        N[:L] = n
        alive[:L] = True
        z = np.zeros_like
        return (jnp.asarray(LS), jnp.asarray(z(LS)), jnp.asarray(SS),
                jnp.asarray(z(SS)), jnp.asarray(N), jnp.asarray(alive)), np.zeros(d)

    def test_mesh_invariant_and_matches_unsharded(self):
        view, origin = self._flat_state()
        ref, rep_r, nb_r, c_r = ops.offline_recluster_from_device_table(
            *view, origin, MIN_PTS, min_cluster_size=MCS, use_ref=True)
        first = None
        for k in _feasible_ks():
            res, rep_s, nb_s, c_s = ops.offline_recluster_from_device_table(
                *view, origin, MIN_PTS, min_cluster_size=MCS, use_ref=True,
                mesh=_submesh(k))
            # compaction/derivation are mesh-independent: bitwise always
            np.testing.assert_array_equal(rep_s, rep_r)
            np.testing.assert_array_equal(nb_s, nb_r)
            np.testing.assert_array_equal(c_s, c_r)
            if first is None:
                first = res
                assert_same_partition(res.labels, ref.labels, f"k={k}")
                np.testing.assert_allclose(
                    np.sort(res.mst[2]), np.sort(ref.mst[2]),
                    rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(
                    res.stabilities, ref.stabilities, rtol=1e-3, atol=1e-4)
            else:
                np.testing.assert_array_equal(res.labels, first.labels)
                np.testing.assert_array_equal(
                    np.sort(res.mst[2]), np.sort(first.mst[2]))
                np.testing.assert_array_equal(
                    res.stabilities, first.stabilities)


class TestEngineMeshOptIn:
    """StreamingClusterEngine(mesh=…): changes no contracts, no bits."""

    def _stream(self, **kw):
        from repro.serving.stream import StreamingClusterEngine

        rng = np.random.default_rng(3)
        X = np.concatenate([
            rng.normal(size=(80, 3)) * 0.3 + c
            for c in (np.zeros(3), np.full(3, 4.0))
        ])
        eng = StreamingClusterEngine(dim=3, min_pts=5, **kw)
        for i in range(0, len(X), 40):
            eng.ingest(X[i:i + 40])
        return eng.flush()

    @pytest.mark.parametrize("device_online", [False, True])
    def test_snapshot_matches_unsharded(self, device_online):
        a = self._stream(device_online=device_online)
        b = self._stream(device_online=device_online, mesh=True)
        assert_same_partition(a.bubble_labels, b.bubble_labels)
        np.testing.assert_allclose(
            np.sort(a.mst[2]), np.sort(b.mst[2]), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            a.stabilities, b.stabilities, rtol=1e-3, atol=1e-4)
        # the summarizer itself is untouched by mesh=: same bubbles, bit for bit
        np.testing.assert_array_equal(a.bubble_rep, b.bubble_rep)
        np.testing.assert_array_equal(a.bubble_n, b.bubble_n)

    def test_mesh_with_exact_rejected(self):
        from repro.serving.stream import StreamingClusterEngine

        with pytest.raises(ValueError, match="exact"):
            StreamingClusterEngine(dim=2, mesh=True, exact=True)


# ---------------------------------------------------------------------------
# subprocess digest parity: 1 vs 2 vs 8 simulated devices
# ---------------------------------------------------------------------------

_SCENARIOS = ("fused_dense", "fused_spatial", "device_table", "engine")


def _worker_digests():
    """The identical scenario suite every forced-device-count process
    runs; each scenario digests the arrays the acceptance criterion
    names (labels, MST weights, stabilities)."""
    mesh = make_host_mesh()
    out = {"devices": len(jax.devices())}

    rep, n_b, extent = _table(129, 2, seed=0)
    out["fused_dense"] = _digest_result(ops.offline_recluster_from_table(
        rep, n_b, extent, 9, min_cluster_size=MCS, use_ref=True, mesh=mesh))

    rep, n_b, extent = _table(300, 3, seed=1)
    out["fused_spatial"] = _digest_result(ops.offline_recluster_from_table(
        rep, n_b, extent, MIN_PTS, min_cluster_size=MCS, use_ref=True,
        spatial_index=True, mesh=mesh))

    t = TestDeviceTableSharded()
    view, origin = t._flat_state()
    res, rep_o, nb_o, c_o = ops.offline_recluster_from_device_table(
        *view, origin, MIN_PTS, min_cluster_size=MCS, use_ref=True, mesh=mesh)
    h = hashlib.sha256(_digest_result(res).encode())
    for a in (rep_o, nb_o, c_o):
        h.update(np.ascontiguousarray(a).tobytes())
    out["device_table"] = h.hexdigest()

    from repro.serving.stream import StreamingClusterEngine

    rng = np.random.default_rng(5)
    X = np.concatenate([
        rng.normal(size=(80, 3)) * 0.3 + c
        for c in (np.zeros(3), np.full(3, 4.0), np.array([4.0, -4.0, 0.0]))
    ])
    eng = StreamingClusterEngine(dim=3, min_pts=5, mesh=True, device_online=True)
    for i in range(0, len(X), 60):
        eng.ingest(X[i:i + 60])
    snap = eng.flush()
    h = hashlib.sha256()
    for a in (snap.bubble_labels, np.sort(snap.mst[2]), snap.stabilities,
              snap.bubble_rep, snap.bubble_n, snap.center):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    out["engine"] = h.hexdigest()
    return out


def _spawn_digests(n_devices):
    env = dict(
        os.environ, PYTHONPATH="src",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
    )
    r = subprocess.run(
        [sys.executable, __file__, "--digest"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestMultiDeviceDigestParity:
    """The CI leg's teeth: forced 1/2/8-device processes must produce
    byte-identical offline results on the identical scenario suite."""

    def test_digests_identical_across_device_counts(self):
        runs = {n: _spawn_digests(n) for n in (1, 2, 8)}
        assert runs[1]["devices"] == 1 and runs[8]["devices"] == 8
        for name in _SCENARIOS:
            got = {n: runs[n][name] for n in runs}
            assert len(set(got.values())) == 1, f"{name}: {got}"


if __name__ == "__main__":
    if "--digest" in sys.argv:
        print(json.dumps(_worker_digests()))
    else:
        sys.exit(pytest.main([__file__, "-q"]))
