"""The hierarchy's three sweeps as SMEM kernels (`kernels.hierarchy`)
against the jnp scans they replace: bit for bit, on every output of
`hierarchy_fixed`.  On this machine the kernels run in the Pallas
interpreter (`pallas-interpret`), which executes the kernel body."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import hierarchy_jax as hj
from repro.kernels import ops
from repro.serving.stream import StreamingClusterEngine


def _edge_buffers(rng, n, Lp, *, tie_heavy=False, zero_frac=0.0, rejected=0):
    """A random spanning tree over n of Lp leaves in Borůvka's buffer
    layout: n − 1 valid edges at shuffled slots.  ``rejected`` edges are
    overwritten with copies of others, so their merges close a cycle."""
    parent = np.array([rng.integers(0, i) for i in range(1, n)], dtype=np.int32)
    child = np.arange(1, n, dtype=np.int32)
    if tie_heavy:
        w = rng.choice([0.5, 1.0, 2.0], size=n - 1)
    else:
        w = rng.uniform(0.1, 10.0, size=n - 1)
    w[rng.random(n - 1) < zero_frac] = 0.0  # duplicate points: λ = MAX_LAMBDA
    flip = rng.random(n - 1) < 0.5
    u, v = np.where(flip, child, parent), np.where(flip, parent, child)
    for j in rng.choice(n - 1, size=rejected, replace=False):
        k = (j + 1) % (n - 1)
        u[j], v[j], w[j] = v[k], u[k], w[k]
    slots = rng.permutation(Lp)[: n - 1]
    eu = np.zeros(Lp, np.int32)
    ev = np.zeros(Lp, np.int32)
    ew = np.zeros(Lp, np.float32)
    valid = np.zeros(Lp, bool)
    eu[slots], ev[slots], ew[slots], valid[slots] = u, v, w, True
    weights = np.zeros(Lp, np.float32)
    weights[:n] = rng.integers(1, 5, size=n)
    return (jnp.asarray(eu), jnp.asarray(ev), jnp.asarray(ew), jnp.asarray(valid),
            jnp.asarray(n, jnp.int32), jnp.asarray(weights))


def _bits(a):
    a = np.atleast_1d(np.asarray(a))
    return a if a.dtype == bool else a.view(np.uint8)


CASES = {
    # name: (n, Lp, seed, tree options, min_cluster_size, method, allow_single)
    "lp8": (5, 8, 2, {}, 2.0, "eom", False),
    "lp256": (200, 256, 2, {}, 6.0, "eom", False),
    "lp8192": (8000, 8192, 3, {}, 12.0, "eom", False),
    "ties": (230, 256, 4, {"tie_heavy": True}, 6.0, "eom", False),
    "zero-distances": (230, 256, 5, {"zero_frac": 0.2}, 6.0, "eom", False),
    "leaf": (230, 256, 6, {}, 6.0, "leaf", False),
    "eom-single": (230, 256, 7, {}, 6.0, "eom", True),
    "leaf-single": (230, 256, 8, {}, 6.0, "leaf", True),
    "one-label": (200, 256, 9, {}, 1e6, "eom", False),
    "one-label-single": (200, 256, 10, {}, 1e6, "eom", True),
    "rejected-merges": (230, 256, 11, {"rejected": 3}, 6.0, "eom", False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_scans_bitwise(case):
    n, Lp, seed, opts, mcs, method, single = CASES[case]
    args = _edge_buffers(np.random.default_rng(seed), n, Lp, **opts)
    kw = dict(method=method, allow_single_cluster=single)
    ref = hj.hierarchy_fixed(*args, jnp.float32(mcs), impl="jnp", **kw)
    got = hj.hierarchy_fixed(*args, jnp.float32(mcs), impl="pallas-interpret", **kw)
    for r, g in zip(ref, got):
        assert type(r) is type(g)
        for f in r._fields:
            a, b = getattr(r, f), getattr(g, f)
            assert a.shape == b.shape and a.dtype == b.dtype, (type(r).__name__, f)
            assert np.array_equal(_bits(a), _bits(b)), (type(r).__name__, f)
    n_labels = int(ref[1].n_labels)
    if case.startswith("one-label"):
        assert n_labels == 1
    else:
        assert n_labels >= 3  # the tree has a split for the sweeps to walk
    if case == "rejected-merges":
        assert (np.asarray(ref[0].left) == 2 * Lp - 1).sum() >= 3


@pytest.mark.parametrize("use_ref,want", [(False, "pallas-interpret"), (True, "jnp")])
def test_impl_follows_the_kernel_switch(use_ref, want):
    assert ops.hierarchy_impl(8192, use_ref) == want
    assert ops.offline_stages(8192, use_ref, spatial=False)["hierarchy"] == want
    assert ops.offline_stages(32768, use_ref, spatial=True)["hierarchy"] == want


def test_scans_serve_what_the_kernels_cannot():
    # resident state beyond SMEM, and the mesh's GSPMD jit
    assert ops.hierarchy_impl(65536, False) == "jnp"
    assert ops.hierarchy_impl(256, False, mesh=object()) == "jnp"


@pytest.mark.parametrize("backend,per_pass", [("pallas", 1), ("jnp", 0)])
def test_engine_counts_kernel_passes(rng, backend, per_pass):
    eng = StreamingClusterEngine(dim=2, min_pts=5, compression=0.2, backend=backend,
                                 epsilon=0.2, min_offline_points=10)
    for _ in range(3):
        before = dict(eng.stats)
        eng.ingest(rng.normal(size=(60, 2)))
        eng.maybe_recluster(force=True)
        eng.join()
        passes = eng.stats["recluster_count"] - before["recluster_count"]
        assert passes >= 1
        grew = (eng.stats["hierarchy_kernel_passes_total"]
                - before["hierarchy_kernel_passes_total"])
        assert grew == per_pass * passes
    assert eng.stats["offline_impl"]["hierarchy"] == ("pallas-interpret" if per_pass else "jnp")
