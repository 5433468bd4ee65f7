"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Every f32 distance dot here, in the Pallas kernels and in the grid layer
runs at ``Precision.HIGHEST``: the TPU's default f32 matmul rounds its
inputs to bfloat16, which the ‖x‖²+‖y‖²−2xy expansion turns into
distance errors far above the cluster separations.  CPU ignores the
setting, so CPU bits are unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_sqdist(x, y):
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    return jnp.maximum(xx + yy - 2.0 * jnp.matmul(x, y.T, precision=HIGHEST), 0.0)


def pairwise_dist_pinned(x):
    """Self euclidean distance matrix with every intermediate pinned so
    the bits cannot depend on the surrounding program.

    XLA takes two fusion liberties with the naive
    ``sqrt(xx + yy - 2·x@xᵀ)`` chain, both ulp-level and both sensitive
    to which consumers the chain is inlined next to: it may contract the
    last product of the row-norm reduction into an FMA (seen at d=2: one
    multiply + one add become a single fused op), and it may reassociate
    the ``xx_i + xx_j - 2·xy`` adds.  ``optimization_barrier`` pins the
    dot, the norm outer-sum, and the shifted square as materialized
    values; the ``maximum(x², 0)`` blocks the FMA contraction (XLA fuses
    through both barrier and ``abs`` there) and is a bit identity on
    squares.  What remains — ``nn - 2·xy`` (2·xy is exact, so the
    subtract is single-rounded with or without FMA), ``maximum``,
    ``sqrt`` — is correctly rounded everywhere, so every program that
    calls this helper on the same table gets the same bits.  The sharded
    offline stages (kernels/ops.py) rely on this for bit-identity across
    mesh shapes."""
    x = x.astype(jnp.float32)
    xx = jax.lax.optimization_barrier(
        jnp.sum(jnp.maximum(x * x, 0.0), axis=-1))
    xy = jax.lax.optimization_barrier(jnp.matmul(x, x.T, precision=HIGHEST))
    nn = jax.lax.optimization_barrier(xx[:, None] + xx[None, :])
    sq = jnp.maximum(nn - 2.0 * xy, 0.0)
    return jnp.sqrt(jax.lax.optimization_barrier(sq))


def mutual_reachability(x, y, cd_x, cd_y, zero_diag=True):
    d = jnp.sqrt(pairwise_sqdist(x, y))
    m = jnp.maximum(d, jnp.maximum(cd_x.astype(jnp.float32)[:, None], cd_y.astype(jnp.float32)[None, :]))
    if zero_diag:
        n, mm = m.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, mm), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, mm), 1)
        m = jnp.where(rows == cols, 0.0, m)
    return m


def knn(x, y, k):
    """Ascending k smallest distances + indices, min-index tie-break
    (jax.lax.top_k already orders equal keys by ascending index)."""
    d = jnp.sqrt(pairwise_sqdist(x, y))
    neg_d, idx = jax.lax.top_k(-d, k)
    return -neg_d, idx.astype(jnp.int32)


def _nearest(x, reps):
    """Lowest nearest-rep column per row + the ROW-SHIFTED squared
    distance it attains (true sq = shifted + ‖x‖², added back only where
    a caller wants the distance itself).

    Two deliberate deviations from a naive `argmin(pairwise_sqdist(…))`,
    both for serve-plane query latency:
      * ‖x‖² is elided from the minimized matrix — it is constant per
        row, so the argmin is invariant and one full (n, L) broadcast
        pass disappears;
      * the index comes from min + masked index-min instead of argmin —
        XLA CPU lowers argmin to a variadic (value, index) pair reduce
        ~6× slower than two simple vectorized reductions, and the
        where(== row_min) form matches argmin's first-occurrence
        tie-break AND the Pallas assign kernel's extraction."""
    x = x.astype(jnp.float32)
    r = reps.astype(jnp.float32)
    L = r.shape[0]
    sq = jnp.sum(r * r, axis=-1)[None, :] - 2.0 * jnp.matmul(x, r.T, precision=HIGHEST)
    m = jnp.min(sq, axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
    idx = jnp.min(jnp.where(sq == m[:, None], cols, L), axis=1).astype(jnp.int32)
    return idx, m


def assign(x, reps):
    idx, _ = _nearest(x, reps)
    return idx


def assign_with_dist(x, reps):
    """Nearest-rep index + euclidean distance (the serve plane's fused
    query path; mirrors the kernel's dual output)."""
    idx, m = _nearest(x, reps)
    xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    return idx, jnp.sqrt(jnp.maximum(xx + m, 0.0))


def dim_root(x, dim):
    """x**(1/dim) with context-stable rounding for power-of-two dims.

    XLA's `pow` lowering is fusion-context sensitive on CPU: the same
    `jnp.power(x, 0.5)` can compile to a correctly-rounded sqrt in one
    program and a ~1e-5-rel exp/log approximation in another, so two
    programs computing Eq. 6 disagree bitwise.  Repeated `sqrt` is
    IEEE-correctly-rounded everywhere, making the dense and grid-pruned
    core-distance paths bit-identical for dim ∈ {1, 2, 4, 8, 16, …}
    (non-pow2 dims keep `pow` and only get allclose-level parity)."""
    if dim >= 1 and (dim & (dim - 1)) == 0:
        for _ in range(int(dim).bit_length() - 1):
            x = jnp.sqrt(x)
        return x
    return jnp.power(x, 1.0 / float(dim))


def bubble_core_distances_from_dm(d, row_ids, n_b, extent, min_pts, dim):
    """Eq. 6 for a (m, L) euclidean-distance strip — rows ``row_ids`` of
    the full (L, L) distance matrix.

    Every reduction (sort, cumsum, candidate gather) runs along the full
    column axis, so each row's result depends only on that row's distance
    slice and the whole table.  Crucially every op here is bit-determined
    given ``d`` (stable sort has a unique answer, cumsum over
    integer-valued f32 masses is exact, the rest is correctly-rounded
    elementwise) — so a strip of a materialized distance matrix yields
    bitwise the dense program's rows on any shard shape.  The shard_map
    offline pass (kernels/ops.py) relies on exactly that."""
    m, L = d.shape
    cols = jnp.arange(L, dtype=jnp.int32)
    d = jnp.where(row_ids.astype(jnp.int32)[:, None] == cols[None, :], 0.0, d)
    order = jnp.argsort(d, axis=1, stable=True)
    d_sorted = jnp.take_along_axis(d, order, axis=1)
    n_sorted = n_b.astype(jnp.float32)[order]
    csum = jnp.cumsum(n_sorted, axis=1)
    reach = csum >= float(min_pts)
    idx = jnp.where(reach.any(axis=1), jnp.argmax(reach, axis=1), L - 1)
    rows = jnp.arange(m)
    before = jnp.where(idx > 0, csum[rows, jnp.maximum(idx - 1, 0)], 0.0)
    k_resid = jnp.maximum(float(min_pts) - before, 1.0)
    C = order[rows, idx]
    nC = jnp.maximum(n_b.astype(jnp.float32)[C], 1.0)
    k_resid = jnp.clip(k_resid, 0.0, nC)
    nnd = dim_root(k_resid / nC, dim) * extent.astype(jnp.float32)[C]
    return d_sorted[rows, idx] + nnd


def bubble_core_distances_rows(rep_rows, row_ids, rep, n_b, extent, min_pts, dim):
    """Eq. 6 for a strip of rows against the full bubble table (computes
    the strip's own distance rows; see `bubble_core_distances_from_dm`
    for the bit-stability contract given a shared distance matrix)."""
    d = jnp.sqrt(pairwise_sqdist(rep_rows, rep))
    return bubble_core_distances_from_dm(d, row_ids, n_b, extent, min_pts, dim)


def bubble_core_distances(rep, n_b, extent, min_pts, dim):
    """Eq. 6 in pure jnp (vectorized over all bubbles)."""
    L = rep.shape[0]
    return bubble_core_distances_rows(
        rep, jnp.arange(L, dtype=jnp.int32), rep, n_b, extent, min_pts, dim)


def bubble_mutual_reachability(rep, n_b, extent, min_pts):
    cd = bubble_core_distances(rep, n_b, extent, min_pts, rep.shape[1])
    return mutual_reachability(rep, rep, cd, cd, zero_diag=True)


def flash_attention(q, k, v, qpos, kpos, causal=True, window=None):
    """Oracle for kernels.flash_attention: masked softmax attention over
    (H, S, D) head-major tensors with positional masking (kpos<0 dead)."""
    d = q.shape[-1]
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    mask = kpos[:, None, :] < 0
    if causal:
        mask = mask | (kpos[:, None, :] > qpos[:, :, None])
    if window is not None:
        mask = mask | (kpos[:, None, :] <= qpos[:, :, None] - window)
    s = jnp.where(mask, -1e30, s)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", w, v.astype(jnp.float32)).astype(q.dtype)
