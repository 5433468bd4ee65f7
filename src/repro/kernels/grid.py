"""Grid-pruned exact neighbor engine (DESIGN.md §10).

Every offline pass, exact rebuild, and query assignment used to pay the
dense O(L²·d) matrix (`bubble_cd.py` strips, `boruvka_jax`,
`kernels/assign.py`).  This module is the sub-quadratic layer behind the
``spatial_index=`` opt-in: bubble reps are bucketed into fixed-shape
Morton-ordered tiles, and each consumer enumerates, per query row-block,
only the tiles whose axis-aligned lower-bound distance can still beat
the current best — the chunked-argkmin idiom, expressed as fixed-shape
jit programs (scan over row blocks, `while_loop` over candidate tiles in
ascending lower-bound order).

Exactness contract — the point of the whole layer is that pruning is
EXACT, not approximate:

  * a tile is skipped only when ``lb - slack > bound`` STRICTLY, where
    ``slack`` is a conservative f32 forward-error budget (``_slack``)
    covering every rounding step between the exact box bound and the
    computed candidate distance; ties are always visited, so candidates
    that could still win on the lowest-index tie-break are never lost;
  * candidate distances are computed with the exact arithmetic of
    `kernels.ref` (`(xx + yy) - 2·dot`, then `sqrt(max(·, 0))`): a
    gathered tile column produces the SAME f32 bits as the dense matrix
    entry (dot products over contiguous rows are blocking-invariant),
    so the pruned results match the dense jnp reference bit for bit;
  * merges use two-key `lax.sort`/lexicographic min on (value, original
    index), reproducing the reference's stable-argsort / masked
    index-min tie-breaks exactly.

The grid itself is backend-independent jnp (the same status as
`core.hierarchy_jax` / `core.dynamic_jax`): both `ClusterBackend`
flavors route through it when ``spatial_index=True``, and its outputs
are pinned bitwise against the DENSE jnp reference path by
tests/test_grid_pruning.py.  (The two dense backends themselves differ
by ulps in a few epilogue ops on CPU interpret mode, so "bit-exact" is
anchored at the jnp reference — the repo's allclose ground truth.)

Scope caveat: rows marked invalid (size-bucket padding, dead slots
parked at ``ops._PAD_COORD``) are excluded from the candidate set
outright, whereas the dense path merely parks them far away — the two
paths agree for data inside the sane envelope (≪ the 1e6 parking
coordinate), which is the documented contract of the parking scheme.
Weighted Eq. 6 parity additionally assumes integral bubble masses
(point counts — exact in f32 cumsum at any prefix length), which is
what the pipeline produces.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

__all__ = [
    "GridIndex",
    "build_grid",
    "grid_core_distances",
    "grid_core_distances_shard",
    "grid_assign",
    "morton_codes",
    "tile_gap_sq",
    "DEFAULT_TILE",
    "DEFAULT_BLOCK",
]

# quantization bits per grid dimension; with <= 3 interleaved dims the
# Morton code stays inside the int32 budget (3 * 10 = 30 bits)
_BITS = 10
_MAX_GDIMS = 3
_EPS32 = 2.0 ** -23

DEFAULT_TILE = 32   # candidate-tile rows (contiguous in Morton order)
DEFAULT_BLOCK = 64  # query rows per block


class GridIndex(NamedTuple):
    """Morton-sorted copy of a rep table + per-tile bounding boxes.

    All arrays, so the whole index is a pytree that passes through jit
    boundaries (the serve plane caches one per snapshot version).  The
    static tile size is recoverable from shapes: ``T = pts.shape[0] //
    tile_lo.shape[0]``.
    """

    pts: jax.Array      # (Lp, d) rows in Morton order (invalid rows last)
    sq: jax.Array       # (Lp,) per-row squared norms of pts
    orig: jax.Array     # (Lp,) int32 original row index per sorted position
    valid: jax.Array    # (Lp,) bool per sorted position
    tile_lo: jax.Array  # (NT, d) per-tile AABB over valid rows (+inf if none)
    tile_hi: jax.Array  # (NT, d) (-inf if none)
    lo: jax.Array       # (d,) quantization lower corner
    inv_w: jax.Array    # (d,) inverse cell width per dim (0 ⇒ dim unused)
    gdims: jax.Array    # (g,) int32 dims interleaved into the Morton code
    r2: jax.Array       # () max squared norm over valid rows
    n_valid: jax.Array  # () int32 number of valid rows


def _slack(dim: int, r2a, r2b):
    """Conservative absolute error budget for computed SQUARED distances
    and box bounds at magnitude scale r2a + r2b.  A standard forward
    analysis of ``(xx + yy) - 2·xy`` bounds the error by ~(2d+4)·eps·
    (r2a + r2b); the 64·(d+8) constant leaves >10× headroom for the box
    arithmetic and the threshold subtractions themselves.  Over-estimating
    only costs extra tile visits, never exactness."""
    return jnp.float32(64.0 * (dim + 8) * _EPS32) * (
        jnp.asarray(r2a, jnp.float32) + jnp.asarray(r2b, jnp.float32)
    ) + jnp.float32(1e-30)


def morton_codes(x, lo, inv_w, gdims):
    """Interleaved grid codes: quantize the ``gdims`` columns of ``x`` to
    ``2**_BITS`` cells each and bit-interleave.  Purely a visit-order
    heuristic — correctness never depends on the code."""
    x = jnp.asarray(x, jnp.float32)
    g = gdims.shape[0]
    cells = float(1 << _BITS)
    q = jnp.clip(
        jnp.floor((x - lo[None, :]) * inv_w[None, :]),
        0.0, cells - 1.0,
    ).astype(jnp.int32)
    qg = q[:, gdims]  # (n, g)
    code = jnp.zeros(x.shape[0], jnp.int32)
    for b in range(_BITS):
        for k in range(g):
            code = code | (((qg[:, k] >> b) & 1) << (b * g + k))
    return code


def tile_gap_sq(blo, bhi, tlo, thi):
    """Squared distance lower bound between a query AABB (blo, bhi) and
    every tile AABB: per-dim gap ``max(tlo - bhi, blo - thi, 0)``,
    squared and summed.  Empty boxes (lo=+inf / hi=-inf) yield +inf."""
    gap = jnp.maximum(jnp.maximum(tlo - bhi[None, :], blo[None, :] - thi), 0.0)
    return jnp.sum(gap * gap, axis=-1)


@functools.partial(jax.jit, static_argnames=("tile",))
def build_grid(pts, valid, tile: int = DEFAULT_TILE) -> GridIndex:
    """Bucket ``pts`` rows into Morton-ordered tiles of ``tile`` rows.

    ``valid`` masks real rows (padding / dead slots excluded from every
    candidate set and from the quantization frame).  Lp must be a
    multiple of the (clamped) tile size — the callers' power-of-two size
    buckets guarantee it."""
    pts = jnp.asarray(pts, jnp.float32)
    valid = jnp.asarray(valid, bool)
    Lp, d = pts.shape
    T = min(tile, Lp)
    big = jnp.float32(jnp.inf)
    vlo = jnp.min(jnp.where(valid[:, None], pts, big), axis=0)
    vhi = jnp.max(jnp.where(valid[:, None], pts, -big), axis=0)
    vlo = jnp.where(jnp.isfinite(vlo), vlo, 0.0)
    vhi = jnp.where(jnp.isfinite(vhi), vhi, 0.0)
    rng = vhi - vlo
    inv_w = jnp.where(rng > 0, float(1 << _BITS) / rng, 0.0)
    g = min(d, _MAX_GDIMS)
    # interleave the widest dims (stable: range ties break by dim index)
    gdims = jnp.argsort(-rng, stable=True)[:g].astype(jnp.int32)
    code = morton_codes(pts, vlo, inv_w, gdims)
    code = jnp.where(valid, code, jnp.int32(2**31 - 1))  # invalid rows last
    perm = jnp.argsort(code, stable=True).astype(jnp.int32)
    pts_s = pts[perm]
    valid_s = valid[perm]
    sq = jnp.sum(pts_s * pts_s, axis=-1)
    NT = Lp // T
    p3 = pts_s.reshape(NT, T, d)
    v3 = valid_s.reshape(NT, T)
    tlo = jnp.min(jnp.where(v3[:, :, None], p3, big), axis=1)
    thi = jnp.max(jnp.where(v3[:, :, None], p3, -big), axis=1)
    r2 = jnp.max(jnp.where(valid_s, sq, 0.0))
    return GridIndex(
        pts=pts_s, sq=sq, orig=perm, valid=valid_s,
        tile_lo=tlo, tile_hi=thi, lo=vlo, inv_w=inv_w, gdims=gdims,
        r2=r2, n_valid=jnp.sum(valid, dtype=jnp.int32),
    )


def _block_views(grid: GridIndex, bn: int):
    """Reshape the sorted layout into contiguous (NB, bn, ·) row blocks
    plus each block's tile visit order by ascending adjusted lower bound
    (in DISTANCE space, slack already subtracted)."""
    Lp, d = grid.pts.shape
    NB = Lp // bn
    xb = grid.pts.reshape(NB, bn, d)
    xv = grid.valid.reshape(NB, bn)
    blo = jnp.min(jnp.where(xv[:, :, None], xb, jnp.inf), axis=1)
    bhi = jnp.max(jnp.where(xv[:, :, None], xb, -jnp.inf), axis=1)
    slack = _slack(d, grid.r2, grid.r2)
    gap = jnp.maximum(
        jnp.maximum(grid.tile_lo[None, :, :] - bhi[:, None, :],
                    blo[:, None, :] - grid.tile_hi[None, :, :]),
        0.0,
    )  # (NB, NT, d)
    lb_sq = jnp.sum(gap * gap, axis=-1)
    lb_d = jnp.sqrt(jnp.maximum(lb_sq - slack, 0.0))
    lb_d = jnp.where(jnp.isfinite(lb_sq), lb_d, jnp.inf)
    order = jnp.argsort(lb_d, axis=1, stable=True).astype(jnp.int32)
    lbs = jnp.take_along_axis(lb_d, order, axis=1)
    return (
        xb, grid.sq.reshape(NB, bn), xv, grid.orig.reshape(NB, bn),
        order, lbs,
    )


def _tile_slices(grid: GridIndex, tl, T: int):
    """Gather one contiguous tile of the sorted layout (dynamic_slice —
    no scatter/gather of scattered rows, the blocking-invariance of the
    distance dot product only holds for contiguous row runs)."""
    d = grid.pts.shape[1]
    ys = jax.lax.dynamic_slice(grid.pts, (tl * T, jnp.zeros((), tl.dtype)), (T, d))
    yy = jax.lax.dynamic_slice(grid.sq, (tl * T,), (T,))
    yv = jax.lax.dynamic_slice(grid.valid, (tl * T,), (T,))
    yo = jax.lax.dynamic_slice(grid.orig, (tl * T,), (T,))
    return ys, yy, yv, yo


@functools.partial(jax.jit, static_argnames=("min_pts", "dim", "block"))
def grid_core_distances(grid: GridIndex, n_b, extent, min_pts: int, dim: int,
                        block: int = DEFAULT_BLOCK):
    """Eq. 6 bubble core distances via grid-pruned exact top-K.

    ``n_b`` / ``extent`` are in ORIGINAL row order; the result comes back
    in original order, bitwise equal to `ref.bubble_core_distances` for
    integral masses and pre-clamped ``min_pts`` (≤ total mass — the same
    precondition every dense caller already enforces).

    Only K = min(min_pts, Lp) neighbors are ever needed: masses are ≥ 1,
    so the weighted cumsum crosses min_pts within the first K candidates,
    and f32 cumsum over a prefix equals the same prefix of the full-row
    cumsum (integral values are exact; verified bitwise regardless)."""
    n_b = jnp.asarray(n_b, jnp.float32)
    extent = jnp.asarray(extent, jnp.float32)
    Lp, d = grid.pts.shape
    NT = grid.tile_lo.shape[0]
    T = Lp // NT
    bn = min(block, Lp)
    K = min(int(min_pts), Lp)
    mp_f = float(min_pts)

    views = _block_views(grid, bn)

    def block_fn(cd_out, xs):
        xo = xs[3]
        vals = _cd_block_values(grid, n_b, extent, mp_f, dim, K, NT, T, bn, xs)
        return cd_out.at[xo].set(vals), None

    cd, _ = jax.lax.scan(block_fn, jnp.zeros(Lp, jnp.float32), views)
    return cd


def _cd_block_values(grid, n_b, extent, mp_f, dim, K, NT, T, bn, xs):
    """One query block's pruned exact top-K sweep + Eq. 6 epilogue.

    Returns the (bn,) core-distance values for the block (0.0 on invalid
    rows).  A block's result depends only on its own rows and the static
    grid — never on which other blocks share the scan — which is what
    lets ``grid_core_distances_shard`` split the block axis across a
    mesh and reassemble bitwise-identical output.
    """
    Lp = grid.pts.shape[0]
    INF = jnp.float32(jnp.inf)
    xb, xx, xv, xo, order, lbs = xs

    def cond(st):
        t, bd, _ = st
        kth = jnp.max(jnp.where(xv, bd[:, K - 1], -INF))
        return (t < NT) & (lbs[jnp.minimum(t, NT - 1)] <= kth)

    def body(st):
        t, bd, bi = st
        ys, yy, yv, yo = _tile_slices(grid, order[t], T)
        xy = jax.lax.dot_general(xb, ys, (((1,), (1,)), ((), ())),
                                 precision=_ref.HIGHEST)
        # exact ref arithmetic: (xx + yy) - 2*xy, clamp, sqrt
        dm = jnp.sqrt(jnp.maximum((xx[:, None] + yy[None, :]) - 2.0 * xy, 0.0))
        dm = jnp.where(yo[None, :] == xo[:, None], 0.0, dm)  # ref's zero diag
        dm = jnp.where(yv[None, :], dm, INF)
        ci = jnp.where(yv, yo, jnp.int32(Lp))
        ci = jnp.broadcast_to(ci[None, :], (bn, T))
        # exact lexicographic (d, original index) top-K merge
        sd, si = jax.lax.sort(
            (jnp.concatenate([bd, dm], axis=1),
             jnp.concatenate([bi, ci], axis=1)),
            dimension=1, num_keys=2,
        )
        return t + 1, sd[:, :K], si[:, :K]

    _, buf_d, buf_i = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.full((bn, K), INF), jnp.full((bn, K), jnp.int32(Lp))),
    )
    # --- ref.bubble_core_distances epilogue, verbatim over the K-prefix
    rows = jnp.arange(bn)
    safe_i = jnp.minimum(buf_i, Lp - 1)
    n_sorted = jnp.where(buf_i < Lp, n_b[safe_i], 0.0)
    csum = jnp.cumsum(n_sorted, axis=1)
    reach = csum >= mp_f
    idx = jnp.where(reach.any(axis=1), jnp.argmax(reach, axis=1), K - 1)
    before = jnp.where(idx > 0, csum[rows, jnp.maximum(idx - 1, 0)], 0.0)
    k_resid = jnp.maximum(mp_f - before, 1.0)
    C = safe_i[rows, idx]
    nC = jnp.maximum(n_b[C], 1.0)
    k_resid = jnp.clip(k_resid, 0.0, nC)
    nnd = _ref.dim_root(k_resid / nC, dim) * extent[C]
    cdb = buf_d[rows, idx] + nnd
    return jnp.where(xv, cdb, 0.0)


def grid_core_distances_shard(grid: GridIndex, n_b, extent, min_pts: int,
                              dim: int, axis: str, k: int,
                              block: int = DEFAULT_BLOCK):
    """`grid_core_distances` with the query-block scan sharded over a
    mesh axis.  Call INSIDE ``shard_map`` with every input replicated:
    shard i sweeps its contiguous ``ceil(NB/k)`` slice of the block
    views, one tiled ``all_gather`` reassembles the block values in
    global block order, and the scatter back to original row order runs
    replicated.  When the axis does not divide the block count (e.g. 3
    devices over a pow-2 table) the trailing shards re-scan the last
    block and the gathered tail is dropped — a duplicate-tail lift, so
    no shard shape depends on divisibility.  Per-block values don't
    depend on the blocking (the module's exactness contract), so output
    is bitwise ``grid_core_distances`` — itself bitwise
    `ref.bubble_core_distances` — on any mesh shape."""
    n_b = jnp.asarray(n_b, jnp.float32)
    extent = jnp.asarray(extent, jnp.float32)
    Lp, d = grid.pts.shape
    NT = grid.tile_lo.shape[0]
    T = Lp // NT
    bn = min(block, Lp)
    NB = Lp // bn
    NBk = -(-NB // k)  # ceil: trailing shards duplicate the last block
    K = min(int(min_pts), Lp)
    mp_f = float(min_pts)

    views = _block_views(grid, bn)
    shard = jax.lax.axis_index(axis)
    blk_ids = jnp.minimum(
        shard * NBk + jnp.arange(NBk, dtype=jnp.int32), NB - 1)
    views_l = jax.tree_util.tree_map(lambda a: a[blk_ids], views)

    def block_fn(carry, xs):
        return carry, _cd_block_values(grid, n_b, extent, mp_f, dim, K, NT, T, bn, xs)

    _, vals_l = jax.lax.scan(block_fn, 0, views_l)
    vals = jax.lax.all_gather(vals_l, axis, tiled=True)[:NB]  # (NB, bn)
    # views[3] (grid.orig blocked) is a permutation of rows: one scatter
    # reassembles original order exactly like the dense per-block scatter
    return jnp.zeros(Lp, jnp.float32).at[views[3].reshape(Lp)].set(vals.reshape(Lp))


@functools.partial(jax.jit, static_argnames=("block",))
def grid_assign(grid: GridIndex, x, block: int = DEFAULT_BLOCK):
    """Nearest-valid-rep per query row, pruned but index/value-exact
    against `ref._nearest`: returns (idx int32 (B,), row-shifted squared
    distance m (B,)) — callers wanting the distance add ‖x‖² back with
    the reference's exact ``sqrt(max(xx + m, 0))`` form.

    Queries are themselves Morton-sorted (in the grid's frame) so a row
    block shares a tight AABB; results are scattered back to input
    order.  B must be a multiple of the (clamped) block size — callers
    pad with duplicate/zero rows and slice, like the dense wrappers."""
    x = jnp.asarray(x, jnp.float32)
    B, d = x.shape
    Lp = grid.pts.shape[0]
    NT = grid.tile_lo.shape[0]
    T = Lp // NT
    bn = min(block, B)
    NB = B // bn
    INF = jnp.float32(jnp.inf)
    BIGJ = jnp.int32(Lp)

    qcode = morton_codes(x, grid.lo, grid.inv_w, grid.gdims)
    qperm = jnp.argsort(qcode, stable=True).astype(jnp.int32)
    xs = x[qperm]
    xx = jnp.sum(xs * xs, axis=-1)
    slack = _slack(d, jnp.max(xx), grid.r2)

    xb3 = xs.reshape(NB, bn, d)
    xx2 = xx.reshape(NB, bn)
    blo = jnp.min(xb3, axis=1)
    bhi = jnp.max(xb3, axis=1)
    gap = jnp.maximum(
        jnp.maximum(grid.tile_lo[None, :, :] - bhi[:, None, :],
                    blo[:, None, :] - grid.tile_hi[None, :, :]),
        0.0,
    )
    # adjusted lower bound in the ROW-SHIFTED space ref minimizes:
    # true shifted value ≥ (lb_sq - slack) - ‖x‖²  (per row)
    lb_adj = jnp.sum(gap * gap, axis=-1) - slack  # (NB, NT)
    order = jnp.argsort(lb_adj, axis=1, stable=True).astype(jnp.int32)
    lbs = jnp.take_along_axis(lb_adj, order, axis=1)

    def block_fn(_, blk):
        xb, xxb, ordr, lb = blk

        def cond(st):
            t, bm, _ = st
            lt = lb[jnp.minimum(t, NT - 1)]
            return (t < NT) & jnp.any(lt - xxb <= bm)

        def body(st):
            t, bm, bj = st
            ys, yy, yv, yo = _tile_slices(grid, ordr[t], T)
            xy = jax.lax.dot_general(xb, ys, (((1,), (1,)), ((), ())),
                                     precision=_ref.HIGHEST)
            sqs = yy[None, :] - 2.0 * xy  # ref._nearest's shifted form
            sqs = jnp.where(yv[None, :], sqs, INF)
            m = jnp.min(sqs, axis=1)
            cols = jnp.where(yv, yo, BIGJ)
            j = jnp.min(jnp.where(sqs == m[:, None], cols[None, :], BIGJ), axis=1)
            better = (m < bm) | ((m == bm) & (j < bj))
            return t + 1, jnp.where(better, m, bm), jnp.where(better, j, bj)

        _, bm, bj = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.full((bn,), INF), jnp.full((bn,), BIGJ)),
        )
        return 0, (bj.astype(jnp.int32), bm)

    _, (js, ms) = jax.lax.scan(block_fn, 0, (xb3, xx2, order, lbs))
    idx = jnp.zeros((B,), jnp.int32).at[qperm].set(js.reshape(B))
    m = jnp.zeros((B,), jnp.float32).at[qperm].set(ms.reshape(B))
    return idx, m
