"""Minimum-spanning-tree machinery for mutual-reachability graphs.

Three MST engines, used by different layers of the system:

* :func:`kruskal_edges` — Kruskal over an *explicit small edge list*.  This
  is the TPU-idiomatic realization of the paper's reduction rule (Eq. 11):
  ``T' = MST(T ∪ E_inserted ∪ E_modified)`` is a pass over ~2n + minPts²
  edges, not over the complete graph.  (Host-side numpy — the edge list is
  tiny and Kruskal is sort-dominated.)

* :func:`boruvka_dense` — vectorized Borůvka over a dense weight matrix or
  a row-block weight callback.  Every round does per-component masked
  argmin — pure array math, no pointers — which is how the dual-tree
  Borůvka of the paper maps onto VPU/MXU hardware.  Supports starting from
  a partial forest (the contraction rule, Eq. 12).

* :func:`boruvka_jax` in this module's jax section — same algorithm in
  jnp under ``jax.jit`` for the offline bubble-clustering pass (L bubbles,
  dense L×L mutual-reachability weights), differentiable-free integer
  union-find carried through ``lax.while_loop``.

* :func:`boruvka_edges_jax` — Borůvka over an *explicit padded edge list*
  under jit: the device realization of the paper's reduction/contraction
  rules (Eqs. 11–12), where each dynamic update is an MST pass over
  ~O(touched · n) candidate edges instead of the dense n×n matrix
  (core.dynamic_jax).  Fixed shapes, masked invalid slots, label
  propagation instead of pointers.

All engines return edges as ``(u, v, w)`` arrays; total weight is the
clustering-hierarchy invariant the tests assert on.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "UnionFind",
    "kruskal_edges",
    "boruvka_dense",
    "mst_total_weight",
    "boruvka_jax",
    "boruvka_shard_jax",
    "boruvka_grid_shard_jax",
    "boruvka_edges_jax",
    "boruvka_strip_jax",
]


class UnionFind:
    """Array-based union-find with path halving + union by size."""

    def __init__(self, n: int):
        self.parent = np.arange(n, dtype=np.int64)
        self.size = np.ones(n, dtype=np.int64)
        self.n_components = n

    def find(self, x: int) -> int:
        p = self.parent
        while p[x] != x:
            p[x] = p[p[x]]  # path halving
            x = p[x]
        return int(x)

    def union(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if self.size[ra] < self.size[rb]:
            ra, rb = rb, ra
        self.parent[rb] = ra
        self.size[ra] += self.size[rb]
        self.n_components -= 1
        return True

    def labels(self) -> np.ndarray:
        """Root label for every element (fully compressed)."""
        p = self.parent
        # iterate to convergence (log-depth after halving)
        while True:
            pp = p[p]
            if np.array_equal(pp, p):
                break
            p = pp
        self.parent = p
        return p.copy()


def kruskal_edges(u, v, w, n, uf: UnionFind | None = None):
    """MST (or forest completion) over an explicit edge list.

    Args:
      u, v: (E,) int endpoints.
      w: (E,) float weights.
      n: number of nodes.
      uf: optionally a pre-seeded union-find (nodes already merged by a
        partial forest — the contraction rule).  Mutated in place.

    Returns:
      (mu, mv, mw): MST edge arrays, in ascending weight order.
    """
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64)
    order = np.argsort(w, kind="stable")
    if uf is None:
        uf = UnionFind(n)
    mu, mv, mw = [], [], []
    for i in order:
        a, b = int(u[i]), int(v[i])
        if a == b:
            continue
        if uf.union(a, b):
            mu.append(a)
            mv.append(b)
            mw.append(float(w[i]))
            if uf.n_components == 1:
                break
    return (
        np.asarray(mu, dtype=np.int64),
        np.asarray(mv, dtype=np.int64),
        np.asarray(mw, dtype=np.float64),
    )


def _component_min_outgoing(W: np.ndarray, labels: np.ndarray):
    """For every component, the lightest edge leaving it (dense W).

    Returns (src, dst, wt) arrays with one candidate per component.
    Vectorized: mask same-component entries to +inf, row-argmin, then a
    segmented min over rows by component label.
    """
    n = W.shape[0]
    masked = np.where(labels[:, None] == labels[None, :], np.inf, W)
    np.fill_diagonal(masked, np.inf)
    row_min_j = np.argmin(masked, axis=1)
    row_min_w = masked[np.arange(n), row_min_j]
    # segmented min over component labels
    uniq, inv = np.unique(labels, return_inverse=True)
    best = np.full(uniq.shape[0], np.inf)
    np.minimum.at(best, inv, row_min_w)
    # pick one row achieving the per-component min
    src = np.full(uniq.shape[0], -1, dtype=np.int64)
    hit = row_min_w == best[inv]
    # last writer wins; any row achieving the min is a valid Borůvka choice
    src[inv[hit]] = np.nonzero(hit)[0]
    ok = (src >= 0) & np.isfinite(best)
    src = src[ok]
    return src, row_min_j[src], row_min_w[src]


def boruvka_dense(W: np.ndarray, forest=None, uf: UnionFind | None = None):
    """Vectorized Borůvka MST over a dense symmetric weight matrix.

    Args:
      W: (n, n) float weights (np.inf on unusable entries is allowed).
      forest: optional (u, v, w) arrays of an existing partial forest whose
        edges are kept (contraction rule, Eq. 12).
      uf: optional union-find pre-seeded consistently with `forest`.

    Returns: (u, v, w) of the completed spanning forest edges *added or
      kept*, i.e. the full MST edge set including the seed forest.
    """
    n = W.shape[0]
    if uf is None:
        uf = UnionFind(n)
    eu, ev, ew = [], [], []
    if forest is not None:
        fu, fv, fw = forest
        for a, b, c in zip(fu, fv, fw):
            uf.union(int(a), int(b))
            eu.append(int(a))
            ev.append(int(b))
            ew.append(float(c))
    while uf.n_components > 1:
        labels = uf.labels()
        src, dst, wt = _component_min_outgoing(W, labels)
        if src.size == 0:
            break  # disconnected graph (inf-masked): return spanning forest
        merged_any = False
        order = np.argsort(wt, kind="stable")
        for i in order:
            a, b = int(src[i]), int(dst[i])
            if uf.union(a, b):
                eu.append(a)
                ev.append(b)
                ew.append(float(wt[i]))
                merged_any = True
        if not merged_any:
            break
    return (
        np.asarray(eu, dtype=np.int64),
        np.asarray(ev, dtype=np.int64),
        np.asarray(ew, dtype=np.float64),
    )


def mst_total_weight(w) -> float:
    return float(np.sum(np.asarray(w, dtype=np.float64)))


# --------------------------------------------------------------------------
# JAX engine — offline bubble clustering pass.
# --------------------------------------------------------------------------

def _boruvka_round_tail(labels, row_w, row_eid, row_j, row_has,
                        eu, ev, ew, valid, n_edges, n, jumps):
    """Back half of one Borůvka round: component aggregation, hooking,
    pointer jumping, edge append.

    Shared verbatim by the dense, grid-pruned, and shard_map front
    halves — they differ only in HOW the per-row (w, canonical-edge-id)
    minima are reduced, so feeding identical (row_w, row_eid, row_j)
    arrays through this one tail is what makes all three engines
    bitwise-interchangeable.  Takes the full (n,) reduction results and
    the round-carried state; returns the updated state tuple.
    """
    import jax
    import jax.numpy as jnp

    INF = jnp.asarray(np.inf, dtype=row_w.dtype)
    BIGID = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    TRASH = n
    iota = jnp.arange(n, dtype=jnp.int32)
    comp_w = jnp.full((n,), INF, dtype=row_w.dtype).at[labels].min(row_w)
    w_hit = row_has & (row_w == comp_w[labels])
    comp_eid = jnp.full((n,), BIGID).at[labels].min(
        jnp.where(w_hit, row_eid, BIGID)
    )
    full_hit = w_hit & (row_eid == comp_eid[labels])
    comp_row = jnp.full((n,), n, dtype=jnp.int32).at[labels].min(
        jnp.where(full_hit, iota, n)
    )  # label -> row index holding the component's chosen edge
    has_edge = comp_row < n
    safe_row = jnp.minimum(comp_row, n - 1)
    comp_u = safe_row
    comp_v = row_j[safe_row].astype(jnp.int32)
    comp_wt = row_w[safe_row]
    comp_tgt = labels[comp_v]
    # mirrored 2-cycle iff both components chose the same canonical edge
    is_mirror = has_edge & (comp_eid[comp_tgt] == comp_eid)
    keep = has_edge & ~(is_mirror & (iota > comp_tgt))
    # hook: parent = target label; mirror pairs root at the lower label
    parent = jnp.where(has_edge, comp_tgt, iota)
    parent = jnp.where(is_mirror & (iota < comp_tgt), iota, parent)

    def jump(m, _):
        return m[m], None

    # unroll: the body is one gather — while-loop dispatch dominates
    parent, _ = jax.lax.scan(jump, parent, None, length=jumps, unroll=4)
    new_labels = parent[labels]
    # append kept edges: slot via cumsum, rejects land in TRASH
    slot = n_edges + jnp.cumsum(keep.astype(jnp.int32)) - 1
    slot = jnp.where(keep, jnp.minimum(slot, n - 1), TRASH)
    eu = eu.at[slot].set(comp_u.astype(jnp.int32))
    ev = ev.at[slot].set(comp_v)
    ew = ew.at[slot].set(comp_wt)
    valid = valid.at[slot].set(keep)
    n_new = jnp.sum(keep, dtype=jnp.int32)
    return new_labels, eu, ev, ew, valid, n_edges + n_new


def boruvka_jax(W, max_rounds: int | None = None):
    """Borůvka MST in pure jnp under jit (dense (n, n) weights).

    Used by the offline phase on the L×L bubble mutual-reachability matrix.
    Union-find is replaced by label propagation (pointer jumping): each
    round every component finds its lightest outgoing edge, components
    merge by relabeling to the min label, repeated until one component.

    Returns (edges_u, edges_v, edges_w, valid_mask) — fixed-size (n+1,)
    buffers whose last slot is a write trash can; rounds that finish early
    leave the remaining slots masked out.  O(n^2) work per round,
    <= log2(n) rounds — dense, VPU-friendly, no host sync inside.

    Tie-break caution: with duplicate weights, per-component argmin choices
    are deterministic (lowest index), so the result is reproducible; total
    weight matches any valid MST (tests assert weight, not edge identity).
    """
    import jax
    import jax.numpy as jnp

    n = W.shape[0]
    if n * n >= np.iinfo(np.int32).max:
        raise ValueError("boruvka_jax supports n <= 46340 (int32 edge ids)")
    if max_rounds is None:
        max_rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = int(np.ceil(np.log2(max(n, 2)))) + 1

    INF = jnp.asarray(np.inf, dtype=W.dtype)
    iota = jnp.arange(n, dtype=jnp.int32)
    BIGID = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    # canonical undirected edge id gives a strict total order on edges,
    # which guarantees the Borůvka hook graph has only 2-cycles even with
    # tied weights (both sides of a mirrored pair pick the *same* edge).
    eid = jnp.minimum(iota[:, None], iota[None, :]) * n + jnp.maximum(
        iota[:, None], iota[None, :]
    )

    def round_fn(state, _):
        labels, eu, ev, ew, valid, n_edges = state
        same = labels[:, None] == labels[None, :]
        masked = jnp.where(same, INF, W)
        masked = masked.at[iota, iota].set(INF)
        # --- per-row min by composite key (w, edge_id) ---
        row_w = jnp.min(masked, axis=1)
        at_min = masked == row_w[:, None]
        row_eid = jnp.min(jnp.where(at_min, eid, BIGID), axis=1)
        row_j = jnp.argmin(jnp.where(at_min & (eid == row_eid[:, None]), eid, BIGID), axis=1)
        row_has = jnp.isfinite(row_w)
        return _boruvka_round_tail(labels, row_w, row_eid, row_j, row_has,
                                   eu, ev, ew, valid, n_edges, n, jumps), None

    labels0 = jnp.arange(n, dtype=jnp.int32)
    eu0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ev0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ew0 = jnp.zeros((n + 1,), dtype=W.dtype)
    valid0 = jnp.zeros((n + 1,), dtype=bool)
    state = (labels0, eu0, ev0, ew0, valid0, jnp.asarray(0, jnp.int32))
    state, _ = jax.lax.scan(round_fn, state, None, length=max_rounds, unroll=2)
    _, eu, ev, ew, valid, _ = state
    return eu[:-1], ev[:-1], ew[:-1], valid[:-1]


def boruvka_shard_jax(W_strip, n: int, axis: str, max_rounds: int | None = None):
    """Borůvka MST over a row-block-sharded dense weight matrix.

    Call INSIDE ``shard_map``: ``W_strip`` is this shard's contiguous
    (n/k, n) row strip of the full mutual-reachability matrix (full
    columns), ``axis`` the mesh axis name the rows are blocked over.

    Per round each shard reduces its own rows' composite
    (w, canonical-edge-id) minima — bitwise the values the dense kernel
    computes for those rows, because a row's min only ever reads that
    row — then one tiled ``all_gather`` per array reassembles the (n,)
    reduction results in global row order and the component aggregation
    / hooking / pointer-jumping tail runs replicated on every shard
    (``_boruvka_round_tail``, the dense code verbatim on identical
    inputs).  Outputs are therefore replicated and bitwise-identical to
    ``boruvka_jax(W)`` on ANY mesh shape, k = 1 included.
    """
    import jax
    import jax.numpy as jnp

    m = W_strip.shape[0]
    if n * n >= np.iinfo(np.int32).max:
        raise ValueError("boruvka_shard_jax supports n <= 46340 (int32 edge ids)")
    if max_rounds is None:
        max_rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = int(np.ceil(np.log2(max(n, 2)))) + 1

    INF = jnp.asarray(np.inf, dtype=W_strip.dtype)
    iota = jnp.arange(n, dtype=jnp.int32)
    BIGID = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    rows = (jax.lax.axis_index(axis).astype(jnp.int32) * m
            + jnp.arange(m, dtype=jnp.int32))
    eid = jnp.minimum(rows[:, None], iota[None, :]) * n + jnp.maximum(
        rows[:, None], iota[None, :]
    )

    def round_fn(state, _):
        labels, eu, ev, ew, valid, n_edges = state
        same = labels[rows][:, None] == labels[None, :]
        masked = jnp.where(same, INF, W_strip)
        masked = jnp.where(rows[:, None] == iota[None, :], INF, masked)
        # --- per-row min by composite key (w, edge_id), local rows ---
        rw = jnp.min(masked, axis=1)
        at_min = masked == rw[:, None]
        re = jnp.min(jnp.where(at_min, eid, BIGID), axis=1)
        rj = jnp.argmin(
            jnp.where(at_min & (eid == re[:, None]), eid, BIGID), axis=1
        ).astype(jnp.int32)
        row_w = jax.lax.all_gather(rw, axis, tiled=True)
        row_eid = jax.lax.all_gather(re, axis, tiled=True)
        row_j = jax.lax.all_gather(rj, axis, tiled=True)
        row_has = jnp.isfinite(row_w)
        return _boruvka_round_tail(labels, row_w, row_eid, row_j, row_has,
                                   eu, ev, ew, valid, n_edges, n, jumps), None

    labels0 = jnp.arange(n, dtype=jnp.int32)
    eu0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ev0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ew0 = jnp.zeros((n + 1,), dtype=W_strip.dtype)
    valid0 = jnp.zeros((n + 1,), dtype=bool)
    state = (labels0, eu0, ev0, ew0, valid0, jnp.asarray(0, jnp.int32))
    state, _ = jax.lax.scan(round_fn, state, None, length=max_rounds, unroll=2)
    _, eu, ev, ew, valid, _ = state
    return eu[:-1], ev[:-1], ew[:-1], valid[:-1]


def _grid_round_minima(grid, cd, labels, hopeless, views, NT, T, n, bn):
    """Front half of one grid-pruned Borůvka round: scan the given block
    views (all blocks, or one shard's contiguous slice) and return the
    stacked per-block composite minima ``(bws, bes)``.

    Per-block results depend only on that block's rows and the (static)
    grid, never on which other blocks ride the same scan — that is what
    lets ``boruvka_grid_shard_jax`` split the views across shards and
    reassemble bitwise-identical (row_w, row_eid) arrays.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.grid import _tile_slices

    INF = jnp.float32(jnp.inf)
    BIGID = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)

    def block_fn(carry, blk):
        xb, xx, xv, xo, ordr, lbs = blk
        lab_r = labels[xo]
        cd_r = cd[xo]
        alive = xv & ~hopeless[xo]

        def cond(st):
            t, bw, _ = st
            thr = jnp.maximum(lbs[jnp.minimum(t, NT - 1)], cd_r)
            return (t < NT) & jnp.any(alive & (thr <= bw))

        def body(st):
            t, bw, be = st
            ys, yy, yv, yo = _tile_slices(grid, ordr[t], T)
            xy = jax.lax.dot_general(xb, ys, (((1,), (1,)), ((), ())),
                                     precision=jax.lax.Precision.HIGHEST)
            dm = jnp.sqrt(
                jnp.maximum((xx[:, None] + yy[None, :]) - 2.0 * xy, 0.0)
            )
            w = jnp.maximum(dm, jnp.maximum(cd_r[:, None], cd[yo][None, :]))
            ok = xv[:, None] & yv[None, :] & (
                labels[yo][None, :] != lab_r[:, None]
            )
            w = jnp.where(ok, w, INF)
            eid = jnp.minimum(xo[:, None], yo[None, :]) * n + jnp.maximum(
                xo[:, None], yo[None, :]
            )
            eid = jnp.where(ok, eid, BIGID)
            rw = jnp.min(w, axis=1)
            re = jnp.min(jnp.where(w == rw[:, None], eid, BIGID), axis=1)
            better = (rw < bw) | ((rw == bw) & (re < be))
            return (
                t + 1,
                jnp.where(better, rw, bw),
                jnp.where(better, re, be),
            )

        _, bw, be = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.full((bn,), INF), jnp.full((bn,), BIGID)),
        )
        return carry, (bw, be)

    _, (bws, bes) = jax.lax.scan(block_fn, 0, views)
    return bws, bes


def boruvka_grid_jax(grid, cd, max_rounds: int | None = None,
                     block: int = 64):
    """Borůvka MST with grid-pruned candidate search (spatial_index path).

    Bitwise-identical output to ``boruvka_jax(W)`` run on the dense
    mutual-reachability matrix built from the same reps and core
    distances (W = max(d, max(cd_i, cd_j)), pad rows +inf) — but each
    round finds every row's lightest outgoing edge by scanning candidate
    tiles in ascending lower-bound order instead of the full (n, n)
    matrix.  Three properties pin the parity:

      * tile distances use the exact dense arithmetic
        (``(xx + yy) - 2·dot`` over contiguous sorted rows, then
        ``sqrt``/``max`` with the core distances), so every candidate
        weight has the same f32 bits as the matrix entry;
      * per-row minima carry the composite (w, canonical edge id) key,
        the same strict total order ``boruvka_jax`` reduces with, and a
        tile is abandoned only when ``max(tile_lb, cd_row) > best_w``
        STRICTLY — ties are always visited, so equal-weight candidates
        with smaller edge ids are never lost;
      * the component aggregation / hooking / pointer-jumping rounds are
        the dense implementation verbatim, fed the identical
        (row_w, row_eid) reduction results.

    Rows whose component already swallowed every valid row are "hopeless"
    (no outgoing edge can exist) and short-circuit their tile scans —
    that is what keeps post-convergence rounds cheap.  When pruning
    cannot help (few huge components), the while_loop degrades to
    visiting all tiles, which IS the dense strip sweep — the fallback is
    inherent, not a separate code path.

    Args:
      grid: ``repro.kernels.grid.GridIndex`` over the padded rep table
        (invalid rows = size-bucket padding, excluded from candidates —
        they stay isolated, exactly like the dense path's +inf rows).
      cd: (n,) f32 core distances in ORIGINAL row order (the grid path
        leaves don't-care values on invalid rows; they are never read).
      max_rounds: scan length; None = the dense default.
      block: query rows per block (must divide n; pow-2 sizes do).

    Returns:
      (edges_u, edges_v, edges_w, valid_mask) — same fixed-size (n,)
      buffers as ``boruvka_jax``.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.grid import _block_views

    n = grid.pts.shape[0]
    if n * n >= np.iinfo(np.int32).max:
        raise ValueError("boruvka_grid_jax supports n <= 46340 (int32 edge ids)")
    if max_rounds is None:
        max_rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = int(np.ceil(np.log2(max(n, 2)))) + 1

    NT = grid.tile_lo.shape[0]
    T = n // NT
    bn = min(block, n)
    iota = jnp.arange(n, dtype=jnp.int32)
    cd = jnp.asarray(cd, jnp.float32)

    # block views + per-block tile visit orders never change across
    # rounds (the grid is static); compute once outside the scan
    views = _block_views(grid, bn)
    valid_orig = jnp.zeros((n,), bool).at[grid.orig].set(grid.valid)
    total_valid = jnp.sum(grid.valid, dtype=jnp.int32)

    def round_fn(state, _):
        labels, eu, ev, ew, valid, n_edges = state
        # a row whose component contains every valid row has no outgoing
        # edge; force it done instead of scanning all tiles for nothing
        cnt = jnp.zeros((n,), jnp.int32).at[labels].add(
            valid_orig.astype(jnp.int32)
        )
        hopeless = cnt[labels] >= total_valid
        bws, bes = _grid_round_minima(
            grid, cd, labels, hopeless, views, NT, T, n, bn
        )
        row_w = jnp.zeros((n,), jnp.float32).at[grid.orig].set(bws.reshape(n))
        row_eid = jnp.zeros((n,), jnp.int32).at[grid.orig].set(bes.reshape(n))
        # recover the chosen column from the canonical edge id (unique at
        # the (w, eid) minimum); garbage on no-edge rows is gated below,
        # clamp only keeps the label gather in range
        lo_e = row_eid // n
        hi_e = row_eid - lo_e * n
        row_j = jnp.clip(jnp.where(lo_e == iota, hi_e, lo_e), 0, n - 1)
        row_has = jnp.isfinite(row_w)
        # --- component aggregation: boruvka_jax verbatim ---
        return _boruvka_round_tail(labels, row_w, row_eid, row_j, row_has,
                                   eu, ev, ew, valid, n_edges, n, jumps), None

    labels0 = jnp.arange(n, dtype=jnp.int32)
    eu0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ev0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ew0 = jnp.zeros((n + 1,), dtype=jnp.float32)
    valid0 = jnp.zeros((n + 1,), dtype=bool)
    state = (labels0, eu0, ev0, ew0, valid0, jnp.asarray(0, jnp.int32))
    state, _ = jax.lax.scan(round_fn, state, None, length=max_rounds, unroll=2)
    _, eu, ev, ew, valid, _ = state
    return eu[:-1], ev[:-1], ew[:-1], valid[:-1]


def boruvka_grid_shard_jax(grid, cd, axis: str, k: int,
                           max_rounds: int | None = None, block: int = 64):
    """Grid-pruned Borůvka with the per-block candidate scans sharded.

    Call INSIDE ``shard_map`` with every input replicated (the grid
    itself is small); ``k`` is the static size of mesh axis ``axis``.
    The query-block axis of the (static) block views is what gets
    sharded: shard i scans its contiguous ``ceil(NB/k)`` slice of the
    blocks, one tiled ``all_gather`` per round reassembles the block
    minima in global block order, and the scatter + component tail run
    replicated — ``boruvka_grid_jax`` verbatim on identical inputs.

    When the axis does not divide the block count (e.g. 3 devices over
    a pow-2 table) the trailing shards re-scan the last block and the
    gathered tail is dropped — a duplicate-tail lift, same as
    ``grid_core_distances_shard``.  Per-block minima don't depend on
    the blocking (kernels/grid.py's exactness contract), so outputs
    are bitwise ``boruvka_grid_jax`` — and therefore bitwise
    ``boruvka_jax`` on the corresponding dense matrix — on any mesh.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.grid import _block_views

    n = grid.pts.shape[0]
    if n * n >= np.iinfo(np.int32).max:
        raise ValueError("boruvka_grid_shard_jax supports n <= 46340 (int32 edge ids)")
    if max_rounds is None:
        max_rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = int(np.ceil(np.log2(max(n, 2)))) + 1

    NT = grid.tile_lo.shape[0]
    T = n // NT
    bn = min(block, n)
    iota = jnp.arange(n, dtype=jnp.int32)
    cd = jnp.asarray(cd, jnp.float32)

    views = _block_views(grid, bn)
    NB = views[0].shape[0]
    NBk = -(-NB // k)  # ceil: trailing shards duplicate the last block
    shard = jax.lax.axis_index(axis)
    blk_ids = jnp.minimum(
        shard * NBk + jnp.arange(NBk, dtype=jnp.int32), NB - 1)
    views_l = jax.tree_util.tree_map(lambda a: a[blk_ids], views)
    valid_orig = jnp.zeros((n,), bool).at[grid.orig].set(grid.valid)
    total_valid = jnp.sum(grid.valid, dtype=jnp.int32)

    def round_fn(state, _):
        labels, eu, ev, ew, valid, n_edges = state
        cnt = jnp.zeros((n,), jnp.int32).at[labels].add(
            valid_orig.astype(jnp.int32)
        )
        hopeless = cnt[labels] >= total_valid
        bws_l, bes_l = _grid_round_minima(
            grid, cd, labels, hopeless, views_l, NT, T, n, bn
        )
        bws = jax.lax.all_gather(bws_l, axis, tiled=True)[:NB]
        bes = jax.lax.all_gather(bes_l, axis, tiled=True)[:NB]
        row_w = jnp.zeros((n,), jnp.float32).at[grid.orig].set(bws.reshape(n))
        row_eid = jnp.zeros((n,), jnp.int32).at[grid.orig].set(bes.reshape(n))
        lo_e = row_eid // n
        hi_e = row_eid - lo_e * n
        row_j = jnp.clip(jnp.where(lo_e == iota, hi_e, lo_e), 0, n - 1)
        row_has = jnp.isfinite(row_w)
        return _boruvka_round_tail(labels, row_w, row_eid, row_j, row_has,
                                   eu, ev, ew, valid, n_edges, n, jumps), None

    labels0 = jnp.arange(n, dtype=jnp.int32)
    eu0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ev0 = jnp.zeros((n + 1,), dtype=jnp.int32)
    ew0 = jnp.zeros((n + 1,), dtype=jnp.float32)
    valid0 = jnp.zeros((n + 1,), dtype=bool)
    state = (labels0, eu0, ev0, ew0, valid0, jnp.asarray(0, jnp.int32))
    state, _ = jax.lax.scan(round_fn, state, None, length=max_rounds, unroll=2)
    _, eu, ev, ew, valid, _ = state
    return eu[:-1], ev[:-1], ew[:-1], valid[:-1]


def boruvka_edges_jax(eu, ev, ew, valid, n: int):
    """Borůvka minimum spanning forest over an explicit padded edge list.

    The device engine behind the dynamic update rules (core.dynamic_jax):
    Eq. 11 rebuilds the MST from ``T ∪ E_inserted ∪ E_modified`` and
    Eq. 12 completes the survivor forest from a crossing-edge strip —
    both are MST passes over an *explicit candidate list* of
    O(touched · n) edges, far smaller than the dense n×n matrix
    ``boruvka_jax`` consumes.

    Args:
      eu, ev: (E,) int32 endpoint slot ids in [0, n).
      ew: (E,) float weights (selection key; +inf or masked slots never
        chosen).  Mandatory edges (a kept forest) can be forced in by
        giving them a weight below every real weight (e.g. -1 for
        mutual-reachability weights ≥ 0): an acyclic mandatory set is
        then always selected, and the remainder is the exact minimum
        completion.
      valid: (E,) bool — False rows are padding, never selected.
      n: slot-space size (static).  Components are label values in
        [0, n); every node starts as its own singleton, nodes with no
        valid incident edge stay isolated (spanning *forest*).

    Returns:
      (sel_idx, sel_valid, labels): (n,) int32 indices into the edge
      list of the chosen edges (caller gathers endpoints/payloads),
      (n,) bool validity (a connected m-node input yields m-1 True
      slots), and (n,) int32 final component labels.

    Ties break by edge index — a strict total order on (w, index), so
    the hook graph has only 2-cycles (same argument as ``boruvka_jax``)
    and the forest is deterministic.
    """
    import jax
    import jax.numpy as jnp

    E = eu.shape[0]
    rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = rounds
    BIG = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    INF = jnp.asarray(np.inf, dtype=ew.dtype)
    iota = jnp.arange(n, dtype=jnp.int32)
    idx_e = jnp.arange(E, dtype=jnp.int32)
    eu = eu.astype(jnp.int32)
    ev = ev.astype(jnp.int32)

    def round_fn(state, _):
        labels, out_idx, out_valid, n_edges = state
        lu, lv = labels[eu], labels[ev]
        active = valid & (lu != lv)
        w_act = jnp.where(active, ew, INF)
        # per-component min weight, scattering each edge to BOTH sides
        comp_w = jnp.full((n,), INF, ew.dtype).at[lu].min(w_act).at[lv].min(w_act)
        hit_u = active & (ew == comp_w[lu])
        hit_v = active & (ew == comp_w[lv])
        comp_e = (
            jnp.full((n,), BIG)
            .at[lu].min(jnp.where(hit_u, idx_e, BIG))
            .at[lv].min(jnp.where(hit_v, idx_e, BIG))
        )
        has = comp_e < BIG
        e = jnp.minimum(comp_e, max(E - 1, 0))
        # component c's chosen edge joins labels (a, b), one of which is c
        a, b = labels[eu[e]], labels[ev[e]]
        tgt = jnp.where(a == iota, b, a)
        # mirrored 2-cycle iff both components chose the same edge index
        mirror = has & (comp_e[tgt] == comp_e)
        keep = has & ~(mirror & (iota > tgt))
        parent = jnp.where(has, tgt, iota)
        parent = jnp.where(mirror & (iota < tgt), iota, parent)

        def jump(m, _):
            return m[m], None

        parent, _ = jax.lax.scan(jump, parent, None, length=jumps, unroll=4)
        labels = parent[labels]
        slot = n_edges + jnp.cumsum(keep.astype(jnp.int32)) - 1
        slot = jnp.where(keep, jnp.minimum(slot, n - 1), n)  # n = trash
        out_idx = out_idx.at[slot].set(e)
        out_valid = out_valid.at[slot].set(keep)
        return (labels, out_idx, out_valid, n_edges + jnp.sum(keep, dtype=jnp.int32)), None

    state = (
        iota,
        jnp.zeros((n + 1,), jnp.int32),
        jnp.zeros((n + 1,), dtype=bool),
        jnp.asarray(0, jnp.int32),
    )
    state, _ = jax.lax.scan(round_fn, state, None, length=rounds, unroll=2)
    labels, out_idx, out_valid, _ = state
    return out_idx[:-1], out_valid[:-1], labels


def boruvka_strip_jax(eu, ev, ew, evalid, sids, SW, smask, n: int):
    """Borůvka MSF over an explicit edge list PLUS dense row strips.

    The workhorse of the batched insert rule (core.dynamic_jax): the
    candidate set ``T ∪ U×V`` — old tree edges as a (E,) list, the
    touched rows U as a dense (|U|, n) strip — would cost O(|U|·n)
    *scattered* elements per round as a flat list, which is the CPU
    bottleneck.  Here the strip's per-component minima are computed with
    dense masked row/column reductions (vectorized, cheap) and only the
    (|U|,)/(n,)-sized results are scattered; per-round scatter volume
    drops to O(E + n).

    Args:
      eu, ev, ew, evalid: (E,) explicit edges (masked slots inert).
      sids: (U,) int32 node id of each strip row.
      SW: (U, n) strip weights (row u's edge to every node).
      smask: (U, n) bool — usable strip entries (self/dead cols False).
      n: node-slot count (static).

    Returns:
      (pay, pay_valid, labels): (n,) payload of each selected edge —
      ``pay < E`` is an index into the edge list, ``pay >= E`` encodes
      strip entry ``(pay - E) = row * n + col`` — plus the final
      component labels.  Ties break on the canonical undirected pair id
      (min·n+max), so a pair duplicated between the list and the strip
      resolves identically on both sides of a mirror.
    """
    import jax
    import jax.numpy as jnp

    E = eu.shape[0]
    U = SW.shape[0]
    rounds = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    jumps = rounds
    BIG = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    INF = jnp.asarray(np.inf, dtype=SW.dtype)
    iota = jnp.arange(n, dtype=jnp.int32)
    eu = eu.astype(jnp.int32)
    ev = ev.astype(jnp.int32)
    sids = sids.astype(jnp.int32)
    eid_tree = jnp.minimum(eu, ev) * n + jnp.maximum(eu, ev)
    su, sv = sids[:, None], iota[None, :]
    eid_strip = jnp.minimum(su, sv) * n + jnp.maximum(su, sv)
    pay_tree = jnp.arange(E, dtype=jnp.int32)
    pay_strip = E + jnp.arange(U * n, dtype=jnp.int32).reshape(U, n)

    def round_fn(state, _):
        lab, out_pay, out_ok, n_edges = state
        lu, lv = lab[eu], lab[ev]
        eact = evalid & (lu != lv)
        ewa = jnp.where(eact, ew, INF)
        slab = lab[sids]
        act = smask & (slab[:, None] != lab[None, :])
        SWa = jnp.where(act, SW, INF)
        rmin = jnp.min(SWa, axis=1)  # (U,) best outgoing per strip row
        cmin = jnp.min(SWa, axis=0)  # (n,) best incoming per column
        comp_w = (
            jnp.full((n,), INF, SW.dtype)
            .at[lu].min(ewa).at[lv].min(ewa)
            .at[slab].min(rmin).at[lab].min(cmin)
        )
        # tie-break pass: min canonical pair id among weight-achievers
        e_hit_u = eact & (ew == comp_w[lu])
        e_hit_v = eact & (ew == comp_w[lv])
        s_hit_r = act & (SW == comp_w[slab][:, None])
        s_hit_c = act & (SW == comp_w[lab][None, :])
        reid = jnp.min(jnp.where(s_hit_r, eid_strip, BIG), axis=1)
        ceid = jnp.min(jnp.where(s_hit_c, eid_strip, BIG), axis=0)
        comp_eid = (
            jnp.full((n,), BIG)
            .at[lu].min(jnp.where(e_hit_u, eid_tree, BIG))
            .at[lv].min(jnp.where(e_hit_v, eid_tree, BIG))
            .at[slab].min(reid).at[lab].min(ceid)
        )
        # payload pass: an actual edge matching (comp_w, comp_eid)
        rpay = jnp.min(
            jnp.where(s_hit_r & (eid_strip == comp_eid[slab][:, None]), pay_strip, BIG),
            axis=1,
        )
        cpay = jnp.min(
            jnp.where(s_hit_c & (eid_strip == comp_eid[lab][None, :]), pay_strip, BIG),
            axis=0,
        )
        comp_pay = (
            jnp.full((n,), BIG)
            .at[lu].min(jnp.where(e_hit_u & (eid_tree == comp_eid[lu]), pay_tree, BIG))
            .at[lv].min(jnp.where(e_hit_v & (eid_tree == comp_eid[lv]), pay_tree, BIG))
            .at[slab].min(rpay).at[lab].min(cpay)
        )
        has = comp_eid < BIG
        pay = jnp.minimum(comp_pay, E + U * n - 1)
        is_strip = pay >= E
        t_idx = jnp.minimum(pay, max(E - 1, 0))
        s_flat = jnp.maximum(pay - E, 0)
        pu = jnp.where(is_strip, sids[s_flat // n], eu[t_idx])
        pv = jnp.where(is_strip, (s_flat % n).astype(jnp.int32), ev[t_idx])
        a, b = lab[pu], lab[pv]
        tgt = jnp.where(a == iota, b, a)
        mirror = has & (comp_eid[tgt] == comp_eid)
        keep = has & ~(mirror & (iota > tgt))
        parent = jnp.where(has, tgt, iota)
        parent = jnp.where(mirror & (iota < tgt), iota, parent)

        def jump(m, _):
            return m[m], None

        parent, _ = jax.lax.scan(jump, parent, None, length=jumps, unroll=4)
        lab = parent[lab]
        slot = n_edges + jnp.cumsum(keep.astype(jnp.int32)) - 1
        slot = jnp.where(keep, jnp.minimum(slot, n - 1), n)
        out_pay = out_pay.at[slot].set(pay)
        out_ok = out_ok.at[slot].set(keep)
        return (lab, out_pay, out_ok, n_edges + jnp.sum(keep, dtype=jnp.int32)), None

    state = (
        iota,
        jnp.zeros((n + 1,), jnp.int32),
        jnp.zeros((n + 1,), dtype=bool),
        jnp.asarray(0, jnp.int32),
    )
    state, _ = jax.lax.scan(round_fn, state, None, length=rounds, unroll=2)
    labels, out_pay, out_ok, _ = state
    return out_pay[:-1], out_ok[:-1], labels
