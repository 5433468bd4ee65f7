"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * shape padding: callers pass arbitrary (n, d); tiles need row counts
    that are block multiples and a lane-aligned feature axis.  Padded rows
    sit at +inf distance (never selected); padded features are zeros
    (distance-neutral).
  * platform policy: Pallas runs compiled on TPU and in interpret mode on
    CPU (`interpret=True` executes the kernel body in Python — the
    validation mode this container uses).  Set ``REPRO_FORCE_REF=1`` to
    bypass Pallas entirely (pure-jnp reference path).
  * composition: `bubble_mutual_reachability` chains the tiled Eq. 6
    core-distance strip kernel (jnp sort/cumsum scan on the reference
    path) into the fused mutual-reach tile kernel; `offline_recluster`
    extends the chain through Borůvka and the device hierarchy
    (core.hierarchy_jax) so one jit'd call returns flat labels +
    stabilities with no host numpy between the stages.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span

from . import assign as _assign_k
from . import bubble_cd as _bcd_k
from . import hierarchy as _hier_k
from . import knn as _knn_k
from . import mutual_reach as _mr_k
from . import pairwise as _pw_k
from . import ref as _ref

__all__ = [
    "pairwise_sqdist",
    "mutual_reachability",
    "knn",
    "core_distances",
    "assign",
    "bubble_core_distances",
    "bubble_mutual_reachability",
    "bubble_table",
    "OfflineClusterResult",
    "offline_recluster",
    "offline_recluster_from_table",
    "offline_recluster_from_device_table",
    "incremental_update",
    "incremental_recluster",
    "ClusterBackend",
    "get_backend",
]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_impl(use_ref: bool) -> str:
    """Name of what serves a dense kernel call: ``"jnp"`` (the reference
    path), ``"pallas"`` (compiled Mosaic kernels, TPU) or
    ``"pallas-interpret"`` (the kernel body run by the interpreter —
    every non-TPU backend)."""
    if use_ref:
        return "jnp"
    return "pallas-interpret" if _interpret() else "pallas"


def _use_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


def _resolve_ref(use_ref: bool | None) -> bool:
    """Per-call override beats the env var; None = env-var policy."""
    return _use_ref() if use_ref is None else bool(use_ref)


def _pad_rows(a: jax.Array, mult: int, fill: float = 0.0) -> jax.Array:
    n = a.shape[0]
    p = (-n) % mult
    if p == 0:
        return a
    pad = [(0, p)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=fill)


def _pad_feats(a: jax.Array, mult: int = 128) -> jax.Array:
    """Zero-pad the feature axis to a multiple of ``mult`` (distance-neutral)."""
    d = a.shape[1]
    p = (-d) % mult
    if p == 0:
        return a
    return jnp.pad(a, [(0, 0), (0, p)])


def pairwise_sqdist(x, y, bn: int | None = None, bm: int | None = None, use_ref: bool | None = None):
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if _resolve_ref(use_ref):
        return _ref.pairwise_sqdist(x, y)
    n, m = x.shape[0], y.shape[0]
    bn = bn or min(_pw_k.DEFAULT_BN, max(8, 1 << (max(n - 1, 1)).bit_length()))
    bm = bm or min(_pw_k.DEFAULT_BM, max(8, 1 << (max(m - 1, 1)).bit_length()))
    xp = _pad_feats(_pad_rows(x, bn))
    yp = _pad_feats(_pad_rows(y, bm))
    out = _pw_k.pairwise_sqdist(xp, yp, bn=bn, bm=bm, interpret=_interpret())
    return out[:n, :m]


def mutual_reachability(x, y, cd_x, cd_y, zero_diag: bool = True, use_ref: bool | None = None):
    x, y = jnp.asarray(x), jnp.asarray(y)
    cd_x, cd_y = jnp.asarray(cd_x), jnp.asarray(cd_y)
    if _resolve_ref(use_ref):
        return _ref.mutual_reachability(x, y, cd_x, cd_y, zero_diag=zero_diag)
    n, m = x.shape[0], y.shape[0]
    bn = min(_mr_k.DEFAULT_BN, max(8, 1 << (max(n - 1, 1)).bit_length()))
    bm = min(_mr_k.DEFAULT_BM, max(8, 1 << (max(m - 1, 1)).bit_length()))
    xp = _pad_feats(_pad_rows(x, bn))
    yp = _pad_feats(_pad_rows(y, bm))
    cdxp = _pad_rows(cd_x, bn)
    cdyp = _pad_rows(cd_y, bm)
    out = _mr_k.mutual_reachability(
        xp, yp, cdxp, cdyp, bn=bn, bm=bm, zero_diag=zero_diag, interpret=_interpret()
    )
    return out[:n, :m]


# Above this reference-set size the single-tile VMEM strategy stops being
# appropriate; fall back to a two-stage jnp top-k over kernel distance tiles.
_KNN_VMEM_LIMIT = 1 << 14


def knn(x, y, k: int, use_ref: bool | None = None):
    """k nearest distances (ascending) and indices of y for each x row.

    Rows of x that also appear in y return themselves at distance 0 —
    callers exclude self-matches (hdbscan's convention counts the point
    itself inside minPts, so this is what core_distances wants).
    """
    x, y = jnp.asarray(x), jnp.asarray(y)
    n, m = x.shape[0], y.shape[0]
    k = min(k, m)
    if _resolve_ref(use_ref) or m > _KNN_VMEM_LIMIT:
        return _ref.knn(x, y, k)
    bn = min(_knn_k.DEFAULT_BN, max(8, 1 << (max(n - 1, 1)).bit_length()))
    xp = _pad_feats(_pad_rows(x, bn))
    # pad reference rows at +inf distance: zero features collide with real
    # points at the origin, so pad then mask via a giant coordinate
    p = (-m) % 8
    if p:
        far = jnp.full((p, y.shape[1]), 1e18, dtype=y.dtype)
        yp = jnp.concatenate([y, far], axis=0)
    else:
        yp = y
    yp = _pad_feats(yp)
    dists, idx = _knn_k.knn(xp, yp, k, bn=bn, interpret=_interpret())
    return dists[:n], idx[:n]


def core_distances(x, min_pts: int):
    """cd(p) per Def. 1 (self-inclusive convention)."""
    d, _ = knn(x, x, min_pts)
    return d[:, min(min_pts, x.shape[0]) - 1]


def _pow2_rows(n: int) -> int:
    return max(8, 1 << (max(n - 1, 1)).bit_length())


def assign(
    x, reps, use_ref: bool | None = None, with_dist: bool = False,
    spatial_index: bool = False, valid=None,
):
    """Nearest-representative index per row; with ``with_dist=True`` also
    the euclidean distance to it (one fused pass — the serve plane's
    query path wants both without a second gather).

    ``spatial_index=True`` routes through the grid-pruned engine
    (kernels.grid): index-exact against the dense path, sub-quadratic in
    the rep count.  ``valid`` (spatial only) masks rep rows out of the
    candidate set entirely — the dense path instead relies on dead rows
    being parked far away (``_PAD_COORD``), so the two differ only for
    queries outside the sane data envelope (see kernels/grid.py).
    """
    x, reps = jnp.asarray(x), jnp.asarray(reps)
    if spatial_index:
        from repro.kernels.grid import build_grid, grid_assign

        B, d = x.shape
        L = reps.shape[0]
        reps = reps.astype(jnp.float32)
        if valid is None:
            valid = jnp.ones((L,), bool)
        Lp = _pow2_rows(L)
        if Lp != L:
            far = jnp.full((Lp - L, d), _PAD_COORD, dtype=jnp.float32)
            reps = jnp.concatenate([reps, far], axis=0)
            valid = jnp.concatenate([valid, jnp.zeros((Lp - L,), bool)])
        Bp = _pow2_rows(B)
        xq = _pad_rows(x.astype(jnp.float32), Bp)
        g = build_grid(reps, valid)
        idx, m = grid_assign(g, xq)
        # no valid candidate at all (empty table) degrades to row L-1 so
        # gathers stay in range; dense lands on a parked row there too
        idx = jnp.minimum(idx[:B], L - 1)
        if not with_dist:
            return idx
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
        return idx, jnp.sqrt(jnp.maximum(xx + m[:B], 0.0))
    if _resolve_ref(use_ref):
        return _ref.assign_with_dist(x, reps) if with_dist else _ref.assign(x, reps)
    n, L = x.shape[0], reps.shape[0]
    bn = min(_assign_k.DEFAULT_BN, _pow2_rows(n))
    xf = x.astype(jnp.float32)
    xp = _pad_feats(_pad_rows(xf, bn), 8)
    # the rep axis pads to its power-of-two bucket, so a growing table
    # recompiles per bucket; pad rows sit far away and never win
    Lp = _pow2_rows(L)
    rp = reps.astype(jnp.float32)
    if Lp != L:
        far = jnp.full((Lp - L, rp.shape[1]), 1e18, dtype=jnp.float32)
        rp = jnp.concatenate([rp, far], axis=0)
    rp = _pad_feats(rp, 8)
    idx, m = _assign_k.assign(
        xp, rp, bn=bn, bl=min(_assign_k.DEFAULT_BL, Lp), interpret=_interpret())
    idx = idx[:n]
    if not with_dist:
        return idx
    xx = jnp.sum(xf * xf, axis=-1)
    return idx, jnp.sqrt(jnp.maximum(xx + m[:n], 0.0))


@functools.partial(jax.jit, static_argnames=("min_pts",))
def _bubble_cd(rep, n_b, extent, min_pts: int):
    return _ref.bubble_core_distances(rep, n_b, extent, min_pts, rep.shape[1])


# Largest bubble table the Eq. 6 strip kernel keeps resident in VMEM
# (its (bn, L) strips and the transposed (d, L) table; the v5e compile
# rehearsal holds L = 32768 at d = 16); larger tables take the jnp scan.
# `bcd_impl` names the choice so callers record it.
_BCD_VMEM_LIMIT = 1 << 15


def bcd_impl(L: int, use_ref: bool) -> str:
    """What serves Eq. 6 core distances over an L-row table on the dense
    path (see `kernel_impl`)."""
    return kernel_impl(use_ref or L > _BCD_VMEM_LIMIT)


def bubble_core_distances(
    rep, n_b, extent, min_pts: int, use_ref: bool | None = None,
    spatial_index: bool = False,
):
    """Eq. 6 bubble core distances: tiled Pallas strip kernel (blockwise
    over bubble rows, no L×L materialization) or the jnp sort+cumsum
    reference under the backend switch.  ``spatial_index=True`` instead
    routes through the grid-pruned engine (kernels.grid) — bit-identical
    to the jnp reference for power-of-two dims, sub-quadratic in L."""
    rep = jnp.asarray(rep)
    n_b = jnp.asarray(n_b)
    extent = jnp.asarray(extent)
    L, d = rep.shape
    try:
        # Eq. 6's scan can never reach min_pts beyond the represented
        # mass (knn's k=min(k,m) rule; the strip kernel's extraction
        # prefix relies on it).  Jitted callers see tracers (the int()
        # below raises) and must pre-clamp — offline_recluster_from_table
        # does.  ConcretizationTypeError is how a tracer shows here.
        min_pts = max(1, min(int(min_pts), int(jnp.sum(n_b))))
    except jax.errors.ConcretizationTypeError:
        pass
    if spatial_index:
        from repro.kernels.grid import build_grid, grid_core_distances

        Lp = _pow2_rows(L)
        repp = rep.astype(jnp.float32)
        nbp = n_b.astype(jnp.float32)
        extp = extent.astype(jnp.float32)
        if Lp != L:
            far = jnp.full((Lp - L, d), _PAD_COORD, dtype=jnp.float32)
            repp = jnp.concatenate([repp, far], axis=0)
            nbp = _pad_rows(nbp, Lp)
            extp = _pad_rows(extp, Lp)
        g = build_grid(repp, jnp.arange(Lp) < L)
        return grid_core_distances(g, nbp, extp, int(min_pts), d)[:L]
    if bcd_impl(L, _resolve_ref(use_ref)) == "jnp":
        return _bubble_cd(rep, n_b, extent, min_pts)
    # shrink blocks toward tiny tables, floor at the f32 sublane count
    bn = max(8, min(_bcd_k.DEFAULT_BN, 1 << (max(L - 1, 1)).bit_length()))
    p = (-L) % bn
    if p:
        # pad rows far away with zero mass: never extracted before the
        # scan crosses min_pts, never the crossing bubble
        far = jnp.full((p, d), _PAD_COORD, dtype=rep.dtype)
        repp = jnp.concatenate([rep, far], axis=0)
        nbp = jnp.concatenate([n_b, jnp.zeros((p,), n_b.dtype)])
        extp = jnp.concatenate([extent, jnp.zeros((p,), extent.dtype)])
    else:
        repp, nbp, extp = rep, n_b, extent
    cd = _bcd_k.bubble_core_distances(
        _pad_feats(repp, 8), nbp, extp, min_pts=min_pts, dim=d, bn=bn,
        interpret=_interpret(),
    )
    return cd[:L]


def hierarchy_impl(Lp: int, use_ref: bool, mesh=None) -> str:
    """What runs the hierarchy's three sweeps over an Lp-leaf bucket
    (`kernel_impl` names): the SMEM kernels of `kernels.hierarchy` where
    their resident state fits (Lp ≤ ``SMEM_ROWS``), else the jnp scans.
    The mesh pass keeps the scans: its hierarchy runs replicated under
    the GSPMD jit, which a Mosaic call does not partition."""
    return kernel_impl(use_ref or mesh is not None or Lp > _hier_k.SMEM_ROWS)


def offline_stages(Lp: int, use_ref: bool, spatial: bool, mesh=None,
                   with_w: bool = True) -> dict[str, str]:
    """What serves each stage of `_offline_pipeline` over an Lp-row
    table — the choices the pipeline makes while it traces, named so the
    engine can record them (``kernel_impl`` names)."""
    hierarchy = hierarchy_impl(Lp, use_ref, mesh)
    if mesh is not None:
        kind = "grid" if spatial else "jnp"
        return {
            "core_distances": f"{kind}-sharded",
            "mutual_reach": "none" if spatial else "jnp-sharded",
            "mst": f"boruvka-{kind}-sharded",
            "hierarchy": hierarchy,
        }
    if spatial:
        return {
            "core_distances": "grid",
            "mutual_reach": kernel_impl(use_ref) if with_w else "none",
            "mst": f"boruvka-strips-{kernel_impl(use_ref)}",
            "hierarchy": hierarchy,
        }
    return {
        "core_distances": bcd_impl(Lp, use_ref),
        "mutual_reach": kernel_impl(use_ref),
        "mst": "boruvka-dense",
        "hierarchy": hierarchy,
    }


def bubble_mutual_reachability(
    rep, n_b, extent, min_pts: int, use_ref: bool | None = None,
    spatial_index: bool = False,
):
    """Offline phase: (L,L) bubble d_m matrix (Eqs. 6–7).

    Pallas path: the tiled Eq. 6 strip kernel feeds the fused
    mutual-reach tile kernel; jnp path: the sort+cumsum reference scan.
    ``spatial_index=True`` computes the Eq. 6 core distances through the
    grid-pruned engine (the matrix assembly itself is inherently dense);
    the matrix then carries jnp-reference bits on both backends.
    """
    rep = jnp.asarray(rep)
    n_b = jnp.asarray(n_b)
    extent = jnp.asarray(extent)
    if spatial_index:
        cd = bubble_core_distances(rep, n_b, extent, min_pts, spatial_index=True)
        return mutual_reachability(rep, rep, cd, cd, zero_diag=True, use_ref=True)
    cd = bubble_core_distances(rep, n_b, extent, min_pts, use_ref=use_ref)
    return mutual_reachability(rep, rep, cd, cd, zero_diag=True, use_ref=use_ref)


def flash_attention(q, k, v, qpos=None, kpos=None, *, causal=True, window=None,
                    bq: int = 512, bk: int = 512):
    """Batched GQA flash attention over model-layout tensors.

    q: (B, Sq, H, Dh); k/v: (B, Sk, KV, Dh).  Each query head is paired
    with its kv head by index mapping (no KV duplication in HBM); heads ×
    batch fold into the kernel's grid axis.  Falls back to ref on
    non-128-divisible sequence tails after padding (dead-key masking).
    """
    from . import flash_attention as _fa

    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if qpos is None:
        qpos = jnp.arange(Sq, dtype=jnp.int32)
    if kpos is None:
        kpos = jnp.arange(Sk, dtype=jnp.int32)
    qpos = jnp.broadcast_to(jnp.asarray(qpos, jnp.int32), (B, Sq)) if qpos.ndim <= 1 else qpos
    kpos = jnp.broadcast_to(jnp.asarray(kpos, jnp.int32), (B, Sk)) if kpos.ndim <= 1 else kpos
    bq = min(bq, 1 << (max(Sq - 1, 1)).bit_length())
    bk = min(bk, 1 << (max(Sk - 1, 1)).bit_length())
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pk)), constant_values=-1)
    # (B, S, H, D) -> (B*H, S, D); kv head of query head h is h // G
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq + pq, Dh)
    kv_idx = jnp.arange(H) // G
    kh = jnp.moveaxis(k, 2, 1)[:, kv_idx].reshape(B * H, Sk + pk, Dh)
    vh = jnp.moveaxis(v, 2, 1)[:, kv_idx].reshape(B * H, Sk + pk, Dh)
    qp = jnp.repeat(qpos, H, axis=0)
    kp = jnp.repeat(kpos, H, axis=0)
    out = _fa.flash_attention(
        qh, kh, vh, qp, kp, causal=causal, window=window, bq=bq, bk=bk,
        interpret=_interpret(),
    )
    out = out.reshape(B, H, Sq + pq, Dh)[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)


# Padding coordinate for size-bucketed bubble tables: far from any data
# (so padded bubbles are never a nearest neighbour) but small enough that
# its squared distances stay finite in f32 (1e12·d ≪ 3.4e38).
_PAD_COORD = 1e6


def bubble_table(LS, SS, N, ids):
    """Host-side f64 bubble derivation shared by the offline pipeline and
    the serve plane: gather the L alive-leaf rows and apply Eqs. 3–4.

    Returns (rep, extent, n, center) — `center` is the mass-weighted
    centroid, the translation every f32 device call site must subtract
    (the ‖x‖²+‖y‖²−2xy expansion cancels catastrophically off-origin).
    """
    from repro.core.cf import cf_extent, cf_rep

    ids = np.asarray(ids)
    LSg = np.asarray(LS, dtype=np.float64)[ids]
    SSg = np.asarray(SS, dtype=np.float64)[ids]
    Ng = np.asarray(N, dtype=np.float64)[ids]
    rep = cf_rep(LSg, Ng)
    extent = cf_extent(LSg, SSg, Ng)
    center = LSg.sum(axis=0) / max(Ng.sum(), 1.0)
    return rep, extent, Ng, center


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled: the sharded
    offline stages deliberately RETURN replicated values — every shard
    holds identical bits by construction (tiled all_gathers feeding
    replicated tails) — which the checker cannot see through."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _sharded_mst_stage(rep, n_b, extent, n_valid, min_pts: int, mesh,
                       axis: str, spatial: bool):
    """The O(L²) heart of the offline pass — Eq. 6 core distances, d_m
    candidate weights, Borůvka rounds — under ONE ``shard_map`` over the
    ``axis`` row blocks of the mesh (DESIGN.md §12).

    The bit-parity contract with the single-device path rests on a
    division of labor: the (Lp, Lp) euclidean distance matrix — the ONE
    computation whose bits are shape-sensitive (XLA lowers the
    ``xx + yy - 2·x@yᵀ`` dot differently for different output shapes,
    ulp-level) — is computed REPLICATED at exactly the dense path's
    shape, and each shard then takes a row-strip SLICE of it.  Everything
    downstream of the slice is bit-determined per row given those
    distance bits: stable sort has a unique answer, cumsum over
    integer-valued f32 masses is exact, min/max reductions are
    order-insensitive, and the Borůvka component/hook tail runs
    replicated on tiled all_gathers.  So the returned (Lp,) edge buffers
    are replicated and bitwise the single-device kernels on any mesh
    shape, while the expensive per-row work (the sort-heavy Eq. 6 scan
    and each Borůvka round's (m, n) min-reductions) runs at 1/k cost.

    Inputs are pinned to replicated sharding: the table is small (the
    whole point of the summary) and replicating it keeps every
    full-column reduction in single-device order.  When the device count
    does not divide Lp (never the case for power-of-two meshes over the
    pow2-bucketed table), the MATERIALIZED distance matrix is padded with
    +inf rows/cols after the fact — an exact, bit-inert lift.

    Like the grid layer, this stage carries jnp-reference bits on BOTH
    backends (the strip kernels are the ref path).
    """
    from repro.core.mst import boruvka_grid_shard_jax, boruvka_shard_jax
    from repro.kernels.grid import build_grid, grid_core_distances_shard

    P = jax.sharding.PartitionSpec
    Lp, d = rep.shape
    k = int(mesh.shape[axis])
    n = Lp + ((-Lp) % k)  # lifted system size; == Lp for pow2 meshes
    repl = jax.sharding.NamedSharding(mesh, P())
    rep = jax.lax.with_sharding_constraint(rep, repl)
    n_b = jax.lax.with_sharding_constraint(n_b, repl)
    extent = jax.lax.with_sharding_constraint(extent, repl)

    if spatial:
        with jax.named_scope("core_distance"):
            grid = build_grid(rep, jnp.arange(Lp) < n_valid)

        def stage(grid, n_b, extent):
            with jax.named_scope("core_distance"):
                cd = grid_core_distances_shard(grid, n_b, extent, min_pts, d, axis, k)
            with jax.named_scope("mst"):
                return boruvka_grid_shard_jax(grid, cd, axis, k)

        f = _shard_map(stage, mesh, in_specs=(P(), P(), P()),
                       out_specs=(P(), P(), P(), P()))
        eu, ev, ew, valid = f(grid, n_b, extent)
        return eu[:Lp], ev[:Lp], ew[:Lp], valid[:Lp]

    def stage(rep_f, n_b_f, extent_f, n_valid_):
        with jax.named_scope("core_distance"):
            # replicated (Lp, Lp) distance matrix, every intermediate pinned
            # (ref.pairwise_dist_pinned) so the bits cannot depend on the
            # mesh-shaped consumers this program inlines it next to
            dm = _ref.pairwise_dist_pinned(rep_f)
            nb_l, ext_l = n_b_f, extent_f
            if n != Lp:  # exact lift of the materialized matrix
                dm = jnp.pad(dm, ((0, n - Lp), (0, n - Lp)),
                             constant_values=jnp.inf)
                nb_l = jnp.pad(n_b_f, (0, n - Lp))
                ext_l = jnp.pad(extent_f, (0, n - Lp))
            m = n // k
            i0 = jax.lax.axis_index(axis).astype(jnp.int32) * m
            rows = i0 + jnp.arange(m, dtype=jnp.int32)
            dm_s = jax.lax.dynamic_slice_in_dim(dm, i0, m, 0)
            cd_s = _ref.bubble_core_distances_from_dm(
                dm_s, rows, nb_l, ext_l, min_pts, d)
            cd = jax.lax.all_gather(cd_s, axis, tiled=True)
        with jax.named_scope("mutual_reach"):
            W_s = jnp.maximum(dm_s, jnp.maximum(cd_s[:, None], cd[None, :]))
            cols = jnp.arange(n, dtype=jnp.int32)
            W_s = jnp.where(rows[:, None] == cols[None, :], 0.0, W_s)
            pad_r = rows >= n_valid_
            pad_c = cols >= n_valid_
            W_s = jnp.where(pad_r[:, None] | pad_c[None, :], jnp.inf, W_s)
        with jax.named_scope("mst"):
            return boruvka_shard_jax(W_s, n, axis)

    f = _shard_map(stage, mesh, in_specs=(P(), P(), P(), P()),
                   out_specs=(P(), P(), P(), P()))
    eu, ev, ew, valid = f(rep, n_b, extent, n_valid)
    # real edges fit in Lp-1 slots; lifted rows never produce any
    return eu[:Lp], ev[:Lp], ew[:Lp], valid[:Lp]


@functools.partial(
    jax.jit,
    static_argnames=(
        "min_pts", "use_ref", "method", "allow_single", "spatial", "with_w",
        "mesh", "mesh_axis",
# trace-contract: offline_pipeline rules=f32,no-callbacks,pow2,no-dense
    ),
)
def _offline_pipeline(
    rep, n_b, extent, n_valid, mcs, min_pts: int, use_ref: bool,
    method: str = "eom", allow_single: bool = False,
    spatial: bool = False, with_w: bool = True,
    mesh=None, mesh_axis: str = "data",
):
    """Device-side offline pass over a size-bucketed bubble table, fused
    end to end under ONE jit: (Lp, Lp) mutual-reachability matrix (Eqs.
    6–7) → Borůvka → single-linkage → condensed tree → stability
    extraction → flat labels.  Nothing syncs to host until the caller
    pulls the fixed-size label/stability buffers back.  Rows ≥ n_valid
    are padding (weight 0, reps at _PAD_COORD): their W rows/cols are
    forced to +inf so they stay isolated in the MST, and the hierarchy
    stage re-attaches them at PAD_DIST where they are invisible to
    stabilities and labels (core.hierarchy_jax docstring).

    With ``mesh`` (a `jax.sharding.Mesh`, static) the O(L²) stage runs
    row-block sharded over ``mesh_axis`` under shard_map
    (`_sharded_mst_stage`) and the small hierarchy stage runs replicated
    on its gathered edge buffers; results are bitwise the single-device
    path (never W — mesh callers must not ask for it).

    Each stage runs under a `jax.named_scope` from `repro.spans.STAGES`:
    op-name metadata only, so a device trace can sum the time of each
    stage while the HLO instruction names stay as they were."""
    from repro.core.hierarchy_jax import hierarchy_fixed
    from repro.core.mst import boruvka_jax, boruvka_strips_jax

    iota = jnp.arange(rep.shape[0])
    is_pad = iota >= n_valid
    out = {}
    if mesh is not None:
        eu, ev, ew, valid = _sharded_mst_stage(
            rep, n_b, extent, n_valid, min_pts, mesh, mesh_axis, spatial)
    elif spatial:
        # the pass that never holds the (Lp, Lp) matrix: core distances
        # from the grid's tile-pruned exact search (jnp-reference bits on
        # both backends), then Borůvka over row strips of the backend's
        # mutual-reachability kernel (`boruvka_strips_jax`); the matrix is
        # only assembled when a caller asked for it (return_w), from the
        # same kernel, so the MST is exact against the W it returns
        from repro.kernels.grid import build_grid, grid_core_distances

        with jax.named_scope("core_distance"):
            grid = build_grid(rep, ~is_pad)
            cd = grid_core_distances(grid, n_b, extent, min_pts, rep.shape[1])
        with jax.named_scope("mst"):
            eu, ev, ew, valid, out["mst_rounds"], out["mst_strip_calls"] = (
                boruvka_strips_jax(rep, cd, is_pad, use_ref=use_ref))
        if with_w:
            with jax.named_scope("mutual_reach"):
                W = mutual_reachability(rep, rep, cd, cd, zero_diag=True, use_ref=use_ref)
                out["W"] = jnp.where(is_pad[:, None] | is_pad[None, :], jnp.inf, W)
    else:
        # bubble_mutual_reachability's two steps, each under its own scope
        with jax.named_scope("core_distance"):
            cd = bubble_core_distances(rep, n_b, extent, min_pts, use_ref=use_ref)
        with jax.named_scope("mutual_reach"):
            W = mutual_reachability(rep, rep, cd, cd, zero_diag=True, use_ref=use_ref)
            W = jnp.where(is_pad[:, None] | is_pad[None, :], jnp.inf, W)
        with jax.named_scope("mst"):
            eu, ev, ew, valid = boruvka_jax(W)
        out["W"] = W
    with jax.named_scope("hierarchy"):
        slt, ct, ex = hierarchy_fixed(
            eu, ev, ew, valid, n_valid, n_b, mcs,
            method=method, allow_single_cluster=allow_single,
            impl=hierarchy_impl(rep.shape[0], use_ref, mesh),
        )
    out.update({
        "eu": eu, "ev": ev, "ew": ew, "valid": valid,
        "labels": ex.labels,
        "stability": ex.stability,
        "selected": ex.selected,
        "n_clusters": ex.n_clusters,
        "point_parent": ct.point_parent,
        "point_lambda": ct.point_lambda,
        "cluster_parent": ct.cluster_parent,
        "cluster_birth": ct.cluster_birth,
        "cluster_weight": ct.cluster_weight,
        "n_labels": ct.n_labels,
    })
    return out


@dataclasses.dataclass
class OfflineClusterResult:
    """One fused offline pass: flat labels + the arrays behind them.

    ``labels[k]``'s cluster has stability ``stabilities[labels[k]]`` —
    flat ids are the ascending-rank of selected condensed labels.  The
    condensed tree is kept in the device layout (label 0 = root; see
    core.hierarchy_jax); ``to_condensed()`` re-emits it in the host
    oracle's ``CondensedTree`` layout for inspection and tests.
    """

    labels: np.ndarray  # (L,) int64 flat bubble labels, -1 noise
    stabilities: np.ndarray  # (n_clusters,) f64 per selected cluster
    mst: tuple  # (u, v, w) host numpy MST edge arrays
    weights: np.ndarray  # (L,) leaf weights (bubble masses)
    min_cluster_size: float
    point_parent: np.ndarray  # (L,) condensed label per leaf
    point_lambda: np.ndarray  # (L,)
    cluster_parent: np.ndarray  # (K,) condensed label of each label's parent
    cluster_birth: np.ndarray  # (K,)
    cluster_weight: np.ndarray  # (K,)
    selected: np.ndarray  # (K,) bool — flat-extraction winners
    all_stabilities: np.ndarray  # (K,) stability of every condensed label
    stages: dict = dataclasses.field(default_factory=dict)  # offline_stages()
    # seconds of the "offline.program" span: the device program's call
    # through its result fetch (None on paths that have no such span)
    program_s: float | None = None
    # rounds the strip Borůvka ran and the strip kernel calls it made
    # (0 on passes that ran another MST engine)
    mst_rounds: int = 0
    mst_strip_calls: int = 0

    @property
    def n_clusters(self) -> int:
        return int(self.stabilities.shape[0])

    @property
    def n_bubbles(self) -> int:
        return int(self.labels.shape[0])

    def to_condensed(self):
        """Device arrays → host ``hdbscan.CondensedTree`` (oracle layout:
        leaves 0..L-1, cluster ids L + device label, root = L)."""
        from repro.core.hdbscan import CondensedTree

        L = self.n_bubbles
        K = int(self.cluster_parent.shape[0])
        lbl = np.arange(1, K, dtype=np.int64)
        parent = np.concatenate([L + self.cluster_parent[1:], L + self.point_parent])
        child = np.concatenate([L + lbl, np.arange(L, dtype=np.int64)])
        lam = np.concatenate([self.cluster_birth[1:], self.point_lambda])
        w = np.concatenate([self.cluster_weight[1:], self.weights])
        return CondensedTree(
            parent=parent.astype(np.int64),
            child=child.astype(np.int64),
            lambda_val=lam.astype(np.float64),
            child_weight=w.astype(np.float64),
            n_leaves=L,
        )


def offline_recluster(
    LS, SS, N, ids, min_pts: int, min_cluster_size: float | None = None,
    use_ref: bool | None = None, return_w: bool = False,
    spatial_index: bool = False,
):
    """Offline re-clustering over leaf CF buffers: `bubble_table` (f64
    host derivation, Eqs. 3–4) + `offline_recluster_from_table`.  Callers
    that need the table themselves (the streaming engine keeps rep/center
    for the serve plane) call the two pieces separately so the O(L·d)
    derivation happens once."""
    rep, extent, Ng, _ = bubble_table(LS, SS, N, ids)
    return offline_recluster_from_table(
        rep, Ng, extent, min_pts, min_cluster_size=min_cluster_size,
        use_ref=use_ref, return_w=return_w, spatial_index=spatial_index,
    )


def offline_recluster_from_table(
    rep, n_b, extent, min_pts: int, min_cluster_size: float | None = None,
    use_ref: bool | None = None, return_w: bool = False,
    method: str = "eom", allow_single_cluster: bool = False,
    spatial_index: bool = False, mesh=None, mesh_axis: str = "data",
):
    """The streaming engine's offline hot path, from a derived bubble table.

    ONE compiled call returns flat labels + stabilities: d_m (Eqs. 6–7)
    → Borůvka → single-linkage → condense → extract all run on device
    (core.hierarchy_jax); the host only mean-centers, pads, and unwraps
    the fixed-size output buffers — no numpy in the hierarchy itself.

    Host side: mean-center (d_m is translation-invariant; the f32 device
    ‖x‖²+‖y‖²−2xy tiles cancel catastrophically off-origin) and pad to a
    power-of-two bucket so the jit'd pipeline recompiles per bucket, not
    per leaf count, as the stream grows.

    Args:
      rep, n_b, extent: (L, d)/(L,)/(L,) float64 bubble table (Eqs. 3–4),
        e.g. from `bubble_table`.
      min_pts: HDBSCAN density parameter.
      min_cluster_size: flat-extraction threshold (None = min_pts).
      use_ref: backend override (None = env-var policy).
      return_w: also materialize the dense (L, L) d_m matrix on host.
        Off by default — at large L the matrix transfer dwarfs everything.
      method, allow_single_cluster: flat-extraction policy (oracle-
        compatible "eom"/"leaf").
      mesh, mesh_axis: optional `jax.sharding.Mesh` — run the O(L²)
        stage row-block-sharded over ``mesh_axis`` (bitwise the
        single-device result; incompatible with ``return_w``, which is
        the matrix the sharded pass exists to never materialize).

    Returns:
      OfflineClusterResult; with ``return_w=True``, ``(W, result)``.
    """
    if mesh is not None and return_w:
        raise ValueError("return_w is unsupported on the sharded (mesh=) path")
    use = _resolve_ref(use_ref)
    rep = np.asarray(rep, dtype=np.float64)
    Ng = np.asarray(n_b, dtype=np.float64)
    extent = np.asarray(extent, dtype=np.float64)
    L = int(rep.shape[0])
    mcs = float(min_pts if min_cluster_size is None else min_cluster_size)
    rep = rep - ((Ng @ rep) / max(Ng.sum(), 1.0))[None, :]
    # if the whole summary represents < min_pts points, Eq. 6's weighted
    # scan can never reach min_pts and the fallback would land on a
    # padding bubble; clamp to the available mass (knn's k=min(k,m) rule)
    min_pts = max(1, min(int(min_pts), int(Ng.sum())))
    Lp = _pow2_rows(L)
    pad = Lp - L
    if pad:
        rep = np.concatenate([rep, np.full((pad, rep.shape[1]), _PAD_COORD)])
        Ng_p = np.concatenate([Ng, np.zeros(pad)])
        extent = np.concatenate([extent, np.zeros(pad)])
    else:
        Ng_p = Ng
    # the spatial pass exists to NOT build the (Lp, Lp) matrix; only
    # materialize it when the caller explicitly asked
    with_w = ((not spatial_index) or bool(return_w)) and mesh is None
    with span("offline.program") as sp:
        out = _offline_pipeline(
            jnp.asarray(rep, jnp.float32),
            jnp.asarray(Ng_p, jnp.float32),
            jnp.asarray(extent, jnp.float32),
            jnp.asarray(L, jnp.int32),
            jnp.asarray(mcs, jnp.float32),
            int(min_pts),
            use,
            method,
            bool(allow_single_cluster),
            spatial=bool(spatial_index),
            with_w=with_w,
            mesh=mesh,
            mesh_axis=mesh_axis,
        )
        W_dev = out.pop("W", None)
        out = jax.device_get(out)  # ONE host sync for all result buffers
    result = _unwrap_result(out, L, mcs, Ng)
    result.stages = offline_stages(Lp, use, bool(spatial_index), mesh, with_w)
    result.program_s = sp.seconds
    if return_w:
        return np.asarray(W_dev)[:L, :L], result
    return result


def _unwrap_result(out, L: int, mcs: float, weights: np.ndarray) -> OfflineClusterResult:
    """Device output dict (fixed-size buffers) → host OfflineClusterResult.
    Shared by the fused offline pipeline and the incremental fast path
    (which pre-fetches the dict; device_get is a no-op on numpy)."""
    out = jax.device_get(out)  # ONE host sync for all result buffers
    keep = out["valid"]
    edges = (
        out["eu"].astype(np.int64)[keep],
        out["ev"].astype(np.int64)[keep],
        out["ew"].astype(np.float64)[keep],
    )
    K = int(out["n_labels"])
    sel = out["selected"][:K]
    all_stab = out["stability"].astype(np.float64)[:K]
    return OfflineClusterResult(
        labels=out["labels"].astype(np.int64)[:L],
        stabilities=all_stab[sel],
        mst=edges,
        weights=weights,
        min_cluster_size=mcs,
        point_parent=out["point_parent"].astype(np.int64)[:L],
        point_lambda=out["point_lambda"].astype(np.float64)[:L],
        cluster_parent=out["cluster_parent"].astype(np.int64)[:K],
        cluster_birth=out["cluster_birth"].astype(np.float64)[:K],
        cluster_weight=out["cluster_weight"].astype(np.float64)[:K],
        selected=sel,
        all_stabilities=all_stab,
        mst_rounds=int(out.get("mst_rounds", 0)),
        mst_strip_calls=int(out.get("mst_strip_calls", 0)),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "min_pts", "use_ref", "method", "allow_single", "spatial",
        "mesh", "mesh_axis", "rows",
# trace-contract: device_table_pipeline rules=f32,no-callbacks,pow2,no-dense
    ),
)
def _device_table_pipeline(
    LS, LSe, SS, SSe, N, alive, mcs, min_pts: int, use_ref: bool, rows: int,
    method: str = "eom", allow_single: bool = False, spatial: bool = False,
    mesh=None, mesh_axis: str = "data",
):
    """Offline pass straight from a device-resident flat leaf-CF state
    (core.bubble_flat): compact the populated slots to rows 0..L-1
    (stable argsort on the alive mask, like the incremental pipeline),
    derive the bubble table ON DEVICE (Eqs. 3–4 over compensated
    origin-centered sums), re-center at the mass centroid, and run the
    same fused `_offline_pipeline` stages.  Nothing about the summary
    crosses the host boundary on the way in — this is the zero-copy
    handoff the streaming engine's device-online mode uses.  The
    compacted representative rows and masses ride along in the output
    dict so the serve plane gets everything from ONE host sync.

    With ``mesh``, the compaction/derivation reductions are pinned to
    replicated sharding — the table is small and a GSPMD-split f32 sum
    would change accumulation order, i.e. bits — and only the quadratic
    stage inside `_offline_pipeline` row-blocks over the mesh.

    ``rows`` (static, a power of two ≥ the populated-slot count, at most
    the slot bucket) is the bucket the compacted table is cut to: the
    slot table carries ~2x headroom for structural churn, which the
    quadratic stages must not pay for."""
    if mesh is not None:
        repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        LS, LSe, SS, SSe, N, alive = (
            jax.lax.with_sharding_constraint(a, repl)
            for a in (LS, LSe, SS, SSe, N, alive)
        )
    Lp = rows
    ok = alive & (N > 0)
    n_valid = jnp.sum(ok, dtype=jnp.int32)
    perm = jnp.argsort(jnp.where(ok, 0, 1), stable=True)[:Lp]
    LSs = (LS - LSe)[perm]
    SSs = (SS - SSe)[perm]
    Ns = N[perm]
    mask = jnp.arange(Lp) < n_valid
    safe_n = jnp.maximum(Ns, 1.0)
    rep = LSs / safe_n[:, None]
    tot = jnp.maximum(jnp.sum(jnp.where(mask, Ns, 0.0)), 1.0)
    mu = jnp.sum(jnp.where(mask, Ns, 0.0)[:, None] * rep, axis=0) / tot
    rep_c = jnp.where(mask[:, None], rep - mu[None, :], _PAD_COORD)
    # extent = sqrt((2 n SS - 2 ||LS||^2) / (n (n-1)))  (Eq. 4, f32 on
    # origin-centered sums — the same cancellation guard as the rep)
    lsq = jnp.sum(LSs * LSs, axis=-1)
    rad = (2.0 * Ns * SSs - 2.0 * lsq) / jnp.maximum(Ns * (safe_n - 1.0), 1.0)
    extent = jnp.sqrt(jnp.maximum(rad, 0.0))
    extent = jnp.where(mask & (Ns > 1.0), extent, 0.0)
    nb = jnp.where(mask, Ns, 0.0)
    out = _offline_pipeline(
        rep_c, nb, extent, n_valid, mcs, min_pts, use_ref, method, allow_single,
        spatial=spatial, with_w=not spatial,  # device path never returns W
        mesh=mesh, mesh_axis=mesh_axis,
    )
    out["rep"] = rep  # origin frame; host adds the f64 origin back
    out["nb"] = nb
    out["mu"] = mu
    out["n_valid"] = n_valid
    return out


def offline_recluster_from_device_table(
    LS, LSe, SS, SSe, N, alive, origin, min_pts: int,
    min_cluster_size: float | None = None, use_ref: bool | None = None,
    method: str = "eom", allow_single_cluster: bool = False,
    spatial_index: bool = False, mesh=None, mesh_axis: str = "data",
):
    """Streaming-engine offline hot path over a `BubbleFlat` view.

    Unlike `offline_recluster_from_table` there is no host-side f64
    derivation and no per-pass upload: the (already padded, already
    origin-centered) device arrays feed one jit'd pipeline and only the
    fixed-size result buffers come back.  ``min_pts`` must be pre-clamped
    by the caller (it is static; the engine clamps against its own
    point count — the flat table's mass equals it by construction).
    NOTE: with ``min_cluster_size=None`` the default derives from that
    CLAMPED min_pts, whereas `offline_recluster_from_table` defaults
    from the raw value before clamping — callers needing tiny-population
    parity across both paths (the engine does) pass it explicitly.

    Returns (OfflineClusterResult, rep, n_b, center): ``rep`` the (L, d)
    f64 uncentered serve-plane representatives, ``center`` the f64 mass
    centroid every f32 assignment must subtract.
    """
    use = _resolve_ref(use_ref)
    mcs = float(min_pts if min_cluster_size is None else min_cluster_size)
    if mesh is not None:
        # the flat table's arrays are committed to a single device; re-place
        # them replicated over the mesh so the sharded jit accepts them
        repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        LS, LSe, SS, SSe, N, alive = (
            jax.device_put(a, repl) for a in (LS, LSe, SS, SSe, N, alive)
        )
    # cut the compacted table to the power-of-two bucket of its live slots
    rows = min(_pow2_rows(int(np.count_nonzero(np.asarray(alive)))),
               int(LS.shape[0]))
    with span("offline.program") as sp:
        out = _device_table_pipeline(
            LS, LSe, SS, SSe, N, alive,
            jnp.asarray(mcs, jnp.float32), int(min_pts), use, rows,
            method, bool(allow_single_cluster), spatial=bool(spatial_index),
            mesh=mesh, mesh_axis=mesh_axis,
        )
        out.pop("W", None)  # fused path never transfers the (Lp, Lp) matrix to host
        out = jax.device_get(out)
    L = int(out.pop("n_valid"))
    origin = np.asarray(origin, dtype=np.float64)
    rep = out.pop("rep").astype(np.float64)[:L] + origin[None, :]
    nb = out.pop("nb").astype(np.float64)[:L]
    center = out.pop("mu").astype(np.float64) + origin
    result = _unwrap_result(out, L, mcs, nb)
    result.stages = offline_stages(rows, use, bool(spatial_index), mesh,
                                   with_w=not spatial_index)
    result.program_s = sp.seconds
    return result, rep, nb, center


# --------------------------------------------------------------------------
# hybrid exact-dynamic fast path (core.dynamic_jax + hierarchy-only labels)
# --------------------------------------------------------------------------

def incremental_update(
    state, *, insert=None, slots=None, delete=None, valid=None,
    min_pts: int, rk_cap: int = 64, s_cap: int = 64,
):
    """One jit'd incremental-maintenance step over a padded block.

    The device realization of the paper's update rules (Eqs. 11–12,
    core.dynamic_jax): pass EITHER ``insert`` ((Bp, d) rows + ``slots``)
    OR ``delete`` ((Bp,) slot ids); ``valid`` masks padding rows.
    Returns the updated DynState; check ``state.ok`` — False means an
    RkNN/S' strip overflowed its bucket and the caller must rebuild
    (``core.dynamic_jax.rebuild`` / the engine's full pass).
    """
    from repro.core import dynamic_jax as dj

    if (insert is None) == (delete is None):
        raise ValueError("pass exactly one of insert= / delete=")
    if insert is not None:
        return dj.insert_batch(
            state, jnp.asarray(insert), jnp.asarray(slots), jnp.asarray(valid),
            min_pts=int(min_pts), rk_cap=int(rk_cap),
        )
    return dj.delete_batch(
        state, jnp.asarray(delete), jnp.asarray(valid),
        min_pts=int(min_pts), rk_cap=int(rk_cap), s_cap=int(s_cap),
    )


# trace-contract: incremental_pipeline rules=f32,no-callbacks,pow2
@functools.partial(jax.jit, static_argnames=("method", "allow_single", "use_ref"))
def _incremental_pipeline(
    X, mst_u, mst_v, mst_raw, mst_valid, cd, alive, n_alive, mcs,
    method: str = "eom", allow_single: bool = False, use_ref: bool = False,
):
    """Maintained MST buffers → flat labels, skipping d_m → Borůvka.

    The incremental fast path's second half: compact the alive slots to
    leaf ids 0..n-1 (rank = running count over the alive mask — ascending
    slot order, matching the host-side slot→row mapping), re-derive the
    mutual-reachability edge weights from raw lengths + current core
    distances, and feed the same fused hierarchy stages the offline pass
    uses (single-linkage → condense → extract, core.hierarchy_jax).  The
    compacted coordinate rows ride along in the same output dict so the
    serve plane gets its representatives from the ONE host sync."""
    from repro.core.hierarchy_jax import hierarchy_fixed

    Np = alive.shape[0]
    rank = (jnp.cumsum(alive.astype(jnp.int32)) - 1).astype(jnp.int32)
    perm = jnp.argsort(jnp.where(alive, 0, 1), stable=True)
    eu = jnp.where(mst_valid, rank[mst_u], 0)
    ev = jnp.where(mst_valid, rank[mst_v], 0)
    ew = jnp.maximum(mst_raw, jnp.maximum(cd[mst_u], cd[mst_v])).astype(jnp.float32)
    ew = jnp.where(mst_valid, ew, 0.0)
    weights = (jnp.arange(Np) < n_alive).astype(jnp.float32)
    slt, ct, ex = hierarchy_fixed(
        eu, ev, ew, mst_valid, n_alive, weights, mcs,
        method=method, allow_single_cluster=allow_single,
        impl=hierarchy_impl(Np, use_ref),
    )
    return {
        "rep": X[perm],
        "eu": eu, "ev": ev, "ew": ew, "valid": mst_valid,
        "labels": ex.labels,
        "stability": ex.stability,
        "selected": ex.selected,
        "n_clusters": ex.n_clusters,
        "point_parent": ct.point_parent,
        "point_lambda": ct.point_lambda,
        "cluster_parent": ct.cluster_parent,
        "cluster_birth": ct.cluster_birth,
        "cluster_weight": ct.cluster_weight,
        "n_labels": ct.n_labels,
    }


def incremental_recluster(
    state, min_cluster_size: float, method: str = "eom",
    allow_single_cluster: bool = False, use_ref: bool | None = None,
):
    """Labels straight from an incrementally maintained MST (DynState).

    Returns (OfflineClusterResult, alive_slots, rep): result rows are in
    ascending-slot order, ``alive_slots[i]`` is the state slot id of row
    i, and ``rep`` is the (n, d) f32 coordinate row per result row
    (gathered on device, so the serve plane never re-transfers the
    padded X buffer).  This is the payoff of the hybrid path — an
    update's labels cost only the O(Np) hierarchy scans, never the
    O(Np²) d_m → Borůvka stages a from-scratch pass pays.
    """
    n = int(state.n_alive)
    mcs = float(min_cluster_size)
    use = _resolve_ref(use_ref)
    out = _incremental_pipeline(
        state.X, state.mst_u, state.mst_v, state.mst_raw, state.mst_valid,
        state.cd, state.alive, jnp.asarray(n, jnp.int32),
        jnp.asarray(mcs, jnp.float32),
        method, bool(allow_single_cluster), use,
    )
    out = jax.device_get(out)  # ONE host sync: labels, arrays, serve reps
    rep = out.pop("rep")[:n]
    result = _unwrap_result(out, n, mcs, np.ones(n, dtype=np.float64))
    result.stages = {"hierarchy": hierarchy_impl(int(state.alive.shape[0]), use)}
    alive_slots = np.nonzero(np.asarray(state.alive))[0]
    return result, alive_slots, rep


class ClusterBackend:
    """Kernel-dispatch handle resolved ONCE at engine construction.

    Every module-level wrapper in this file re-checks platform/env per
    call; long-lived engines (serving.stream) instead hold one of these so
    the policy is frozen up front and hot loops never branch on strings:

      * ``pallas`` — tiled Pallas kernels (compiled on TPU; interpret-mode
        Python execution on CPU — validation only, slow),
      * ``jnp``    — the pure-jnp reference path (CPU/GPU fallback; on TPU
        still XLA-compiled, just without the hand-tiled kernels),
      * ``auto``   — pallas on TPU, jnp elsewhere.

    ``spatial_index=True`` additionally routes Eq. 6 core distances and
    batched assignment through the grid-pruned exact engine (kernels.grid,
    DESIGN.md §10), and Borůvka through row strips of this backend's
    mutual-reachability kernel (core.mst.boruvka_strips_jax), so no
    (L, L) matrix is held.  The grid layer itself is backend-independent
    jnp; the flag composes with either backend name.
    """

    _ALIASES = {"ref": "jnp", "cpu": "jnp", "tpu": "pallas"}

    def __init__(self, name: str = "auto", spatial_index: bool = False):
        name = self._ALIASES.get(name, name)
        if name == "auto":
            name = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if name not in ("pallas", "jnp"):
            raise ValueError(f"unknown backend {name!r} (want auto|pallas|jnp)")
        self.name = name
        self.use_ref = name == "jnp"
        self.spatial_index = bool(spatial_index)

    def __repr__(self):
        if self.spatial_index:
            return f"ClusterBackend({self.name!r}, spatial_index=True)"
        return f"ClusterBackend({self.name!r})"

    def pairwise_sqdist(self, x, y):
        return pairwise_sqdist(x, y, use_ref=self.use_ref)

    def knn(self, x, y, k: int):
        return knn(x, y, k, use_ref=self.use_ref)

    def assign(self, x, reps, valid=None):
        return assign(
            x, reps, use_ref=self.use_ref,
            spatial_index=self.spatial_index, valid=valid,
        )

    def assign_with_dist(self, x, reps, valid=None):
        return assign(
            x, reps, use_ref=self.use_ref, with_dist=True,
            spatial_index=self.spatial_index, valid=valid,
        )

    def bubble_core_distances(self, rep, n_b, extent, min_pts: int):
        return bubble_core_distances(
            rep, n_b, extent, min_pts, use_ref=self.use_ref,
            spatial_index=self.spatial_index,
        )

    def bubble_mutual_reachability(self, rep, n_b, extent, min_pts: int):
        return bubble_mutual_reachability(
            rep, n_b, extent, min_pts, use_ref=self.use_ref,
            spatial_index=self.spatial_index,
        )

    def offline_recluster(
        self, LS, SS, N, ids, min_pts: int,
        min_cluster_size: float | None = None, return_w: bool = False,
    ):
        return offline_recluster(
            LS, SS, N, ids, min_pts, min_cluster_size=min_cluster_size,
            use_ref=self.use_ref, return_w=return_w,
            spatial_index=self.spatial_index,
        )

    def offline_recluster_from_table(
        self, rep, n_b, extent, min_pts: int,
        min_cluster_size: float | None = None, return_w: bool = False, **kw,
    ):
        return offline_recluster_from_table(
            rep, n_b, extent, min_pts, min_cluster_size=min_cluster_size,
            use_ref=self.use_ref, return_w=return_w,
            spatial_index=self.spatial_index, **kw,
        )

    def offline_recluster_from_device_table(
        self, LS, LSe, SS, SSe, N, alive, origin, min_pts: int,
        min_cluster_size: float | None = None, **kw,
    ):
        return offline_recluster_from_device_table(
            LS, LSe, SS, SSe, N, alive, origin, min_pts,
            min_cluster_size=min_cluster_size, use_ref=self.use_ref,
            spatial_index=self.spatial_index, **kw,
        )

    def make_flat(self, dim: int, capacity: int = 64, mesh=None,
                  mesh_axis: str = "data"):
        """Device-resident flat leaf-CF state (core.bubble_flat) bound to
        this backend's assign kernels — the online summarizer's
        throughput path (DESIGN.md §8).  ``mesh`` bakes the sharded
        offline pass into every capture the table hands out (§12)."""
        from repro.core.bubble_flat import BubbleFlat

        return BubbleFlat(
            dim, use_ref=self.use_ref, capacity=capacity,
            spatial_index=self.spatial_index, mesh=mesh, mesh_axis=mesh_axis,
        )

    def make_dynamic(self, min_pts: int, dim: int, capacity: int = 256, **kw):
        """Incremental-maintenance handle (core.dynamic_jax).  The
        update scans are backend-independent jnp; the backend still owns
        the hierarchy sweeps (`incremental_recluster`) and the
        serve-plane assign kernels."""
        from repro.core.dynamic_jax import DynamicJaxHDBSCAN

        return DynamicJaxHDBSCAN(min_pts, dim, capacity=capacity, **kw)

    def incremental_recluster(self, state, min_cluster_size: float, **kw):
        return incremental_recluster(state, min_cluster_size, use_ref=self.use_ref, **kw)


def get_backend(name: str = "auto", spatial_index: bool = False) -> ClusterBackend:
    return ClusterBackend(name, spatial_index=spatial_index)


def bubble_mutual_reachability_sharded(rep, n_b, extent, min_pts: int, mesh, axis: str = "data"):
    """Mesh-distributed d_m matrix (DESIGN.md §12): Eq. 6 core distances
    AND the (L, L) mutual-reachability rows are row-block sharded over
    `axis` with shard_map — each device runs the sort-heavy Eq. 6 scan
    and the Eq. 7 max for its (L/k, L) strip, with ONE all_gather to
    exchange the per-strip core distances.  The euclidean distance
    matrix itself is computed replicated at the dense path's shape and
    row-sliced per shard (the dot's bits are output-shape-sensitive;
    everything downstream of the slice is bit-determined per row), so
    the result is bitwise identical on every mesh shape, and agrees
    with `bubble_mutual_reachability` to float32 ulp level (the dense
    path's fused jit uses FMA contractions the pinned chain forbids).
    The fused offline pass (`_sharded_mst_stage`) extends this same
    decomposition through Borůvka.
    """
    from jax.sharding import PartitionSpec as P

    rep = jnp.asarray(rep, jnp.float32)
    n_b = jnp.asarray(n_b, jnp.float32)
    extent = jnp.asarray(extent, jnp.float32)
    L, d = rep.shape
    k = mesh.shape[axis]
    pad = (-L) % k
    Lk = L + pad

    def strip(rep_f, n_b_f, extent_f):
        # replicated (L, L) distance matrix with every intermediate
        # pinned (ref.pairwise_dist_pinned): strips must be SLICES of one
        # program-independent computation so any mesh shape sees the same
        # bits (see _sharded_mst_stage)
        dm = _ref.pairwise_dist_pinned(rep_f)
        dm_p = jnp.pad(dm, ((0, pad), (0, 0)))  # exact row lift
        m = Lk // k
        i0 = jax.lax.axis_index(axis).astype(jnp.int32) * m
        rows = i0 + jnp.arange(m, dtype=jnp.int32)
        dm_s = jax.lax.dynamic_slice_in_dim(dm_p, i0, m, 0)
        cd_s = _ref.bubble_core_distances_from_dm(
            dm_s, rows, n_b_f, extent_f, min_pts, d)
        cd = jax.lax.all_gather(cd_s, axis, tiled=True)[:L]
        mm = jnp.maximum(dm_s, jnp.maximum(cd_s[:, None], cd[None, :]))
        cols = jnp.arange(L, dtype=jnp.int32)
        return jnp.where(rows[:, None] == cols[None, :], 0.0, mm)

    f = jax.jit(_shard_map(
        strip, mesh, in_specs=(P(), P(), P()), out_specs=P(axis)))
    return f(rep, n_b, extent)[:L]
