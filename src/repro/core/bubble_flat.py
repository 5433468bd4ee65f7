"""Device-resident flat leaf-CF state — the online summarizer's
throughput path (DESIGN.md §8).

`BubbleTree` keeps topology (children/parent lists, splits, dissolves)
host-side because descent and rebalancing are latency-bound pointer
chasing; what dominates a *block* op is the dense part — point→leaf
assignment (O(B·L·d)) and the CF accumulation — and that is what this
module moves onto the device as fixed-shape jit programs:

  * the leaf CF table lives in a padded power-of-two slot bucket
    (`Lp` rows; recompile per bucket, not per leaf count, §5/§6),
    **mean-centered** at a fixed f64 `origin` so the f32 rows never see
    off-origin cancellation (§2);
  * `insert_block` runs assignment through `kernels/assign.py`
    (Pallas tiles or the jnp reference under the engine's
    `ClusterBackend` switch) and applies the CF deltas as segment-sum
    scatters in the SAME jit call;
  * the scatter accumulators are **compensated** (Kahan hi+err pairs):
    thousands of small block deltas would otherwise drift the f32 table
    off the f64 host oracle; with compensation the table tracks the
    `BubbleTree` truth to ~1e-7 rel for the differential suites;
  * overfull/underfilled slots come back as a dense work-list that the
    host tree consumes to run splits/dissolves to a fixpoint
    (`BubbleTree.apply_assigned_block` / `_maintain_to_fixpoint`);
  * structural maintenance (splits, dissolves, reorg) is mirrored by
    *patching* exactly the rows the tree marked dirty
    (`consume_struct_dirty`) — an overwrite from host f64 truth, so the
    patch path composes idempotently with the scatter path.

The payoff is at offline time: the pass consumes this table directly
(`ops.offline_recluster_from_device_table`) — zero per-pass host→device
transfer of the summary.  `core/bubble_tree.py` stays the oracle; the
differential contract is pinned by tests/test_bubble_flat.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BubbleFlat", "DeadSlotError"]


class DeadSlotError(RuntimeError):
    """A block's assignment landed on a dead slot: the block sits outside
    the table's centered frame.  The table has marked itself stale; the
    caller applies the block on the host tree and the next use reloads
    at a fresh origin."""

# same far-away coordinate ops.py uses for padded bubble rows: dead slots
# park there so no real (centered) point ever selects them in the argmin
_PAD_COORD = 1e6


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << (max(n - 1, 1)).bit_length())


def _kahan_add(hi, err, delta):
    """Compensated accumulate: (hi, err) += delta with the running f32
    rounding error carried in err (so the true sum is ``hi - err``)."""
    y = delta - err
    t = hi + y
    err = (t - hi) - y
    return t, err


# trace-contract: flat_insert rules=f32,no-callbacks,pow2
@functools.partial(jax.jit, static_argnames=("hp", "use_ref", "spatial"))
def _flat_insert(LS, LSe, SS, SSe, N, alive, Xc, valid, cap, hp, use_ref,
                 spatial=False):
    """Fixed-shape insert program: assignment + scatter CF update +
    overfull detection, one dispatch.  Shapes: (Lp, d)/(Lp,) state,
    (Bp, d) centered block, (Bp,) row-valid mask.  ``hp`` is the
    power-of-two ceiling of the live-slot watermark: the slot bucket
    carries ~2x headroom so structural churn rarely forces a reload, but
    the O(B·L·d) assignment only runs over the prefix that can actually
    hold live slots — the scatters still cover the full bucket.

    With ``spatial`` the grid-pruned assignment excludes dead slots via
    the live mask instead of parking them at ``_PAD_COORD`` — a block
    outside the centered frame then lands on the nearest LIVE slot
    rather than tripping the dead-slot drift guard (same answer inside
    the sane envelope, where parked slots are never nearest anyway)."""
    from repro.kernels import ops

    Lp = LS.shape[0]
    reps = LS[:hp] / jnp.maximum(N[:hp], 1.0)[:, None]
    live = alive[:hp] & (N[:hp] > 0)
    reps = jnp.where(live[:, None], reps, _PAD_COORD)
    a = ops.assign(
        Xc, reps, use_ref=use_ref, spatial_index=spatial,
        valid=live if spatial else None,
    ).astype(jnp.int32)
    seg = jnp.where(valid, a, Lp)  # padded rows land in a dropped bin
    w = valid.astype(Xc.dtype)
    dLS = jax.ops.segment_sum(Xc * w[:, None], seg, num_segments=Lp + 1)[:Lp]
    dSS = jax.ops.segment_sum(jnp.sum(Xc * Xc, axis=-1) * w, seg, num_segments=Lp + 1)[:Lp]
    dN = jax.ops.segment_sum(w, seg, num_segments=Lp + 1)[:Lp]
    LS, LSe = _kahan_add(LS, LSe, dLS)
    SS, SSe = _kahan_add(SS, SSe, dSS)
    N = N + dN  # exact: integral values in f32
    over = alive & (N > cap)
    return LS, LSe, SS, SSe, N, a, over


# trace-contract: flat_patch rules=f32,no-callbacks,pow2
@jax.jit
def _flat_patch(LS, LSe, SS, SSe, N, alive, idx, LSr, SSr, Nr, al):
    """Structural row patch: overwrite the given slots from host truth
    (compensations reset).  ``idx`` is padded to a power-of-two bucket by
    REPEATING its first entry with identical values — duplicate scatter
    targets with equal payloads are idempotent — so patches of any size
    hit a handful of compiled shapes instead of one per count."""
    return (
        LS.at[idx].set(LSr),
        LSe.at[idx].set(0.0),
        SS.at[idx].set(SSr),
        SSe.at[idx].set(0.0),
        N.at[idx].set(Nr),
        alive.at[idx].set(al),
    )


# trace-contract: flat_delete rules=f32,no-callbacks,pow2
@jax.jit
def _flat_delete(LS, LSe, SS, SSe, N, alive, slots, Xc, valid, m):
    """Fixed-shape delete program: per-victim leaf slots are known to the
    host (`point_leaf`), so this is pure scatter subtraction + underfill
    detection."""
    Lp = LS.shape[0]
    seg = jnp.where(valid, slots.astype(jnp.int32), Lp)
    w = valid.astype(Xc.dtype)
    dLS = jax.ops.segment_sum(Xc * w[:, None], seg, num_segments=Lp + 1)[:Lp]
    dSS = jax.ops.segment_sum(jnp.sum(Xc * Xc, axis=-1) * w, seg, num_segments=Lp + 1)[:Lp]
    dN = jax.ops.segment_sum(w, seg, num_segments=Lp + 1)[:Lp]
    LS, LSe = _kahan_add(LS, LSe, -dLS)
    SS, SSe = _kahan_add(SS, SSe, -dSS)
    N = N - dN
    under = alive & (N < m)
    return LS, LSe, SS, SSe, N, under


class BubbleFlat:
    """Flat SoA mirror of a BubbleTree's alive-leaf CF table on device.

    Life cycle: `load(tree)` (full upload — bucket growth, bootstrap, or
    explicit resync), then per block `insert_block`/`delete_block`
    (scatter) and `sync_struct(tree)` (patch rows the tree's maintenance
    touched).  `device_view()` hands the immutable arrays to the offline
    pass; `host_cfs()` reconstructs uncentered f64 CFs for the
    differential tests.
    """

    def __init__(self, dim: int, use_ref: bool = True, capacity: int = 64,
                 spatial_index: bool = False, mesh=None, mesh_axis: str = "data"):
        self.dim = int(dim)
        self.use_ref = bool(use_ref)
        self.spatial_index = bool(spatial_index)
        # baked into every capture(): offline passes over this table run
        # the O(L²) stage row-block-sharded over the mesh (DESIGN.md §12)
        self.mesh = mesh
        self.mesh_axis = str(mesh_axis)
        self.stale = True  # needs a full load before first use
        self.loads = 0  # full host->device uploads (bootstrap + re-buckets)
        self.origin = np.zeros(self.dim, dtype=np.float64)
        self._alloc(_pow2(capacity))

    def _alloc(self, Lp: int):
        self.Lp = int(Lp)
        z = jnp.zeros
        self.LS = z((Lp, self.dim), jnp.float32)
        self.LSe = z((Lp, self.dim), jnp.float32)
        self.SS = z((Lp,), jnp.float32)
        self.SSe = z((Lp,), jnp.float32)
        self.N = z((Lp,), jnp.float32)
        self.alive = jnp.zeros((Lp,), bool)
        self.leaf_of_slot = np.full(Lp, -1, dtype=np.int64)
        self.slot_of_leaf: dict[int, int] = {}
        self._free = list(range(Lp - 1, -1, -1))
        self._alive_host = np.zeros(Lp, dtype=bool)
        self._hi = 0  # live-slot watermark (exact after load, then grows)

    # -- full (re)load ----------------------------------------------------

    def load(self, tree):
        """Full upload from the tree's f64 SoA: re-center at the current
        mass centroid, re-bucket to a power of two with ~2x headroom for
        structural churn.  One transfer per bucket epoch — never per
        offline pass."""
        ids = tree.alive_leaf_ids()
        ids = ids[tree.N[ids] > 0]
        L = len(ids)
        self._alloc(_pow2(max(2 * L, 8)))
        LS = tree.LS[ids].astype(np.float64)
        SS = tree.SS[ids].astype(np.float64)
        N = tree.N[ids].astype(np.float64)
        tot = max(N.sum(), 1.0)
        self.origin = LS.sum(axis=0) / tot
        LSc, SSc = self._center(LS, SS, N)
        buf_LS = np.zeros((self.Lp, self.dim), dtype=np.float32)
        buf_SS = np.zeros(self.Lp, dtype=np.float32)
        buf_N = np.zeros(self.Lp, dtype=np.float32)
        buf_LS[:L] = LSc
        buf_SS[:L] = SSc
        buf_N[:L] = N
        self.LS = jnp.asarray(buf_LS)
        self.LSe = jnp.zeros_like(self.LS)
        self.SS = jnp.asarray(buf_SS)
        self.SSe = jnp.zeros_like(self.SS)
        self.N = jnp.asarray(buf_N)
        self._alive_host[:L] = True
        self.alive = jnp.asarray(self._alive_host)
        self.leaf_of_slot[:L] = ids
        self.slot_of_leaf = {int(leaf): s for s, leaf in enumerate(ids)}
        self._free = list(range(self.Lp - 1, L - 1, -1))
        self._hi = L
        tree.consume_struct_dirty()  # the load covered everything
        self.stale = False
        self.loads += 1

    def _center(self, LS, SS, N):
        """f64 host centering: CF of {x} → CF of {x - origin}."""
        o = self.origin
        LS = np.asarray(LS, dtype=np.float64)
        N = np.asarray(N, dtype=np.float64)
        LSc = LS - N[..., None] * o
        SSc = SS - 2.0 * (LS @ o) + N * float(o @ o)
        return LSc, SSc

    # -- block ops --------------------------------------------------------

    def insert_block(self, X, cap: float):
        """Device assignment + scatter for a block: returns (leaf ids per
        row, overfull-leaf work-list).  ``cap`` is the tree's leaf_cap at
        the post-block population (the overfull threshold the work-list
        reports against)."""
        X = np.asarray(X, dtype=np.float64)
        B = X.shape[0]
        Bp = _pow2(B)
        Xc = np.zeros((Bp, self.dim), dtype=np.float32)
        Xc[:B] = X - self.origin
        valid = np.zeros(Bp, dtype=bool)
        valid[:B] = True
        self.LS, self.LSe, self.SS, self.SSe, self.N, a, over = _flat_insert(
            self.LS, self.LSe, self.SS, self.SSe, self.N, self.alive,
            jnp.asarray(Xc), jnp.asarray(valid), jnp.float32(cap),
            _pow2(self._hi), self.use_ref, spatial=self.spatial_index,
        )
        slots = np.asarray(a)[:B]
        leaf_ids = self.leaf_of_slot[slots]
        if leaf_ids.min(initial=0) < 0:
            # a point picked a dead slot: only possible when the block sits
            # further from every live rep than the _PAD_COORD parking
            # coordinate (~1e6 in the centered frame), i.e. the stream
            # drifted far outside the origin frame.  Refuse loudly — the
            # caller must reload (fresh origin) rather than let a -1 leaf
            # id reach the tree as a Python negative index.
            self.stale = True
            raise DeadSlotError(
                "flat assignment landed on a dead slot — block is outside "
                "the centered frame; reload the flat state (fresh origin)"
            )
        work = self.leaf_of_slot[np.flatnonzero(np.asarray(over))]
        return leaf_ids, work

    def delete_block(self, leaf_ids, X, m: int):
        """Scatter subtraction for a victim block whose per-point leaves
        the host already knows.  Returns the underfilled slot mask as a
        DEVICE array — the engine's host tree re-derives dissolves from
        its own f64 state, so the mask is informational; materializing it
        (``leaf_of_slot[np.flatnonzero(np.asarray(mask))]``) would force
        a host sync the hot path doesn't need."""
        X = np.asarray(X, dtype=np.float64)
        B = X.shape[0]
        Bp = _pow2(B)
        Xc = np.zeros((Bp, self.dim), dtype=np.float32)
        Xc[:B] = X - self.origin
        slots = np.zeros(Bp, dtype=np.int32)
        slots[:B] = [self.slot_of_leaf[int(leaf)] for leaf in leaf_ids]
        valid = np.zeros(Bp, dtype=bool)
        valid[:B] = True
        self.LS, self.LSe, self.SS, self.SSe, self.N, under = _flat_delete(
            self.LS, self.LSe, self.SS, self.SSe, self.N, self.alive,
            jnp.asarray(slots), jnp.asarray(Xc), jnp.asarray(valid), jnp.float32(m),
        )
        return under

    # -- structural patching ----------------------------------------------

    def sync_struct(self, tree):
        """Consume the tree's structural-dirty set and patch those rows
        (overwrite from f64 truth).  Grows to a fresh bucket via a full
        reload when slots run out."""
        if self.stale:
            self.load(tree)
            return
        dirty = tree.consume_struct_dirty()
        if not dirty:
            return
        born = [
            leaf for leaf in dirty
            if leaf not in self.slot_of_leaf
            and leaf < tree.node_alive.shape[0]
            and tree.node_alive[leaf] and tree.is_leaf[leaf]
        ]
        if len(born) > len(self._free):
            self.load(tree)  # bucket exhausted: re-bucket + fresh origin
            return
        rows, alive_leaves, al = [], [], []
        for leaf in sorted(dirty):
            leaf = int(leaf)
            alive = (
                leaf < tree.node_alive.shape[0]
                and tree.node_alive[leaf]
                and tree.is_leaf[leaf]
            )
            if alive:
                slot = self.slot_of_leaf.get(leaf)
                if slot is None:
                    slot = self._free.pop()
                    self.slot_of_leaf[leaf] = slot
                    self.leaf_of_slot[slot] = leaf
                    self._hi = max(self._hi, slot + 1)
                rows.append(slot)
                alive_leaves.append(leaf)
                al.append(True)
            else:
                slot = self.slot_of_leaf.pop(leaf, None)
                if slot is None:
                    continue  # died before it ever had a row
                self.leaf_of_slot[slot] = -1
                self._free.append(slot)
                rows.append(slot)
                al.append(False)
        if not rows:
            return
        k = len(rows)
        kp = _pow2(k)
        # dead rows zero; alive rows overwritten from centered f64 truth
        # (one vectorized gather+center for the whole patch)
        LSa = np.zeros((kp, self.dim), dtype=np.float32)
        SSa = np.zeros(kp, dtype=np.float32)
        Na = np.zeros(kp, dtype=np.float32)
        ala = np.zeros(kp, dtype=bool)
        ala[:k] = al
        if alive_leaves:
            ids = np.asarray(alive_leaves, dtype=np.int64)
            LSc, SSc = self._center(tree.LS[ids], tree.SS[ids], tree.N[ids])
            live = np.flatnonzero(ala[:k])
            LSa[live] = LSc
            SSa[live] = SSc
            Na[live] = tree.N[ids]
        # pad by repeating row 0 (duplicate targets, identical payloads —
        # idempotent) so patches hit power-of-two compile buckets
        idx = np.full(kp, rows[0], dtype=np.int32)
        idx[:k] = rows
        LSa[k:] = LSa[0]
        SSa[k:] = SSa[0]
        Na[k:] = Na[0]
        ala[k:] = ala[0]
        self.LS, self.LSe, self.SS, self.SSe, self.N, self.alive = _flat_patch(
            self.LS, self.LSe, self.SS, self.SSe, self.N, self.alive,
            jnp.asarray(idx), jnp.asarray(LSa), jnp.asarray(SSa),
            jnp.asarray(Na), jnp.asarray(ala),
        )
        self._alive_host[np.asarray(rows)] = np.asarray(al)

    # -- consumers (core.device_table.DeviceTableProtocol) ----------------

    @property
    def ready(self) -> bool:
        """Protocol view of staleness: a stale table must reload from the
        host tree before an offline capture can trust its rows."""
        return not self.stale

    def sync(self, tree) -> None:
        """Protocol alias for `sync_struct` (which already covers the
        stale → full-reload case)."""
        self.sync_struct(tree)

    def capture(self, n_points: int):
        """Immutable offline capture (core.device_table.FlatTableCapture):
        the six device arrays are jax-immutable, so this is a free
        snapshot — async passes need no isolation copy.  Carries the
        table's mesh so captures route through the sharded offline pass
        without the caller re-plumbing it."""
        from repro.core.device_table import FlatTableCapture

        return FlatTableCapture(
            view=self.device_view(), origin=self.origin.copy(),
            n_points=int(n_points), mesh=self.mesh, mesh_axis=self.mesh_axis,
        )

    def device_view(self):
        """(LS, LSe, SS, SSe, N, alive) — immutable device arrays; safe to
        hand to an async offline pass with no snapshot copy."""
        return (self.LS, self.LSe, self.SS, self.SSe, self.N, self.alive)

    def alive_slots(self) -> np.ndarray:
        """Slot ids of populated leaves in ascending-slot order — the row
        order the device offline pass compacts to."""
        slots = np.flatnonzero(self._alive_host)
        n = np.asarray(self.N)[slots]
        return slots[n > 0]

    def host_cfs(self):
        """(leaf_ids, LS, SS, N) uncentered f64 per populated slot
        (ascending-slot order) — the differential-parity view.  The
        compensation term is folded in (true sum ≈ hi − err)."""
        slots = self.alive_slots()
        LS = (
            np.asarray(self.LS, dtype=np.float64)[slots]
            - np.asarray(self.LSe, dtype=np.float64)[slots]
        )
        SS = (
            np.asarray(self.SS, dtype=np.float64)[slots]
            - np.asarray(self.SSe, dtype=np.float64)[slots]
        )
        N = np.asarray(self.N, dtype=np.float64)[slots]
        o = self.origin
        LSu = LS + N[:, None] * o
        SSu = SS + 2.0 * (LS @ o) + N * float(o @ o)
        return self.leaf_of_slot[slots], LSu, SSu, N
