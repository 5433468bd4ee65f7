"""Streaming clustering engine — the online–offline split as a service
(DESIGN.md §5).

The paper's framework is two phases glued by a summary: a Bubble-tree
absorbs fully-dynamic insertions/deletions *online* while static HDBSCAN
runs *offline* over the ≤ L data bubbles.  This module turns those library
calls into a serving loop with three planes:

  request plane   `submit_insert` / `submit_delete` enqueue ops into a
                  `HostBatcher`; `poll()` drains them in contiguous
                  same-kind blocks and applies `BubbleTree.insert_block` /
                  `delete_block` — CF additivity makes the batched stream
                  equivalent to the sequential one (paper §5.1's
                  order-independence), so batching is free throughput.

  offline plane   a staleness policy mirrors the paper's compression-factor
                  steering: the tree tracks *dirty mass* (points touched
                  since the last pass) and the offline pass re-runs only
                  when dirty/total ≥ ε.  The pass is
                  `kernels.ops.offline_recluster`: the host derives the
                  L-row bubble table from the tree's SoA buffers (O(L·d)
                  in f64 — the summary, never the raw points), then ONE
                  jit'd device pipeline — bubble d_m (Eqs. 6–7) →
                  Borůvka → single-linkage → condensed tree → stability
                  extraction (core.hierarchy_jax) — returns flat labels
                  + stabilities over a size-bucketed table (recompiles
                  per bucket, not per leaf count; no host numpy between
                  the stages).  Async mode runs it in a background
                  thread against a snapshot of those rows.

  serve plane     `query(X)` / `query_detailed(X)` label points against
                  the *cached* snapshot through the versioned device
                  cache (serving.query, DESIGN.md §9): each published
                  snapshot's rep/label/λ arrays go to the device ONCE,
                  and queries run a jit'd fused assign → label-gather →
                  membership-strength program under power-of-two batch
                  buckets — reads never block on ingestion or
                  re-clustering, never re-upload the summary, and always
                  see one complete snapshot version end to end.

  device-online ingestion (``device_online=True``, DESIGN.md §8): the
  throughput half of every block op — point→leaf assignment and the CF
  accumulation — runs as fixed-shape jit programs over a device-resident
  flat leaf-CF state (core.bubble_flat, behind the same ClusterBackend
  switch).  The host tree keeps topology and consumes the emitted
  overfull/underfilled work-lists to run splits/dissolves to a fixpoint,
  patching exactly the structurally-touched rows back into the flat
  state; ε-triggered offline passes then consume the flat table
  *directly* (`ops.offline_recluster_from_device_table`) — zero per-pass
  host→device transfer of the summary.

  hybrid exact-dynamic fast path (``exact=True``, DESIGN.md §7): instead
  of summarizing into bubbles and re-clustering from scratch on ε drift,
  the engine maintains the *point-level* mutual-reachability MST
  device-resident (core.dynamic_jax — the paper's Eqs. 11–12 as array
  code) and labels come from the maintained edges through the same fused
  hierarchy stages (`ops.incremental_recluster`), skipping the
  O(n²) d_m → Borůvka stages entirely.  An `UpdatePolicy` routes each
  applied block: small dirty batches go through the incremental rules;
  blocks above the touched-fraction crossover (or ones forcing a
  capacity-bucket grow) fall back to a from-scratch device pass —
  `core.dynamic.DynamicHDBSCAN` stays unchanged as the host oracle.

The kernel backend (Pallas vs pure-jnp) is resolved ONCE at construction
via `ops.get_backend`; hot loops never re-check platform or env vars.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from repro.core.bubble_flat import DeadSlotError
from repro.core.bubble_tree import BubbleTree
from repro.core.device_table import DynamicStateCapture, SnapshotDeviceTable
from repro.kernels import ops
from repro.launch.mesh import resolve_mesh

from .engine import HostBatcher
from .query import QueryEngine, QueryResult

__all__ = [
    "Ticket",
    "StalenessPolicy",
    "UpdatePolicy",
    "ClusterSnapshot",
    "QueryResult",
    "StreamingClusterEngine",
]


@dataclasses.dataclass
class Ticket:
    """Handle for a queued insert block; `pids` is filled when the
    scheduler applies the block (needed to delete those points later)."""

    size: int
    pids: list | None = None

    @property
    def applied(self) -> bool:
        return self.pids is not None


@dataclasses.dataclass
class StalenessPolicy:
    """When does the cached hierarchy go stale?

    Re-cluster when the dirty mass (points inserted/deleted since the last
    offline pass) reaches ``epsilon`` × current population — the same
    proportional steering the paper applies to the leaf count (L =
    compression × N), applied to the offline cadence.  Below
    ``min_points`` there is nothing worth clustering and the pass is
    skipped entirely.
    """

    epsilon: float = 0.1
    min_points: int = 32

    def stale(self, tree: BubbleTree, have_snapshot: bool, pending: float = 0.0) -> bool:
        """`pending` = dirty mass an in-flight pass has already captured
        (it will be covered when that pass lands, so it doesn't count
        toward triggering the next one)."""
        if tree.n_points < self.min_points:
            return False
        if not have_snapshot:
            return True
        eff = max(0.0, tree.dirty_mass - pending)
        return eff / max(float(tree.n_points), 1.0) >= self.epsilon


@dataclasses.dataclass
class UpdatePolicy:
    """Crossover heuristic for the hybrid exact-dynamic fast path.

    The paper's feasibility study (Fig. 3) and the fig3_dynamic bench
    agree: incremental maintenance wins while the touched fraction is
    small and loses to a from-scratch pass as it grows.  Each applied
    block is routed accordingly:

      * ``incremental`` — block points ≤ ``max_update_frac`` × current
        population: apply Eqs. 11–12 on device, then labels via the
        hierarchy-only stages.
      * ``full`` — big blocks, tiny populations (a full pass is cheap
        and compiles the incremental scans lazily), or blocks that
        would grow the capacity bucket (recompilation is paid either
        way, and a rebuild at the new bucket resets the free list in
        one step).

    A third, *retroactive* fallback lives in core.dynamic_jax: an
    RkNN/S' strip overflow flips the state's ``ok`` bit and the engine
    rebuilds — same economics, discovered mid-flight.
    """

    max_update_frac: float = 0.05
    min_incremental_points: int = 64

    def route(self, n_before: int, block_points: int, grows: bool) -> str:
        if grows or n_before < self.min_incremental_points:
            return "full"
        if block_points > self.max_update_frac * max(n_before, 1):
            return "full"
        return "incremental"


@dataclasses.dataclass
class ClusterSnapshot:
    """Immutable result of one offline pass; the serve plane reads this."""

    version: int
    n_points: int
    bubble_rep: np.ndarray  # (L, d) representatives (serve-plane index)
    bubble_n: np.ndarray  # (L,) represented mass
    center: np.ndarray  # (d,) summary centroid — assignments are centered
    #   before the f32 device kernel (off-origin cancellation, DESIGN.md §2)
    result: ops.OfflineClusterResult  # full fused-pass output (labels,
    #   stabilities, condensed-tree arrays — see ops.OfflineClusterResult)
    wall_seconds: float
    dirty_consumed: float = 0.0  # dirty mass this pass absorbed (settled
    #   against the tree by the MAIN thread — see _settle)

    @property
    def bubble_labels(self) -> np.ndarray:
        """(L,) flat cluster labels, -1 noise."""
        return self.result.labels

    @property
    def mst(self) -> tuple:
        """(u, v, w) MST edge arrays over bubbles."""
        return self.result.mst

    @property
    def n_bubbles(self) -> int:
        return int(self.bubble_rep.shape[0])

    @property
    def n_clusters(self) -> int:
        return len(set(self.bubble_labels.tolist()) - {-1})

    @property
    def stabilities(self) -> np.ndarray:
        """Per-flat-cluster stability (index = label id)."""
        return self.result.stabilities

    @property
    def condensed(self):
        """Host-layout CondensedTree (rebuilt on demand from the device
        arrays; the hot path never constructs it)."""
        return self.result.to_condensed()

    @property
    def total_mst_weight(self) -> float:
        return float(np.sum(self.mst[2]))


class StreamingClusterEngine:
    """Batched Bubble-tree ingestion + incremental offline re-clustering.

    Args:
      dim: feature dimensionality.
      min_pts: HDBSCAN density parameter (offline phase).
      compression: Bubble-tree leaf steering factor (L ≈ compression × N).
      min_cluster_size: flat-extraction threshold (defaults to min_pts).
      epsilon: staleness threshold — re-cluster when ≥ this fraction of
        the population changed since the last pass.
      max_block: scheduler block cap (requests coalesced per apply).
      backend: 'auto' | 'pallas' | 'jnp' — resolved once, see ops.get_backend.
      spatial_index: route core distances, Borůvka candidate generation and
        query/ingest assignment through the grid-pruned neighbor engine
        (kernels.grid).  Bit-exact against the dense paths; opt-in because
        the win only shows at serving-scale L.
      async_offline: run offline passes in a background thread; `query`
        keeps serving the previous snapshot meanwhile.
      device_assign: route the online point→leaf argmin through the kernel
        backend (None = only when the backend is Pallas/TPU; host numpy is
        faster for CPU-sized blocks).
      device_online: run block ingestion through the device-resident flat
        leaf-CF state (core.bubble_flat): assignment + scatter CF updates
        as fixed-shape jit programs, host tree consuming the emitted
        work-lists for splits/dissolves, and ε-passes reading the flat
        table with zero per-pass host→device transfer.  Default off —
        explicit opt-in for serving-scale block workloads (the fig8
        ingestion A/B shows where it wins, even on CPU).
        NOTE: snapshot rows follow the flat state's
        slot order, not ascending leaf id, so callers that correlate
        snapshot rows with `leaf_cf_buffers()` must opt in knowingly.
        Incompatible with ``exact=True``.
      exact: hybrid exact-dynamic fast path — maintain the point-level
        MST incrementally on device (core.dynamic_jax) and refresh exact
        labels every poll; ε-staleness and bubble summarization are
        bypassed (the tree still ingests, as the authoritative point
        store).  Sync-only.
      update_policy: incremental-vs-full routing (exact mode only).
      exact_capacity: initial slot-capacity bucket of the dynamic state.
      mesh: opt-in device mesh for the offline plane (DESIGN.md §12):
        ``True`` = a host mesh over every visible device, or a
        `jax.sharding.Mesh`.  ε-triggered passes then run the O(L²)
        stage — Eq. 6 core distances, d_m candidate strips, Borůvka
        rounds — row-block-sharded over the mesh's ``mesh_axis`` under
        shard_map, producing bitwise the unsharded results (the CI
        multidevice leg digests 1/2/8-device runs against each other).
        Changes no contracts: snapshots, queries, checkpoints, and the
        ingest planes are untouched.  Incompatible with ``exact=True``
        (the incremental path has no O(L²) stage to shard).
      mesh_axis: mesh axis name carrying the row blocks.
      query_cache: a shared `SnapshotDeviceCache` (multi-tenant pooling,
        serving.tenants); None = a private per-engine cache.
      query_scope: cache-key scope tag used with ``query_cache`` so
        independent engines' version counters never collide.
      **tree_kw: forwarded to BubbleTree.
    """

    def __init__(
        self,
        dim: int,
        *,
        min_pts: int = 10,
        compression: float = 0.05,
        min_cluster_size: float | None = None,
        epsilon: float = 0.1,
        max_block: int = 512,
        backend: str = "auto",
        spatial_index: bool = False,
        async_offline: bool = False,
        min_offline_points: int = 32,
        device_assign: bool | None = None,
        device_online: bool | None = None,
        exact: bool = False,
        update_policy: UpdatePolicy | None = None,
        exact_capacity: int = 256,
        mesh=None,
        mesh_axis: str = "data",
        query_cache=None,
        query_scope=None,
        **tree_kw,
    ):
        self.backend = ops.get_backend(backend, spatial_index=spatial_index)
        if device_assign is None:
            device_assign = self.backend.name == "pallas"
        assign_fn = None
        if device_assign:
            assign = jax.jit(self.backend.assign)

            def assign_fn(X, reps):
                # argmin is translation-invariant; center before the f32
                # kernel so off-origin coordinates don't cancel (same as the
                # offline path).  Both sides pad on the host to power-of-two
                # buckets, so one compiled program serves every leaf count
                # in a bucket (pad reps park far away and never win).
                mu = reps.mean(axis=0)
                Xb = np.zeros((ops._pow2_rows(X.shape[0]), X.shape[1]), np.float32)
                Xb[: X.shape[0]] = X - mu
                Rb = np.full((ops._pow2_rows(reps.shape[0]), reps.shape[1]),
                             ops._PAD_COORD, np.float32)
                Rb[: reps.shape[0]] = reps - mu
                return np.asarray(assign(Xb, Rb))[: X.shape[0]]
        self.tree = BubbleTree(  # owner: ingest thread (workers read captures)
            dim=dim, compression=compression, assign_fn=assign_fn, **tree_kw
        )
        self.min_pts = int(min_pts)
        self.min_cluster_size = float(
            min_pts if min_cluster_size is None else min_cluster_size
        )
        self.policy = StalenessPolicy(epsilon=float(epsilon), min_points=int(min_offline_points))
        self.batcher = HostBatcher(max_block=max_block)
        self.async_offline = bool(async_offline)
        self._snapshot: ClusterSnapshot | None = None  # guarded-by: _snapshot_lock
        self._snapshot_lock = threading.Lock()
        self._offline_thread: threading.Thread | None = None  # owner: ingest thread
        self._version = 0  # guarded-by: _snapshot_lock
        self._settled_version = 0  # owner: ingest thread (_settle)
        # dirty mass captured by the running pass
        self._inflight_consumed = 0.0  # owner: ingest thread
        # unsynchronized: single reference swap (GIL-atomic); the worker
        # writes once on failure, the ingest thread reads-and-clears
        self._offline_error: BaseException | None = None
        self.exact = bool(exact)
        if device_online and exact:
            raise ValueError(
                "device_online summarizes into the flat leaf-CF state; "
                "exact=True bypasses bubble summarization entirely"
            )
        if device_online is None:
            device_online = False  # explicit opt-in (row-order contract above)
        self.mesh = resolve_mesh(mesh)
        self.mesh_axis = str(mesh_axis)
        if self.mesh is not None and exact:
            raise ValueError(
                "mesh= shards the offline pass's O(L²) stage; exact=True "
                "maintains the point-level MST incrementally and has none"
            )
        self._flat = (  # owner: ingest thread (workers read captured views)
            self.backend.make_flat(dim, mesh=self.mesh, mesh_axis=self.mesh_axis)
            if device_online else None
        )
        # offline plane sources (core.device_table): the host tree is the
        # always-ready fallback; device_online prefers the flat table
        self._host_table = SnapshotDeviceTable(self.tree)
        self._table = self._flat if device_online else self._host_table
        self.update_policy = update_policy if update_policy is not None else UpdatePolicy()
        self._dyn = None  # owner: ingest thread (exact mode is synchronous)
        # no incremental state until the first rebuild
        self._dyn_stale = True  # owner: ingest thread
        self._pid2slot: dict[int, int] = {}  # owner: ingest thread
        if self.exact:
            if self.async_offline:
                raise ValueError(
                    "exact=True refreshes labels synchronously per poll; "
                    "async_offline is not supported"
                )
            self._dyn = self.backend.make_dynamic(
                self.min_pts, dim, capacity=int(exact_capacity)
            )
        # serve plane: versioned device cache + fused query program
        # (serving.query); labels() memoizes per-pid labels keyed on
        # (snapshot version, tree mutation counter).  query_cache/scope
        # let a TenantRouter pool ONE device cache across engines with
        # (tenant, version) keys (serving.tenants).
        self._query_engine = QueryEngine(
            self.backend, dim, cache=query_cache, scope=query_scope
        )
        # which implementation serves each plane (ops.kernel_impl names):
        # fixed at construction for ingest and queries, recorded per pass
        # for the offline stages (ops.offline_stages)
        dense = ops.kernel_impl(self.backend.use_ref)
        device_impl = "grid" if self.backend.spatial_index else dense
        ingest_impl = device_impl if (device_online or device_assign) else "host"
        # unsynchronized: single-reference swap; readers take ONE read of
        # the (key, payload) tuple (see labels()) so entries never mix
        self._labels_cache: tuple | None = None
        # unsynchronized: best-effort observability counters (worker and
        # ingest thread both increment; a lost count is acceptable)
        self.stats = {
            "inserts": 0,
            "deletes": 0,
            "blocks_applied": 0,
            "recluster_count": 0,
            "recluster_skipped_busy": 0,
            "recluster_failures": 0,
            "offline_seconds_total": 0.0,
            "incremental_blocks": 0,
            "exact_full_blocks": 0,
            "exact_rebuilds": 0,
            "device_online_blocks": 0,
            "flat_loads": 0,
            "label_cache_hits": 0,
            "ingest_impl": ingest_impl,
            "query_impl": device_impl,
            "offline_impl": {},
        }

    # -- request plane -----------------------------------------------------

    def submit_insert(self, X) -> Ticket:
        """Queue a block of points for insertion; returns a Ticket whose
        `pids` fill in once the scheduler applies the block.  The points
        are copied at submit time — callers may reuse their buffer."""
        X = np.array(X, dtype=np.float64, copy=True, ndmin=2)
        if X.size == 0:  # e.g. [] arrives as (1, 0); normalize to 0 points
            X = X.reshape(0, self.tree.dim)
        if X.ndim != 2 or X.shape[1] != self.tree.dim:
            # validate at submit time: a bad request deferred into poll()
            # would crash the drain loop and take coalesced siblings down
            raise ValueError(f"expected (n, {self.tree.dim}) points, got {X.shape}")
        t = Ticket(size=X.shape[0])
        self.batcher.push((X, t), kind="insert")
        return t

    def submit_delete(self, pids):
        """Queue point retirements (pids from an applied insert Ticket)."""
        pids = [int(p) for p in np.atleast_1d(np.asarray(pids)).ravel()]
        self.batcher.push(pids, kind="delete")

    def poll(self, max_blocks: int | None = None) -> int:
        """Drain the request queue: coalesce contiguous same-kind requests
        into blocks (≤ max_block points each), apply them to the tree, then
        consult the staleness policy.  Returns the number of ops applied."""
        applied = 0
        blocks = 0
        while self.batcher and (max_blocks is None or blocks < max_blocks):
            kind, items = self._next_point_block()
            if kind == "insert":
                X = np.concatenate([x for x, _ in items], axis=0)
                pids = self._apply_insert_block(X)
                self._exact_apply_insert(X, pids)
                off = 0
                for x, ticket in items:  # requests are never split: one fill
                    take = x.shape[0]
                    ticket.pids = pids[off : off + take]
                    off += take
                self.stats["inserts"] += X.shape[0]
                applied += X.shape[0]
            else:
                flat_pids = [p for chunk in items for p in chunk]
                try:
                    self._apply_delete_block(flat_pids)
                except KeyError:
                    # coalescing must not change failure semantics vs the
                    # sequential stream: a bad request (dead/duplicate pid)
                    # can't take its siblings down.  delete_block is atomic
                    # per call, so replay per request and surface the first
                    # failure — exactly what sequential submission would do.
                    done, err = 0, None
                    for chunk in items:
                        try:
                            self._apply_delete_block(chunk)
                            done += len(chunk)
                        except KeyError as e:
                            if err is None:
                                err = e
                        else:
                            self._exact_apply_delete(chunk)
                    self.stats["deletes"] += done
                    if err is not None:
                        raise err from None
                else:
                    self._exact_apply_delete(flat_pids)
                    self.stats["deletes"] += len(flat_pids)
                    applied += len(flat_pids)
            self.stats["blocks_applied"] += 1
            blocks += 1
        self.maybe_recluster()
        return applied

    @staticmethod
    def _point_count(item) -> int:
        """Points in one queued request: insert items are (X, Ticket),
        delete items are pid lists."""
        return item[0].shape[0] if isinstance(item, tuple) else len(item)

    def _next_point_block(self):
        """HostBatcher.next_block counting *points*, not requests (one
        insert request may carry a whole array).  Coalescing never exceeds
        max_block points; a single oversized request still forms its own
        block (tickets are not split)."""
        return self.batcher.next_block(size=self._point_count)

    def ingest(self, X) -> list[int]:
        """Synchronous convenience: submit + drain; returns the new pids."""
        t = self.submit_insert(X)
        self.poll()
        return t.pids

    def retire(self, pids):
        """Synchronous convenience: submit deletions + drain."""
        self.submit_delete(pids)
        self.poll()

    # -- device-online ingestion (core.bubble_flat, DESIGN.md §8) ----------

    def _apply_insert_block(self, X) -> list:
        """Apply one coalesced insert block: the device-online path runs
        assignment + scatter CF updates as one jit dispatch, hands the
        tree the pre-computed assignment plus the overfull work-list, and
        patches structurally-touched rows back; otherwise the host
        `insert_block` path."""
        if self._flat is None or self.tree.num_leaves <= 1:
            pids = self.tree.insert_block(X)
            if self._flat is not None:
                if self.tree.num_leaves > 1:
                    # bootstrap done: load eagerly so this poll's ε-pass
                    # already reads the device table
                    self._flat.load(self.tree)
                    self.stats["flat_loads"] = self._flat.loads
                else:
                    self._flat.stale = True
            return pids
        if self._flat.stale:
            self._flat.load(self.tree)
        cap = self.tree._leaf_cap_at(self.tree.n_points + X.shape[0])
        try:
            leaf_ids, work = self._flat.insert_block(X, cap)
        except DeadSlotError:
            # the stream drifted outside the centered frame: the table has
            # marked itself stale and reloads at a fresh origin before the
            # next scatter or ε-pass; this block goes through the host
            # path.  Device and compile errors are not caught here.
            return self.tree.insert_block(X)
        pids = self.tree.apply_assigned_block(X, leaf_ids, overfull_hint=work)
        self._flat.sync_struct(self.tree)
        self.stats["device_online_blocks"] += 1
        self.stats["flat_loads"] = self._flat.loads
        return pids

    def _apply_delete_block(self, pids):
        """Apply one coalesced delete block; the device-online path
        mirrors the per-leaf CF subtraction as a scatter (victim leaves
        are captured from `point_leaf` BEFORE the tree mutates, and the
        device table is touched only after the tree's atomic validation
        passed)."""
        if self._flat is None or self._flat.stale:
            self.tree.delete_block(pids)
            return
        arr = np.asarray(pids, dtype=np.int64)
        ok = arr.size > 0 and bool(
            ((arr >= 0) & (arr < self.tree.point_alive.shape[0])).all()
        )
        leaves = self.tree.point_leaf[arr].copy() if ok else None
        Xv = self.tree.PX[arr].copy() if ok else None
        self.tree.delete_block(pids)  # raises before any mutation on bad pids
        if leaves is not None and len(leaves):
            self._flat.delete_block(leaves, Xv, self.tree.m)
        self._flat.sync_struct(self.tree)
        self.stats["device_online_blocks"] += 1
        self.stats["flat_loads"] = self._flat.loads

    # -- hybrid exact-dynamic fast path ------------------------------------

    def _exact_apply_insert(self, X, pids):
        """Route one applied insert block through the incremental rules
        (Eq. 11) or mark the device state stale for a full rebuild at the
        next refresh — the UpdatePolicy crossover."""
        if not self.exact:
            return
        route = self.update_policy.route(
            self._dyn.n, len(pids), self._dyn.would_grow(len(pids))
        )
        if self._dyn_stale or route == "full":
            self._dyn_stale = True
            self.stats["exact_full_blocks"] += 1
            return
        slots = self._dyn.insert_block(X)
        for p, s in zip(pids, slots):
            self._pid2slot[int(p)] = s
        self.stats["incremental_blocks"] += 1

    def _exact_apply_delete(self, pids):
        """Same, for deletions (Eq. 12).  An RkNN/S' strip overflow
        inside the update rebuilds the state in place (slot assignments
        survive), so the mapping stays valid either way."""
        if not self.exact:
            return
        route = self.update_policy.route(self._dyn.n, len(pids), False)
        if self._dyn_stale or route == "full":
            self._dyn_stale = True
            self.stats["exact_full_blocks"] += 1
            for p in pids:
                self._pid2slot.pop(int(p), None)
            return
        self._dyn.delete_block([self._pid2slot.pop(int(p)) for p in pids])
        self.stats["incremental_blocks"] += 1

    def _rebuild_dyn(self):
        """Full pass: reload the device state from the tree's alive
        points (the authoritative store) and rebuild kNN/cd/MST from
        scratch — the fallback leg of the hybrid path."""
        pids, X = self.tree.alive_points()
        slots = self._dyn.load(X, slots=list(range(len(pids))), shrink=True)
        self._pid2slot = {int(p): s for p, s in zip(pids, slots)}
        self._dyn_stale = False
        self.stats["exact_rebuilds"] += 1

    def _exact_refresh(self, force: bool = False) -> bool:
        """Exact-mode analog of maybe_recluster: every poll that left the
        tree dirty refreshes the snapshot — incremental states pay only
        the hierarchy stages; stale/overflowed ones pay one rebuild."""
        n = self.tree.n_points
        if n < 2 or (n < self.policy.min_points and not force):
            return False
        if self.tree.dirty_mass <= 0 and self.snapshot is not None and not force:
            return False
        t0 = time.perf_counter()
        dirty_captured = self.tree.dirty_mass
        if self._dyn_stale or not self._dyn.ok or self._dyn.n != n:
            self._rebuild_dyn()
        # snapshot rows = ascending device slot; the pipeline gathers the
        # serve-plane representatives on device, so the per-poll refresh
        # is ONE host sync — no tree gather, no pid-map inversion, no
        # padded-buffer re-transfer
        cap = DynamicStateCapture(state=self._dyn.state, dim=self.tree.dim)
        res, rep, n_b, center = cap.recluster(
            self.backend, min_pts=self.min_pts,
            min_cluster_size=self.min_cluster_size,
        )
        self._publish_snapshot(res, rep, n_b, center, n, dirty_captured, t0)
        self._settle()
        return True

    # -- offline plane -----------------------------------------------------

    def _settle(self):
        """Consume a finished pass's dirty mass — on the MAIN thread only,
        so `tree.dirty_mass` has a single writer thread and the worker
        never races the ingestion path's `+=`."""
        with self._snapshot_lock:
            snap = self._snapshot
        if snap is not None and snap.version > self._settled_version:
            self.tree.dirty_mass = max(0.0, self.tree.dirty_mass - snap.dirty_consumed)
            self._settled_version = snap.version
            self._inflight_consumed = 0.0

    def maybe_recluster(self, force: bool = False) -> bool:
        """Trigger an offline pass if the policy says the hierarchy is
        stale (or `force`).  Async mode: returns immediately; a pass
        already in flight absorbs the trigger (its successor will see the
        accumulated dirty mass).  Exact mode routes to the hybrid
        fast-path refresh instead (per-poll, never ε-deferred)."""
        if self.exact:
            return self._exact_refresh(force)
        self._raise_pending_offline_error()
        # liveness BEFORE settle: if the pass lands in between, settle still
        # consumes its mass before any capture below — never after (a
        # settle-then-liveness order lets a pass finishing in the gap get
        # its consumed mass captured again and later double-settled)
        busy = self._offline_thread is not None and self._offline_thread.is_alive()
        self._settle()
        pending = self._inflight_consumed if busy else 0.0
        # an in-flight pass counts as "hierarchy coming": only mass it did
        # NOT capture argues for another trigger
        have = self.snapshot is not None or busy
        if not force and not self.policy.stale(self.tree, have, pending=pending):
            return False
        if self.tree.n_points < 2:
            return False
        if busy:
            # a trigger actually fired but a pass is in flight; it stays
            # absorbed (the next pass sees the accumulated dirty mass)
            self.stats["recluster_skipped_busy"] += 1
            return False
        # capture: dirty mass consumed by this pass + the summary rows,
        # through whichever DeviceTableProtocol source is ready — the
        # flat table when device_online and fresh (its jax arrays are
        # immutable, so the capture is a free snapshot with zero per-pass
        # host→device transfer), the host tree otherwise (the capture
        # copies the L gathered CF rows, so the async worker is immune to
        # concurrent tree edits)
        dirty_captured = self.tree.dirty_mass
        n_points = self.tree.n_points
        src = self._table if self._table.ready else self._host_table
        cap = src.capture(n_points)
        if self.async_offline:
            self._inflight_consumed = dirty_captured
            th = threading.Thread(
                target=self._offline_pass_guarded,
                args=(self._offline_pass, cap, n_points, dirty_captured),
                daemon=True,
            )
            self._offline_thread = th
            th.start()
        else:
            self._offline_pass(cap, n_points, dirty_captured)
            self._settle()
        return True

    def _offline_pass_guarded(self, fn, *args):
        """Worker entry: capture failures for the main thread instead of
        dying silently with the traceback lost to stderr; join()/poll()
        re-raise so a failed pass can't masquerade as a fresh hierarchy."""
        try:
            fn(*args)
        except BaseException as e:  # noqa: BLE001 — transported, not handled
            self._offline_error = e
            self.stats["recluster_failures"] += 1

    def _raise_pending_offline_error(self):
        if self._offline_error is not None:
            err, self._offline_error = self._offline_error, None
            self._inflight_consumed = 0.0
            raise RuntimeError("async offline re-cluster pass failed") from err

    def _offline_pass(self, capture, n_points, dirty_captured):
        """One offline pass over a `DeviceTableProtocol` capture
        (core.device_table): the capture runs the fused pipeline — the
        host-table capture derives + uploads the f64 summary; the
        flat-table capture reads the device state with zero per-pass
        transfer; either routes the O(L²) stage through the mesh-sharded
        shard_map path when the engine opted in — and the result
        publishes as ONE snapshot."""
        t0 = time.perf_counter()
        res, rep, n_b, center = capture.recluster(
            self.backend, min_pts=self.min_pts,
            min_cluster_size=self.min_cluster_size,
            mesh=self.mesh, mesh_axis=self.mesh_axis,
        )
        return self._publish_snapshot(
            res, rep, n_b, center, n_points, dirty_captured, t0)

    def _publish_snapshot(self, res, rep, n_b, center, n_points,
                          dirty_captured, t0):
        """Version-bump + atomic swap in ONE place — the ε-triggered
        offline plane and the exact fast path both publish through here.
        Publish only; dirty-mass settlement happens on the main thread
        (updates that raced this pass stay dirty for the next one)."""
        wall = time.perf_counter() - t0
        # version bump + swap under ONE lock hold: checkpoint_state captures
        # (version, snapshot) under the same lock, so a blocking save during
        # an in-flight async pass can never record engine version N alongside
        # a version-N+1 snapshot — after restore, the next publish would
        # re-issue N+1 and collide with the stale entry in the version-keyed
        # device cache (serving.query), serving old labels as fresh.
        with self._snapshot_lock:
            self._version += 1
            snap = ClusterSnapshot(
                version=self._version,
                n_points=int(n_points),
                bubble_rep=rep,
                bubble_n=n_b,
                center=center,
                result=res,
                wall_seconds=wall,
                dirty_consumed=float(dirty_captured),
            )
            self._snapshot = snap
        self.stats["recluster_count"] += 1
        self.stats["offline_seconds_total"] += wall
        self.stats["offline_impl"] = dict(res.stages)
        return snap

    def flush(self) -> ClusterSnapshot | None:
        """Drain every queued request, finish any in-flight offline pass,
        and force one final pass if anything is still dirty."""
        while self.batcher:
            self.poll()
        self.join()
        if self.tree.n_points >= 2 and (
            self.snapshot is None or self.tree.dirty_mass > 0
        ):
            self.maybe_recluster(force=True)
            self.join()
        return self.snapshot

    def join(self):
        if self._offline_thread is not None:
            self._offline_thread.join()
            self._offline_thread = None
        self._settle()
        self._raise_pending_offline_error()

    # -- checkpointing (DESIGN.md §11: snapshot shipping & recovery) -------

    _CKPT_FORMAT = 1

    def _ragged_pack(self, lists):
        """list-of-int-lists → (flat, offsets) int64 arrays (CSR)."""
        off = np.zeros(len(lists) + 1, dtype=np.int64)
        for i, xs in enumerate(lists):
            off[i + 1] = off[i] + len(xs)
        flat = np.fromiter(
            (p for xs in lists for p in xs), dtype=np.int64, count=int(off[-1])
        )
        return flat, off

    @staticmethod
    def _ragged_unpack(flat, off):
        return [flat[off[i] : off[i + 1]].tolist() for i in range(len(off) - 1)]

    def checkpoint_state(self) -> dict:
        """The engine's durable state as one flat dict of host arrays —
        the Bubble-tree summary IS the durable state (paper's online–
        offline split), so this is O(summary), never O(raw stream).

        Captured: the full tree (CF SoA, topology, point store, free
        lists — free-list ORDER included, so pid allocation replays
        bit-for-bit), the ε/dirty-mass accounting, the flat device table
        (device_online — origin, slot order and Kahan compensations, so
        post-restore ε-passes reproduce the same bits), and the last
        PUBLISHED `ClusterSnapshot`.  Not captured: an in-flight async
        pass (recovery replays to the last published version; the lost
        pass re-triggers off the preserved dirty mass), the exact-mode
        dynamic MST state (rebuilt from the tree at the next refresh),
        queued-but-unapplied requests, and observability counters.

        Call from the ingest thread (the tree's single writer), same as
        `poll()`."""
        # ONE lock hold for (version, snapshot): an async publish between
        # separate reads could pair version N with a version-N+1 snapshot,
        # and the restored engine would re-issue N+1 (see _publish_snapshot)
        with self._snapshot_lock:
            version = self._version
            snap = self._snapshot
        t = self.tree
        cap = t.LS.shape[0]
        ch_flat, ch_off = self._ragged_pack(t.children[:cap])
        lp_flat, lp_off = self._ragged_pack(t.leaf_points[:cap])
        state = {
            "cfg/format": np.int64(self._CKPT_FORMAT),
            "cfg/dim": np.int64(t.dim),
            "cfg/min_pts": np.int64(self.min_pts),
            "cfg/min_cluster_size": np.float64(self.min_cluster_size),
            "cfg/compression": np.float64(t.compression),
            "cfg/epsilon": np.float64(self.policy.epsilon),
            "cfg/exact": np.bool_(self.exact),
            "cfg/device_online": np.bool_(self._flat is not None),
            "tree/LS": t.LS.copy(),
            "tree/SS": t.SS.copy(),
            "tree/N": t.N.copy(),
            "tree/parent": t.parent.copy(),
            "tree/height": t.height.copy(),
            "tree/node_alive": t.node_alive.copy(),
            "tree/is_leaf": t.is_leaf.copy(),
            "tree/children_flat": ch_flat,
            "tree/children_off": ch_off,
            "tree/leaf_points_flat": lp_flat,
            "tree/leaf_points_off": lp_off,
            "tree/node_free": np.asarray(t._node_free, dtype=np.int64),
            "tree/PX": t.PX.copy(),
            "tree/point_alive": t.point_alive.copy(),
            "tree/point_leaf": t.point_leaf.copy(),
            "tree/point_free": np.asarray(t._point_free, dtype=np.int64),
            "tree/struct_dirty": np.asarray(sorted(t._struct_dirty), dtype=np.int64),
            "tree/root": np.int64(t.root),
            "tree/n_points": np.int64(t.n_points),
            "tree/dirty_mass": np.float64(t.dirty_mass),
            "tree/mutations": np.int64(t.mutations),
            "tree/op_count": np.int64(t._op_count),
            "eng/version": np.int64(version),
            "eng/settled_version": np.int64(self._settled_version),
        }
        state["snap/has"] = np.bool_(snap is not None)
        if snap is not None:
            state.update(
                {
                    "snap/version": np.int64(snap.version),
                    "snap/n_points": np.int64(snap.n_points),
                    "snap/bubble_rep": np.asarray(snap.bubble_rep),
                    "snap/bubble_n": np.asarray(snap.bubble_n),
                    "snap/center": np.asarray(snap.center),
                    "snap/wall_seconds": np.float64(snap.wall_seconds),
                    "snap/dirty_consumed": np.float64(snap.dirty_consumed),
                    "snap/mst_u": np.asarray(snap.mst[0]),
                    "snap/mst_v": np.asarray(snap.mst[1]),
                    "snap/mst_w": np.asarray(snap.mst[2]),
                }
            )
            res = snap.result
            for f in (
                "labels", "stabilities", "weights", "point_parent",
                "point_lambda", "cluster_parent", "cluster_birth",
                "cluster_weight", "selected", "all_stabilities",
            ):
                state[f"snap/res_{f}"] = np.asarray(getattr(res, f))
            state["snap/res_min_cluster_size"] = np.float64(res.min_cluster_size)
        flat_live = self._flat is not None and not self._flat.stale
        state["flat/has"] = np.bool_(flat_live)
        if flat_live:
            f = self._flat
            state.update(
                {
                    "flat/LS": np.asarray(f.LS),
                    "flat/LSe": np.asarray(f.LSe),
                    "flat/SS": np.asarray(f.SS),
                    "flat/SSe": np.asarray(f.SSe),
                    "flat/N": np.asarray(f.N),
                    "flat/alive": np.asarray(f.alive),
                    "flat/origin": f.origin.copy(),
                    "flat/leaf_of_slot": f.leaf_of_slot.copy(),
                    "flat/free": np.asarray(f._free, dtype=np.int64),
                    "flat/hi": np.int64(f._hi),
                    "flat/loads": np.int64(f.loads),
                }
            )
        return state

    def save(self, store, step: int | None = None, *, blocking: bool = True):
        """Checkpoint through a `repro.checkpoint.CheckpointStore` (atomic
        publish + async writes + retention).  ``step`` defaults to the
        tree's monotonic mutation counter, so successive saves of a live
        stream land under distinct, ordered step ids.  Returns the step."""
        if step is None:
            step = int(self.tree.mutations)
        store.save(step, self.checkpoint_state(), blocking=blocking)
        return step

    def restore(self, store, step: int | None = None) -> int:
        """Load a checkpoint written by `save()` into THIS engine (built
        with a compatible constructor config) — the killed-worker
        recovery path: the summary, accounting, and last published
        snapshot replay, so serving resumes at that version and the
        stream continues bit-for-bit where the checkpoint left it.
        Returns the restored step."""
        step, d = store.restore(step=step)
        if int(d["cfg/format"]) != self._CKPT_FORMAT:
            raise ValueError(f"unknown checkpoint format {int(d['cfg/format'])}")
        if int(d["cfg/dim"]) != self.tree.dim:
            raise ValueError(
                f"checkpoint dim {int(d['cfg/dim'])} != engine dim {self.tree.dim}"
            )
        for key, mine in (
            ("cfg/exact", self.exact),
            ("cfg/device_online", self._flat is not None),
        ):
            if bool(d[key]) != bool(mine):
                raise ValueError(
                    f"checkpoint {key}={bool(d[key])} does not match this "
                    f"engine ({bool(mine)}) — construct the replacement "
                    f"worker with the same mode"
                )
        if self.batcher:
            raise RuntimeError("restore() into an engine with queued requests")
        t = self.tree
        cap = int(d["tree/LS"].shape[0])
        t.LS = np.array(d["tree/LS"], dtype=np.float64)
        t.SS = np.array(d["tree/SS"], dtype=np.float64)
        t.N = np.array(d["tree/N"], dtype=np.float64)
        t.parent = np.array(d["tree/parent"], dtype=np.int64)
        t.height = np.array(d["tree/height"], dtype=np.int64)
        t.node_alive = np.array(d["tree/node_alive"], dtype=bool)
        t.is_leaf = np.array(d["tree/is_leaf"], dtype=bool)
        t.children = self._ragged_unpack(d["tree/children_flat"], d["tree/children_off"])
        t.leaf_points = self._ragged_unpack(
            d["tree/leaf_points_flat"], d["tree/leaf_points_off"]
        )
        assert len(t.children) == cap and len(t.leaf_points) == cap
        t._node_free = d["tree/node_free"].astype(int).tolist()
        t.PX = np.array(d["tree/PX"], dtype=np.float64)
        t.point_alive = np.array(d["tree/point_alive"], dtype=bool)
        t.point_leaf = np.array(d["tree/point_leaf"], dtype=np.int64)
        t._point_free = d["tree/point_free"].astype(int).tolist()
        t._struct_dirty = set(d["tree/struct_dirty"].astype(int).tolist())
        t.root = int(d["tree/root"])
        t.n_points = int(d["tree/n_points"])
        t.dirty_mass = float(d["tree/dirty_mass"])
        t.mutations = int(d["tree/mutations"])
        t._op_count = int(d["tree/op_count"])
        self._settled_version = int(d["eng/settled_version"])
        self._inflight_consumed = 0.0
        self._offline_thread = None
        self._offline_error = None
        self._labels_cache = None
        snap = None
        if bool(d["snap/has"]):
            res = ops.OfflineClusterResult(
                labels=d["snap/res_labels"],
                stabilities=d["snap/res_stabilities"],
                mst=(d["snap/mst_u"], d["snap/mst_v"], d["snap/mst_w"]),
                weights=d["snap/res_weights"],
                min_cluster_size=float(d["snap/res_min_cluster_size"]),
                point_parent=d["snap/res_point_parent"],
                point_lambda=d["snap/res_point_lambda"],
                cluster_parent=d["snap/res_cluster_parent"],
                cluster_birth=d["snap/res_cluster_birth"],
                cluster_weight=d["snap/res_cluster_weight"],
                selected=d["snap/res_selected"],
                all_stabilities=d["snap/res_all_stabilities"],
            )
            snap = ClusterSnapshot(
                version=int(d["snap/version"]),
                n_points=int(d["snap/n_points"]),
                bubble_rep=np.asarray(d["snap/bubble_rep"]),
                bubble_n=np.asarray(d["snap/bubble_n"]),
                center=np.asarray(d["snap/center"]),
                result=res,
                wall_seconds=float(d["snap/wall_seconds"]),
                dirty_consumed=float(d["snap/dirty_consumed"]),
            )
        with self._snapshot_lock:
            self._version = int(d["eng/version"])
            self._snapshot = snap
        if self._flat is not None:
            if bool(d["flat/has"]):
                self._restore_flat(d)
            else:
                self._flat.stale = True
        if self.exact:
            # the dynamic MST state is NOT serialized: one rebuild from
            # the restored tree (the authoritative point store) at the
            # next refresh reproduces it
            self._dyn_stale = True
            self._pid2slot = {}
        return step

    def _restore_flat(self, d: dict):
        """Rebuild the device-resident flat table bit-for-bit: origin,
        slot order, and Kahan compensations all round-trip, so the next
        ε-pass compacts the same rows in the same order as the
        uninterrupted worker would have."""
        import jax.numpy as jnp

        f = self._flat
        f._alloc(int(d["flat/LS"].shape[0]))
        f.LS = jnp.asarray(d["flat/LS"])
        f.LSe = jnp.asarray(d["flat/LSe"])
        f.SS = jnp.asarray(d["flat/SS"])
        f.SSe = jnp.asarray(d["flat/SSe"])
        f.N = jnp.asarray(d["flat/N"])
        f.alive = jnp.asarray(d["flat/alive"])
        f.origin = np.array(d["flat/origin"], dtype=np.float64)
        f.leaf_of_slot = np.array(d["flat/leaf_of_slot"], dtype=np.int64)
        f.slot_of_leaf = {
            int(leaf): s for s, leaf in enumerate(f.leaf_of_slot) if leaf >= 0
        }
        f._free = d["flat/free"].astype(int).tolist()
        f._alive_host = np.array(d["flat/alive"], dtype=bool)
        f._hi = int(d["flat/hi"])
        f.loads = int(d["flat/loads"])
        f.stale = False

    # -- serve plane -------------------------------------------------------

    @property
    def snapshot(self) -> ClusterSnapshot | None:
        with self._snapshot_lock:
            return self._snapshot

    def query(self, X) -> np.ndarray:
        """Cluster labels for query points from the cached hierarchy:
        nearest-bubble assignment, label inherited (paper offline step 2).
        Never blocks on ingestion or re-clustering; -1 (noise) for all
        points when no snapshot exists yet.  Thin wrapper over the
        device-cached fused path (serving.query) — the snapshot's rep
        table is uploaded once per version, not per call."""
        return self.query_detailed(X).labels

    def query_detailed(self, X, *, snapshot: ClusterSnapshot | None = None) -> QueryResult:
        """Full per-query serve output: flat label, nearest-bubble row,
        distance to its representative, and membership strength derived
        from the condensed tree (DESIGN.md §9).  ``snapshot`` pins the
        pass to serve against (default: the newest published one) —
        label, representative, and λ arrays all come from that ONE
        snapshot object, so a concurrent swap can never mix versions."""
        snap = self.snapshot if snapshot is None else snapshot
        return self._query_engine.query_detailed(snap, X)

    def labels(self) -> tuple[np.ndarray, np.ndarray]:
        """(pids, labels) for every currently-alive point, via the cached
        snapshot (points inserted since the pass are assigned, not noise).

        Memoized on (snapshot version, tree mutation counter): repeated
        calls with no ingest/retire/pass in between skip the full
        alive-point round-trip and assignment; any churn invalidates."""
        snap = self.snapshot
        key = (0 if snap is None else snap.version, self.tree.mutations)
        cache = self._labels_cache  # ONE read: a concurrent overwrite
        #   between key check and payload unpack must not mix entries
        if cache is not None and cache[0] == key:
            pids, lab = cache[1]
            self.stats["label_cache_hits"] += 1
            return pids.copy(), lab.copy()
        pids, X = self.tree.alive_points()
        lab = self._query_engine.query(snap, X)
        self._labels_cache = (key, (pids, lab))
        return pids.copy(), lab.copy()
