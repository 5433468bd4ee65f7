"""Versioned, device-cached batched query subsystem (serve plane,
DESIGN.md §9).

PR 4 made the *write* path device-resident; this module is its read-path
counterpart.  The previous serve plane re-uploaded the full bubble-rep
table to the device on EVERY `query()` call — a host→device transfer
that scales with L on the hottest endpoint in the system.  Here a
published `ClusterSnapshot` is placed on device ONCE:

  snapshot entry   `SnapshotDeviceCache` builds one immutable
                   `DeviceSnapshotEntry` per snapshot *version*: the
                   mean-centered f32 rep table, flat labels, and the
                   per-bubble λ / per-cluster λ_max arrays padded into a
                   power-of-two L-bucket (snapshot swaps between passes
                   re-upload but do NOT re-jit while the bucket holds).
                   Entries are keyed by version and never patched in
                   place — a reader holding version v keeps a fully
                   consistent view while version v+1 publishes.

  fused program    `_fused_query` is ONE jit'd call per (batch-bucket,
                   L-bucket) pair: nearest-rep assignment through
                   `kernels/assign.py` (behind the engine's
                   `ClusterBackend` switch, with the fused min-distance
                   output) → label gather → membership strength.  Query
                   batches pad to power-of-two row buckets, so steady
                   traffic at any size hits a warm compile.

  membership       strength is derived from the condensed tree the
                   snapshot already carries (hdbscan's probabilities
                   generalized to out-of-sample points, after McInnes &
                   Healy's prediction-on-summary and Malzer & Baum's
                   richer per-query outputs): for a query q assigned
                   bubble b with flat label c at distance r,

                     λ_q = 1 / r,
                     strength(q) = clip(min(λ_q, λ_b) / λ_max(c), 0, 1)

                   where λ_b is b's condensed-tree departure λ
                   (`point_lambda`) and λ_max(c) the largest λ among
                   c's member bubbles (the cluster's "death").  At
                   r → 0 this converges to b's own membership
                   probability λ_b / λ_max(c); far queries decay to 0;
                   noise assignments are exactly 0.

  micro-batching   `QueryBatcher` generalizes the request plane's
                   `HostBatcher` to the serve plane: concurrent callers
                   enqueue (X, ticket) pairs, a leader-elected caller
                   drains them into one fused dispatch, and results fan
                   back out by ticket — concurrent batch-1 callers ride
                   one device call instead of N.

`StreamingClusterEngine.query()` / `.labels()` are thin wrappers over
this module; `query_detailed()` exposes the full per-query output
(label, nearest-bubble index, distance, membership strength, snapshot
version).
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.grid import GridIndex, build_grid, grid_assign
from repro.spans import span

from .batcher import HostBatcher

__all__ = [
    "QueryResult",
    "DeviceSnapshotEntry",
    "SnapshotDeviceCache",
    "QueryEngine",
    "QueryBatcher",
    "validate_query",
]

_MIN_BUCKET = 8  # f32 sublane floor shared with the offline buckets
_MAX_CHUNK = 1 << 14  # huge batches split into bucketed chunks
_EPS = 1e-12
_LAM_CEIL = 1e30  # finite stand-in for λ = ∞ (duplicate-heavy bubbles)


def _bucket(n: int) -> int:
    return max(_MIN_BUCKET, 1 << (max(n - 1, 1)).bit_length())


def validate_query(X, dim: int) -> np.ndarray:
    """Normalize query input to (n, dim) f64, mirroring the ingest-side
    validation (`submit_insert`): zero-ROW inputs are 0 points, a 1-D
    length-``dim`` vector is a single point, anything else — including
    n rows of the wrong feature count, 0 features among them — raises.

    The pre-validation serve plane ran ``np.atleast_2d`` unchecked: a
    bare ``[]`` became shape (1, 0) and returned one garbage label.
    """
    X = np.asarray(X, dtype=np.float64)
    shape = X.shape
    if X.ndim == 1:
        if X.shape[0] == 0:
            return X.reshape(0, dim)
        if X.shape[0] != dim:
            raise ValueError(f"expected (n, {dim}) query points, got {shape}")
        X = X[None, :]
    if X.ndim != 2:
        raise ValueError(f"expected (n, {dim}) query points, got {shape}")
    if X.shape[0] == 0:
        return X.reshape(0, dim)
    if X.shape[1] != dim:
        # NOT forgiven for being empty: (n, 0) carries n real rows the
        # caller expects answers for — silently dropping them misaligns
        # every downstream pairing
        raise ValueError(f"expected (n, {dim}) query points, got {shape}")
    return X


# trace-contract: fused_query rules=f32,no-callbacks,pow2
@functools.partial(jax.jit, static_argnames=("use_ref",))
def _fused_query(xc, reps, labels, lam, lam_max, use_ref: bool):
    """assign → label gather → membership strength, one compiled program
    per (batch-bucket, L-bucket) pair.  ``xc`` rows are mean-centered in
    the snapshot's frame; pad rows (both query- and L-side) are sliced
    away by the caller."""
    idx, dist = ops.assign(xc, reps, use_ref=use_ref, with_dist=True)
    lbl = labels[idx]
    lam_b = lam[idx]
    lam_c = jnp.maximum(lam_max[idx], _EPS)
    lam_q = 1.0 / jnp.maximum(dist, _EPS)
    strength = jnp.clip(jnp.minimum(lam_q, lam_b) / lam_c, 0.0, 1.0)
    strength = jnp.where(lbl >= 0, strength, 0.0)
    return idx, lbl, dist, strength


# trace-contract: fused_query_grid rules=f32,no-callbacks,pow2,no-dense
@jax.jit
def _fused_query_grid(xc, grid, labels, lam, lam_max):
    """Spatial-index variant of `_fused_query`: the snapshot entry carries
    a `GridIndex` built ONCE per version, so each batch only pays the
    query-side Morton sort plus the tiles that can still beat the running
    nearest.  Bit-exact vs the dense program (kernels.grid contract);
    grid candidates exclude the L-bucket pad rows by construction, so the
    caller's pad-hit guard is vestigial here."""
    idx, m = grid_assign(grid, xc)
    idx = jnp.minimum(idx, labels.shape[0] - 1)  # empty-grid belt-and-braces
    xx = jnp.sum(xc * xc, axis=-1)
    dist = jnp.sqrt(jnp.maximum(xx + m, 0.0))
    lbl = labels[idx]
    lam_b = lam[idx]
    lam_c = jnp.maximum(lam_max[idx], _EPS)
    lam_q = 1.0 / jnp.maximum(dist, _EPS)
    strength = jnp.clip(jnp.minimum(lam_q, lam_b) / lam_c, 0.0, 1.0)
    strength = jnp.where(lbl >= 0, strength, 0.0)
    return idx, lbl, dist, strength


@dataclasses.dataclass(frozen=True)
class DeviceSnapshotEntry:
    """One snapshot version's device residency.  Immutable: swaps build
    a NEW entry under the next version key, never patch these arrays."""

    version: int
    n_bubbles: int
    bucket: int  # Lp — power-of-two row count of the device arrays
    center: np.ndarray  # (d,) f64 — subtract before the f32 program
    reps: jax.Array  # (Lp, d) f32 mean-centered representatives
    labels: jax.Array  # (Lp,) int32 flat labels, -1 noise/pad
    lam: jax.Array  # (Lp,) f32 per-bubble condensed-tree λ
    lam_max: jax.Array  # (Lp,) f32 λ_max of the bubble's cluster
    grid: GridIndex | None = None  # spatial index over the L real rows


def _build_entry(snap, spatial: bool = False) -> DeviceSnapshotEntry:
    """Host-side O(L·d) derivation + ONE upload per published snapshot."""
    L = snap.n_bubbles
    d = int(snap.bubble_rep.shape[1])
    Lp = _bucket(L)
    # pad rows sit far away (never the nearest bubble for real queries)
    # and carry label -1 / λ 0, so even a pathological hit serves noise
    rep_c = np.full((Lp, d), ops._PAD_COORD, dtype=np.float32)
    rep_c[:L] = (snap.bubble_rep - snap.center[None, :]).astype(np.float32)
    lbl = np.full(Lp, -1, dtype=np.int32)
    lbl[:L] = snap.bubble_labels
    raw_lam = np.asarray(snap.result.point_lambda, dtype=np.float64)
    finite = np.isfinite(raw_lam)
    lam = np.zeros(Lp, dtype=np.float32)
    lam[:L] = np.where(finite, np.minimum(raw_lam, _LAM_CEIL), _LAM_CEIL)
    # per-cluster death λ: segment max of FINITE member λ only.  λ = ∞
    # (duplicate-heavy bubbles that never leave before the cluster dies)
    # means membership probability 1 — it must contribute ∞ to the
    # numerator (capped at _LAM_CEIL, so min(λ_q, λ_b) = λ_q wins), NOT
    # poison the denominator for every sibling; clusters whose members
    # are all ∞ fall back to a denominator of 1.
    lam_max = np.ones(Lp, dtype=np.float32)
    member = lbl[:L] >= 0
    if member.any():
        acc = np.zeros(int(lbl[:L].max()) + 1, dtype=np.float64)
        contrib = member & finite
        if contrib.any():
            np.maximum.at(acc, lbl[:L][contrib], raw_lam[contrib])
        acc = np.where(acc > 0.0, acc, 1.0)
        lmx = np.ones(L, dtype=np.float64)
        lmx[member] = np.maximum(acc[lbl[:L][member]], _EPS)
        lam_max[:L] = lmx
    reps_dev = jnp.asarray(rep_c)
    # grid amortization: ONE build per published version, shared by every
    # query batch served against it (the whole point of entry residency)
    grid = build_grid(reps_dev, jnp.arange(Lp) < L) if spatial else None
    return DeviceSnapshotEntry(
        version=int(snap.version),
        n_bubbles=L,
        bucket=Lp,
        center=np.asarray(snap.center, dtype=np.float64),
        reps=reps_dev,
        labels=jnp.asarray(lbl),
        lam=jnp.asarray(lam),
        lam_max=jnp.asarray(lam_max),
        grid=grid,
    )


class SnapshotDeviceCache:
    """Device entries keyed by snapshot VERSION — never patched in place.

    Readers racing a snapshot swap stay consistent: whichever snapshot
    object a reader captured, `entry()` hands back (or builds) the entry
    for exactly that version, and the arrays inside are immutable.  A
    small LRU (on ACCESS, not insertion — a version still being actively
    served must outlive ``keep`` newer publishes) keeps recent versions
    resident so in-flight readers of the previous snapshot don't rebuild.

    Builds are **single-flight** per key: the first caller of a fresh
    version builds the entry (O(L·d) derivation + device upload) while
    every racer blocks on that build's event and reuses the result —
    N readers racing a publish cost ONE build, not N.  A failed build
    releases the key so the next caller retries rather than inheriting a
    poisoned entry.

    ``key`` scopes entries for shared use: the multi-tenant router passes
    ``(tenant, version)`` so independent engines can pool ONE cache (and
    one device-memory budget) without their version counters colliding.
    """

    def __init__(self, keep: int = 4, spatial: bool = False):
        self.keep = int(keep)
        self.spatial = bool(spatial)
        self._entries: dict = {}  # guarded-by: _lock
        self._order: list = []  # guarded-by: _lock
        # key -> Event of the in-flight build
        self._building: dict = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self.hits = 0  # guarded-by: _lock
        self.builds = 0  # guarded-by: _lock

    def entry(self, snap, key=None) -> DeviceSnapshotEntry:
        k = int(snap.version) if key is None else key
        while True:
            with self._lock:
                e = self._entries.get(k)
                if e is not None:
                    self.hits += 1
                    # refresh recency: a reader pinned to an old version
                    # must not lose its entry to newer publishes it outlived
                    self._order.remove(k)
                    self._order.append(k)
                    return e
                ev = self._building.get(k)
                if ev is None:  # we are the builder
                    ev = threading.Event()
                    self._building[k] = ev
                    break
            # single-flight follower: wait for the builder, then re-check
            # (entry installed, or the build failed and the key is free)
            ev.wait()
        try:
            with span("query.entry_build"):
                e = _build_entry(snap, self.spatial)  # unlocked: O(L·d) + upload
        except BaseException:
            with self._lock:
                del self._building[k]
            ev.set()  # wake followers so they observe the failure/retry
            raise
        with self._lock:
            self._entries[k] = e
            self._order.append(k)
            self.builds += 1
            del self._building[k]
            while len(self._order) > self.keep:
                self._entries.pop(self._order.pop(0), None)
        ev.set()
        return e


@dataclasses.dataclass
class QueryResult:
    """Per-query serve-plane output (`query_detailed`)."""

    labels: np.ndarray  # (n,) int64 flat labels, -1 noise
    bubble_index: np.ndarray  # (n,) int64 snapshot row of the nearest bubble
    distance: np.ndarray  # (n,) f64 distance to that representative
    strength: np.ndarray  # (n,) f64 membership strength in [0, 1]
    version: int  # snapshot version served (0 = none yet)
    # seconds in the "query.program" spans of the call that served these
    # rows (upload, fused program, fetch); None when no program ran
    program_s: float | None = None

    def __len__(self) -> int:
        return int(self.labels.shape[0])


def _empty_result(n: int, version: int) -> QueryResult:
    return QueryResult(
        labels=np.full(n, -1, dtype=np.int64),
        bubble_index=np.full(n, -1, dtype=np.int64),
        distance=np.full(n, np.inf, dtype=np.float64),
        strength=np.zeros(n, dtype=np.float64),
        version=int(version),
    )


class QueryEngine:
    """Batched queries against a `ClusterSnapshot` through the device
    cache.  Stateless per call apart from the cache: the caller passes
    whichever snapshot object it captured, so labels, representatives,
    and λ arrays always come from that ONE snapshot."""

    def __init__(self, backend, dim: int, cache_keep: int = 4, *,
                 cache: SnapshotDeviceCache | None = None, scope=None):
        """``cache``/``scope`` support multi-tenant pooling: tenants share
        ONE SnapshotDeviceCache (one LRU budget, one set of L-bucket
        compile shapes) with entries keyed ``(scope, version)`` so their
        independent version counters never collide."""
        self.backend = backend
        self.dim = int(dim)
        self.scope = scope
        self.cache = cache if cache is not None else SnapshotDeviceCache(
            keep=cache_keep, spatial=getattr(backend, "spatial_index", False)
        )

    def _cache_key(self, version: int):
        v = int(version)
        return v if self.scope is None else (self.scope, v)

    def query_detailed(self, snap, X) -> QueryResult:
        X = validate_query(X, self.dim)
        n = X.shape[0]
        if snap is None or snap.n_bubbles == 0 or n == 0:
            return _empty_result(n, 0 if snap is None else snap.version)
        entry = self.cache.entry(snap, key=self._cache_key(snap.version))
        parts = []
        program_s = 0.0
        for c0 in range(0, n, _MAX_CHUNK):
            Xr = X[c0 : c0 + _MAX_CHUNK]
            m = Xr.shape[0]
            Bp = _bucket(m)
            xc = np.zeros((Bp, self.dim), dtype=np.float32)
            xc[:m] = Xr - entry.center[None, :]
            with span("query.program") as sp:
                if entry.grid is not None:
                    out = _fused_query_grid(
                        jnp.asarray(xc), entry.grid, entry.labels, entry.lam,
                        entry.lam_max,
                    )
                else:
                    out = _fused_query(
                        jnp.asarray(xc), entry.reps, entry.labels, entry.lam,
                        entry.lam_max, self.backend.use_ref,
                    )
                out = jax.device_get(out)  # ONE host sync
            program_s += sp.seconds
            idx, lbl, dist, strength = (a[:m].copy() for a in out)
            # a query out past _PAD_COORD can land on an L-bucket pad row:
            # it must surface as "no bubble" (the _empty_result convention),
            # never as a fictitious row ≥ n_bubbles with distance ~0
            pad_hit = idx >= entry.n_bubbles
            if pad_hit.any():
                idx[pad_hit] = -1
                lbl[pad_hit] = -1
                dist[pad_hit] = np.inf
                strength[pad_hit] = 0.0
            parts.append((idx, lbl, dist, strength))
        idx, lbl, dist, strength = (np.concatenate(a) for a in zip(*parts))
        return QueryResult(
            labels=lbl.astype(np.int64),
            bubble_index=idx.astype(np.int64),
            distance=dist.astype(np.float64),
            strength=strength.astype(np.float64),
            version=int(snap.version),
            program_s=program_s,
        )

    def query(self, snap, X) -> np.ndarray:
        return self.query_detailed(snap, X).labels


class _QueryTicket:
    __slots__ = ("event", "result", "error")

    def __init__(self):
        self.event = threading.Event()
        self.result: QueryResult | None = None
        self.error: BaseException | None = None


class QueryBatcher:
    """Micro-batch concurrent `query()` callers into one fused dispatch.

    The serve-plane generalization of the request plane's `HostBatcher`
    coalescing: callers push (X, ticket) pairs, whoever grabs the
    dispatch lock drains contiguous pending requests (point-counted, the
    same `next_block(size=...)` discipline the ingestion scheduler
    uses), runs ONE device-cached query over the concatenation, and fans
    the slices back out by ticket.  Followers wait on their ticket and
    periodically re-contend for the lock, so a request pushed in the
    gap after the leader's last drain never strands.

    **Leader-death contract**: a caller whose acquire won the dispatch
    lock is executing OTHER callers' requests.  ANY failure while it
    holds a drained block — the fused call raising on a poisoned batch,
    the concatenation, a malformed result — fans the exception out to
    every ticket in that block and re-raises at each ticket's caller;
    followers must never spin forever on a ticket their dead leader
    popped from the queue.

    **Multi-tenant dispatch** (serving.tenants): one batcher can front
    many engines — requests are tagged with a ``kind`` (the tenant name)
    and ``resolve(kind)`` maps each drained block to its engine.
    HostBatcher only coalesces contiguous SAME-kind runs, so a block
    never mixes tenants and each still rides one fused device call.
    """

    def __init__(self, engine=None, max_batch: int = 1024,
                 poll_s: float = 0.002, resolve=None):
        if engine is None and resolve is None:
            raise ValueError("QueryBatcher needs an engine or a resolve(kind)")
        self.engine = engine  # StreamingClusterEngine (or anything with
        self.poll_s = float(poll_s)  # .query_detailed and ._query_engine)
        self._resolve = resolve if resolve is not None else (lambda kind: self.engine)
        self._q = HostBatcher(max_block=int(max_batch))
        self._dispatch = threading.Lock()
        self.batches = 0  # guarded-by: _dispatch
        self.fanned_out = 0  # guarded-by: _dispatch

    def query_detailed(self, X, *, kind: str = "query") -> QueryResult:
        eng = self._resolve(kind)
        # validate in the CALLER so bad input raises here, not in a peer
        X = validate_query(X, eng._query_engine.dim)
        if X.shape[0] == 0:
            return eng.query_detailed(X)
        t = _QueryTicket()
        self._q.push((X, t), kind=kind)
        while True:
            if self._dispatch.acquire(blocking=False):
                try:
                    self._drain(own=t)
                except BaseException as e:  # noqa: BLE001 — leader died
                    # outside any block's fan-out (e.g. next_block itself):
                    # surface on our own ticket rather than escaping with
                    # the ticket still pending
                    if not t.event.is_set():
                        t.error = e
                        t.event.set()
                finally:
                    self._dispatch.release()
            if t.event.wait(self.poll_s):
                break
        if t.error is not None:
            raise t.error
        return t.result

    def query(self, X, *, kind: str = "query") -> np.ndarray:
        return self.query_detailed(X, kind=kind).labels

    def _drain(self, own: _QueryTicket | None = None):  # holds: _dispatch
        """Service pending blocks; a leader caller stops once its OWN
        ticket is fulfilled (remaining requests are drained by their own
        pushers' acquire loops), so one unlucky caller never turns into
        a dedicated server thread with unbounded latency.

        Only ever called with `_dispatch` held (query_detailed's
        try/acquire loop), hence the `# holds:` annotation above."""
        while self._q and not (own is not None and own.event.is_set()):
            kind, items = self._q.next_block(size=lambda it: it[0].shape[0])
            try:
                # EVERYTHING between popping the block and completing its
                # tickets runs under the fan-out guard: once items left
                # the queue, this leader is the only thread that can ever
                # complete them
                eng = self._resolve(kind)  # may-acquire: TenantRouter._lock
                X = np.concatenate([x for x, _ in items], axis=0)
                # may-acquire: StreamingClusterEngine._snapshot_lock, SnapshotDeviceCache._lock
                res = eng.query_detailed(X)
                if len(res) != X.shape[0]:
                    raise RuntimeError(
                        f"batched query returned {len(res)} rows "
                        f"for {X.shape[0]} requests"
                    )
                out = []
                off = 0
                for x, _ in items:
                    sl = slice(off, off + x.shape[0])
                    out.append(
                        QueryResult(
                            labels=res.labels[sl],
                            bubble_index=res.bubble_index[sl],
                            distance=res.distance[sl],
                            strength=res.strength[sl],
                            version=res.version,
                            program_s=res.program_s,
                        )
                    )
                    off += x.shape[0]
            except BaseException as e:  # noqa: BLE001 — fanned out, not handled
                for _, t in items:
                    if not t.event.is_set():
                        t.error = e
                        t.event.set()
                continue
            # fan out only after EVERY slice exists — a mid-loop failure
            # above must poison the whole block, not complete half of it
            for (_, t), r in zip(items, out):
                t.result = r
                t.event.set()
            self.batches += 1
            self.fanned_out += len(items)
