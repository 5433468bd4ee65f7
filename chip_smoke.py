"""Smoke run of the streaming HDBSCAN service on a TPU, end to end.

    python chip_smoke.py             # phases A and B on one chip
    python chip_smoke.py --chips 4   # the mesh-sharded ε-pass on four chips

Phase A drives the default dense path of `StreamingClusterEngine`
(``backend="auto"``): a sliding window over the ``chem`` stand-in
(d = 16, the UCI gas-sensor array under dynamic gas mixtures) of 160,000
points, sliding by 16,000 for 4 slides, inserts and FIFO deletes in
blocks of 512.  The table settles near L = 8k bubbles (Lp = 8192), where
the compiled Pallas Eq. 6 strip and mutual-reachability kernels serve the
ε-pass and the Pallas assign kernel serves ingest and queries.  Its
reference re-clusters the last pass's bubble table with the numpy
`core.summarizer.cluster_bubbles`.

Phase B drives the serving-scale path: the same stream at a window of
640,000 with ``device_online=True`` and ``spatial_index=True``, L near
32k (Lp = 32768, where one dense f32 d_m matrix is 4 GiB).  Its reference
is a dense jnp pass on the chip over the same table — Eq. 6 in row strips
and a Prim MST over d_m computed on the fly — labelled by the numpy
hierarchy of `core.hdbscan`, because the fused dense pipeline needs
16 GiB there.

Both phases then answer 8 query batches of 1,024 rows and one of 1 row,
checked against an f64 nearest-representative replay on the host, and
score the surviving points against the generator's labels (NMI).

With ``--chips 4`` the script runs only phase B's window through an
engine built with ``mesh=True`` (the ε-pass row-sharded over all four
chips) and compares it with the one-chip pass over the same table in the
same process.

Every timing printed is from one cold run of this script, compilation
included where it says so: a smoke check, not a benchmark.  The script
exits nonzero, without a result line, when JAX finds no TPU or any phase
or check fails; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
COLD = "[cold smoke run, not a benchmark]"

# reference tolerances: bubble partitions must agree exactly or to this
# adjusted Rand index, and MST total weights to this relative error
# (CPU rehearsal of both phases at full size: see PERF.md)
LABEL_ARI_MIN = 0.99
MST_REL_TOL = 1e-4
# a query's served representative must be as near as the f64 nearest one
# up to this relative slack on the distance (f32 argmin near-ties)
QUERY_DIST_RTOL = 1e-4


class SmokeFailure(AssertionError):
    """A check of this script did not hold."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# the deployment both phases run: the chem stand-in (d = 16) at the
# engine's default summary steering and staleness threshold, seeded
SPEC = "chem"
COMPRESSION = 0.05
EPSILON = 0.1
SEED = 0


@dataclasses.dataclass(frozen=True)
class PhaseConfig:
    name: str
    window: int
    slide: int
    slides: int
    device_online: bool
    spatial_index: bool
    nmi_floor: float
    block: int = 512
    min_pts: int = 15
    query_batches: tuple = (1024,) * 8 + (1,)
    cd_strip: int = 1024  # rows per Eq. 6 strip of the dense reference


# NMI floors sit just under the CPU rehearsal of each phase at full size
# (jnp backend on XLA-CPU): A scored 0.8648, B 0.8986
PHASE_A = PhaseConfig("A", window=160_000, slide=16_000, slides=4,
                      device_online=False, spatial_index=False, nmi_floor=0.84)
PHASE_B = PhaseConfig("B", window=640_000, slide=16_000, slides=4,
                      device_online=True, spatial_index=True, nmi_floor=0.88)


class CompileMeter:
    """Compile time and persistent-cache traffic, from JAX's monitoring
    events.  ``snapshot()`` gives running totals; subtract two of them
    for a window."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> np.ndarray:
        return np.array([self.seconds, self.compiles, self.cache_hits,
                         self.cache_misses])


def _since(meter, mark) -> str:
    if meter is None:
        return "compile not metered"
    s, n, hit, miss = meter.snapshot() - mark
    return (f"compile {s:.2f} s in {int(n)} compiles "
            f"(persistent cache: {int(hit)} hits, {int(miss)} misses)")


def _mark(meter):
    return None if meter is None else meter.snapshot()


# -- the stream -------------------------------------------------------------


class _Window:
    """Drives an engine through the sliding window and keeps the pid→row
    map the NMI needs (the tree recycles pids after deletion)."""

    def __init__(self, eng, X, block):
        self.eng, self.X, self.block = eng, X, block
        self.fifo = collections.deque()  # alive pids, oldest first
        self.row_of = {}

    def slide(self, lo, hi, n_delete):
        """Insert rows [lo, hi) and retire the n_delete oldest points in
        blocks of ``block``, then drain and force the ε-pass."""
        tickets = [(s, self.eng.submit_insert(self.X[s:min(s + self.block, hi)]))
                   for s in range(lo, hi, self.block)]
        victims = [self.fifo.popleft() for _ in range(n_delete)]
        for s in range(0, n_delete, self.block):
            self.eng.submit_delete(victims[s:s + self.block])
        self.eng.flush()
        for p in victims:
            del self.row_of[p]
        for s, t in tickets:
            _check(t.applied, "an insert ticket was not applied")
            for k, p in enumerate(t.pids):
                self.row_of[p] = s + k
                self.fifo.append(p)


def _same_partition(a, b) -> bool:
    from repro.core.metrics import contingency

    C = contingency(a, b) > 0
    return bool((C.sum(axis=0) == 1).all() and (C.sum(axis=1) == 1).all())


def _compare_labels(what, labels, ref_labels, mst_w, ref_mst_w, emit):
    from repro.core.metrics import ari

    same = _same_partition(labels, ref_labels)
    score = 1.0 if same else ari(labels, ref_labels)
    rel = abs(mst_w - ref_mst_w) / max(abs(ref_mst_w), 1e-30)
    emit(f"  reference ({what}): same partition={same} ARI={score:.6f} "
         f"(min {LABEL_ARI_MIN}); clusters {len(set(labels) - {-1})} vs "
         f"{len(set(ref_labels) - {-1})}; MST weight {mst_w:.6f} vs "
         f"{ref_mst_w:.6f} (rel {rel:.2e}, tol {MST_REL_TOL:.0e})")
    _check(same or score >= LABEL_ARI_MIN,
           f"{what}: bubble labels disagree with the reference (ARI {score})")
    _check(rel <= MST_REL_TOL, f"{what}: MST weight off by {rel:.2e}")


# -- references -------------------------------------------------------------


def numpy_reference(rep, n_b, extent, min_pts, mcs):
    """The numpy oracle: `cluster_bubbles` with no device backend."""
    from repro.core.bubbles import DataBubbles
    from repro.core.summarizer import cluster_bubbles

    b = DataBubbles(rep=rep, n=n_b, extent=extent, dim=int(rep.shape[1]))
    res = cluster_bubbles(b, min_pts, min_cluster_size=mcs, backend=None)
    return res.labels, float(np.sum(res.mst[2]))


@functools.partial(jax.jit, static_argnames=("min_pts", "dim"))
def _cd_strip(rows, row_ids, rep, n_b, extent, min_pts, dim):
    from repro.kernels import ref

    return ref.bubble_core_distances_rows(rows, row_ids, rep, n_b, extent,
                                          min_pts, dim)


@jax.jit
def _prim_mst(rep, cd, n_valid):
    """Prim's MST over d_m(i, j) = max(cd_i, cd_j, ‖rep_i − rep_j‖) on the
    first n_valid rows, distances from plain differences (no expansion).
    Returns (parent, child, weight) of the n_valid − 1 tree edges."""
    Lp = rep.shape[0]
    iota = jnp.arange(Lp)

    def dm_from(v):
        diff = rep - rep[v]
        return jnp.maximum(jnp.sqrt(jnp.sum(diff * diff, axis=1)),
                           jnp.maximum(cd, cd[v]))

    in_tree = (iota == 0) | (iota >= n_valid)
    best = jnp.where(in_tree, jnp.inf, dm_from(0))
    parent = jnp.zeros(Lp, jnp.int32)
    eu = jnp.zeros(Lp, jnp.int32)
    ev = jnp.zeros(Lp, jnp.int32)
    ew = jnp.zeros(Lp, jnp.float32)

    def body(i, st):
        in_tree, best, parent, eu, ev, ew = st
        v = jnp.argmin(best).astype(jnp.int32)
        eu, ev, ew = eu.at[i].set(parent[v]), ev.at[i].set(v), ew.at[i].set(best[v])
        in_tree = in_tree.at[v].set(True)
        d = dm_from(v)
        closer = ~in_tree & (d < best)
        best = jnp.where(in_tree, jnp.inf, jnp.where(closer, d, best))
        parent = jnp.where(closer, v, parent)
        return in_tree, best, parent, eu, ev, ew

    st = jax.lax.fori_loop(0, n_valid - 1, body,
                           (in_tree, best, parent, eu, ev, ew))
    return st[3], st[4], st[5]


def dense_reference(rep, n_b, extent, min_pts, mcs, strip):
    """Dense jnp pass on the device in bounded memory: Eq. 6 over
    ``strip``-row slices of the (L, L) distance matrix, then Prim's MST
    over d_m, then the numpy hierarchy of `core.hdbscan`."""
    from repro.core.hdbscan import (condense_tree, extract_clusters,
                                    hdbscan_labels, single_linkage)
    from repro.kernels.ops import _PAD_COORD, _pow2_rows

    L, d = rep.shape
    Lp = _pow2_rows(L)
    rep_c = np.full((Lp, d), _PAD_COORD, np.float32)
    rep_c[:L] = rep - (n_b @ rep) / n_b.sum()
    nb_p = np.zeros(Lp, np.float32)
    nb_p[:L] = n_b
    ext_p = np.zeros(Lp, np.float32)
    ext_p[:L] = extent
    rep_d, nb_d, ext_d = jnp.asarray(rep_c), jnp.asarray(nb_p), jnp.asarray(ext_p)
    strip = min(strip, Lp)
    cd = jnp.concatenate([
        _cd_strip(rep_d[s:s + strip], jnp.arange(s, s + strip, dtype=jnp.int32),
                  rep_d, nb_d, ext_d, min_pts=int(min_pts), dim=int(d))
        for s in range(0, Lp, strip)
    ])
    eu, ev, ew = (np.asarray(a)[:L - 1]
                  for a in _prim_mst(rep_d, cd, jnp.int32(L)))
    slt = single_linkage(eu, ev, ew.astype(np.float64), L, weights=n_b)
    ct = condense_tree(slt, min_cluster_size=mcs)
    labels = hdbscan_labels(ct, extract_clusters(ct))
    return labels, float(np.sum(ew, dtype=np.float64))


def check_queries(snap, res, Q, what):
    """Served rows against an f64 nearest-representative replay."""
    n = Q.shape[0]
    _check(len(res) == n and res.version == snap.version,
           f"{what}: {len(res)} rows at version {res.version}")
    _check(np.isfinite(res.distance).all(), f"{what}: non-finite distance")
    _check(((res.strength >= 0) & (res.strength <= 1)).all(),
           f"{what}: strength outside [0, 1]")
    idx = res.bubble_index
    _check(((idx >= 0) & (idx < snap.n_bubbles)).all(), f"{what}: bad bubble row")
    _check((res.labels == snap.bubble_labels[idx]).all(),
           f"{what}: label is not the served bubble's label")
    q = Q - snap.center
    r = snap.bubble_rep - snap.center
    sq = (np.einsum("nd,nd->n", q, q)[:, None] + np.einsum("ld,ld->l", r, r)[None, :]
          - 2.0 * (q @ r.T))
    dist = np.sqrt(np.maximum(sq, 0.0))
    best = dist.min(axis=1)
    got = dist[np.arange(n), idx]
    _check((got <= best * (1 + QUERY_DIST_RTOL) + 1e-6).all(),
           f"{what}: a served bubble is not the nearest representative")
    _check(np.allclose(res.distance, got, rtol=1e-3, atol=1e-4),
           f"{what}: served distance disagrees with the replay")


# -- phases -----------------------------------------------------------------


def _engine(cfg, backend, mesh=None):
    from repro.data.synthetic import DATASET_SPECS
    from repro.serving.stream import StreamingClusterEngine

    return StreamingClusterEngine(
        DATASET_SPECS[SPEC]["d"],
        min_pts=cfg.min_pts, compression=COMPRESSION,
        epsilon=EPSILON, max_block=cfg.block, backend=backend,
        device_online=cfg.device_online, spatial_index=cfg.spatial_index,
        mesh=mesh,
    )


def _fill(cfg, eng, X, emit, meter):
    """Fill the window and run the slides; one forced ε-pass after each."""
    win = _Window(eng, X, cfg.block)
    steps = [(0, cfg.window, 0)] + [
        (cfg.window + k * cfg.slide, cfg.window + (k + 1) * cfg.slide, cfg.slide)
        for k in range(cfg.slides)
    ]
    for k, (lo, hi, n_del) in enumerate(steps):
        mark = _mark(meter)
        t0 = time.perf_counter()
        win.slide(lo, hi, n_del)
        wall = time.perf_counter() - t0
        snap = eng.snapshot
        emit(f"  {'fill' if k == 0 else f'slide {k}'}: +{hi - lo} −{n_del} "
             f"points, N={eng.tree.n_points} L={snap.n_bubbles}: ingest+pass "
             f"{wall:.3f} s, ε-pass {snap.wall_seconds:.3f} s; "
             f"{_since(meter, mark)} {COLD}")
    return win


def _expect_impl(cfg, stats, kernel):
    """The implementation each plane must have been served by."""
    dense = not cfg.spatial_index
    want = {
        "ingest_impl": kernel if dense else "grid",
        "query_impl": kernel if dense else "grid",
        "offline_impl": (
            {"core_distances": kernel, "mutual_reach": kernel, "mst": "boruvka-dense"}
            if dense else
            {"core_distances": "grid", "mutual_reach": "none", "mst": "boruvka-grid"}
        ),
    }
    got = {k: stats[k] for k in want}
    _check(got == want, f"phase {cfg.name} served by {got}, expected {want}")
    return got


def run_phase(cfg: PhaseConfig, *, backend="auto", kernel="pallas",
              emit=print, meter=None) -> dict:
    """One phase through `StreamingClusterEngine`: stream, ε-passes, the
    reference check, queries and NMI.  ``kernel`` is the implementation
    name (`ops.kernel_impl`) the dense stages must report.  Raises
    SmokeFailure when a check fails; returns the phase's numbers."""
    from repro.core.metrics import nmi
    from repro.data.synthetic import dataset
    from repro.kernels import ops

    dev = jax.devices()[0]
    emit(f"phase {cfg.name} on {dev.platform} {dev.device_kind}: window "
         f"{cfg.window} slide {cfg.slide} x{cfg.slides}, block {cfg.block}, "
         f"device_online={cfg.device_online} spatial_index={cfg.spatial_index}")
    t_phase = time.perf_counter()
    X, y = dataset(SPEC, cfg.window + cfg.slide * cfg.slides, seed=SEED)
    eng = _engine(cfg, backend)
    win = _fill(cfg, eng, X, emit, meter)
    snap = eng.snapshot
    impl = _expect_impl(cfg, eng.stats, kernel)
    emit(f"  served by: {impl}")

    # reference over the last pass's bubble table (no mutation since)
    tree = eng.tree
    _check(tree.dirty_mass == 0 and snap.n_points == tree.n_points,
           "the last pass does not cover the tree")
    if cfg.device_online:
        # snapshot rows follow the flat table's ascending-slot order
        leaves = eng._flat.leaf_of_slot[eng._flat.alive_slots()]
    else:
        leaves = tree.leaf_cf_buffers()[0]
    rep, extent, n_b, _ = ops.bubble_table(tree.LS, tree.SS, tree.N, leaves)
    _check(rep.shape == snap.bubble_rep.shape
           and np.allclose(rep, snap.bubble_rep, rtol=1e-4, atol=1e-4),
           "the reference table is not the pass's table")
    t0 = time.perf_counter()
    if cfg.device_online:
        what = "dense jnp on device"
        ref_labels, ref_w = dense_reference(rep, n_b, extent, eng.min_pts,
                                            eng.min_cluster_size, cfg.cd_strip)
    else:
        what = "numpy cluster_bubbles"
        ref_labels, ref_w = numpy_reference(rep, n_b, extent, eng.min_pts,
                                            eng.min_cluster_size)
    emit(f"  reference pass ({what}, L={len(n_b)}): "
         f"{time.perf_counter() - t0:.3f} s {COLD}")
    _compare_labels(what, snap.bubble_labels, ref_labels,
                    snap.total_mst_weight, ref_w, emit)

    rng = np.random.default_rng(SEED + 1)
    survivors = np.fromiter(win.row_of.values(), dtype=np.int64)
    for k, B in enumerate(cfg.query_batches):
        Q = X[rng.choice(survivors, size=B)]
        mark = _mark(meter)
        t0 = time.perf_counter()
        res = eng.query_detailed(Q)
        wall = time.perf_counter() - t0
        check_queries(snap, res, Q, f"query batch {k}")
        emit(f"  query batch {k} ({B} rows): {wall * 1e3:.3f} ms; "
             f"{_since(meter, mark)} {COLD}")

    pids, labels = eng.labels()
    score = nmi(y[[win.row_of[int(p)] for p in pids]], labels)
    emit(f"  NMI of {len(pids)} surviving points vs generator labels: "
         f"{score:.4f} (floor {cfg.nmi_floor})")
    _check(score >= cfg.nmi_floor, f"phase {cfg.name}: NMI {score} below floor")
    emit(f"phase {cfg.name} passed in {time.perf_counter() - t_phase:.1f} s {COLD}")
    return {"impl": impl, "nmi": score, "L": snap.n_bubbles}


def run_mesh(cfg: PhaseConfig, *, emit=print, meter=None) -> dict:
    """Phase B's window through an engine whose ε-pass is row-sharded
    over every visible chip, against the one-chip pass over the same
    table in this process."""
    dev = jax.devices()[0]
    eng = _engine(dataclasses.replace(cfg, slides=0), "auto", mesh=True)
    mesh = eng.mesh
    n_dev = len(jax.devices())
    _check(mesh.shape["data"] == n_dev and len(set(mesh.devices.flat)) == n_dev,
           f"host mesh {dict(mesh.shape)} does not spread rows over {n_dev} devices")
    emit(f"mesh phase on {n_dev} x {dev.platform} {dev.device_kind}: mesh "
         f"{dict(mesh.shape)}, window {cfg.window}")
    from repro.data.synthetic import dataset

    X, _ = dataset(SPEC, cfg.window, seed=SEED)
    _fill(dataclasses.replace(cfg, slides=0), eng, X, emit, meter)
    snap = eng.snapshot
    stages = eng.stats["offline_impl"]
    _check(stages["core_distances"] == "grid-sharded",
           f"the mesh pass was served by {stages}")
    emit(f"  sharded pass served by: {stages}")
    one_chip = dataclasses.replace(eng._flat.capture(eng.tree.n_points), mesh=None)
    t0 = time.perf_counter()
    res, rep, _, _ = one_chip.recluster(eng.backend, min_pts=eng.min_pts,
                                        min_cluster_size=eng.min_cluster_size)
    emit(f"  one-chip pass over the same table: {time.perf_counter() - t0:.3f} s "
         f"(compile included) {COLD}")
    _check(np.array_equal(rep, snap.bubble_rep), "the two passes read different tables")
    _compare_labels("one-chip pass", snap.bubble_labels, res.labels,
                    snap.total_mst_weight, float(np.sum(res.mst[2])), emit)
    same = (np.array_equal(snap.bubble_labels, res.labels)
            and np.array_equal(np.sort(snap.mst[2]), np.sort(res.mst[2])))
    emit(f"  sharded vs one-chip bitwise labels and MST weights: {same}")

    # the dense sharded pass over the same table keeps the whole (Lp, Lp)
    # distance matrix on every chip (9.0 GiB of a v5e chip at Lp = 32768
    # in the compile rehearsal)
    from repro.kernels import ops

    t0 = time.perf_counter()
    dense, *_ = eng._flat.capture(eng.tree.n_points).recluster(
        ops.get_backend("auto"), min_pts=eng.min_pts,
        min_cluster_size=eng.min_cluster_size)
    emit(f"  dense sharded pass ({dense.stages}): "
         f"{time.perf_counter() - t0:.3f} s (compile included) {COLD}")
    _check(dense.stages["core_distances"] == "jnp-sharded",
           f"the dense mesh pass was served by {dense.stages}")
    _compare_labels("dense sharded pass vs one-chip grid pass", dense.labels,
                    res.labels, float(np.sum(dense.mst[2])),
                    float(np.sum(res.mst[2])), emit)
    return {"impl": [stages, dense.stages], "bitwise": same}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded ε-pass over four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels import ops
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's code is missing: {e}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)

    def emit(line):
        print(line, flush=True)

    if ops.kernel_impl(False) != "pallas":
        print("chip_smoke: Pallas kernels would run interpreted", file=sys.stderr)
        return 1
    if args.chips == 4:
        phases = [functools.partial(run_mesh, PHASE_B)]
    else:
        phases = [functools.partial(run_phase, PHASE_A),
                  functools.partial(run_phase, PHASE_B)]
    t0 = time.perf_counter()
    failed = 0
    for phase in phases:
        try:
            report = phase(emit=emit, meter=meter)
            _check("interpret" not in json.dumps(report["impl"]),
                   f"a kernel ran interpreted: {report['impl']}")
        except Exception:  # noqa: BLE001 — the script's boundary: report, go on
            traceback.print_exc()
            failed += 1
    if failed:
        print(f"chip_smoke: {failed} phase(s) FAILED", file=sys.stderr)
        return 1
    emit(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
         f"{_since(meter, np.zeros(4))} {COLD}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
