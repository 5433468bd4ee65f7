"""The benchmark's harness: cells, set-up, the measured window, the check.

Everything that belongs to one cell is data found by name:
``BENCHMARK.json`` names the cell's configuration (a file of sizes under
``bench/configs``) and its traffic mix (``bench/traffic/<traffic>.json``),
and every metric is a reader ``bench/metrics/<metric>.py`` with a
function ``read(run) -> float | None`` over the `Run` record.

One general generator drives one `StreamingClusterEngine`:

* a writer that, per step, submits a block of new readings and expires
  as many of the oldest (FIFO), then calls ``poll``; closed-loop
  (``writer.mode = "closed"``) or at a fixed operation rate (``"rate"``);
* optionally an open-loop query schedule: Poisson arrivals at a fixed
  batch rate, sizes log-uniform between two bounds, sent by a pool of
  client threads when due, each timed from its due time.

Set-up fills the window (one ε-pass), runs warm-up steps of the
window's own shape and warms every query bucket the mix uses.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

from bench import compare
from bench.data import Stream


class HarnessError(RuntimeError):
    """The cell cannot be run as described."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# -- cells ----------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(root: Path, workload: str) -> Cell:
    """Resolve a workload of ``root/BENCHMARK.json`` to its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(w["name"], int(w["chips"]), c["name"], config, w["traffic"], traffic, e2e, per)


def load_reader(root: Path, metric: str):
    """The ``read`` function of ``root/bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise HarnessError(f"no reader for metric {metric!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what a run records -------------------------------------------------------------


@dataclasses.dataclass
class QueryRecord:
    due: float
    rows: np.ndarray
    sent: float = math.nan  # when a client thread took it
    start: float = math.nan  # call into the engine
    end: float = math.nan  # result back
    result: object = None  # QueryResult, None when the call failed


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read.  Times are host-clock seconds."""

    setup_s: float = math.nan
    window_start: float = math.nan
    window_end: float = math.nan
    ops_attempted: int = 0
    ops_failed: int = 0
    ops_applied: int = 0
    steps: list = dataclasses.field(default_factory=list)  # [t_submit, ops, t_covered]
    polls: list = dataclasses.field(default_factory=list)  # [(t_submit, t_return)]
    writer_lateness: list = dataclasses.field(default_factory=list)
    passes: list = dataclasses.field(default_factory=list)  # [(t, n_bubbles, poll_s)]
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    queries: list = dataclasses.field(default_factory=list)  # [QueryRecord]
    query_wait_cap: float = 60.0
    trace: object = None  # trace.TraceSummary of the traced run
    dim: int = 0
    device_kind: str = ""
    window_compiles: int = 0

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def op_lags(self):
        """(lag, ops) per writer step: submission to the first snapshot that
        covers it, or to the window's end where none did."""
        lag = [((c if c == c else self.window_end) - t, n) for t, n, c in self.steps]
        return np.array([x for x, _ in lag]), np.array([n for _, n in lag], float)

    def query_latencies(self):
        """Due time to result of every request due in the window; one that
        never came back counts with the longest wait the run allows."""
        out = []
        for q in self.queries:
            ok = q.result is not None and q.end == q.end
            out.append(q.end - q.due if ok else self.window_end + self.query_wait_cap - q.due)
        return np.array(out)


def weighted_percentile(x, w, q: float) -> float:
    """The q-th percentile of x with integer weights w (each value counted
    w times, numpy's linear interpolation)."""
    return float(np.percentile(np.repeat(x, w.astype(np.int64)), q))


class CompileMeter:
    """Compiles counted from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# -- the system under test -----------------------------------------------------------


def _annotate(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Harness:
    """One cell's engine, stream and bookkeeping for one seed."""

    def __init__(self, cell: Cell, seed: int):
        from repro.serving.stream import StreamingClusterEngine

        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed = cell, int(seed)
        st = cfg["stream"]
        self.stream = Stream(st["spec"], self.seed, int(tr.get("chunk", 65536)))
        self.eng = StreamingClusterEngine(
            self.stream.dim, min_pts=int(cfg["min_pts"]), compression=float(cfg["compression"]),
            epsilon=float(cfg["epsilon"]), max_block=int(cfg["max_block"]), **cfg["engine"])
        self.block = int(tr["block"])
        self.fifo: collections.deque = collections.deque()  # alive pids, oldest first
        self.pid_row: dict = {}  # pid -> stream position
        self.snapshots: dict = {}  # version -> ClusterSnapshot seen by the writer
        self.run = Run(dim=self.stream.dim)
        self.tracing = False

    # -- writer ---------------------------------------------------------------

    def _apply(self, X, pos, victims, record: bool):
        """Submit one insert request and one expiry request, poll, and book
        the outcome.  Returns the number of operations applied."""
        eng, run = self.eng, self.run
        t_sub = time.perf_counter()
        ticket = eng.submit_insert(X)
        if victims:
            eng.submit_delete(victims)
        n_ops = X.shape[0] + len(victims)
        try:
            with _annotate(self.tracing, "bench.poll"):
                applied = eng.poll()
        except Exception as e:  # noqa: BLE001 — a refused step is counted, not fatal
            log(f"poll failed: {type(e).__name__}: {e}")
            applied = 0
            if record:
                run.ops_failed += n_ops
        t_ret = time.perf_counter()
        for p in victims:
            self.pid_row.pop(p, None)
        if ticket.applied:
            for p, r in zip(ticket.pids, pos):
                self.pid_row[int(p)] = int(r)
                self.fifo.append(int(p))
        snap = eng.snapshot
        new = record and snap is not None and snap.version not in self.snapshots
        if new:
            self.snapshots[snap.version] = snap
        if record:
            run.ops_attempted += n_ops
            run.ops_applied += applied
            run.polls.append((t_sub, t_ret))
            run.steps.append([t_sub, n_ops, math.nan])
            if new:
                run.passes.append((t_ret, snap.n_bubbles, t_ret - t_sub))
                for s in reversed(run.steps):
                    if s[2] == s[2]:
                        break
                    s[2] = t_ret
        return applied

    def step(self, record: bool = True):
        """One writer step: ``block`` new readings in, the oldest out."""
        with _annotate(self.tracing, "bench.generator"):
            X, pos = self.stream.take(self.block)
            k = min(self.block, len(self.fifo))
            victims = [self.fifo.popleft() for _ in range(k)]
        return self._apply(X, pos, victims, record)

    # -- set-up ------------------------------------------------------------------

    def setup(self):
        """Fill the window (every request queued, then one poll, so the fill
        pays one ε-pass and not one per tenth of growth), warm the window's
        own step shapes, and warm the query buckets the mix uses.  The
        window then starts at the same point of the ε-cycle in every run."""
        cfg, tr = self.cell.config, self.cell.traffic
        window, req = int(cfg["window"]), int(tr.get("fill_request", self.block))
        t0 = time.perf_counter()
        tickets = []
        for s in range(0, window, req):
            X, pos = self.stream.take(min(req, window - s))
            tickets.append((self.eng.submit_insert(X), pos))
        self.eng.poll()
        for ticket, pos in tickets:
            for p, r in zip(ticket.pids, pos):
                self.pid_row[int(p)] = int(r)
                self.fifo.append(int(p))
        log(f"setup: filled {window} points in {time.perf_counter() - t0:.3f} s, "
            f"L={self.eng.snapshot.n_bubbles}")
        t0 = time.perf_counter()
        for _ in range(int(tr.get("warm_steps", 0))):
            self.step(record=False)
        self.snapshots = {self.eng.snapshot.version: self.eng.snapshot}
        q = tr.get("queries")
        if q:
            rows = self.query_pool()
            b = 8
            while b < 2 * int(q["max_rows"]):
                self.eng.query_detailed(rows[: min(b, int(q["max_rows"]))])
                b *= 2
        log(f"setup: warm-up in {time.perf_counter() - t0:.3f} s, "
            f"L={self.eng.snapshot.n_bubbles}, version {self.eng.snapshot.version}, "
            f"dirty {self.eng.tree.dirty_mass:.0f}")

    def query_pool(self) -> np.ndarray:
        """Readings the writer never ingests: new draws of the same
        mixture, from a fork of the stream."""
        if not hasattr(self, "_pool"):
            q = self.cell.traffic["queries"]
            self._pool, _ = self.stream.fork(1).take(int(q.get("pool_rows", 65536)))
        return self._pool

    # -- the measured window ---------------------------------------------------------

    def _schedule(self, seconds: float):
        """Due offsets and sizes of the open-loop requests: one fixed set
        drawn from the mix's own seed, in an order drawn from the run's."""
        q = self.cell.traffic["queries"]
        base = np.random.default_rng(int(q["order_seed"]))
        n = int(q["rate_per_s"] * seconds * 1.25) + 16
        gaps = base.exponential(1.0 / float(q["rate_per_s"]), size=n)
        lo, hi = math.log(int(q["min_rows"])), math.log(int(q["max_rows"]) + 1)
        sizes = np.floor(np.exp(base.uniform(lo, hi, size=n))).astype(np.int64)
        sizes = np.clip(sizes, int(q["min_rows"]), int(q["max_rows"]))
        order = np.random.default_rng([self.seed, 1])
        due = np.cumsum(order.permutation(gaps))
        sizes = order.permutation(sizes)
        offs = order.integers(0, len(self.query_pool()) - int(q["max_rows"]), size=n)
        keep = due < seconds
        return due[keep], sizes[keep], offs[keep]

    def _query(self, rec: QueryRecord):
        rec.sent = time.perf_counter()
        try:
            with _annotate(self.tracing, "bench.query"):
                rec.start = time.perf_counter()
                rec.result = self.eng.query_detailed(rec.rows)
                rec.end = time.perf_counter()
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            log(f"query failed: {type(e).__name__}: {e}")
            rec.result = None

    def window(self, seconds: float, meter: CompileMeter, tracing: bool = False,
               trace_dir: Path | None = None):
        """Drive the mix for ``seconds``; the last writer step may end past it."""
        import jax

        run, tr = self.run, self.cell.traffic
        run.stats0 = dict(self.eng.stats)
        compiles0 = meter.compiles
        q = tr.get("queries")
        if q:
            due, sizes, offs = self._schedule(seconds)
            pool = self.query_pool()
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            self.tracing = True
        span = _annotate(tracing, "bench.window")
        span.__enter__()  # left in stop_trace(), on this thread
        t0 = time.perf_counter()
        run.window_start = t0
        t_end = t0 + seconds
        threads = []
        if q:
            run.queries = [QueryRecord(due=t0 + d, rows=pool[o : o + s])
                           for d, s, o in zip(due, sizes, offs)]
            pool_ex = concurrent.futures.ThreadPoolExecutor(int(q["clients"]))
            futures = []

            def scheduler():
                for rec in run.queries:
                    wait = rec.due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    futures.append(pool_ex.submit(self._query, rec))

            threads.append(threading.Thread(target=scheduler, name="bench-queries"))
        w = tr["writer"]
        if w["mode"] == "rate":
            period = 2 * self.block / float(w["ops_per_s"])

            def writer():
                k = 0
                while True:
                    due_k = t0 + k * period
                    if due_k >= t_end:
                        break
                    now = time.perf_counter()
                    if due_k > now:
                        time.sleep(due_k - now)
                    run.writer_lateness.append(max(0.0, time.perf_counter() - due_k))
                    self.step()
                    k += 1

            threads.append(threading.Thread(target=writer, name="bench-writer"))
        # the configuration's trace length: one ε-cycle or more, short enough
        # that the device tracer keeps every event
        trace_end = t0 + min(seconds, float(self.cell.config.get("trace_seconds", seconds)))

        def stop_trace():
            if self.tracing:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.tracing = False

        for th in threads:
            th.start()
        if w["mode"] == "closed":
            while time.perf_counter() < t_end:
                self.step()
                if time.perf_counter() >= trace_end:
                    stop_trace()
        elif self.tracing:
            while time.perf_counter() < trace_end and any(th.is_alive() for th in threads):
                time.sleep(0.01)
            stop_trace()
        for th in threads:
            th.join()
        stop_trace()
        if q:
            done, pending = concurrent.futures.wait(futures, timeout=run.query_wait_cap)
            for f in done:
                f.result()
            pool_ex.shutdown(wait=False, cancel_futures=True)
            if pending:
                log(f"{len(pending)} queries never came back")
        run.window_end = (run.polls[-1][1] if w["mode"] == "closed"
                          else max([t_end] + [p[1] for p in run.polls]))
        run.stats1 = dict(self.eng.stats)
        run.window_compiles = meter.compiles - compiles0

    # -- close and check ------------------------------------------------------------

    def outputs(self) -> compare.Outputs:
        """Force the engine's own pass over the final summary and read what
        the timed path produced."""
        self.eng.flush()
        snap = self.eng.snapshot
        self.snapshots.setdefault(snap.version, snap)
        tree = self.eng.tree
        pids, X = tree.alive_points()

        def mine(s):
            return compare.Snapshot(version=s.version, rep=np.asarray(s.bubble_rep, np.float64),
                                    n=np.asarray(s.bubble_n, np.float64),
                                    labels=np.asarray(s.bubble_labels),
                                    mst_u=np.asarray(s.mst[0]), mst_v=np.asarray(s.mst[1]),
                                    mst_w=np.asarray(s.mst[2], np.float64),
                                    center=np.asarray(s.center, np.float64))

        served_versions = {q.result.version for q in self.run.queries if q.result is not None}
        served = [compare.Served(rows=q.rows,
                                 labels=None if q.result is None else q.result.labels,
                                 bubble_index=None if q.result is None else q.result.bubble_index,
                                 distance=None if q.result is None else q.result.distance,
                                 version=-1 if q.result is None else q.result.version)
                  for q in self.run.queries]
        return compare.Outputs(
            alive_pids=np.asarray(pids, np.int64), alive_X=np.array(X),
            leaf=np.array(tree.point_leaf[pids]), final=mine(snap), served=served,
            snapshots={v: mine(s) for v, s in self.snapshots.items() if v in served_versions})

    def expected(self) -> compare.Expected:
        return compare.Expected(pid_row=dict(self.pid_row), X=self.stream.rows(
            np.arange(self.stream.made())))


# -- one run of a cell --------------------------------------------------------------------


def device_info():
    import jax

    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: where the environment says, or
    a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / "bench" / ".out" / "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(root: Path, cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, meter: CompileMeter) -> dict:
    """One run: set-up, window, metrics, check.  Returns the result line."""
    import jax

    platform, kind, count = device_info()
    h = Harness(cell, seed)
    h.run.device_kind = kind
    h.setup()
    trace_dir = root / "bench" / ".out" / "trace"
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    h.window(seconds, meter, tracing=trace, trace_dir=trace_dir)
    run = h.run
    run.setup_s = run.window_start - t_start
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {run.window_s:.3f} s, {run.ops_applied} ops applied, "
        f"{len(run.passes)} passes, {len(run.queries)} queries, "
        f"{run.window_compiles} compiles in the window")
    print(f"compiles in window: {run.window_compiles}", flush=True)
    log("passes (end s into the window, poll s): " + ", ".join(
        f"({t - run.window_start:.3f}, {dt:.3f})" for t, _, dt in run.passes))
    late = run.writer_lateness + [q.sent - q.due for q in run.queries if q.sent == q.sent]
    if late:
        log(f"generator lateness: p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
            f"p95 {np.percentile(late, 95) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms")
    out = h.outputs()
    exp = h.expected()
    del h
    gc.collect()
    device = {"platform": platform, "kind": kind, "count": count, "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from bench.trace import reduce_trace

        path = next(trace_dir.rglob("*.xplane.pb"))
        t0 = time.perf_counter()
        run.trace = reduce_trace(path)
        log(f"trace: {path.stat().st_size} bytes read in {time.perf_counter() - t0:.3f} s; "
            f"last program ends {run.trace.tail_s:.3f} s before the window's end")
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops, "idle_gaps": run.trace.idle_gaps}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    t0 = time.perf_counter()
    nums = compare.numbers(cell.config, out, exp, sample_seed=seed)
    ok, checks = compare.verdict(nums, cell.config["limits"])
    log(f"reference check in {time.perf_counter() - t0:.3f} s")
    failed_q = sum(1 for q in run.queries if q.result is None)
    result = {
        "correct": bool(ok and run.ops_failed == 0 and failed_q == 0),
        "attempted": int(run.ops_attempted + len(run.queries)),
        "failed": int(run.ops_failed + failed_q),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v if math.isfinite(v) else 1e308, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return result
