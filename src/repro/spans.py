"""Program spans: named host intervals on the profiler's clock.

A span is a `jax.profiler.TraceAnnotation`.  While a profiler session
runs (`jax.profiler.start_trace`) it is written on the trace's host plane,
on the clock the device planes share, so an idle gap on the chip can be
put down to the stage the host was in.  Outside a session it records
nothing and costs about a microsecond.  Spans sit at block granularity
only: per-point and per-step loops are counted, never spanned.

A span also times itself on the host clock (``seconds``) and, given a
dict, adds that time to its entry there: the counters the engine mirrors
into its ``stats`` are these sums, so a reader of the stats and a reader
of the trace see the same intervals.

`SPANS` is the one table of span names; `STAGES` names the scopes
(`jax.named_scope`) of the ε-pass program's stages, which the compiler
keeps as op-name metadata of the device ops.
"""

from __future__ import annotations

import time

import jax

__all__ = ["SPANS", "STAGES", "span"]

SPANS = (
    "stream.poll",  # StreamingClusterEngine.poll, the whole call
    "ingest.assign",  # point -> leaf assignment of one insert block
    "ingest.cf",  # clustering-feature bookkeeping of one block
    "ingest.maintain",  # Algorithm 1's split/dissolve fixpoint after a block
    "offline.capture",  # the summary rows one ε-pass reads
    "offline.pass",  # one ε-pass, host work and device program
    "offline.program",  # the pass's device program, call through result fetch
    "query.entry_build",  # a snapshot's device entry, built once per version
    "query.program",  # one chunk of a query: upload, fused program, fetch
)

STAGES = ("core_distance", "mutual_reach", "mst", "hierarchy")


class span:
    """``with span(name, into):`` — a profiler span over the block that
    leaves its host-clock duration in ``.seconds`` and, when ``into`` (a
    dict) is given, adds it to ``into[name]``."""

    __slots__ = ("name", "into", "seconds", "_ann", "_t0")

    def __init__(self, name: str, into: dict | None = None):
        self.name = name
        self.into = into
        self.seconds = 0.0

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.into is not None:
            self.into[self.name] = self.into.get(self.name, 0.0) + self.seconds
        return False
