"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that `jax.profiler` writes.  On a TPU
each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Modules``
holds one event per program run and whose line ``XLA Ops`` holds the
operations inside them (nested: a loop's event spans its body's).  The
benchmark's own host spans (``bench.*``, `jax.profiler.TraceAnnotation`)
sit on the host plane on the same clock.

`reduce_trace` gives, over the span ``bench.window``:

* ``busy_s``: the union of program intervals, averaged over the chips;
* ``kernel_s`` / ``kernel_calls``: summed device time and number of
  events of each named kernel (Pallas kernels keep their ``name`` as the
  HLO op name: ``mutual_reach.1 = ... custom-call``);
* ``top_ops``: device operations by self time (an op's time less the
  ops nested in it), largest first;
* ``idle_gaps``: the longest gaps between programs, each labelled by
  the host span that covers most of it (``none`` when no span does).
"""

from __future__ import annotations

import collections
import dataclasses

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.poll", "bench.query", "bench.generator")


@dataclasses.dataclass
class Event:
    start: float  # seconds on the trace clock
    dur: float
    name: str


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    chips: int
    kernel_s: dict
    kernel_calls: dict
    top_ops: list  # [[name, seconds]]
    idle_gaps: list  # [[label, seconds]]
    tail_s: float = 0.0  # window end less the last program's end: a long
    #   tail means the device record stopped early (events dropped)


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events) -> collections.Counter:
    """Self time of each op name: an op's duration less the durations of
    the ops directly nested in it."""
    own = collections.Counter()
    stack: list = []  # [(end, name)]
    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0] <= ev.start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= ev.dur
        own[ev.name] += ev.dur
        stack.append((ev.start + ev.dur, ev.name))
    return own


def label_gap(gap, spans) -> str:
    """The host span that covers most of a gap, or ``none``."""
    best, cover = "none", 0.0
    for name, ivs in spans.items():
        c = union_length(ivs, gap[0], gap[1])
        if c > cover:
            best, cover = name, c
    return best


def _hlo_name(name: str) -> str:
    """``%mutual_reach.1 = f32[...] custom-call(...)`` -> ``mutual_reach.1``."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _kernel(hlo: str) -> str:
    """``mutual_reach.1`` -> ``mutual_reach``."""
    base, _, tail = hlo.rpartition(".")
    return base if base and tail.isdigit() else hlo


def summarize(device_planes, host_spans, window, kernels) -> TraceSummary:
    """``device_planes``: per chip, (programs, ops) as lists of `Event`;
    ``host_spans``: span name -> [(start, end)]; ``window``: (lo, hi)."""
    lo, hi = window
    busy, kernel_s, kernel_calls = [], collections.Counter(), collections.Counter()
    own = collections.Counter()
    all_gaps, tail = [], 0.0
    for programs, ops in device_planes:
        iv = [(e.start, e.start + e.dur) for e in programs]
        tail = max(tail, hi - max((b for _, b in iv), default=lo))
        busy.append(union_length(iv, lo, hi))
        named = []
        for e in ops:
            if e.start < lo or e.start + e.dur > hi:
                continue
            hlo = _hlo_name(e.name)
            k = _kernel(hlo)
            if k in kernels:
                kernel_s[k] += e.dur
                kernel_calls[k] += 1
            named.append(Event(e.start, e.dur, hlo))
        own.update(self_times(named))
        all_gaps += gaps(iv, lo, hi)
    all_gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return TraceSummary(
        window_s=hi - lo,
        busy_s=sum(busy) / max(len(busy), 1),
        chips=len(busy),
        kernel_s=dict(kernel_s),
        kernel_calls=dict(kernel_calls),
        top_ops=[[n, s] for n, s in own.most_common(10)],
        idle_gaps=[[label_gap(g, host_spans), g[1] - g[0]] for g in all_gaps[:10]],
        tail_s=tail,
    )


def reduce_trace(path: str, kernels=("mutual_reach", "bubble_cd", "assign")) -> TraceSummary:
    """Read one ``.xplane.pb`` and reduce it over its ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))

    def events(line):
        return [Event(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name) for e in line.events]

    def host_events(line):
        for e in line.events:
            name = e.name
            if name.startswith("bench."):
                yield name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9

    planes, spans = [], collections.defaultdict(list)
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines:
                continue
            ops = events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            planes.append((events(lines["XLA Modules"]), ops))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, s0, s1 in host_events(ln):
                    if name == WINDOW_SPAN:
                        window = (s0, s1)
                    elif name in HOST_SPANS:
                        spans[name].append((s0, s1))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    if not planes:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    return summarize(planes, dict(spans), window, set(kernels))
