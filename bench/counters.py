"""The program's own counters over the measured window.

The engine keeps its counters in ``stats``, which the harness copies at
the window's start (`Run.stats0`) and end (`Run.stats1`);
``stats["span_s"]`` holds the seconds spent inside each program span
(``repro.spans``), and each served query carries the seconds of its
``query.program`` spans (``QueryResult.program_s``).  A program without
the counter gives None, so every reader runs on older programs too.
"""

from __future__ import annotations


def growth(run, key: str):
    """Growth of ``stats[key]`` over the window."""
    if key not in run.stats0 or key not in run.stats1:
        return None
    return run.stats1[key] - run.stats0[key]


def span_growth(run, name: str):
    """Seconds spent inside the span ``name`` during the window."""
    s0, s1 = run.stats0.get("span_s"), run.stats1.get("span_s")
    if s0 is None or s1 is None or name not in s1:
        return None
    return s1[name] - s0.get(name, 0.0)


def per_kop(run, x):
    """``x`` per 1,000 operations applied in the window."""
    if x is None or run.ops_applied == 0:
        return None
    return x / (run.ops_applied / 1e3)


def span_ms_per_kop(run, name: str):
    """Milliseconds inside the span ``name`` per 1,000 operations applied
    in the window."""
    s = span_growth(run, name)
    return None if s is None else per_kop(run, 1e3 * s)


def query_program_s(run) -> list:
    """Seconds of the ``query.program`` spans of each served request."""
    out = []
    for q in run.queries:
        s = getattr(q.result, "program_s", None)
        if s is not None:
            out.append(s)
    return out
