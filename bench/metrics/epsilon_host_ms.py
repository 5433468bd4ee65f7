"""Host time of one ε-pass: the program's ``offline.capture`` and
``offline.pass`` spans less its ``offline.program`` span (the device
program's call through its result fetch), summed over the window and
divided by the passes it ran."""

from bench.counters import growth, span_growth


def read(run):
    n = growth(run, "recluster_count")
    parts = [span_growth(run, s) for s in ("offline.capture", "offline.pass", "offline.program")]
    if not n or None in parts:
        return None
    capture, whole, program = parts
    return 1e3 * (capture + whole - program) / n
