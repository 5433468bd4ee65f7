"""Share (%) of the ``mutual_reach`` kernel's least time at the chip's
peaks in the device time the trace measured for it.  Every pass of the
traced window must share one bucket Lp; otherwise nothing is read."""

from bench.roofline import mutual_reach_work, roofline_pct


def _pow2(n):
    return max(8, 1 << (max(n - 1, 1)).bit_length())


def read(run):
    tr = run.trace
    if tr is None or not tr.kernel_calls.get("mutual_reach"):
        return None
    buckets = {_pow2(p[1]) for p in run.passes}
    if len(buckets) != 1:
        return None
    Lp = buckets.pop()
    calls = [mutual_reach_work(Lp, run.dim)] * tr.kernel_calls["mutual_reach"]
    return roofline_pct(calls, tr.kernel_s["mutual_reach"], run.device_kind)
