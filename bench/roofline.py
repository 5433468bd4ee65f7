"""Operation and byte counts of the kernels, and their roofline shares.

A kernel's least time on a chip is the larger of its operations over
the peak rate and its bytes over the memory bandwidth (`bench/peaks.json`,
keyed by JAX's ``device_kind``); its roofline share is that least time
over the time the trace measured.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def mutual_reach_work(Lp: int, d: int) -> tuple[float, float]:
    """(flops, bytes) that one (Lp, Lp) mutual-reachability tile pass needs
    at least: 2·Lp²·d for the distance dot, the f32 output written once,
    both (Lp, d) f32 point sets and both core-distance vectors read once."""
    flops = 2.0 * Lp * Lp * d
    nbytes = 4.0 * (Lp * Lp + 2 * Lp * d + 2 * Lp)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def roofline_pct(calls, measured_s: float, device_kind: str) -> float | None:
    """Share (%) of the least time of ``calls`` — a list of (flops, bytes),
    one per kernel call — in ``measured_s``; None when nothing ran."""
    if not calls or measured_s <= 0:
        return None
    least = sum(least_seconds(f, b, device_kind) for f, b in calls)
    return 100.0 * least / measured_s
