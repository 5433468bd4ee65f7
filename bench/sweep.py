"""Sweep of the query rate a cell's mix offers, to find its knee.

    python3 bench/sweep.py --workload gas16-w160k.query --seed 5 --seconds 15 --rates 50,100,200

One set-up, then one window per rate with the mix's writer running and
the open-loop query schedule at that batch rate.  Prints, per rate, the
latency quartiles from due time and whether the backlog grew: the
median latency of the last third of the requests against the first
third's.  The knee is the highest rate whose backlog stays bounded; the
mix runs at four fifths of it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated batch rates (1/s)")
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if p != here]
    import numpy as np

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    if harness.device_info()[0] != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    harness.cache_dir(ROOT)
    meter = harness.CompileMeter()
    cell.traffic = copy.deepcopy(cell.traffic)
    h = harness.Harness(cell, args.seed)
    h.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["queries"]["rate_per_s"] = rate
        h.run = harness.Run(dim=h.stream.dim)
        h.window(args.seconds, meter)
        lat = h.run.query_latencies() * 1e3
        k = max(len(lat) // 3, 1)
        wait = np.array([q.sent - q.due for q in h.run.queries]) * 1e3
        print(json.dumps({
            "rate_per_s": rate, "requests": int(len(lat)),
            "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_third_p50_ms": float(np.median(lat[:k])),
            "last_third_p50_ms": float(np.median(lat[-k:])),
            "client_wait_p95_ms": float(np.percentile(wait, 95)),
            "writer_ops_per_s": h.run.ops_applied / h.run.window_s,
            "failed": int(sum(q.result is None for q in h.run.queries)),
            "window_compiles": h.run.window_compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
