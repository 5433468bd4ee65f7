"""Readings that set the limits of the correctness check.

    python3 bench/control.py --workload gas16-w160k.slide --seeds 11,12,13 --seconds 20

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the numbers that
`bench.compare.numbers` compares, twice: once for what the program
produced, and once for the control, the float64 references put in the
program's place one precision below what the configuration states
(`bench.compare.control_outputs`).  A limit lies above every program
reading and below every control reading.  Prints one JSON line per seed.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--no-control", action="store_true", help="program readings only")
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if p != here]
    from bench import compare, harness

    cell = harness.load_cell(ROOT, args.workload)
    platform, kind, count = harness.device_info()
    if platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    harness.cache_dir(ROOT)
    meter = harness.CompileMeter()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        h = harness.Harness(cell, seed)
        h.setup()
        h.window(args.seconds, meter)
        out, exp = h.outputs(), h.expected()
        del h
        gc.collect()
        t1 = time.perf_counter()
        line = {"seed": seed, "device": kind, "program": compare.numbers(cell.config, out, exp, seed)}
        t2 = time.perf_counter()
        if not args.no_control:
            ctl = compare.control_outputs(cell.config, out, exp)
            line["control"] = compare.numbers(cell.config, ctl, exp, seed)
        line["seconds"] = {"run": t1 - t0, "reference": t2 - t1,
                           "control": time.perf_counter() - t2}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
