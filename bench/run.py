"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload gas16-w160k.slide --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json`` (see `bench.harness`).  The run needs a
TPU: with no accelerator, or fewer chips than the cell asks for, it
exits 1 and prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``checks``,
every number compared beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if p != here]
    try:
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot load the harness: {e}", file=sys.stderr)
        return 1
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except (harness.HarnessError, OSError, KeyError, StopIteration, ValueError) as e:
        print(f"bench: {e!r}", file=sys.stderr)
        return 1
    import jax

    platform, kind, count = harness.device_info()
    if platform != "tpu":
        print(f"bench: JAX found no TPU (platform {platform!r}); the benchmark runs on "
              "the chip only", file=sys.stderr)
        return 1
    if count < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found {count}",
              file=sys.stderr)
        return 1
    try:
        import repro.serving.stream  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is missing: {e}", file=sys.stderr)
        return 1
    path = harness.cache_dir(ROOT)
    harness.log(f"device: {platform} {kind} x{count}; jax {jax.__version__}; "
                f"compile cache {path}")
    meter = harness.CompileMeter()
    result = harness.run_cell(ROOT, cell, abs(args.seed), args.seconds, bool(args.trace),
                              T_START, meter)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
