"""Benchmark harness entry point: one section per paper table/figure plus
the roofline report.  Emits ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything (scaled)
  PYTHONPATH=src python -m benchmarks.run --only fig5,fig6
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help="comma list: fig3,fig3_dynamic,fig4,fig5,fig5_query,fig6,fig7,fig7_pruned,fig7_mesh,fig8,fig9,kernels,roofline",
    )
    ap.add_argument("--dryrun", default="dryrun_results.json")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    failures = []
    t_all = time.time()

    if want("fig3"):
        from . import fig3_feasibility

        _guard(fig3_feasibility.run, failures, "fig3")
    if want("fig3_dynamic"):
        from . import fig3_dynamic

        _guard(fig3_dynamic.run, failures, "fig3_dynamic")
    if want("fig4"):
        from . import fig4_quality_toy

        _guard(fig4_quality_toy.run, failures, "fig4")
    if want("fig5"):
        from . import fig5_latency

        _guard(fig5_latency.run, failures, "fig5")
    elif want("fig5_query"):
        # serve-plane query A/B alone (the full fig5 runs it too); merges
        # the `query` section into an existing fig5_latency.json
        from . import fig5_latency

        _guard(fig5_latency.run_query, failures, "fig5_query")
    if want("fig6"):
        from . import fig6_nmi

        _guard(fig6_nmi.run, failures, "fig6")
    if want("fig7"):
        from . import fig7_scalability

        _guard(fig7_scalability.run, failures, "fig7")
        _guard(fig7_scalability.run_pruned, failures, "fig7_pruned")
        _guard(fig7_scalability.run_mesh, failures, "fig7_mesh")
    else:
        if want("fig7_pruned"):
            # grid-pruned vs dense neighbor-engine L-sweep alone; merges
            # the `pruned` section into an existing fig7_scalability.json
            from . import fig7_scalability

            _guard(fig7_scalability.run_pruned, failures, "fig7_pruned")
        if want("fig7_mesh"):
            # mesh strip sweep alone (DESIGN.md §12); merges the `mesh`
            # section into an existing fig7_scalability.json
            from . import fig7_scalability

            _guard(fig7_scalability.run_mesh, failures, "fig7_mesh")
    if want("fig8"):
        from . import fig8_streaming

        _guard(fig8_streaming.run, failures, "fig8")
    if want("fig9"):
        from . import fig9_service

        _guard(fig9_service.run, failures, "fig9")
    if want("kernels"):
        from . import kernels_bench

        _guard(kernels_bench.run, failures, "kernels")
    if want("roofline"):
        if os.path.exists(args.dryrun):
            from . import roofline

            _guard(lambda: roofline.main(["--dryrun", args.dryrun]), failures, "roofline")
        else:
            print(f"roofline/skipped,0,no {args.dryrun} (run repro.launch.dryrun first)")

    dt = time.time() - t_all
    print(f"\ntotal,{dt * 1e6:.0f},{'OK' if not failures else 'FAILURES: ' + ','.join(failures)}")
    return 1 if failures else 0


def _guard(fn, failures, name):
    try:
        fn()
    except Exception:
        failures.append(name)
        traceback.print_exc()


if __name__ == "__main__":
    sys.exit(main())
