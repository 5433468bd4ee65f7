"""Share of the window's ε-passes whose hierarchy sweeps ran as compiled
Mosaic kernels: the growth of the engine's
``hierarchy_kernel_passes_total`` over the growth of its
``recluster_count``.  A program that does not count such passes, or a
window with no pass, gives None."""

from bench.counters import growth


def read(run):
    kernel, passes = growth(run, "hierarchy_kernel_passes_total"), growth(run, "recluster_count")
    if kernel is None or not passes:
        return None
    return kernel / passes
