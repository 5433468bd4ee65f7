"""Compile rehearsals for TPU v5e: the service path's Pallas kernels and
fused programs lowered and compiled at deployment sizes for a described
v5e chip, with no chip attached.

What the chip's compiler refuses — a block not aligned to the (8, 128)
tiling, more VMEM than a kernel may use, a program larger than HBM — it
refuses here too, so these tests guard every change at no chip time.
Nothing runs: they say nothing about results or speed.

`ops._interpret()` reads `jax.default_backend()`, which is the CPU here,
so every test patches it to False; otherwise the kernels would lower in
interpret mode and the program would hold no Mosaic kernel at all.
The topology is described inside a fixture (never at import: one process
at a time may load the TPU library, and every xdist worker imports this
file).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.serving import query

HBM_BYTES = 16 * 2**30  # one v5e chip
D = 16  # the chem deployment's width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_kernels(one_chip):
    """Kernels lower for Mosaic (not the interpreter) and the persistent
    compilation cache stays off: a compile for a described chip is
    written to it but cannot be read back without one.  Trace caches are
    cleared on both sides so no CPU trace is reused for the chip and no
    chip trace leaks into later CPU tests."""
    from jax.experimental.compilation_cache import compilation_cache

    saved_interpret = ops._interpret
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops._interpret = lambda: False
    jax.clear_caches()
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    ops._interpret = saved_interpret
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", saved_cache)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert total < HBM_BYTES, total
    return compiled.as_text()


@pytest.mark.parametrize("with_dist", [False, True])
def test_assign(compiled_kernels, with_dist):
    S = compiled_kernels
    hlo = _compile(functools.partial(ops.assign, use_ref=False, with_dist=with_dist),
                   S((1024, D)), S((8192, D)))
    assert "tpu_custom_call" in hlo


def test_bubble_core_distances(compiled_kernels):
    S = compiled_kernels
    hlo = _compile(lambda r, n, e: ops.bubble_core_distances(r, n, e, 15, use_ref=False),
                   S((8192, D)), S((8192,)), S((8192,)))
    assert "tpu_custom_call" in hlo


def test_mutual_reachability(compiled_kernels):
    S = compiled_kernels
    hlo = _compile(lambda x, c: ops.mutual_reachability(x, x, c, c, use_ref=False),
                   S((8192, D)), S((8192,)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("stage", ["core_distances", "mst"])
def test_grid_pass_32k(compiled_kernels, stage):
    """The grid-pruned pass at Lp = 32768, where a dense d_m is 4 GiB."""
    from repro.core.mst import boruvka_grid_jax
    from repro.kernels.grid import build_grid, grid_core_distances

    S = compiled_kernels
    Lp = 32768

    def cd(rep, n_b, extent, n):
        return grid_core_distances(build_grid(rep, jnp.arange(Lp) < n), n_b, extent, 15, D)

    def mst(rep, cd, n):
        return boruvka_grid_jax(build_grid(rep, jnp.arange(Lp) < n), cd)

    if stage == "core_distances":
        _compile(cd, S((Lp, D)), S((Lp,)), S((Lp,)), S((), jnp.int32))
    else:
        _compile(mst, S((Lp, D)), S((Lp,)), S((), jnp.int32))


def test_offline_pipeline_8k(compiled_kernels):
    """The fused dense ε-pass at Lp = 8192 holds the Mosaic kernels."""
    S = compiled_kernels
    Lp = 8192
    hlo = _compile(
        functools.partial(ops._offline_pipeline, min_pts=15, use_ref=False),
        S((Lp, D)), S((Lp,)), S((Lp,)), S((), jnp.int32), S(()),
    )
    assert hlo.count("tpu_custom_call") >= 2  # Eq. 6 strips + d_m tiles


def test_fused_query_1024(compiled_kernels):
    """A 1024-row query batch against an 8192-row snapshot."""
    S = compiled_kernels
    Lp = 8192
    hlo = _compile(
        functools.partial(query._fused_query, use_ref=False),
        S((1024, D)), S((Lp, D)), S((Lp,), jnp.int32), S((Lp,)), S((Lp,)),
    )
    assert "tpu_custom_call" in hlo
