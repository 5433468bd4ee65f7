"""Pallas TPU kernel: fused mutual-reachability distance tiles (Eq. 1/7).

``d_m(p, q) = max{cd(p), cd(q), d(p, q)}`` — fusing the sqrt and the
two core-distance broadcasts into the pairwise tile avoids materializing
the raw distance matrix in HBM (the paper computes d_m "on demand" for the
same reason; on TPU the fusion keeps the epilogue in VREGs).  Diagonal
blocks zero their diagonal (the convention hdbscan.mutual_reachability
uses) via an iota mask keyed on the global tile offsets.  Core
distances arrive as a (n, 1) column and a (1, m) row, the layouts their
broadcasts take in the tile, so both blocks lower on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 256
DEFAULT_BM = 256


def _mutual_reach_kernel(x_ref, y_ref, cdx_ref, cdy_ref, out_ref, *, bn, bm, zero_diag):
    x = x_ref[...]
    y = y_ref[...]
    xx = jnp.sum(x * x, axis=-1, keepdims=True)
    yy = jnp.sum(y * y, axis=-1, keepdims=True).T
    xy = jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    d = jnp.sqrt(jnp.maximum(xx + yy - 2.0 * xy, 0.0))
    cdx = cdx_ref[...]  # (bn, 1)
    cdy = cdy_ref[...]  # (1, bm)
    m = jnp.maximum(d, jnp.maximum(cdx, cdy))
    if zero_diag:
        i = pl.program_id(0)
        j = pl.program_id(1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0) + i * bn
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1) + j * bm
        m = jnp.where(rows == cols, 0.0, m)
    out_ref[...] = m


@functools.partial(jax.jit, static_argnames=("bn", "bm", "zero_diag", "interpret"))
def mutual_reachability(
    x: jax.Array,
    y: jax.Array,
    cd_x: jax.Array,
    cd_y: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    zero_diag: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """(n,d),(m,d),(n,),(m,) -> (n,m) mutual reachability distances."""
    n, d = x.shape
    m = y.shape[0]
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    grid = (n // bn, m // bm)
    kernel = functools.partial(_mutual_reach_kernel, bn=bn, bm=bm, zero_diag=zero_diag)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mutual_reach",
    )(
        x.astype(jnp.float32),
        y.astype(jnp.float32),
        cd_x.astype(jnp.float32).reshape(n, 1),
        cd_y.astype(jnp.float32).reshape(1, m),
    )
