"""Pallas TPU kernel: nearest-bubble assignment (offline step 2, §4.2).

For every original point, the index of the closest data-bubble
representative.  The grid is (point tiles, representative tiles): each
step scores one (bl, bn) tile — representatives on sublanes, points on
lanes — as one MXU matmul, reduces it over the sublane axis, and folds
the tile's minimum into a running (1, bn) minimum/argmin that stays
resident in the output block across the representative sweep.  Both
outputs are lane-dense rows, so any point-tile width that is a multiple
of 128 (or the whole array) lowers on TPU, and VMEM holds one rep tile
at a time whatever L is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 256  # points per tile (lane axis of the outputs)
DEFAULT_BL = 512  # representatives per tile (reduction axis)

_BIG_IDX = 2**31 - 1


def _assign_kernel(x_ref, rep_ref, idx_ref, min_ref, *, bl):
    j = pl.program_id(1)
    x = x_ref[...]  # (bn, dp)
    r = rep_ref[...]  # (bl, dp)
    rr = jnp.sum(r * r, axis=-1, keepdims=True)  # (bl, 1)
    rx = jax.lax.dot_general(
        r, x, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (bl, bn)
    # ‖x‖² is constant per point, so the argmin runs on the shifted
    # form (ref._nearest); the wrapper adds it back for the distance
    sq = rr - 2.0 * rx
    m = jnp.min(sq, axis=0, keepdims=True)  # (1, bn)
    rows = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 0) + j * bl
    win = jnp.min(jnp.where(sq == m, rows, _BIG_IDX), axis=0, keepdims=True)

    @pl.when(j == 0)
    def _():
        min_ref[...] = m
        idx_ref[...] = win

    @pl.when(j > 0)
    def _():
        # strict <: on a tie the earlier tile (lower index) keeps the win
        better = m < min_ref[...]
        idx_ref[...] = jnp.where(better, win, idx_ref[...])
        min_ref[...] = jnp.where(better, m, min_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bl", "interpret"))
def assign(
    x: jax.Array,
    reps: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bl: int = DEFAULT_BL,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(n, d), (L, d) -> ((n,) int32 nearest-representative index,
    (n,) f32 row-shifted squared distance ``‖r‖² − 2·x·r`` at it).

    The true squared distance is the shifted one plus ``‖x‖²``."""
    n, d = x.shape
    L = reps.shape[0]
    assert n % bn == 0 and L % bl == 0, (n, bn, L, bl)
    idx, m = pl.pallas_call(
        functools.partial(_assign_kernel, bl=bl),
        grid=(n // bn, L // bl),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bl, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="assign",
    )(x.astype(jnp.float32), reps.astype(jnp.float32))
    return idx[0], m[0]
