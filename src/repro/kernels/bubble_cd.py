"""Pallas TPU kernel: tiled bubble core distances (Eq. 6).

The jnp reference (`ref.bubble_core_distances`) materializes the full
(L, L) bubble distance matrix and argsorts every row to run the
weighted-rank scan.  This kernel is blocked over bubble *rows*: each grid
step holds one (bn, L) distance strip in VMEM — nothing L×L ever exists
in HBM — and replaces the sort with ``min_pts`` rounds of masked
lexicographic-min extraction.

Layout: the whole table stays resident, stored TRANSPOSED as a (dp, L)
block (features on sublanes, bubbles on lanes) next to (1, L) mass and
extent rows, so its VMEM footprint is d·L words rather than a lane-padded
128·L, and every per-bubble vector is lane-dense.  The output is a
(L, 1) column written in (bn, 1) blocks — the natural layout of a
lane-axis reduction.

Why extraction is enough: every real bubble carries mass n_b ≥ 1, so the
cumulative-mass scan of Eq. 6 crosses ``min_pts`` within its first
``min_pts`` entries in ascending-(distance, index) order.  Extracting the
(d, j) minimum ``min_pts`` times visits exactly the prefix the sort
would, with identical stable tie-breaking (lowest index wins), at
O(min_pts · bn · L) VPU work and no sort primitive — which Mosaic does
not provide.  ``min_pts`` is a static argument, so the loop unrolls.

Padding contract (matches kernels.ops): pad rows sit at a far coordinate
with n_b = 0 — if one is ever extracted it contributes nothing to the
cumulative mass and cannot be the crossing bubble while total real mass
≥ min_pts (callers clamp min_pts to the represented mass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

DEFAULT_BN = 8

# Mask value for visited candidates: above any real distance (pads sit at
# ~1e6·√d) but far below f32 max, so min() never overflows.
_MASKED = 1e30


def _bubble_cd_kernel(x_ref, yt_ref, nb_ref, ext_ref, out_ref, *, bn, min_pts, dim):
    x = x_ref[...]  # (bn, dp) row block
    yt = yt_ref[...]  # (dp, L) whole table, transposed
    L = yt.shape[1]
    xx = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
    yy = jnp.sum(yt * yt, axis=0, keepdims=True)  # (1, L)
    xy = jax.lax.dot_general(
        x, yt, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    d = jnp.sqrt(jnp.maximum(xx + yy - 2.0 * xy, 0.0))
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, L), 0) + i * bn
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, L), 1)
    d = jnp.where(rows == cols, 0.0, d)  # self at distance 0 (Def. 1 convention)
    nb = nb_ref[...]  # (1, L)
    ext = ext_ref[...]

    mp = jnp.float32(min_pts)
    col0 = jnp.zeros((bn, 1), jnp.float32)
    visited = jnp.zeros((bn, L), dtype=bool)
    csum = col0
    done = jnp.zeros((bn, 1), dtype=bool)
    dstar = col0
    before = col0
    nb_c = col0 + 1.0
    ext_c = col0
    m = col0
    nb_j = col0
    ext_j = col0
    for _ in range(min_pts):  # static unroll — min_pts bounds the scan prefix
        masked = jnp.where(visited, _MASKED, d)
        m = jnp.min(masked, axis=1, keepdims=True)
        at_min = masked == m
        j = jnp.min(jnp.where(at_min, cols, L), axis=1, keepdims=True)  # stable tie-break
        hit = cols == j
        nb_j = jnp.sum(jnp.where(hit, nb, 0.0), axis=1, keepdims=True)
        ext_j = jnp.sum(jnp.where(hit, ext, 0.0), axis=1, keepdims=True)
        new_csum = csum + nb_j
        crossed = (~done) & (new_csum >= mp)
        dstar = jnp.where(crossed, m, dstar)
        before = jnp.where(crossed, csum, before)
        nb_c = jnp.where(crossed, nb_j, nb_c)
        ext_c = jnp.where(crossed, ext_j, ext_c)
        done = done | crossed
        csum = new_csum
        visited = visited | hit
    # mass < min_pts (upstream clamps; belt-and-braces): the last extracted
    # candidate plays the boundary bubble, mirroring ref's farthest-entry
    # fallback as closely as a min_pts-step prefix can
    dstar = jnp.where(done, dstar, m)
    before = jnp.where(done, before, csum - nb_j)
    nb_c = jnp.where(done, nb_c, nb_j)
    ext_c = jnp.where(done, ext_c, ext_j)

    n_c = jnp.maximum(nb_c, 1.0)
    k_resid = jnp.clip(jnp.maximum(mp - before, 1.0), 0.0, n_c)
    nnd = _ref.dim_root(k_resid / n_c, dim) * ext_c
    out_ref[...] = dstar + nnd


@functools.partial(jax.jit, static_argnames=("min_pts", "dim", "bn", "interpret"))
def bubble_core_distances(
    rep: jax.Array,
    n_b: jax.Array,
    extent: jax.Array,
    *,
    min_pts: int,
    dim: int,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """(L, dp), (L,), (L,) -> (L,) bubble core distances (Eq. 6).

    ``dim`` is the TRUE feature dimensionality (the nnd exponent), which
    differs from rep.shape[1] once features are zero-padded.
    """
    L, dp = rep.shape
    assert L % bn == 0, (L, bn)
    rep = rep.astype(jnp.float32)
    kernel = functools.partial(_bubble_cd_kernel, bn=bn, min_pts=int(min_pts), dim=int(dim))
    cd = pl.pallas_call(
        kernel,
        grid=(L // bn,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((dp, L), lambda i: (0, 0)),
            pl.BlockSpec((1, L), lambda i: (0, 0)),
            pl.BlockSpec((1, L), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bubble_cd",
    )(
        rep,
        rep.T,  # the whole reference table, bubbles on lanes
        n_b.astype(jnp.float32).reshape(1, L),
        extent.astype(jnp.float32).reshape(1, L),
    )
    return cd[:, 0]
