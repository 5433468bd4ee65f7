"""Pallas TPU kernels: the three sequential sweeps of the device hierarchy.

`core.hierarchy_jax` builds the HDBSCAN hierarchy from fixed-size MST
buffers with three O(Lp) sweeps whose every step is a handful of
dependent scalar loads and stores: the single-linkage union-find, the
top-down condense, and the bottom-up excess-of-mass selection.  As XLA
while loops each step is a chain of tiny dynamic-slice ops; here each
sweep is the loop of one kernel on the TensorCore's scalar unit, with
its random-access state resident in SMEM.

Layout: the arrays a sweep reads or writes in order are streamed
through SMEM in ``chunk``-entry blocks by the grid's pipeline (one
sequential grid axis; the condense and EOM sweeps walk the blocks in
descending order), while the state it indexes at random lives in SMEM
scratch that persists across grid steps.  ``chunk`` is the whole array
up to `CHUNK` entries, so a small bucket is one grid step.  The
resident state is three Lp-word arrays (union-find, condense) or one
2·Lp-word array (EOM): `SMEM_ROWS` is the largest bucket whose state
and stream buffers fit v5e's 1 MiB of SMEM.

Each kernel returns what the sweep alone decides, in per-step streams;
`core.hierarchy_jax` rebuilds the reference's outputs from them with
scatters of unique indices, so both paths return the same bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# stream block size in entries (a 8 KiB SMEM buffer per stream and slot)
CHUNK = 2048
# largest Lp whose resident state fits SMEM beside the stream buffers
SMEM_ROWS = 32768


def _stream(ch: int, n_blocks: int, descending: bool = False) -> pl.BlockSpec:
    if descending:
        return pl.BlockSpec((ch,), lambda g: (n_blocks - 1 - g,),
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec((ch,), lambda g: (g,), memory_space=pltpu.SMEM)


def _scalar() -> pl.BlockSpec:
    return pl.BlockSpec((1,), lambda g: (0,), memory_space=pltpu.SMEM)


def _sequential():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# --------------------------------------------------------------------------
# single linkage: union-find over the weight-sorted edges
# --------------------------------------------------------------------------

def _single_linkage_kernel(w_hbm, iota_hbm, u_ref, v_ref, d_ref,
                           ml_ref, mr_ref, md_ref, mw_ref, tw_ref,
                           parent, node_of, comp_w, sem, *, ch, Lp):
    g = pl.program_id(0)
    M = Lp - 1
    trash = 2 * Lp - 1

    @pl.when(g == 0)
    def _():
        copies = (
            pltpu.make_async_copy(iota_hbm, parent, sem.at[0]),
            pltpu.make_async_copy(iota_hbm, node_of, sem.at[1]),
            pltpu.make_async_copy(w_hbm, comp_w, sem.at[2]),
        )
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        tw_ref[0] = jnp.float32(0.0)

    def find(x):
        # path halving; the root is the component's representative
        def hop(x):
            gp = parent[parent[x]]
            parent[x] = gp
            return gp

        return jax.lax.while_loop(lambda x: parent[x] != x, hop, x)

    def step(j, tw):
        k = g * ch + j
        ra, rb = find(u_ref[j]), find(v_ref[j])
        ok = ra != rb  # surplus +inf slots / disconnected inputs: no-op
        wsum = comp_w[ra] + comp_w[rb]
        ml_ref[j] = jnp.where(ok, node_of[ra], trash)
        mr_ref[j] = jnp.where(ok, node_of[rb], trash)
        md_ref[j] = jnp.where(ok, d_ref[j], 0.0)
        mw_ref[j] = jnp.where(ok, wsum, 0.0)

        @pl.when(ok)
        def _():
            parent[rb] = ra  # u's side keeps its representative
            node_of[ra] = Lp + k
            comp_w[ra] = wsum

        return jnp.where(ok, tw, wsum)  # a rejected merge's weight lands on trash

    n = jnp.minimum(ch, M - g * ch)  # merge slot M is never processed
    tw_ref[0] = jax.lax.fori_loop(0, n, step, tw_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def single_linkage_sweep(u_s, v_s, w_s, weights, *, interpret: bool = False):
    """Union-find over (Lp,) weight-sorted edges.

    Returns ``(left, right, dist, weight, trash_weight)``: the (Lp,)
    merge records, of which the first Lp − 1 are `single_linkage_fixed`'s
    rows (rejected merges read trash/0), and the weight its trash node
    ends with (that of the last rejected merge, else 0)."""
    Lp = u_s.shape[0]
    ch = min(Lp, CHUNK)
    nb = Lp // ch
    s = _stream(ch, nb)
    f32, i32 = jnp.float32, jnp.int32
    ml, mr, md, mw, tw = pl.pallas_call(
        functools.partial(_single_linkage_kernel, ch=ch, Lp=Lp),
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
                  s, s, s],
        out_specs=[s, s, s, s, _scalar()],
        out_shape=[jax.ShapeDtypeStruct((Lp,), i32), jax.ShapeDtypeStruct((Lp,), i32),
                   jax.ShapeDtypeStruct((Lp,), f32), jax.ShapeDtypeStruct((Lp,), f32),
                   jax.ShapeDtypeStruct((1,), f32)],
        scratch_shapes=[pltpu.SMEM((Lp,), i32), pltpu.SMEM((Lp,), i32),
                        pltpu.SMEM((Lp,), f32), pltpu.SemaphoreType.DMA((3,))],
        compiler_params=_sequential(),
        interpret=interpret,
        name="single_linkage",
    )(weights.astype(f32), jnp.arange(Lp, dtype=i32), u_s.astype(i32),
      v_s.astype(i32), w_s.astype(f32))
    return ml, mr, md, mw, tw[0]


# --------------------------------------------------------------------------
# condense: top-down over merge index M-1 … 0
# --------------------------------------------------------------------------

def _condense_kernel(zi_hbm, zf_hbm, lc_ref, rc_ref, lam_ref, hv_ref,
                     lab_l_ref, lab_r_ref, clam_ref, par_ref, nl_ref,
                     cl, lam_in, fallen, sem, *, ch, nb, Lp):
    g = pl.program_id(0)
    blk = nb - 1 - g
    M = Lp - 1

    @pl.when(g == 0)
    def _():
        # every internal node enters cluster 0 at λ 0, not fallen (the
        # root keeps that; a node no merge reaches reads it too)
        copies = (
            pltpu.make_async_copy(zi_hbm, cl, sem.at[0]),
            pltpu.make_async_copy(zf_hbm, lam_in, sem.at[1]),
            pltpu.make_async_copy(zi_hbm, fallen, sem.at[2]),
        )
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        nl_ref[0] = jnp.int32(1)

    def set_child(c, lab, lam, fal):
        # internal children carry state down (node Lp + i lives at row i);
        # leaves are rebuilt outside from the streams, so their writes go to
        # the trash node's row M, which no step reads.  The row is clamped
        # to [0, M] whatever c holds: with the store guarded by c >= Lp and
        # the row left unclamped, this kernel halted the v5e core, though
        # no step of a valid tree reaches past row M.
        k = jnp.where(c >= Lp, jnp.minimum(c - Lp, M), M)
        cl[k] = lab
        lam_in[k] = lam
        fallen[k] = fal.astype(jnp.int32)

    def step(t, nxt):
        j = ch - 1 - t
        i = blk * ch + j  # merge index; node id Lp + i
        P, lin, fal = cl[i], lam_in[i], fallen[i] != 0
        lc, rc, hv = lc_ref[j], rc_ref[j], hv_ref[j]
        l_c, r_c = (hv & 1) != 0, (hv & 2) != 0  # heavy AND structural
        both = l_c & r_c & ~fal
        lab_l = jnp.where(both, nxt, P)
        lab_r = jnp.where(both, nxt + 1, P)
        child_lam = jnp.where(fal, lin, lam_ref[j])
        set_child(lc, lab_l, child_lam, fal | ~(both | (l_c & ~r_c)))
        set_child(rc, lab_r, child_lam, fal | ~(both | (r_c & ~l_c)))
        lab_l_ref[j] = lab_l
        lab_r_ref[j] = lab_r
        clam_ref[j] = child_lam
        par_ref[j] = P
        return nxt + 2 * both.astype(jnp.int32)

    n = jnp.minimum(ch, M - blk * ch)  # row M of the top block is padding
    nl_ref[0] = jax.lax.fori_loop(ch - n, ch, step, nl_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def condense_sweep(left, right, lam, heavy, *, interpret: bool = False):
    """Top-down condense over (Lp,) merge rows (row Lp − 1 padding).

    ``heavy`` holds bit 0 / bit 1 when the left / right child is an
    internal node of at least min_cluster_size weight.  Returns per-row
    ``(left_label, right_label, child_lambda, parent_label)`` and the
    label count: a row founds two clusters iff its left label differs
    from its parent's."""
    Lp = left.shape[0]
    ch = min(Lp, CHUNK)
    nb = Lp // ch
    s = _stream(ch, nb, descending=True)
    f32, i32 = jnp.float32, jnp.int32
    lab_l, lab_r, clam, par, nl = pl.pallas_call(
        functools.partial(_condense_kernel, ch=ch, nb=nb, Lp=Lp),
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
                  s, s, s, s],
        out_specs=[s, s, s, s, _scalar()],
        out_shape=[jax.ShapeDtypeStruct((Lp,), i32), jax.ShapeDtypeStruct((Lp,), i32),
                   jax.ShapeDtypeStruct((Lp,), f32), jax.ShapeDtypeStruct((Lp,), i32),
                   jax.ShapeDtypeStruct((1,), i32)],
        scratch_shapes=[pltpu.SMEM((Lp,), i32), pltpu.SMEM((Lp,), f32),
                        pltpu.SMEM((Lp,), i32), pltpu.SemaphoreType.DMA((3,))],
        compiler_params=_sequential(),
        interpret=interpret,
        name="condense",
    )(jnp.zeros((Lp,), i32), jnp.zeros((Lp,), f32), left.astype(i32),
      right.astype(i32), lam.astype(f32), heavy.astype(i32))
    return lab_l, lab_r, clam, par, nl[0]


# --------------------------------------------------------------------------
# excess of mass: bottom-up over the live labels n_labels-1 … 0
# --------------------------------------------------------------------------

def _eom_kernel(nl_ref, stab_ref, par_ref, kids_ref, sel_ref, acc, *, ch, nb):
    g = pl.program_id(0)
    blk = nb - 1 - g
    nl = nl_ref[0]

    @pl.when(g == 0)
    def _():
        def zero(c, carry):
            acc[c] = jnp.float32(0.0)
            return carry

        jax.lax.fori_loop(0, nl, zero, 0)

    live = jnp.clip(nl - blk * ch, 0, ch)  # labels ≥ n_labels are dead

    def step(t, carry):
        j = live - 1 - t
        c = blk * ch + j
        s, ksum = stab_ref[j], acc[c]
        is_sel = (kids_ref[j] == 0) | (s >= ksum)
        sel_ref[j] = is_sel.astype(jnp.int32)

        @pl.when(c >= 1)
        def _():
            # the root's parent is the trash label C, past acc's end: the
            # root never adds, and the clamp keeps even the guarded
            # address in range (see the condense kernel)
            p = jnp.clip(par_ref[j], 0, acc.shape[0] - 1)
            acc[p] = acc[p] + jnp.where(is_sel, s, ksum)

        return carry

    jax.lax.fori_loop(0, live, step, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def eom_sweep(stability, parent, kids, n_labels, *, interpret: bool = False):
    """Excess-of-mass selection over (C,) label rows, live below
    ``n_labels``: a label is selected iff it has no live child or its
    stability reaches the selected mass below it.  Returns an int32 flag
    per row; rows at or above ``n_labels`` are unwritten."""
    C = stability.shape[0]
    ch = min(C, CHUNK)
    nb = C // ch
    s = _stream(ch, nb, descending=True)
    f32, i32 = jnp.float32, jnp.int32
    return pl.pallas_call(
        functools.partial(_eom_kernel, ch=ch, nb=nb),
        grid=(nb,),
        in_specs=[_scalar(), s, s, s],
        out_specs=s,
        out_shape=jax.ShapeDtypeStruct((C,), i32),
        scratch_shapes=[pltpu.SMEM((C,), f32)],
        compiler_params=_sequential(),
        interpret=interpret,
        name="eom",
    )(jnp.reshape(n_labels, (1,)).astype(i32), stability.astype(f32),
      parent.astype(i32), kids.astype(i32))
