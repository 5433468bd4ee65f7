"""Device-resident HDBSCAN hierarchy: single-linkage → condense → extract.

`core.hdbscan` keeps the sequential host implementation as the *oracle*;
this module is the jit-compatible array reformulation that lets the whole
offline pass (d_m → MST → dendrogram → condensed tree → flat labels) run
as ONE compiled call with no host round-trip (ISSUE 2 / ROADMAP "make a
hot path measurably faster").  Everything operates on fixed,
power-of-two-bucketed shapes so the streaming engine recompiles per
bucket, not per leaf count.

Padding scheme (shared with kernels.ops.offline_recluster):

  * ``Lp`` leaves, of which the first ``n_valid`` are real; pad leaves
    carry weight 0.
  * Borůvka returns (Lp,) edge buffers with ``n_valid - 1`` valid edges
    (pad rows are +inf-isolated and never connect).  The dendrogram
    needs ``Lp - 1`` merges, so the ``Lp - n_valid`` missing edges are
    synthesized: pad leaf ``n_valid + j`` is attached to node 0 at
    ``PAD_DIST`` (≫ any real d_m).  Sorted ascending, those merges land
    at the very top of the tree, where λ = 1/PAD_DIST ≈ 0 and weight 0
    — the condensed tree sees them as zero-mass members of the root
    cluster at λ→0, which perturbs neither stabilities nor labels.
  * One edge slot is always left over (``Lp`` slots, ``Lp - 1`` merges);
    it is parked at +inf and never processed.

Cluster labels are dense ints: 0 is the root cluster, children get
increasing labels in top-down processing order (so a child's label is
always greater than its parent's — both extraction loops rely on it).
They are a *relabeling* of the oracle's ``n, n+1, …`` convention; parity
tests compare up to permutation.

Sequential-but-on-device is the point: union-find single-linkage, the
condense DFS, and bottom-up EOM are O(Lp) sweeps, while selection
blocking and label resolution collapse to O(log Lp) pointer-doubling
sweeps.  Each sequential sweep has two implementations, named by
``impl`` (`kernels.ops.hierarchy_impl` chooses): ``"jnp"``, the
`lax.scan`s below (unroll=2 — the measured CPU sweet spot between
while-loop dispatch overhead and compile time), which stay the
reference; and the Pallas kernels of `kernels.hierarchy`, which run each
sweep on the TPU's scalar unit with its state in SMEM.  As XLA while
loops the three sweeps cost 6–10 µs a step on a v5e, most of a dense
ε-pass; both return the same bits.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PAD_DIST",
    "MAX_LAMBDA",
    "SingleLinkageArrays",
    "CondensedArrays",
    "ExtractionArrays",
    "single_linkage_fixed",
    "condense_fixed",
    "extract_fixed",
    "hierarchy_fixed",
    "single_linkage_jax",
]

# Weight of the synthesized pad-leaf merges.  Far above any real mutual
# reachability but finite in f32 (so 1/PAD_DIST is a clean denormal-free
# ~1e-30, not a NaN-generating inf).
PAD_DIST = 1e30
# λ = 1/dist clamp for zero/denormal distances (duplicate points).  The
# host oracle uses np.inf and clamps at 1e308 inside the stability sum;
# 1e12 keeps (λ · total_weight) comfortably inside f32.
MAX_LAMBDA = 1e12


class SingleLinkageArrays(NamedTuple):
    """scipy-``linkage``-style merge records over 2·Lp−1 node ids.

    Row k merges ``left[k]``/``right[k]`` (node ids; leaves < Lp,
    internal node ``Lp + k``) at ``dist[k]`` into weight ``weight[k]``.
    Skipped slots (disconnected inputs — never the MST path) point both
    children at the trash node ``2·Lp − 1``.
    """

    left: jax.Array  # (Lp-1,) int32
    right: jax.Array  # (Lp-1,) int32
    dist: jax.Array  # (Lp-1,) f32
    weight: jax.Array  # (Lp-1,) f32
    node_weight: jax.Array  # (2*Lp,) f32 — per-node subtree weight (+ trash)


class CondensedArrays(NamedTuple):
    """Array-form condensed tree (oracle: hdbscan.CondensedTree).

    Point rows: leaf i belongs to condensed cluster ``point_parent[i]``
    from λ ``point_lambda[i]``.  Cluster rows: label c ≥ 1 is a child of
    ``cluster_parent[c]`` born at ``cluster_birth[c]`` carrying
    ``cluster_weight[c]``; label 0 is the root (birth 0).  Slots ≥
    ``n_labels`` are unused (parent = trash index).
    """

    point_parent: jax.Array  # (Lp,) int32 — condensed cluster label per leaf
    point_lambda: jax.Array  # (Lp,) f32
    point_weight: jax.Array  # (Lp,) f32 — leaf weights (pads 0)
    cluster_parent: jax.Array  # (C+1,) int32, C = 2*Lp
    cluster_birth: jax.Array  # (C+1,) f32
    cluster_weight: jax.Array  # (C+1,) f32
    n_labels: jax.Array  # () int32 — labels in use (root included)


class ExtractionArrays(NamedTuple):
    stability: jax.Array  # (C+1,) f32 — per condensed cluster label
    selected: jax.Array  # (C+1,) bool — flat-extraction winners
    labels: jax.Array  # (Lp,) int32 — per-leaf flat labels, -1 noise
    n_clusters: jax.Array  # () int32


# --------------------------------------------------------------------------
# step 4: single-linkage dendrogram from fixed-size MST buffers
# --------------------------------------------------------------------------

def single_linkage_fixed(eu, ev, ew, valid, n_valid, weights,
                         impl: str = "jnp") -> SingleLinkageArrays:
    """Edge-sorted union-find single-linkage over padded edge buffers.

    Args:
      eu, ev, ew, valid: (Lp,) Borůvka edge buffers (``kernels.ops`` /
        ``mst.boruvka_jax`` layout); exactly ``n_valid - 1`` valid edges
        for a connected valid block.
      n_valid: () int — real leaf count L; leaves ≥ L are padding.
      weights: (Lp,) f32 leaf weights (pad rows 0).
      impl: ``"jnp"`` runs the sweep as the scan below (the reference);
        ``"pallas"`` / ``"pallas-interpret"`` as the union-find kernel
        of `kernels.hierarchy`, compiled or interpreted.

    Union-find is component *relabeling* (O(Lp) vectorized `where` per
    merge) rather than pointer chasing: each merge relabels the absorbed
    component in one VPU sweep, so there are no data-dependent find
    depths and the loop body is branch-free.
    """
    Lp = eu.shape[0]
    M = Lp - 1
    trash_node = 2 * Lp - 1

    eu = eu.astype(jnp.int32)
    ev = ev.astype(jnp.int32)
    ew = ew.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    n_valid = jnp.asarray(n_valid, jnp.int32)

    # synthesize the pad merges: j-th invalid slot attaches pad leaf
    # n_valid + j to node 0 at PAD_DIST; surplus slots park at +inf
    inv_rank = jnp.cumsum((~valid).astype(jnp.int32)) - 1
    pad_leaf = n_valid + inv_rank
    is_pad = (~valid) & (pad_leaf < Lp)
    u_e = jnp.where(valid, eu, jnp.where(is_pad, pad_leaf, 0))
    v_e = jnp.where(valid, ev, 0)
    pad_w = jnp.where(
        is_pad, jnp.asarray(PAD_DIST, ew.dtype), jnp.asarray(jnp.inf, ew.dtype)
    )
    w_e = jnp.where(valid, ew, pad_w)

    order = jnp.argsort(w_e, stable=True)
    u_s, v_s, w_s = u_e[order], v_e[order], w_e[order]
    if impl != "jnp":
        from repro.kernels.hierarchy import single_linkage_sweep

        ml, mr, md, mw, trash_w = single_linkage_sweep(
            u_s, v_s, w_s, weights, interpret=impl == "pallas-interpret")
        # internal node Lp + k weighs mw[k] (0 where the merge was rejected)
        node_weight = jnp.concatenate([weights, mw[:M], trash_w[None]])
        return SingleLinkageArrays(ml[:M], mr[:M], md[:M], mw[:M], node_weight)

    comp0 = jnp.arange(Lp, dtype=jnp.int32)
    node_of_comp0 = jnp.concatenate(
        [comp0, jnp.asarray([trash_node], jnp.int32)]
    )  # (Lp+1,): slot Lp absorbs skipped-merge writes
    node_weight0 = jnp.zeros((2 * Lp,), jnp.float32).at[:Lp].set(weights)
    zeros_m = jnp.zeros((M + 1,), jnp.float32)
    trash_i32 = jnp.full((M + 1,), trash_node, jnp.int32)

    def body(k, state):
        comp, node_of_comp, node_weight, ml, mr, md, mw = state
        u, v, w = u_s[k], v_s[k], w_s[k]
        ca, cb = comp[u], comp[v]
        ok = ca != cb  # surplus +inf slots / disconnected inputs: no-op
        na, nb = node_of_comp[ca], node_of_comp[cb]
        wsum = node_weight[na] + node_weight[nb]
        slot = jnp.where(ok, k, M)  # rejected merges land in the trash row
        ml = ml.at[slot].set(jnp.where(ok, na, trash_node))
        mr = mr.at[slot].set(jnp.where(ok, nb, trash_node))
        md = md.at[slot].set(w)
        mw = mw.at[slot].set(wsum)
        node_weight = node_weight.at[jnp.where(ok, Lp + k, trash_node)].set(wsum)
        comp = jnp.where(comp == cb, ca, comp)
        node_of_comp = node_of_comp.at[jnp.where(ok, ca, Lp)].set(Lp + k)
        return comp, node_of_comp, node_weight, ml, mr, md, mw

    state = (
        comp0,
        node_of_comp0,
        node_weight0,
        trash_i32.copy(),
        trash_i32.copy(),
        zeros_m.copy(),
        zeros_m.copy(),
    )
    # scan+unroll over fori_loop: amortizes the per-iteration while-loop
    # dispatch that dominates these O(1)-body loops on CPU
    state, _ = jax.lax.scan(
        lambda s, k: (body(k, s), None), state, jnp.arange(M, dtype=jnp.int32), unroll=2
    )
    _, _, node_weight, ml, mr, md, mw = state
    return SingleLinkageArrays(ml[:M], mr[:M], md[:M], mw[:M], node_weight)


# --------------------------------------------------------------------------
# step 5a: condensed tree (array-form DFS, top-down over node ids)
# --------------------------------------------------------------------------

def condense_fixed(slt: SingleLinkageArrays, weights, min_cluster_size,
                   impl: str = "jnp") -> CondensedArrays:
    """Collapse the dendrogram exactly like ``hdbscan.condense_tree``:

    a split spawns two new condensed clusters only when both sides are
    structural subtrees carrying ≥ min_cluster_size weight; one heavy
    side continues its parent's label; light sides "fall out" leaf by
    leaf at the split's λ.  Node ids descend from the root (internal ids
    increase with merge order), so one top-down fori_loop settles every
    node's (condensed label, entry λ, fallen?) before it is visited.
    """
    M = slt.left.shape[0]
    Lp = M + 1
    n_nodes = 2 * Lp - 1  # + slot n_nodes = trash
    C = 2 * Lp  # max condensed cluster labels (1 root + 2 per split)
    trash_label = C
    mcs = jnp.asarray(min_cluster_size, jnp.float32)
    weights = weights.astype(jnp.float32)

    root = n_nodes - 1
    lam_of = jnp.where(
        slt.dist > 0.0, jnp.minimum(1.0 / slt.dist, MAX_LAMBDA), MAX_LAMBDA
    ).astype(jnp.float32)
    if impl != "jnp":
        return _condense_kernel_path(slt, weights, mcs, lam_of, impl)

    cl0 = jnp.zeros((n_nodes + 1,), jnp.int32)  # root enters cluster 0
    lam0 = jnp.zeros((n_nodes + 1,), jnp.float32)
    fal0 = jnp.zeros((n_nodes + 1,), bool)
    cp0 = jnp.full((C + 1,), trash_label, jnp.int32)
    cb0 = jnp.zeros((C + 1,), jnp.float32)
    cw0 = jnp.zeros((C + 1,), jnp.float32).at[0].set(slt.node_weight[root])

    def body(t, state):
        cl, lam_in, fallen, cp, cb, cw, nxt = state
        i = M - 1 - t  # merge index; node id Lp + i, root first
        node = Lp + i
        P, lin, fal = cl[node], lam_in[node], fallen[node]
        lc, rc = slt.left[i], slt.right[i]
        lam = lam_of[i]
        wl, wr = slt.node_weight[lc], slt.node_weight[rc]
        l_c = (wl >= mcs) & (lc >= Lp)  # heavy AND structural (internal)
        r_c = (wr >= mcs) & (rc >= Lp)
        both = l_c & r_c & ~fal
        A, B = nxt, nxt + 1
        cl = cl.at[lc].set(jnp.where(both, A, P)).at[rc].set(jnp.where(both, B, P))
        child_lam = jnp.where(fal, lin, lam)
        lam_in = lam_in.at[lc].set(child_lam).at[rc].set(child_lam)
        # a child stays "live" only if it founds a cluster (both) or is
        # the single continuing heavy side; everything else falls out
        fallen = (
            fallen.at[lc].set(fal | ~(both | (l_c & ~r_c)))
            .at[rc].set(fal | ~(both | (r_c & ~l_c)))
        )
        sa = jnp.where(both, A, trash_label)
        sb = jnp.where(both, B, trash_label)
        cp = cp.at[sa].set(P).at[sb].set(P)
        cb = cb.at[sa].set(lam).at[sb].set(lam)
        cw = cw.at[sa].set(wl).at[sb].set(wr)
        return cl, lam_in, fallen, cp, cb, cw, nxt + 2 * both.astype(jnp.int32)

    state = (cl0, lam0, fal0, cp0, cb0, cw0, jnp.asarray(1, jnp.int32))
    state, _ = jax.lax.scan(
        lambda s, t: (body(t, s), None), state, jnp.arange(M), unroll=2
    )
    cl, lam_in, _, cp, cb, cw, n_labels = state
    # trash-label writes must not corrupt slot C's defaults for readers
    cp = cp.at[trash_label].set(trash_label)
    cb = cb.at[trash_label].set(0.0)
    cw = cw.at[trash_label].set(0.0)
    return CondensedArrays(
        point_parent=cl[:Lp],
        point_lambda=lam_in[:Lp],
        point_weight=weights,
        cluster_parent=cp,
        cluster_birth=cb,
        cluster_weight=cw,
        n_labels=n_labels,
    )


def _condense_kernel_path(slt, weights, mcs, lam_of, impl) -> CondensedArrays:
    """`condense_fixed` through the condense kernel: the sweep gives each
    merge row its children's labels, their entry λ and its own label; the
    leaf rows and the cluster table are scattered from those (every node
    is the child of one merge and every label is born once, so the
    scatters' indices are unique and the bits are the scan's)."""
    from repro.kernels.hierarchy import condense_sweep

    M = slt.left.shape[0]
    Lp = M + 1
    C = 2 * Lp
    left, right = slt.left, slt.right
    wl, wr = slt.node_weight[left], slt.node_weight[right]
    heavy = (((wl >= mcs) & (left >= Lp)).astype(jnp.int32)
             | (((wr >= mcs) & (right >= Lp)).astype(jnp.int32) << 1))

    def pad(a):  # the kernel's streams are Lp rows; the last is never read
        return jnp.concatenate([a, jnp.zeros((1,), a.dtype)])

    lab_l, lab_r, clam, par, n_labels = condense_sweep(
        pad(left), pad(right), pad(lam_of), pad(heavy),
        interpret=impl == "pallas-interpret")
    lab_l, lab_r, clam, par = lab_l[:M], lab_r[:M], clam[:M], par[:M]

    # leaves: out-of-range indices (internal children, trash) are dropped
    leaf_l = jnp.where(left < Lp, left, Lp)
    leaf_r = jnp.where(right < Lp, right, Lp)
    point_parent = (jnp.zeros((Lp,), jnp.int32)
                    .at[leaf_l].set(lab_l, mode="drop").at[leaf_r].set(lab_r, mode="drop"))
    point_lambda = (jnp.zeros((Lp,), jnp.float32)
                    .at[leaf_l].set(clam, mode="drop").at[leaf_r].set(clam, mode="drop"))
    # clusters: a merge whose left label is not its own founds two
    born = lab_l != par
    sa = jnp.where(born, lab_l, C + 1)
    sb = jnp.where(born, lab_r, C + 1)
    cp = (jnp.full((C + 1,), C, jnp.int32)
          .at[sa].set(par, mode="drop").at[sb].set(par, mode="drop"))
    cb = (jnp.zeros((C + 1,), jnp.float32)
          .at[sa].set(lam_of, mode="drop").at[sb].set(lam_of, mode="drop"))
    cw = (jnp.zeros((C + 1,), jnp.float32).at[0].set(slt.node_weight[2 * Lp - 2])
          .at[sa].set(wl, mode="drop").at[sb].set(wr, mode="drop"))
    return CondensedArrays(
        point_parent=point_parent,
        point_lambda=point_lambda,
        point_weight=weights,
        cluster_parent=cp,
        cluster_birth=cb,
        cluster_weight=cw,
        n_labels=n_labels,
    )


# --------------------------------------------------------------------------
# step 5b: stabilities + flat extraction + label resolution
# --------------------------------------------------------------------------

def extract_fixed(
    ct: CondensedArrays,
    method: str = "eom",
    allow_single_cluster: bool = False,
    impl: str = "jnp",
) -> ExtractionArrays:
    """Excess-of-mass (or leaf) extraction over the array condensed tree.

    stability(c) = Σ_rows (λ_row − λ_birth(c)) · w_row, via two scatter
    adds.  EOM runs as one descending fori_loop: child labels exceed
    their parent's, so each cluster's children are final when visited;
    a running scatter into the parent's accumulator replaces the
    subtree-stability dict of the oracle.  Selection blocking and label
    resolution are one ascending loop each (parents final first).
    """
    C = ct.cluster_parent.shape[0] - 1
    trash = C
    ids = jnp.arange(C + 1, dtype=jnp.int32)
    in_use = ids < ct.n_labels

    # --- stabilities (root birth is 0 by construction) ---
    birth = ct.cluster_birth
    stab = jnp.zeros((C + 1,), jnp.float32)
    stab = stab.at[ct.point_parent].add(
        (ct.point_lambda - birth[ct.point_parent]) * ct.point_weight
    )
    row_mask = in_use & (ids >= 1)
    par_of = jnp.where(row_mask, ct.cluster_parent, trash)
    stab = stab.at[par_of].add(
        jnp.where(row_mask, (birth - birth[par_of]) * ct.cluster_weight, 0.0)
    )

    # --- bottom-up EOM: selected iff stability ≥ Σ selected-descendant ---
    # (the only stage that stays a sequential sweep: the subtree sum
    # flips through the selection flag, so no pointer-doubling shortcut)
    def eom_body(state, t):
        acc, kids, sel = state
        c = C - 1 - t
        live = c < ct.n_labels
        s, ksum = stab[c], acc[c]
        is_sel = live & ((kids[c] == 0) | (s >= ksum))
        sub = jnp.where(is_sel, s, ksum)
        sel = sel.at[c].set(is_sel)
        p = jnp.where(live & (c >= 1), ct.cluster_parent[c], trash)
        return (acc.at[p].add(sub), kids.at[p].add(1), sel), None

    if impl == "jnp":
        acc0 = jnp.zeros((C + 1,), jnp.float32)
        kids0 = jnp.zeros((C + 1,), jnp.int32)
        sel0 = jnp.zeros((C + 1,), bool)
        (_, kid_count, sel), _ = jax.lax.scan(
            eom_body, (acc0, kids0, sel0), jnp.arange(C), unroll=2
        )
    else:
        # the kernel walks only the live labels; child counts need no sweep
        from repro.kernels.hierarchy import eom_sweep

        kid_of = jnp.where(row_mask[:C], ct.cluster_parent[:C], trash)
        kid_count = jnp.zeros((C + 1,), jnp.int32).at[kid_of].add(1)
        flag = eom_sweep(stab[:C], ct.cluster_parent[:C], kid_count[:C], ct.n_labels,
                         interpret=impl == "pallas-interpret")
        sel = jnp.concatenate([(flag != 0) & in_use[:C], jnp.zeros((1,), bool)])

    # pointer-doubling setup: the label tree is ≤ C deep but log₂(C)
    # doubling steps traverse any ancestor chain
    n_jumps = max(C - 1, 1).bit_length() + 1  # ceil(log2(max(C, 2))) + 1
    parent_or_trash = jnp.where(in_use & (ids >= 1), ct.cluster_parent, trash)

    if method == "leaf":
        eff = in_use & (kid_count == 0) & (allow_single_cluster | (ids != 0))
    else:
        # a selected cluster blocks every selected descendant; the root
        # only counts when allow_single_cluster.  "blocked" ⇔ some proper
        # ancestor is selected-and-allowed — an OR over the ancestor
        # chain, computed by pointer doubling in log₂(C) vector steps
        sel_allowed = sel & (allow_single_cluster | (ids != 0)) & in_use

        def or_step(state, _):
            g, anc = state
            return (g[g], anc | anc[g]), None

        (_, anc_or), _ = jax.lax.scan(
            or_step, (parent_or_trash, sel_allowed[parent_or_trash]),
            None, length=n_jumps,
        )
        eff = sel_allowed & ~anc_or
    if allow_single_cluster:
        none = ~eff.any()
        eff = eff.at[0].set(eff[0] | none)
    eff = eff & in_use

    # --- labels: nearest selected ancestor-or-self, ranked ascending ---
    # f[c] = c where selected else parent; doubling converges every label
    # onto its nearest selected ancestor (or trash ⇒ noise)
    rank = (jnp.cumsum(eff.astype(jnp.int32)) - 1).astype(jnp.int32)
    f0 = jnp.where(eff, ids, parent_or_trash)

    def hop(f, _):
        return jnp.where(eff[f], f, f[f]), None

    f, _ = jax.lax.scan(hop, f0, None, length=n_jumps)
    resolved = jnp.where(eff[f], rank[f], -1)
    labels = resolved[ct.point_parent]
    return ExtractionArrays(
        stability=stab, selected=eff, labels=labels, n_clusters=eff.sum().astype(jnp.int32)
    )


# trace-contract: hierarchy_fixed rules=f32,no-callbacks,pow2
@functools.partial(jax.jit, static_argnames=("method", "allow_single_cluster", "impl"))
def hierarchy_fixed(
    eu, ev, ew, valid, n_valid, weights, min_cluster_size,
    method: str = "eom",
    allow_single_cluster: bool = False,
    impl: str = "jnp",
):
    """MST buffers → (SingleLinkageArrays, CondensedArrays, ExtractionArrays).

    The fully fused device path, shape-static in Lp.  jit'd here so eager
    callers (tests, notebooks) hit the per-bucket compile cache instead
    of re-tracing the scans each call; inside `kernels.ops`'s fused
    pipeline the jit nests and inlines.  ``impl`` names what runs the
    three sweeps (`kernels.ops.hierarchy_impl`); every choice returns the
    same bits.
    """
    slt = single_linkage_fixed(eu, ev, ew, valid, n_valid, weights, impl=impl)
    ct = condense_fixed(slt, jnp.asarray(weights, jnp.float32), min_cluster_size, impl=impl)
    ex = extract_fixed(ct, method=method, allow_single_cluster=allow_single_cluster,
                       impl=impl)
    return slt, ct, ex


# --------------------------------------------------------------------------
# explicit-edge-list convenience (property tests / oracle comparisons)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(4, 6))
def _sl_fixed_jit(eu, ev, ew, valid, n, weights, impl):
    return single_linkage_fixed(
        eu, ev, ew, valid, jnp.asarray(n, jnp.int32), weights, impl=impl)


def single_linkage_jax(u, v, w, n: int, weights=None):
    """Device single-linkage from an explicit edge list (host mirror of
    ``hdbscan.single_linkage``).  Pads to the power-of-two bucket, runs
    the fixed kernel, and returns the ``n - 1`` real merge records as
    host numpy ``(left, right, dist, weight)`` — pad merges (attached at
    PAD_DIST) are sliced away, exactly the rows the oracle produces.
    The sweep runs where `kernels.ops.hierarchy_impl` puts it under the
    ``REPRO_FORCE_REF`` policy, as every kernel wrapper's default does.
    """
    from repro.kernels import ops

    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64)
    if weights is None:
        weights = np.ones(n, dtype=np.float64)
    Lp = max(8, 1 << (max(n - 1, 1)).bit_length())
    E = u.shape[0]
    if E != n - 1:
        # the fixed kernel assumes a spanning tree (MST output); fewer
        # edges would leave unwritten trash rows in the result and more
        # would drop the heaviest ones — reject rather than corrupt
        raise ValueError(f"expected a spanning tree ({n - 1} edges for n={n}), got {E}")
    eu = np.zeros(Lp, dtype=np.int32)
    ev = np.zeros(Lp, dtype=np.int32)
    ew = np.zeros(Lp, dtype=np.float32)
    valid = np.zeros(Lp, dtype=bool)
    eu[:E], ev[:E], ew[:E], valid[:E] = u, v, w, True
    wpad = np.zeros(Lp, dtype=np.float32)
    wpad[:n] = weights
    slt = _sl_fixed_jit(
        jnp.asarray(eu), jnp.asarray(ev), jnp.asarray(ew), jnp.asarray(valid),
        int(n), jnp.asarray(wpad), ops.hierarchy_impl(Lp, ops._resolve_ref(None)),
    )
    keep = np.asarray(slt.dist) < PAD_DIST
    # real merges are the first n-1 in sorted order (pads sort above
    # them), so their internal ids Lp+k remap to the oracle's n+k
    left = np.asarray(slt.left)[keep]
    right = np.asarray(slt.right)[keep]
    left = np.where(left >= Lp, left - Lp + n, left)
    right = np.where(right >= Lp, right - Lp + n, right)
    return (
        left,
        right,
        np.asarray(slt.dist, dtype=np.float64)[keep],
        np.asarray(slt.weight, dtype=np.float64)[keep],
    )
