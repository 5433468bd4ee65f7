"""Plain references for the correctness check, in float64 on the host.

Nothing here imports the program.  The semantics are copied from the
paper's definitions as the program states them:

* clustering features (Def. 4) of each leaf's points, the bubble table
  (Eqs. 3-4) and nnDist (Eq. 5);
* bubble core distances (Eq. 6) and mutual reachability (Eq. 7);
* a Prim minimum spanning tree over mutual reachability, single linkage,
  the weighted condensed tree and excess-of-mass extraction (HDBSCAN,
  Campello-Moulavi-Sander, weighted by bubble mass);
* the serve plane's nearest-representative assignment.

`Precision` names the arithmetic a reference step runs in.  The check
runs the references at ``f64``; the control puts the same references in
the program's place one precision lower (``f32`` sums and ``bf16x3``
distance dots, the TPU's three-pass bfloat16 product).
"""

from __future__ import annotations

import dataclasses

import numpy as np
from scipy.spatial import cKDTree


# -- arithmetic ---------------------------------------------------------------


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16 (nearest even), kept as float32."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    u = x.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def dot_bf16x3(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """a @ b.T as the TPU computes it at ``Precision.HIGH``: each float32
    operand split into a bfloat16 high and low part, the three largest
    partial products summed in float32."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ah, bh = to_bf16(a), to_bf16(b)
    al, bl = to_bf16(a - ah), to_bf16(b - bh)
    return ah @ bh.T + (ah @ bl.T + al @ bh.T)


def sqdist(x: np.ndarray, y: np.ndarray, dots: str = "f64") -> np.ndarray:
    """Squared distances by the ‖x‖² + ‖y‖² − 2x·y expansion, the form the
    program uses, in float64 (``f64``) or float32 with three-pass
    bfloat16 dots (``bf16x3``)."""
    if dots == "f64":
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        xy = x @ y.T
    elif dots == "bf16x3":
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        xy = dot_bf16x3(x, y)
    else:
        raise ValueError(f"unknown dot precision {dots!r}")
    xx = np.einsum("id,id->i", x, x)
    yy = np.einsum("jd,jd->j", y, y)
    return np.maximum(xx[:, None] + yy[None, :] - 2.0 * xy, 0.0)


# -- the online summary ---------------------------------------------------------


def leaf_cfs(X: np.ndarray, leaf: np.ndarray, sums: str = "f64"):
    """Clustering features of each leaf's points.  Returns (leaf ids,
    LS (L, d), SS (L,), N (L,)) over the leaves that hold points, summed
    in float64 or float32 (``sums``)."""
    ids, inv = np.unique(np.asarray(leaf), return_inverse=True)
    L, d = ids.shape[0], X.shape[1]
    if sums == "f64":
        Xs = np.asarray(X, np.float64)
        LS = np.zeros((L, d))
        np.add.at(LS, inv, Xs)
        SS = np.bincount(inv, weights=np.einsum("nd,nd->n", Xs, Xs), minlength=L)
    elif sums == "f32":
        # sequential accumulation in float32, as a running sum
        Xs = np.asarray(X, np.float32)
        LS = np.zeros((L, d), np.float32)
        SS = np.zeros(L, np.float32)
        sq = np.einsum("nd,nd->n", Xs, Xs).astype(np.float32)
        order = np.argsort(inv, kind="stable")
        for r in order:  # one row at a time: each partial sum is rounded
            j = inv[r]
            LS[j] += Xs[r]
            SS[j] += sq[r]
        LS, SS = LS.astype(np.float64), SS.astype(np.float64)
    else:
        raise ValueError(f"unknown sum precision {sums!r}")
    N = np.bincount(inv, minlength=L).astype(np.float64)
    return ids, LS, SS, N


def bubble_table(LS, SS, N):
    """rep = LS / n (Eq. 3) and extent (Eq. 4) of each clustering feature."""
    N = np.asarray(N, np.float64)
    rep = LS / np.maximum(N, 1.0)[:, None]
    lsq = np.einsum("ld,ld->l", LS, LS)
    rad = np.maximum((2.0 * N * SS - 2.0 * lsq) / np.maximum(N * (N - 1.0), 1.0), 0.0)
    extent = np.where(N <= 1.0, 0.0, np.sqrt(rad))
    return rep, extent


# -- the offline pass -----------------------------------------------------------


def core_distances(rep, n, extent, min_pts: int, dots: str = "f64"):
    """Eq. 6: order the bubbles by distance from B (B first), accumulate
    their masses, take the first bubble C at which the mass reaches
    min_pts and k, the mass still wanted from C; then
    cd(B) = d(B, C) + (k / n_C)^(1/d) · extent_C.

    Every bubble holds at least one point, so C is among the min_pts
    nearest; only those are searched."""
    L, dim = rep.shape
    k = min(int(min_pts), L)
    if dots == "f64":
        dist, order = cKDTree(rep).query(rep, k=k)
        dist = np.asarray(dist, np.float64).reshape(L, k)
        order = np.asarray(order).reshape(L, k)
    else:
        dist = np.empty((L, k))
        order = np.empty((L, k), np.int64)
        for s in range(0, L, 1024):
            d2 = sqdist(rep[s : s + 1024], rep, dots)
            d2[np.arange(d2.shape[0]), np.arange(s, s + d2.shape[0])] = 0.0
            part = np.argpartition(d2, k - 1, axis=1)[:, :k]
            dp = np.take_along_axis(d2, part, axis=1)
            o = np.argsort(dp, axis=1, kind="stable")
            order[s : s + 1024] = np.take_along_axis(part, o, axis=1)
            dist[s : s + 1024] = np.sqrt(np.take_along_axis(dp, o, axis=1))
    n_sorted = n[order]
    csum = np.cumsum(n_sorted, axis=1)
    reach = csum >= float(min_pts)
    idx = np.where(reach.any(axis=1), np.argmax(reach, axis=1), k - 1)
    rows = np.arange(L)
    before = np.where(idx > 0, csum[rows, np.maximum(idx - 1, 0)], 0.0)
    C = order[rows, idx]
    nC = np.maximum(n[C], 1.0)
    k_res = np.clip(np.maximum(float(min_pts) - before, 1.0), 0.0, nC)
    return dist[rows, idx] + np.power(k_res / nC, 1.0 / dim) * extent[C]


def prim_mst(rep, cd, dots: str = "f64"):
    """Prim's tree over d_m(i, j) = max(cd_i, cd_j, ‖rep_i − rep_j‖).
    Returns (u, v, w) of the L − 1 edges in the order they joined."""
    L = rep.shape[0]
    if dots == "f64":
        R = np.asarray(rep, np.float64)
        rr = np.einsum("ld,ld->l", R, R)

        def row(v):
            return np.sqrt(np.maximum(rr + rr[v] - 2.0 * (R @ R[v]), 0.0))
    else:
        R = np.asarray(rep, np.float32)
        rr = np.einsum("ld,ld->l", R, R)
        Rh = to_bf16(R)
        Rl = to_bf16(R - Rh)

        def row(v):
            xy = Rh @ Rh[v] + (Rh @ Rl[v] + Rl @ Rh[v])
            return np.sqrt(np.maximum(rr + rr[v] - 2.0 * xy, 0.0))

    cd = np.asarray(cd, np.float64)
    in_tree = np.zeros(L, bool)
    in_tree[0] = True
    best = np.maximum(row(0), np.maximum(cd, cd[0]))
    best[0] = np.inf
    parent = np.zeros(L, np.int64)
    u = np.empty(L - 1, np.int64)
    v = np.empty(L - 1, np.int64)
    w = np.empty(L - 1)
    for i in range(L - 1):
        j = int(np.argmin(best))
        u[i], v[i], w[i] = parent[j], j, best[j]
        in_tree[j] = True
        best[j] = np.inf
        d = np.maximum(row(j), np.maximum(cd, cd[j]))
        closer = (~in_tree) & (d < best)
        best[closer] = d[closer]
        parent[closer] = j
    return u, v, w


def _find(parent, a):
    root = a
    while parent[root] != root:
        root = parent[root]
    while parent[a] != root:
        parent[a], a = root, parent[a]
    return root


def single_linkage(u, v, w, weights):
    """Merge records (left, right, distance, merged weight) from the tree's
    edges in ascending weight; new node ids are L + i."""
    L = len(weights)
    order = np.argsort(w, kind="stable")
    uf = list(range(L))
    node_of_root = list(range(L))
    node_w = list(map(float, weights)) + [0.0] * (L - 1)
    merges = np.zeros((L - 1, 4))
    nxt = L
    for k, i in enumerate(order):
        ra, rb = _find(uf, int(u[i])), _find(uf, int(v[i]))
        na, nb = node_of_root[ra], node_of_root[rb]
        uf[ra] = rb
        merges[k] = (na, nb, w[i], node_w[na] + node_w[nb])
        node_w[nxt] = node_w[na] + node_w[nb]
        node_of_root[rb] = nxt
        nxt += 1
    return merges


def condense(merges, weights, mcs: float):
    """Weighted condensed tree: rows (parent, child, λ, child weight);
    condensed clusters get ids ≥ L, the root is L."""
    L = len(weights)
    left = merges[:, 0].astype(np.int64)
    right = merges[:, 1].astype(np.int64)
    dist = merges[:, 2]
    node_w = np.concatenate([np.asarray(weights, np.float64), merges[:, 3]])
    rows = []

    def emit_leaves(node, cparent, lam):
        sub = [node]
        while sub:
            s = sub.pop()
            if s < L:
                rows.append((cparent, s, lam, node_w[s]))
            else:
                sub.append(int(left[s - L]))
                sub.append(int(right[s - L]))

    root = L + merges.shape[0] - 1
    next_label = L + 1
    stack = [(root, L, 0.0)]
    while stack:
        node, cparent, lam_in = stack.pop()
        if node < L:
            rows.append((cparent, node, lam_in, node_w[node]))
            continue
        i = node - L
        lc, rc = int(left[i]), int(right[i])
        lam = 1.0 / dist[i] if dist[i] > 0 else np.inf
        wl, wr = node_w[lc], node_w[rc]
        l_cl = wl >= mcs and lc >= L
        r_cl = wr >= mcs and rc >= L
        if l_cl and r_cl:
            for ch, wch in ((lc, wl), (rc, wr)):
                rows.append((cparent, next_label, lam, wch))
                stack.append((ch, next_label, lam))
                next_label += 1
        elif l_cl or r_cl:
            stack.append((lc if l_cl else rc, cparent, lam))
            emit_leaves(rc if l_cl else lc, cparent, lam)
        else:
            emit_leaves(lc, cparent, lam)
            emit_leaves(rc, cparent, lam)
    a = np.array(rows, dtype=np.float64).reshape(-1, 4)
    return a[:, 0].astype(np.int64), a[:, 1].astype(np.int64), a[:, 2], a[:, 3]


def eom_labels(parent, child, lam, cw, L: int):
    """Excess-of-mass selection (root never selected) and bubble labels."""
    births = {int(c): float(x) for c, x in zip(child, lam) if c >= L}
    births.setdefault(L, 0.0)
    stab: dict[int, float] = {}
    for p, x, wt in zip(parent, lam, cw):
        p = int(p)
        stab[p] = stab.get(p, 0.0) + (min(float(x), 1e308) - births.get(p, 0.0)) * float(wt)
    kids: dict[int, list] = {}
    for p, c in zip(parent, child):
        if c >= L:
            kids.setdefault(int(p), []).append(int(c))
    selected, sub = {}, {}
    for c in sorted(stab, reverse=True):
        ks = kids.get(c, [])
        ksum = sum(sub.get(k, 0.0) for k in ks)
        s = stab.get(c, 0.0)
        selected[c] = (not ks) or s >= ksum
        sub[c] = s if selected[c] else ksum
    out = []
    stack = [(L, False)]
    while stack:
        c, blocked = stack.pop()
        if selected.get(c, False) and not blocked and c != L:
            out.append(c)
            blocked = True
        stack.extend((k, blocked) for k in kids.get(c, []))
    label_of = {c: i for i, c in enumerate(sorted(out))}
    up = {int(c): int(p) for p, c in zip(parent, child) if c >= L}
    memo: dict[int, int] = {}

    def resolve(c):
        path = []
        while c not in memo:
            if c in label_of:
                memo[c] = label_of[c]
                break
            if c not in up:
                memo[c] = -1
                break
            path.append(c)
            c = up[c]
        for q in path:
            memo[q] = memo[c]
        return memo[c]

    labels = np.full(L, -1, np.int64)
    for p, c in zip(parent, child):
        if c < L:
            labels[int(c)] = resolve(int(p))
    return labels


@dataclasses.dataclass
class OfflineRef:
    cd: np.ndarray  # (L,) bubble core distances (Eq. 6)
    labels: np.ndarray  # (L,) flat labels, -1 noise
    mst_u: np.ndarray  # (L − 1,) tree edges: endpoints and weights
    mst_v: np.ndarray
    mst_w: np.ndarray


def offline_pass(rep, n, extent, min_pts: int, mcs: float, dots: str = "f64") -> OfflineRef:
    """HDBSCAN over a bubble table, from Eq. 6 to flat labels."""
    cd = core_distances(rep, n, extent, min_pts, dots)
    u, v, w = prim_mst(rep, cd, dots)
    merges = single_linkage(u, v, w, n)
    labels = eom_labels(*condense(merges, n, mcs), L=len(n))
    return OfflineRef(cd=cd, labels=labels, mst_u=u, mst_v=v, mst_w=w)


# -- the serve plane ------------------------------------------------------------


def nearest(Q, reps, center, dots: str = "f64"):
    """Nearest representative of each query row and its distance, in the
    snapshot's centred frame."""
    q = np.asarray(Q, np.float64) - center
    r = np.asarray(reps, np.float64) - center
    d2 = sqdist(q, r, dots)
    idx = np.argmin(d2, axis=1)
    return idx, np.sqrt(d2[np.arange(len(idx)), idx]), d2
