"""The paper's clustering-quality claims (Figs. 4 and 6), as tier-1 checks.

Fig. 6: on each dataset's window, HDBSCAN over the Bubble-tree summary
agrees with static HDBSCAN on the raw points (NMI) at least as well as
the streaming baselines do, `ClusTreeLite` and `IncrementalBubbles`,
within 0.15.  Fig. 4: on a 2-D toy set inserted in rounds, the
Bubble-tree summary agrees with static HDBSCAN at least as well as
ClusTree's, within 0.05.
"""

import numpy as np
import pytest

from repro.core import BubbleTree, ClusTreeLite, IncrementalBubbles, hdbscan, nmi
from repro.core.summarizer import BubbleTreeSummarizer, assign_points, cluster_bubbles
from repro.data.synthetic import DATASET_SPECS, dataset


def _summary_labels(b, X, min_pts):
    res = cluster_bubbles(b, min_pts=min_pts)
    return res.labels[assign_points(X, b)]


def _fig6(name, n=3000, min_pts=50, compression=0.05, seed=0):
    X, _ = dataset(name, n, seed=seed)
    static = hdbscan(X, min_pts=min_pts).labels
    bt = BubbleTree(dim=X.shape[1], compression=compression)
    bt.insert_block(X)
    ct = ClusTreeLite(dim=X.shape[1], max_height=10)
    inc = IncrementalBubbles(dim=X.shape[1], compression=compression)
    for p in X:
        ct.insert(p)
        inc.insert(p)
    score = {
        k: float(nmi(_summary_labels(s.to_bubbles(), X, min_pts), static))
        for k, s in (("bubble_tree", bt), ("clustree", ct), ("incremental", inc))
    }
    return score["bubble_tree"], max(score["clustree"], score["incremental"]), 0.15, score


def _toy(n=1000, seed=0):
    """Seeds-like 2-D data: three gaussian blobs and one arc."""
    rng = np.random.default_rng(seed)
    parts = [rng.normal(loc=c, scale=0.7, size=(n // 4, 2)) for c in ((0, 0), (8, 1), (4, 7))]
    t = rng.uniform(0, 3 * np.pi / 2, size=n - 3 * (n // 4))
    arc = np.stack([12 + 3 * np.cos(t), 4 + 3 * np.sin(t)], axis=1)
    parts.append(arc + rng.normal(scale=0.25, size=arc.shape))
    X = np.concatenate(parts)
    rng.shuffle(X)
    return X


def _fig4(n=1000, rounds=10, min_pts=10, seed=0):
    X = _toy(n, seed)
    static = hdbscan(X, min_pts=min_pts).labels
    bt = BubbleTreeSummarizer(dim=2, min_pts=min_pts, compression=0.10)
    ct = ClusTreeLite(dim=2, max_height=6)
    chunk = n // rounds
    for r in range(rounds):
        blk = X[r * chunk : (r + 1) * chunk]
        bt.insert_block(blk)
        for p in blk:
            ct.insert(p)
    out = bt.cluster()
    score = {
        "bubble_tree": float(nmi(out.point_labels, static[out.point_ids])),
        "clustree": float(nmi(_summary_labels(ct.to_bubbles(), X, min_pts), static)),
    }
    return score["bubble_tree"], score["clustree"], 0.05, score


@pytest.mark.parametrize("name", [*DATASET_SPECS, "toy"])
def test_bubble_tree_quality_matches_baselines(name):
    ours, best_baseline, margin, score = _fig4() if name == "toy" else _fig6(name)
    assert ours >= best_baseline - margin, (name, score)
