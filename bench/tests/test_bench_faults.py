"""A run whose timed path is broken underneath must come out not correct.

Each case drives a small CPU version of a cell through the harness (the
look for a chip skipped), with one fault planted in the engine after
set-up, and checks the comparison's verdict."""

import numpy as np
import pytest

from bench import compare
from bench.tests.bench_cells import drive, tiny_cell


def _half_insert(eng):
    """Half of each insert block left out; the block's pids still come back."""
    tree = eng.tree
    host, assigned = tree.insert_block, tree.apply_assigned_block

    def insert_block(X):
        half = host(X[: max(1, X.shape[0] // 2)])
        return (half * 2)[: X.shape[0]]

    def apply_assigned_block(X, leaf_per_row, overfull_hint=None):
        k = max(1, X.shape[0] // 2)
        half = assigned(X[:k], leaf_per_row[:k], overfull_hint=overfull_hint)
        return (half * 2)[: X.shape[0]]

    tree.insert_block = insert_block
    tree.apply_assigned_block = apply_assigned_block


def _unchanged(eng):
    """A poll that drains its requests and applies none of them."""

    def poll(max_blocks=None):
        while eng.batcher:
            eng._next_point_block()
        return 0

    eng.poll = poll


def _label_altered(eng):
    """Every published snapshot with one cluster's label turned to noise."""
    orig = eng._publish_snapshot

    def publish(res, *args):
        lab = np.array(res.labels)
        lab[lab == lab.max()] = -1
        res.labels = lab
        return orig(res, *args)

    eng._publish_snapshot = publish


def _mst_altered(eng):
    """One tree edge of every pass 1 % heavier."""
    orig = eng._publish_snapshot

    def publish(res, *args):
        u, v, w = res.mst
        w = np.array(w)
        w[len(w) // 2] *= 1.01
        res.mst = (u, v, w)
        return orig(res, *args)

    eng._publish_snapshot = publish


def _edges_permuted(eng):
    """Every pass's tree edges with their first endpoints shuffled and
    their weights kept: the right levels on the wrong bubbles."""
    orig = eng._publish_snapshot

    def publish(res, *args):
        u, v, w = res.mst
        u = np.array(u)
        res.mst = (np.random.default_rng(0).permutation(u), v, w)
        return orig(res, *args)

    eng._publish_snapshot = publish


def _answer_altered(eng):
    """Each served batch answered by the neighbouring bubble."""
    qe = eng._query_engine
    orig = qe.query_detailed

    def query_detailed(snap, X):
        r = orig(snap, X)
        r.bubble_index = (r.bubble_index + 1) % max(snap.n_bubbles, 1)
        r.labels = np.asarray(snap.bubble_labels)[r.bubble_index]
        return r

    qe.query_detailed = query_detailed


CASES = [
    ("gas16-w160k.slide", None, True),
    ("gas16-w160k.slide", _half_insert, False),
    ("gas16-w160k.slide", _unchanged, False),
    ("gas16-w160k.slide", _label_altered, False),
    ("gas16-w160k.slide", _mst_altered, False),
    ("gas16-w160k.slide", _edges_permuted, False),
    ("gas16-w160k.query", _answer_altered, False),
    ("gas16-w160k.query", _half_insert, False),
]


@pytest.mark.parametrize("workload,fault,want", CASES,
                         ids=[f"{w}-{f.__name__ if f else 'sound'}" for w, f, _ in CASES])
def test_fault_comes_out_not_correct(workload, fault, want):
    cell = tiny_cell(workload)
    out, exp = drive(cell, seed=2**31 + 17, patch=fault)
    ok, checks = compare.verdict(compare.numbers(cell.config, out, exp, 3), cell.config["limits"])
    assert ok is want, checks
