"""Leaf splits plus leaf dissolves the Bubble-tree made per 1,000
operations applied in the window (engine counters ``tree_splits`` and
``tree_dissolves``)."""

from bench.counters import growth, per_kop


def read(run):
    s, d = growth(run, "tree_splits"), growth(run, "tree_dissolves")
    return None if s is None or d is None else per_kop(run, s + d)
