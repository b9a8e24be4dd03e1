"""Plain NumPy reference of 4-connected component labelling.

The labels a ``ccl`` answer must hold: every water (nonzero) pixel gets
the id of its 4-connected component, ids are 1..n in the row-major order
of each component's first pixel, land is 0; ``n_components`` is n. Both
are int32, the host view an answer has.

Two passes over the runs of each row (maximal horizontal stretches of
water), in the manner of Rosenfeld and Pfaltz's two-pass labelling:

  pass 1  number the runs in row-major order and join, in a union-find
          forest, every two runs of adjacent rows whose columns overlap
          (4-connectivity), each tree keeping its least run as its root;
  pass 2  rank the roots in run order, which is the row-major order of
          the components' first pixels, and paint each run its root's
          rank.

The union-find takes the overlap pairs all at once: each round hooks
both ends of every pair, and their roots, to the lesser root, then
points every run at its parent's parent, until each pair shares a root
that is its own. The reference imports nothing of the program under test.

``acc`` is the integer type of every run number, parent and id.
``acc=np.int16`` is the benchmark's control: the step below the stated
int32, which wraps once a tile holds more than 32767 runs (a MOD44W tile
holds several hundred thousand).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

FIELDS = ("labels", "n_components")


def row_runs(mask: np.ndarray):
    """Row, first column and end column (exclusive) of every run, in
    row-major order."""
    m = np.asarray(mask) != 0
    h, w = m.shape
    edges = np.zeros((h, w + 2), np.int8)
    edges[:, 1:-1] = m
    step = np.diff(edges, axis=1)
    row, first = np.nonzero(step == 1)
    _, end = np.nonzero(step == -1)
    return row, first, end


def touching_runs(row, first, end, width: int):
    """Pairs (k, j) of runs where run j lies in the row above run k and
    their columns overlap."""
    span = width + 2
    start_key = row * span + first
    end_key = row * span + end
    above = (row - 1) * span
    lo = np.searchsorted(end_key, above + first, side="right")
    hi = np.searchsorted(start_key, above + end, side="left") - 1
    count = np.maximum(hi - lo + 1, 0)
    k = np.repeat(np.arange(len(row)), count)
    offset = np.arange(len(k)) - np.repeat(np.cumsum(count) - count, count)
    return k, np.repeat(lo, count) + offset


def union(n: int, a: np.ndarray, b: np.ndarray, acc=np.int32) -> np.ndarray:
    """Root of each of ``n`` runs, the least run joined to it."""
    parent = np.arange(n).astype(acc)
    a, b = a.astype(acc), b.astype(acc)
    while True:
        pa, pb = parent[a], parent[b]
        if np.array_equal(pa, pb) and np.array_equal(parent[pa], pa):
            return parent
        low = np.minimum(pa, pb)
        for ends in (a, b, pa, pb):
            np.minimum.at(parent, ends, low)
        parent = parent[parent]


def labels(mask: np.ndarray, acc=np.int32) -> Dict[str, np.ndarray]:
    """``labels`` (H, W) and ``n_components`` () of one (H, W) mask."""
    if np.ndim(mask) != 2:
        raise ValueError(f"expected an (H, W) mask, got {np.shape(mask)}")
    h, w = np.shape(mask)
    row, first, end = row_runs(mask)
    k, j = touching_runs(row, first, end, w)
    root = union(len(row), k, j, acc)
    rank = np.cumsum(root == np.arange(len(row)).astype(acc)).astype(acc)
    out = np.zeros(h * w, np.int32)
    out[np.flatnonzero(np.asarray(mask))] = np.repeat(rank[root], end - first)
    n = rank[-1] if len(rank) else acc(0)
    return {"labels": out.reshape(h, w),
            "n_components": np.asarray(n, np.int32)}


def same(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> bool:
    """Exact equality of two host answers: fields, dtypes, shapes, values."""
    if set(got) != set(want):
        return False
    for field, w in want.items():
        a, b = np.asarray(got[field]), np.asarray(w)
        if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(a, b):
            return False
    return True
