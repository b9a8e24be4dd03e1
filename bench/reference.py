"""Plain NumPy reference of the yCHG analysis, written from the paper.

The yConvex hypergraph of a binary mask (arXiv:1307.2560) is built in two
steps:

  step 1  every column j is cut into maximal vertical runs of foreground
          pixels; ``runs[j]`` counts them (each run has a top and a bottom
          cut-vertex, so ``cut_vertices[j] = 2 * runs[j]``);
  step 2  ``delta[j] = runs[j] - runs[j-1]`` (``runs[-1]`` is 0):
          ``births = max(delta, 0)``, ``deaths = max(-delta, 0)``,
          ``transitions = delta != 0``; the hyperedge count is the sum of
          births and the transition count the number of transition columns.

Counts are int32, the transition flags bool, and the two totals 0-d int32
arrays: the host view a scene result has.

The reference imports nothing of the program under test. It works in
blocks of rows (a run that crosses a block boundary is carried by the
block's last row), so a 21000 x 21000 scene needs a few hundred MB.

``acc`` narrows every counter (runs, deltas and totals) to another integer
type before widening the answer back to int32. ``acc=np.int8`` is the
benchmark's control: the step below the stated int32 counts that a kernel
keeping its int8 input type would take. It wraps on masks with more than
127 hyperedges, and the comparison has to catch that.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

FIELDS = ("runs", "cut_vertices", "transitions", "births", "deaths",
          "n_hyperedges", "n_transitions")

BLOCK_ROWS = 2048


def column_runs(mask: np.ndarray, acc=np.int32,
                block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """Step 1: maximal vertical runs per column of an (H, W) mask."""
    h, w = mask.shape
    runs = np.zeros(w, acc)
    above = np.zeros(w, bool)
    for r0 in range(0, h, block_rows):
        x = np.asarray(mask[r0:r0 + block_rows]) != 0
        starts = x.copy()
        starts[0] &= ~above
        starts[1:] &= ~x[:-1]
        runs += starts.sum(axis=0, dtype=acc)
        above = x[-1]
    return runs


def from_runs(runs: np.ndarray, acc=np.int32) -> Dict[str, np.ndarray]:
    """Step 2 and the totals from step 1's per-column run counts."""
    runs = runs.astype(acc)
    prev = np.concatenate([np.zeros(1, acc), runs[:-1]])
    delta = runs - prev
    zero = np.zeros((), acc)
    births = np.maximum(delta, zero)
    deaths = np.maximum(-delta, zero)
    transitions = delta != 0
    return {
        "runs": runs.astype(np.int32),
        "cut_vertices": (runs * acc(2)).astype(np.int32),
        "transitions": transitions,
        "births": births.astype(np.int32),
        "deaths": deaths.astype(np.int32),
        "n_hyperedges": np.asarray(births.sum(dtype=acc), np.int32),
        "n_transitions": np.asarray(transitions.sum(dtype=acc), np.int32),
    }


def analyze(mask: np.ndarray, acc=np.int32) -> Dict[str, np.ndarray]:
    """The seven yCHG fields of one (H, W) mask (nonzero = foreground)."""
    if np.ndim(mask) != 2:
        raise ValueError(f"expected an (H, W) mask, got {np.shape(mask)}")
    with np.errstate(over="ignore"):
        return from_runs(column_runs(mask, acc), acc)


def same(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> bool:
    """Exact equality of two host results: fields, dtypes, shapes, values."""
    if set(got) != set(want):
        return False
    for field, w in want.items():
        a, b = np.asarray(got[field]), np.asarray(w)
        if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(a, b):
            return False
    return True
