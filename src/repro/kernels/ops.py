"""jit'd public wrappers for the yCHG Pallas kernels.

``interpret=None`` resolves from the platform (``kernels.platform``): the
kernels compile to Mosaic on a TPU and run in interpret mode elsewhere.

The heuristic between the full-column and streamed step-1 kernels is a VMEM
budget: the kernels widen the (H, block_w) int8 tile to int32 in VMEM, so
past 1 MiB of raw tile (4 MiB widened, H > 8192 at block_w=128) they
stream over H in block_h rows instead. The paper's 21000^2 scene streams.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.ychg import YCHGSummary
from repro.kernels import ychg_colscan as _k
from repro.kernels import ychg_fused as _f

Array = jax.Array

# raw int8 tile budget before switching to the streamed kernel (bytes)
_FULL_COLUMN_VMEM_BUDGET = 1024 * 1024


def uses_streamed(h: int, *, block_w: int = 128,
                  vmem_budget: int | None = None) -> bool:
    """Whether an image ``h`` rows tall takes the H-streamed kernel."""
    if vmem_budget is None:
        vmem_budget = _FULL_COLUMN_VMEM_BUDGET
    return h * block_w > vmem_budget


def colscan_runs(
    img: Array,
    *,
    block_w: int = 128,
    block_h: int = 2048,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
) -> Array:
    """Step 1: per-column maximal-run counts. (H, W) mask -> (W,) int32."""
    h, _ = img.shape
    if uses_streamed(h, block_w=block_w, vmem_budget=vmem_budget):
        return _k.colscan_runs_streamed(
            img, block_w=block_w, block_h=block_h, interpret=interpret
        )
    return _k.colscan_runs_pallas(img, block_w=block_w, interpret=interpret)


def transitions(
    runs: Array, *, block_w: int = 128, interpret: bool | None = None
) -> tuple[Array, Array, Array]:
    """Step 2: (W,) run counts -> (transitions bool, births i32, deaths i32)."""
    return _k.transitions_pallas(runs, block_w=block_w, interpret=interpret)


def analyze(
    img: Array,
    *,
    block_w: int = 128,
    block_h: int = 2048,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
) -> Dict[str, Array]:
    """Both steps fused end-to-end on device; returns the poster's outputs."""
    runs = colscan_runs(img, block_w=block_w, block_h=block_h, interpret=interpret,
                        vmem_budget=vmem_budget)
    trans, births, deaths = transitions(runs, block_w=block_w, interpret=interpret)
    return {
        "runs": runs,
        "cut_vertices": 2 * runs,
        "transitions": trans,
        "births": births,
        "deaths": deaths,
        "n_hyperedges": jnp.sum(births, dtype=jnp.int32),
        "n_transitions": jnp.sum(trans, dtype=jnp.int32),
    }


def analyze_fused(
    img: Array,
    *,
    block_w: int = 128,
    block_h: int = 2048,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
) -> YCHGSummary:
    """Fused batched pipeline: one kernel launch for a whole (B, H, W) stack.

    Accepts (H, W) or (B, H, W); returns a ``YCHGSummary`` bit-identical to
    ``repro.core.ychg.analyze`` (same dtypes, shapes, and values). Tall
    images (full column tile over the VMEM budget) stream over H inside the
    same single launch via the carry-row variant.
    """
    squeeze = img.ndim == 2
    imgs = img[None] if squeeze else img
    if imgs.ndim != 3:
        raise ValueError(f"expected (H, W) or (B, H, W) mask, got {img.shape}")
    b, h, _ = imgs.shape
    if b == 0:  # nothing to launch; keep the contract via the jnp path
        from repro.core import ychg as _ychg

        return _ychg.analyze(img)
    if uses_streamed(h, block_w=block_w, vmem_budget=vmem_budget):
        out = _f.fused_analyze_streamed(
            imgs, block_w=block_w, block_h=block_h, interpret=interpret
        )
    else:
        out = _f.fused_analyze_pallas(imgs, block_w=block_w, interpret=interpret)
    if squeeze:
        out = {k: v[0] for k, v in out.items()}
    return YCHGSummary(
        runs=out["runs"],
        cut_vertices=2 * out["runs"],
        transitions=out["transitions"],
        births=out["births"],
        deaths=out["deaths"],
        n_hyperedges=out["n_hyperedges"],
        n_transitions=out["n_transitions"],
    )
