"""Pallas TPU kernels for the yCHG two-step algorithm.

TPU adaptation of the paper's CUDA mechanism (DESIGN.md §2). The CUDA code
assigns one *thread* per image column; on TPU we assign one *grid step* per
column tile of 128·k lanes, stream the tile HBM->VMEM via BlockSpec, and let
the 8x128 VPU evaluate the run-start predicate ``max(x[i] - x[i-1], 0)``
(int32 0/1, as in ``ychg_fused``) for all columns of the tile at once,
reducing down the row (sublane) axis.

Two kernels, mirroring the paper's two steps:

  step 1a ``_colscan_kernel``          full column per block — grid over W only;
                                       block (H, bw) int8 in VMEM.
  step 1b ``_colscan_streamed_kernel`` grid over (W tiles, H tiles) with an
                                       int32 carry row in VMEM scratch, for
                                       images whose full column tile would
                                       not fit VMEM (H·bw > 1 MiB).
  step 2  ``_diff_kernel``             neighbour-column comparison on the
                                       (W,) counts vector; the wrapper feeds
                                       the shifted copy so each block is
                                       self-contained (the CUDA version
                                       re-reads its left neighbour from
                                       global memory; on TPU we shift once
                                       in HBM instead — cheaper than a halo).

Grid iteration on TPU is sequential row-major with the last grid dim fastest;
the streamed kernel relies on that for its carry (W tile fixed, H tiles in
order) and accumulates into a revisited output block — the standard TPU
reduction pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.ychg_fused import _INT8_SUBLANES, _column_rises

Array = jax.Array


def _colscan_kernel(img_ref, out_ref):
    """Block: img (H, bw) int8 0/1 -> out (1, bw) int32 run counts."""
    x = img_ref[...].astype(jnp.int32)
    runs, _ = _column_rises(x, jnp.zeros_like(x[0:1]))
    out_ref[...] = runs


def _colscan_streamed_kernel(img_ref, out_ref, carry_ref):
    """Grid (W tiles, H tiles); carry_ref (1, bw) int32 holds the previous
    H-block's last row."""
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    rises, last_row = _column_rises(img_ref[...].astype(jnp.int32),
                                    carry_ref[...])
    out_ref[...] += rises
    carry_ref[...] = last_row


def _diff_kernel(runs_ref, prev_ref, trans_ref, births_ref, deaths_ref):
    """Block: runs/prev (1, bw) int32 -> transitions/births/deaths (1, bw) int32."""
    delta = runs_ref[...] - prev_ref[...]
    trans_ref[...] = (delta != 0).astype(jnp.int32)
    births_ref[...] = jnp.maximum(delta, 0)
    deaths_ref[...] = jnp.maximum(-delta, 0)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def colscan_runs_pallas(img: Array, *, block_w: int = 128,
                        interpret: bool | None = None) -> Array:
    """Step 1, full-column blocks. img: (H, W) any dtype; returns (W,) int32.

    The wrapper pads W to a lane multiple with background columns (0 runs,
    sliced off afterwards) and casts to int8 for dense VMEM tiles.
    """
    h, w = img.shape
    x = (img != 0).astype(jnp.int8)
    h_pad = -h % _INT8_SUBLANES
    w_pad = -w % block_w
    if h_pad or w_pad:
        x = jnp.pad(x, ((0, h_pad), (0, w_pad)))  # zero rows start no run
    hp, wp = h + h_pad, w + w_pad
    out = pl.pallas_call(
        _colscan_kernel,
        grid=(wp // block_w,),
        in_specs=[pl.BlockSpec((hp, block_w), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block_w), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, wp), jnp.int32),
        name="ychg_colscan",
        interpret=resolve_interpret(interpret),
    )(x)
    return out[0, :w]


@functools.partial(jax.jit, static_argnames=("block_w", "block_h", "interpret"))
def colscan_runs_streamed(
    img: Array, *, block_w: int = 128, block_h: int = 2048,
    interpret: bool | None = None,
) -> Array:
    """Step 1 for tall images: grid over (W, H) tiles with a carry row."""
    h, w = img.shape
    x = (img != 0).astype(jnp.int8)
    w_pad = -w % block_w
    h_pad = -h % block_h
    if w_pad or h_pad:
        x = jnp.pad(x, ((0, h_pad), (0, w_pad)))  # zero rows end runs; no new rises
    hp, wp = h + h_pad, w + w_pad
    out = pl.pallas_call(
        _colscan_streamed_kernel,
        grid=(wp // block_w, hp // block_h),
        in_specs=[pl.BlockSpec((block_h, block_w), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, block_w), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, wp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.int32)],
        name="ychg_colscan_streamed",
        interpret=resolve_interpret(interpret),
    )(x)
    return out[0, :w]


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def transitions_pallas(
    runs: Array, *, block_w: int = 128, interpret: bool | None = None
) -> tuple[Array, Array, Array]:
    """Step 2. runs: (W,) int32 -> (transitions bool, births i32, deaths i32)."""
    (w,) = runs.shape
    prev = jnp.concatenate([jnp.zeros((1,), runs.dtype), runs[:-1]])
    w_pad = -w % block_w
    if w_pad:
        runs = jnp.pad(runs, (0, w_pad))
        prev = jnp.pad(prev, (0, w_pad))
    wp = w + w_pad
    spec = pl.BlockSpec((1, block_w), lambda j: (0, j))
    trans, births, deaths = pl.pallas_call(
        _diff_kernel,
        grid=(wp // block_w,),
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((1, wp), jnp.int32)] * 3,
        name="ychg_transitions",
        interpret=resolve_interpret(interpret),
    )(runs[None, :], prev[None, :])
    return (trans[0, :w] != 0), births[0, :w], deaths[0, :w]
