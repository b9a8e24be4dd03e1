"""Fused, batched Pallas kernels: both yCHG steps in ONE ``pallas_call``.

The two-kernel pipeline in ``ychg_colscan.py`` mirrors the paper's CUDA
structure (step 1 kernel, HBM round-trip for the (W,) run-count vector,
step 2 kernel over a shifted copy). That round-trip is pure overhead: the
step-2 neighbour diff needs only the previous *tile's* last column count,
which the step-1 kernel already holds in registers. These kernels fuse the
diff into the column scan and batch a whole (B, H, W) stack into a single
launch:

  grid (B, W tiles)            — one grid step per (image, column tile);
  step 1 in-register           — run counts for the tile's columns from the
                                 rising-edge reduction, never written to HBM
                                 before step 2 consumes them;
  inter-tile carry             — a (1, block_w) int32 VMEM scratch holds the
                                 previous W tile's run counts; its last lane
                                 is the left neighbour of this tile's first
                                 column (TPU grid order is row-major, last
                                 dim fastest, so tiles of one image are
                                 visited in order; the carry is re-zeroed at
                                 j == 0 for each new image);
  per-image totals             — ``n_hyperedges`` / ``n_transitions``
                                 accumulate into a revisited (1, 1, 1) output
                                 block of a (B, 1, 1) array (standard TPU
                                 reduction pattern; the last two block dims
                                 equal the array's, which Mosaic's (8, 128)
                                 tiling rule accepts), masked to the valid W
                                 columns so padding never leaks into them.

All in-kernel math is int32 on 2-D (rows, lanes) values: the mask arrives
as int8 0/1, is widened once, and run starts are ``max(x - x_above, 0)``
with ``x_above`` from a sublane ``pltpu.roll``; the left neighbour for
step 2 comes from a lane roll. Mosaic has no i8 <-> i1 vector casts, so
the kernels keep booleans out of loads, stores and 1-D values.

``fused_analyze_streamed`` extends the same structure with a third grid dim
over H tiles for images whose full column does not fit the VMEM budget,
reusing the carry-row pattern of ``_colscan_streamed_kernel``: an int32
(1, block_w) scratch carries the previous H block's last row, the per-column
counts accumulate into the revisited ``runs`` block, and the step-2 diff +
total accumulation fire on the final H tile of each column tile, when the
tile's counts are complete.

Both wrappers return per-image (B, W) planes and (B,) totals; padding
columns (W rounded up to the lane multiple) are sliced off and padded rows
(H rounded up to the int8 sublane tile, or to ``block_h`` when streamed)
are zero, which cannot start a run. Outputs are
bit-identical to ``repro.core.ychg.analyze`` — the parity suite in
``tests/test_ychg_fused.py`` enforces exact equality including dtypes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

Array = jax.Array

# int8 arrays tile as (32, 128) on the TPU: a full-column block is padded
# to this many rows so every block shape is tile-aligned.
_INT8_SUBLANES = 32


def _column_rises(x, above_first):
    """(h, bw) int32 0/1 mask -> (1, bw) count of run starts per column.

    ``above_first`` (1, bw) int32 is the row above the block's first row
    (zeros at the top of an image). Returns the counts and the block's
    last row, which is the next block's ``above_first``.
    """
    rolled = pltpu.roll(x, 1, 0)  # row r <- x[r - 1]; row 0 <- x[h - 1]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    above = jnp.where(row == 0, above_first, rolled)
    rises = jnp.sum(jnp.maximum(x - above, 0), axis=0, keepdims=True)
    return rises, rolled[0:1, :]


def _step2_finish(runs, j, carry_ref, nh_ref, nt_ref, *, w: int, block_w: int):
    """In-register step 2 for a tile's completed (1, bw) int32 run counts:
    diff against the left neighbour (the carried previous tile's last lane
    for column 0), accumulate the masked per-image totals, advance the
    carry. Shared by both kernels so the seam/masking logic cannot diverge.
    Returns (trans, births, deaths) as (1, bw) int32 planes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, runs.shape, 1)
    prev = jnp.where(lane == 0, pltpu.roll(carry_ref[...], 1, 1),
                     pltpu.roll(runs, 1, 1))
    delta = runs - prev
    births = jnp.maximum(delta, 0)
    deaths = jnp.maximum(-delta, 0)
    trans = (delta != 0).astype(jnp.int32)
    valid = j * block_w + lane < w
    nh_ref[0] += jnp.sum(jnp.where(valid, births, 0), keepdims=True)
    nt_ref[0] += jnp.sum(jnp.where(valid, trans, 0), keepdims=True)
    carry_ref[...] = runs
    return trans, births, deaths


def _fused_kernel(
    img_ref,
    runs_ref,
    trans_ref,
    births_ref,
    deaths_ref,
    nh_ref,
    nt_ref,
    carry_ref,
    *,
    w: int,
    block_w: int,
):
    """Grid (B, W tiles). Block: img (1, H, bw) int8 -> all step-1/2 outputs.

    carry_ref (1, bw) int32: run counts of the previous W tile.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _new_image():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        nh_ref[...] = jnp.zeros_like(nh_ref)
        nt_ref[...] = jnp.zeros_like(nt_ref)

    x = img_ref[0].astype(jnp.int32)  # (H, bw) 0/1
    runs, _ = _column_rises(x, jnp.zeros((1, block_w), jnp.int32))

    # step 2 in-register: the only cross-tile dependency is one lane.
    trans, births, deaths = _step2_finish(
        runs, j, carry_ref, nh_ref, nt_ref, w=w, block_w=block_w
    )
    runs_ref[0] = runs
    trans_ref[0] = trans
    births_ref[0] = births
    deaths_ref[0] = deaths


def _unpack(outs, w: int) -> dict[str, Array]:
    runs, trans, births, deaths, nh, nt = outs
    return {
        "runs": runs[:, 0, :w],
        "transitions": trans[:, 0, :w] != 0,
        "births": births[:, 0, :w],
        "deaths": deaths[:, 0, :w],
        "n_hyperedges": nh[:, 0, 0],
        "n_transitions": nt[:, 0, 0],
    }


def _out_shapes(b: int, wp: int):
    return ([jax.ShapeDtypeStruct((b, 1, wp), jnp.int32)] * 4
            + [jax.ShapeDtypeStruct((b, 1, 1), jnp.int32)] * 2)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def fused_analyze_pallas(
    imgs: Array, *, block_w: int = 128, interpret: bool | None = None
) -> dict[str, Array]:
    """Both yCHG steps for a (B, H, W) stack in one kernel launch.

    Returns dict of runs/transitions/births/deaths (B, W) and
    n_hyperedges/n_transitions (B,) — same values as ``core.ychg.analyze``.
    """
    b, h, w = imgs.shape
    x = (imgs != 0).astype(jnp.int8)
    h_pad = -h % _INT8_SUBLANES
    w_pad = -w % block_w
    if h_pad or w_pad:
        # zero rows end runs and start none; zero cols carry zero counts.
        x = jnp.pad(x, ((0, 0), (0, h_pad), (0, w_pad)))
    hp, wp = h + h_pad, w + w_pad
    vec = pl.BlockSpec((1, 1, block_w), lambda bi, j: (bi, 0, j))
    tot = pl.BlockSpec((1, 1, 1), lambda bi, j: (bi, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, w=w, block_w=block_w),
        grid=(b, wp // block_w),
        in_specs=[pl.BlockSpec((1, hp, block_w), lambda bi, j: (bi, 0, j))],
        out_specs=[vec, vec, vec, vec, tot, tot],
        out_shape=_out_shapes(b, wp),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.int32)],
        name="ychg_fused_full",
        interpret=resolve_interpret(interpret),
    )(x)
    return _unpack(outs, w)


def _fused_streamed_kernel(
    img_ref,
    runs_ref,
    trans_ref,
    births_ref,
    deaths_ref,
    nh_ref,
    nt_ref,
    row_carry_ref,
    tile_carry_ref,
    *,
    w: int,
    block_w: int,
):
    """Grid (B, W tiles, H tiles); H fastest so each column tile completes
    before the next starts.

    row_carry_ref  (1, bw) int32 — previous H block's last row (run
                                   detection across the H seam).
    tile_carry_ref (1, bw) int32 — previous W tile's run counts (step-2
                                   seam), updated only on final H tiles so
                                   it survives the H loop.
    """
    j = pl.program_id(1)
    i = pl.program_id(2)
    last_i = pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _new_image():
        tile_carry_ref[...] = jnp.zeros_like(tile_carry_ref)
        nh_ref[...] = jnp.zeros_like(nh_ref)
        nt_ref[...] = jnp.zeros_like(nt_ref)

    @pl.when(i == 0)
    def _new_tile():
        row_carry_ref[...] = jnp.zeros_like(row_carry_ref)
        runs_ref[...] = jnp.zeros_like(runs_ref)
        trans_ref[...] = jnp.zeros_like(trans_ref)
        births_ref[...] = jnp.zeros_like(births_ref)
        deaths_ref[...] = jnp.zeros_like(deaths_ref)

    x = img_ref[0].astype(jnp.int32)  # (bh, bw) 0/1
    rises, last_row = _column_rises(x, row_carry_ref[...])
    runs_ref[0] += rises
    row_carry_ref[...] = last_row

    @pl.when(i == last_i)
    def _finish_tile():
        trans, births, deaths = _step2_finish(
            runs_ref[0], j, tile_carry_ref, nh_ref, nt_ref,
            w=w, block_w=block_w
        )
        trans_ref[0] = trans
        births_ref[0] = births
        deaths_ref[0] = deaths


@functools.partial(jax.jit, static_argnames=("block_w", "block_h", "interpret"))
def fused_analyze_streamed(
    imgs: Array,
    *,
    block_w: int = 128,
    block_h: int = 2048,
    interpret: bool | None = None,
) -> dict[str, Array]:
    """Streamed fused pipeline for tall images: one launch, H tiled too."""
    b, h, w = imgs.shape
    x = (imgs != 0).astype(jnp.int8)
    w_pad = -w % block_w
    h_pad = -h % block_h
    if w_pad or h_pad:
        # zero rows end runs and start none; zero cols carry zero counts.
        x = jnp.pad(x, ((0, 0), (0, h_pad), (0, w_pad)))
    hp, wp = h + h_pad, w + w_pad
    vec = pl.BlockSpec((1, 1, block_w), lambda bi, j, i: (bi, 0, j))
    tot = pl.BlockSpec((1, 1, 1), lambda bi, j, i: (bi, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_fused_streamed_kernel, w=w, block_w=block_w),
        grid=(b, wp // block_w, hp // block_h),
        in_specs=[pl.BlockSpec((1, block_h, block_w), lambda bi, j, i: (bi, i, j))],
        out_specs=[vec, vec, vec, vec, tot, tot],
        out_shape=_out_shapes(b, wp),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.int32)] * 2,
        name="ychg_fused_streamed",
        interpret=resolve_interpret(interpret),
    )(x)
    return _unpack(outs, w)
