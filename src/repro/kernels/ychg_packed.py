"""Beyond-paper optimization of the paper's own kernel: 1-bit row packing.

The column scan is purely memory-bound (reads H*W mask bytes, writes 4*W
count bytes; ~2 integer ops/pixel). The paper stores one pixel per byte (as
does our baseline kernel). Packing 8 rows per byte cuts HBM traffic 8x —
directly 8x on the dominant roofline term — at the cost of a few cheap
bitwise ops per byte, which the VPU absorbs (still memory-bound after).

Bit layout: bit i of packed[r, c] = mask[8r + i, c] (LSB = topmost row).
Rising-edge detection entirely in registers:

    prev_bits = (b << 1) | carry          # bit i <- row above (carry = MSB
    rising    = b & ~prev_bits            #   of the byte above, at bit 0)
    runs[c]  += popcount(rising)          # lax.population_count (TPU native)

The carry chain down packed rows is a vectorised shift of the MSB column —
no sequential loop. In the kernel the bytes are widened to int32 lanes,
since Mosaic has no 8-bit vector shifts. Step 2 (neighbour diff) is fused into the same pass:
within a tile, births/deaths come from the tile-local shifted counts; the
one column per tile boundary is stitched by the wrapper with an O(W/bw)
vector op, so the fused kernel still makes a single trip over the image.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.ychg_fused import _INT8_SUBLANES

Array = jax.Array


def pack_rows(img: Array) -> Array:
    """(H, W) mask -> (ceil(H/8), W) uint8, bit i = row 8r+i (LSB-first)."""
    h, w = img.shape
    x = (img != 0).astype(jnp.uint8)
    pad = -h % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    x = x.reshape(-1, 8, w)
    weights = (1 << jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    return jnp.sum(x * weights, axis=1, dtype=jnp.uint8)


def _packed_runs(pk_ref):
    """Packed (Hp, bw) uint8 block -> (1, bw) int32 run counts.

    Bytes widen to int32 lanes (Mosaic has no 8-bit shifts); the carry
    row comes from a sublane roll with row 0 zeroed."""
    b = pk_ref[...].astype(jnp.int32)
    # carry: MSB of the byte above, placed at bit 0 of this byte's row
    row = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
    carry = jnp.where(row == 0, 0, pltpu.roll(b >> 7, 1, 0))
    prev = ((b << 1) | carry) & 0xFF
    rising = b & (prev ^ 0xFF)
    return jnp.sum(jax.lax.population_count(rising), axis=0, keepdims=True)


def _packed_colscan_kernel(pk_ref, runs_ref):
    """Block: packed (Hp, bw) uint8 -> runs (1, bw) int32."""
    runs_ref[...] = _packed_runs(pk_ref)


def _pad_packed(packed: Array, block_w: int) -> tuple[Array, int]:
    """Pad packed rows to the uint8 sublane tile (zero bytes start no run)
    and W to the lane block; returns the padded array and the real W."""
    hp, w = packed.shape
    pads = (-hp % _INT8_SUBLANES, -w % block_w)
    if any(pads):
        packed = jnp.pad(packed, ((0, pads[0]), (0, pads[1])))
    return packed, w


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def packed_colscan(packed: Array, *, block_w: int = 128,
                   interpret: bool | None = None) -> Array:
    """Step 1 on a row-packed mask. packed: (Hp, W) uint8 -> (W,) int32."""
    packed, w = _pad_packed(packed, block_w)
    hp, wp = packed.shape
    out = pl.pallas_call(
        _packed_colscan_kernel,
        grid=(wp // block_w,),
        in_specs=[pl.BlockSpec((hp, block_w), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block_w), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, wp), jnp.int32),
        name="ychg_packed_colscan",
        interpret=resolve_interpret(interpret),
    )(packed)
    return out[0, :w]


def _packed_fused_kernel(pk_ref, runs_ref, births_ref, deaths_ref):
    """Fused step 1 + tile-local step 2 (boundary column stitched outside)."""
    runs = _packed_runs(pk_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, runs.shape, 1)
    delta = runs - jnp.where(lane == 0, 0, pltpu.roll(runs, 1, 1))
    runs_ref[...] = runs
    births_ref[...] = jnp.maximum(delta, 0)
    deaths_ref[...] = jnp.maximum(-delta, 0)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def packed_analyze(img: Array, *, block_w: int = 128,
                   interpret: bool | None = None) -> dict[str, Array]:
    """Full two-step pipeline, one pass over a bit-packed image."""
    packed, w = _pad_packed(pack_rows(img), block_w)
    hp, wp = packed.shape
    spec = pl.BlockSpec((1, block_w), lambda j: (0, j))
    runs, births, deaths = pl.pallas_call(
        _packed_fused_kernel,
        grid=(wp // block_w,),
        in_specs=[pl.BlockSpec((hp, block_w), lambda j: (0, j))],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((1, wp), jnp.int32)] * 3,
        name="ychg_packed_fused",
        interpret=resolve_interpret(interpret),
    )(packed)
    runs, births, deaths = runs[0, :w], births[0, :w], deaths[0, :w]
    # stitch tile boundaries: the kernel assumed prev=0 at each tile's first
    # column; correct those W/bw columns against the true left neighbour.
    n_tiles = wp // block_w
    starts_np = [i * block_w for i in range(1, n_tiles) if i * block_w < w]
    if starts_np:
        starts = jnp.asarray(starts_np, jnp.int32)
        left = runs[starts - 1]
        delta = runs[starts] - left
        births = births.at[starts].set(jnp.maximum(delta, 0))
        deaths = deaths.at[starts].set(jnp.maximum(-delta, 0))
    return {
        "runs": runs,
        "cut_vertices": 2 * runs,
        "births": births,
        "deaths": deaths,
        "transitions": (births - deaths) != 0,
        "n_hyperedges": jnp.sum(births, dtype=jnp.int32),
        "n_transitions": jnp.sum((births - deaths) != 0, dtype=jnp.int32),
    }
