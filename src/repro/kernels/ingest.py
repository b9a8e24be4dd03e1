"""Byte masks shipped to the device as 32-bit words, restored there.

A ``uint8``/``int8`` array lives on a TPU in tiles that pack the bytes of
four consecutive *rows* into each 32-bit word (``T(8,128)(4,1)``), so the
runtime builds every word on the host from single bytes of four host
rows: a byte-granular shuffle of the whole mask, paid in host CPU on
every transfer. A C-contiguous byte stack whose width is a multiple of 4
is instead viewed as a (B*H, W/4) ``uint32`` array for free (four
consecutive *columns* a word) and shipped as words, which the runtime
moves 32 bits at a time.

For such a word array the TPU's default layout puts the rows minor
(``{0,1}``) wherever that pads less than the words would, the case of
every MODIS-sized mask, so on the device the words already lie as their
transpose, (W/4, B*H), which XLA hands to the kernel as a free bitcast.
``unpack_words`` restores the bytes in one Pallas kernel,
``ingest_unpack``: in the transpose, the word of columns 4j..4j+3 is row
j; ``pltpu.bitcast`` to 8 bits splits row j into rows 4j..4j+3 (byte k,
little-endian as on the host, to row 4j+k); one 8-bit transpose leaves
every byte in its own column. Output blocks are written once and no
temporary is allocated (a shape whose default layout keeps the words
minor gets the transposing copy XLA inserts).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

# the least mask shipped as words: below it the unpack's own dispatch and
# launch cost more than the host relayout they save (on a v5e words lost
# at 4 MiB and won at 5.3 MiB, PERF.md)
MIN_BYTES = 5 << 20

# rows and words a grid step (the fastest of those measured on a v5e
# for the 21000^2 scene, PERF.md); shrunk to the array when it is smaller
BLOCK_ROWS = 1024
BLOCK_WORDS = 512


def _unpack_kernel(t_ref, o_ref):
    # t_ref: (words, rows) of the transposed words
    o_ref[...] = pltpu.bitcast(t_ref[...], o_ref.dtype).T


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def unpack_words(words: jax.Array, batch: int, *, out_dtype=jnp.uint8,
                 interpret: Optional[bool] = None) -> jax.Array:
    """(B*H, W/4) uint32 words of a ``batch``-image stack -> the
    (B, H, W) ``out_dtype`` stack, byte k of word j being column 4j+k."""
    n, q = words.shape
    bh = min(BLOCK_ROWS, _round_up(n, 128))
    bw = min(BLOCK_WORDS, _round_up(q, 32))
    out = pl.pallas_call(
        _unpack_kernel,
        grid=(pl.cdiv(n, bh), pl.cdiv(q, bw)),
        in_specs=[pl.BlockSpec((bw, bh), lambda r, c: (c, r))],
        out_specs=pl.BlockSpec((bh, 4 * bw), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((n, 4 * q), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="ingest_unpack",
        interpret=resolve_interpret(interpret),
    )(words.T)
    return out.reshape(batch, n // batch, 4 * q)


@functools.lru_cache(maxsize=None)
def _unpacker(out_dtype: np.dtype, interpret: Optional[bool]):
    return jax.jit(functools.partial(unpack_words, out_dtype=out_dtype,
                                     interpret=interpret),
                   static_argnums=1)


def ship(words: np.ndarray, batch: int, out_dtype, *,
         interpret: Optional[bool] = None) -> jax.Array:
    """Copy host ``words`` ((B*H, W/4) uint32) to the device and dispatch
    their unpack to the (B, H, W) ``out_dtype`` stack. The device words
    are dropped once the unpack is dispatched."""
    return _unpacker(np.dtype(out_dtype), interpret)(jnp.asarray(words),
                                                     batch)
