"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted elsewhere.

Every kernel wrapper resolves its ``interpret`` flag here, so the choice is
made in one place and from the platform JAX runs on. Interpret mode
evaluates the kernel body with jnp ops: exact, and the way the kernels are
tested on the CPU, but it proves nothing about Mosaic, whose compile must
be rehearsed against a described TPU topology (``tests/test_tpu_compile.py``).

Whole-image kernels (``ccl``, ``denoise``) also size their scoped-VMEM
limit here, from the block they hold, and refuse a block that no limit
can hold instead of letting Mosaic fail deep inside a compile; ``ccl``
takes the height of its bands of rows from the same arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# TPU v5e has 128 MiB of VMEM per TensorCore. A kernel may raise Mosaic's
# scoped limit (16 MiB by default) up to this cap; the rest stays with the
# compiler's own scratch.
VMEM_CAP_BYTES = 100 << 20
_DEFAULT_SCOPED_VMEM = 16 << 20
_VMEM_MARGIN = 8 << 20


class VmemBudgetError(ValueError):
    """A whole-image kernel block needs more VMEM than any limit allows."""


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means: interpret exactly when no TPU backs JAX."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _vmem_bytes_per_px(in_dtype, out_dtype, temp_bytes_per_px: int) -> int:
    return (2 * (jnp.dtype(in_dtype).itemsize + jnp.dtype(out_dtype).itemsize)
            + temp_bytes_per_px)


def whole_image_vmem_limit(kernel: str, block_shape: tuple[int, ...],
                           in_dtype, out_dtype, temp_bytes_per_px: int) -> int:
    """Scoped-VMEM limit for a kernel holding one whole (1, H, W) image.

    The need is the double-buffered input and output blocks plus the
    body's temporaries (``temp_bytes_per_px``, measured by compiling the
    kernel for the chip), plus a fixed margin for Mosaic's own scratch.
    Raises :class:`VmemBudgetError`, naming the shape and the cap, when
    the block cannot fit; no other backend is substituted.
    """
    px = math.prod(block_shape)
    need = px * _vmem_bytes_per_px(in_dtype, out_dtype,
                                   temp_bytes_per_px) + _VMEM_MARGIN
    if need > VMEM_CAP_BYTES:
        raise VmemBudgetError(
            f"{kernel}: a block of shape {tuple(block_shape)} "
            f"{jnp.dtype(in_dtype).name} needs about {need / 2**20:.1f} MiB "
            f"of VMEM, over the {VMEM_CAP_BYTES >> 20} MiB limit; ccl labels "
            f"larger images in bands of rows, denoise has no row-tiled "
            f"kernel")
    return max(need, _DEFAULT_SCOPED_VMEM)


def max_block_rows(kernel: str, width: int, in_dtype, out_dtype,
                   temp_bytes_per_px: int, multiple: int) -> int:
    """The most rows, a multiple of ``multiple``, of a (1, rows, width)
    block that :func:`whole_image_vmem_limit` admits. Raises
    :class:`VmemBudgetError` when not even ``multiple`` rows fit."""
    per_px = _vmem_bytes_per_px(in_dtype, out_dtype, temp_bytes_per_px)
    rows = (VMEM_CAP_BYTES - _VMEM_MARGIN) // (width * per_px)
    rows -= rows % multiple
    if rows == 0:
        whole_image_vmem_limit(kernel, (1, multiple, width), in_dtype,
                               out_dtype, temp_bytes_per_px)
    return rows
