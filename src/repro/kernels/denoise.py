"""P-HGRMS-style hypergraph RMS denoising — jnp reference + Pallas kernel.

The same group's P-HGRMS filter (arXiv 1306.5390) removes impulse noise by
treating each pixel's 3x3 neighbourhood as a hypergraph block: a pixel that
sits far from its neighbourhood consensus is classified noisy and replaced
by the block's root-mean-square value; consistent pixels pass through
untouched. This module implements the data-parallel core of that scheme:

  mean_j  = sum of the zero-padded 3x3 window / 9
  rms_j   = sqrt(sum of squares over the same window / 9)
  out_j   = rms_j   if |x_j - mean_j| > tau * rms_j     (impulse outlier)
            x_j     otherwise

The window uses **zero padding with a fixed divisor of 9** everywhere —
deliberately, because that makes the filter invariant under the service
tier's pad-to-bucket batching: a native pixel at the image border sees
exactly the same (zero-extended) window whether the zeros come from the
mathematical boundary or from bucket padding, so padded outputs crop back
bit-exactly. Output is float32 regardless of input dtype so every backend
shares one arithmetic path.

Layout mirrors ``kernels.ops``: ``denoise(stack)`` is the jnp reference,
``denoise_pallas(stack)`` the kernel path; both take (B, H, W) stacks and
return a :class:`DenoiseSummary` holding ``image`` (B, H, W) float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret, whole_image_vmem_limit

Array = jax.Array

DENOISE_FIELDS = ("image",)

# Outlier threshold: |x - mean| > TAU * rms flags an impulse. A fixed
# module constant (not a config knob) so cache keys and cross-backend
# bit-identity never depend on runtime tuning.
TAU = 0.75


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenoiseSummary:
    """Batched denoise output."""

    image: Array  # (B, H, W) float32


def _window_sum(x: Array) -> Array:
    """Sum of the zero-padded 3x3 window around each pixel, (..., H, W)."""
    p = jnp.pad(x, ((0, 0), (1, 1), (1, 1)))
    return (
        p[:, :-2, :-2] + p[:, :-2, 1:-1] + p[:, :-2, 2:]
        + p[:, 1:-1, :-2] + p[:, 1:-1, 1:-1] + p[:, 1:-1, 2:]
        + p[:, 2:, :-2] + p[:, 2:, 1:-1] + p[:, 2:, 2:]
    )


def _filter(x: Array) -> Array:
    """The shared arithmetic path: (B, H, W) float32 -> float32."""
    mean = _window_sum(x) * (1.0 / 9.0)
    rms = jnp.sqrt(_window_sum(x * x) * (1.0 / 9.0))
    return jnp.where(jnp.abs(x - mean) > TAU * rms, rms, x)


@jax.jit
def denoise(stack: Array) -> DenoiseSummary:
    """jnp reference: (B, H, W) stack of any dtype -> float32 summary."""
    return DenoiseSummary(image=_filter(stack.astype(jnp.float32)))


def _denoise_kernel(img_ref, out_ref):
    """One image per grid step: whole (1, H, W) block in VMEM. Elementwise
    VPU work; the 3x3 halo is materialised by the in-kernel pad, so blocks
    are self-contained without neighbour re-reads."""
    x = img_ref[...]
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)   # Mosaic casts no 8/16-bit int to float
    out_ref[...] = _filter(x.astype(jnp.float32))


# VMEM the filter's float32 window temporaries take per pixel, beyond the
# in/out blocks: 56 B/px measured by compiling for v5e at 512-1024 sides.
_DENOISE_TEMP_BYTES_PER_PX = 56


@functools.partial(jax.jit, static_argnames=("interpret",))
def denoise_pallas(stack: Array, *,
                   interpret: bool | None = None) -> DenoiseSummary:
    """Pallas path, bit-identical to :func:`denoise`."""
    b, h, w = stack.shape
    if b == 0 or h * w == 0:
        return denoise(stack)
    if stack.dtype == jnp.bool_:
        stack = stack.astype(jnp.uint8)   # Mosaic loads no i1 blocks
    limit = whole_image_vmem_limit("denoise", (1, h, w), stack.dtype,
                                   jnp.float32, _DENOISE_TEMP_BYTES_PER_PX)
    out = pl.pallas_call(
        _denoise_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        name="denoise_filter",
        interpret=resolve_interpret(interpret),
    )(stack)
    return DenoiseSummary(image=out)
