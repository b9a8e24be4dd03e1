"""Connected-components labeling (CCL) — jnp reference + Pallas kernel.

Coarse-to-fine parallel CCL in the style of Chen et al. (arXiv 1712.09789):
every foreground pixel starts as its own component seeded with its linear
index, then iterated 4-neighbour **min propagation** drives each component
to a unique fixpoint — the minimum linear index over the component. The
fixpoint is schedule-independent, so any propagation order (the jnp
reference adds pointer-jumping to converge in ~log steps; the Pallas kernel
does plain neighbour sweeps in VMEM) lands on bit-identical labels.

A final **canonical re-ranking** maps root labels to consecutive component
ids 1..n in row-major first-encounter order. That makes labels invariant
under the service tier's pad-to-bucket batching: zero padding never starts
a component, and padding right/bottom preserves the row-major order of the
native pixels, so canonical labels crop back bit-exactly (the same
padding-inertness argument as ``service.batching`` makes for yCHG).

Layout mirrors ``kernels.ops``: ``labels(stack)`` is the jnp reference,
``labels_pallas(stack)`` the kernel path; both take (B, H, W) stacks of
any dtype (nonzero = foreground) and return a :class:`CCLSummary` of
``labels`` (B, H, W) int32 and ``n_components`` (B,) int32.
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

CCL_FIELDS = ("labels", "n_components")

# Sentinel larger than any linear pixel index + 1; background carries it
# during propagation so minima never leak across components. A Python int
# (not a jnp scalar) so the Pallas kernel does not capture a device
# constant; it folds into each trace as an int32 literal.
_INF = 1 << 30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CCLSummary:
    """Batched CCL output: canonical labels + per-image component count."""

    labels: Array        # (B, H, W) int32, 0 = background, 1..n per image
    n_components: Array  # (B,) int32


def _seed_labels(fg: Array) -> Array:
    """(B, H, W) bool -> initial labels: linear index + 1 on fg, _INF on bg."""
    _, h, w = fg.shape
    idx = (jax.lax.broadcasted_iota(jnp.int32, (h, w), 0) * w
           + jax.lax.broadcasted_iota(jnp.int32, (h, w), 1) + 1)
    return jnp.where(fg, idx[None], _INF)


def _neighbor_min(lab: Array) -> Array:
    """Min over self + 4-neighbours; borders padded with _INF."""
    pad = ((0, 0), (1, 0), (0, 0))
    up = jnp.pad(lab[:, :-1, :], pad, constant_values=_INF)
    down = jnp.pad(lab[:, 1:, :], ((0, 0), (0, 1), (0, 0)),
                   constant_values=_INF)
    left = jnp.pad(lab[:, :, :-1], ((0, 0), (0, 0), (1, 0)),
                   constant_values=_INF)
    right = jnp.pad(lab[:, :, 1:], ((0, 0), (0, 0), (0, 1)),
                    constant_values=_INF)
    return jnp.minimum(lab, jnp.minimum(jnp.minimum(up, down),
                                        jnp.minimum(left, right)))


def _canonicalize(lab: Array, fg: Array) -> CCLSummary:
    """Fixpoint labels (min linear index + 1 per component) -> consecutive
    ids 1..n in row-major first-encounter order, 0 on background."""
    b, h, w = lab.shape
    flat = jnp.where(fg, lab, 0).reshape(b, h * w)
    pos = jnp.arange(h * w, dtype=jnp.int32)[None, :] + 1
    is_root = (flat == pos).astype(jnp.int32)   # bg is 0, never a root
    rank = jnp.cumsum(is_root, axis=1, dtype=jnp.int32)
    canon = jnp.where(
        flat > 0,
        jnp.take_along_axis(rank, jnp.maximum(flat - 1, 0), axis=1),
        0,
    )
    n = rank[:, -1] if h * w else jnp.zeros((b,), jnp.int32)
    return CCLSummary(labels=canon.reshape(b, h, w), n_components=n)


@jax.jit
def labels(stack: Array) -> CCLSummary:
    """jnp reference: (B, H, W) stack -> canonical CCL summary.

    Coarse step: 4-neighbour min propagation. Fine step: pointer jumping
    (label <- label-at-root-candidate) so chains collapse logarithmically
    instead of one pixel per sweep. Both preserve the per-component
    minimum, so the fixpoint equals the kernel path's bit for bit.
    """
    fg = stack != 0
    b, h, w = fg.shape
    if h * w == 0:
        return CCLSummary(labels=jnp.zeros((b, h, w), jnp.int32),
                          n_components=jnp.zeros((b,), jnp.int32))
    lab0 = _seed_labels(fg)

    def jump(lab: Array) -> Array:
        # follow the indirection: each fg pixel adopts its current root
        # candidate's own label (bg _INF entries are never dereferenced)
        flat = jnp.where(fg, lab, 0).reshape(b, h * w)
        hop = jnp.take_along_axis(flat, jnp.maximum(flat - 1, 0), axis=1)
        hop = hop.reshape(b, h, w)
        return jnp.where(fg & (hop > 0), hop, lab)

    def body(state):
        lab, _ = state
        new = jnp.where(fg, _neighbor_min(lab), _INF)
        new = jump(jump(new))
        return new, jnp.any(new != lab)

    lab, _ = jax.lax.while_loop(lambda s: s[1], body,
                                (lab0, jnp.bool_(True)))
    return _canonicalize(lab, fg)


def _ccl_kernel(img_ref, out_ref):
    """One image per grid step: whole (1, H, W) block in VMEM; iterated
    neighbour-min sweeps (no gather — TPU-friendly) to the fixpoint."""
    fg = img_ref[...] != 0
    lab0 = _seed_labels(fg)

    def body(state):
        lab, _ = state
        new = jnp.where(fg, _neighbor_min(lab), _INF)
        return new, jnp.any(new != lab)

    lab, _ = jax.lax.while_loop(lambda s: s[1], body,
                                (lab0, jnp.bool_(True)))
    out_ref[...] = jnp.where(fg, lab, 0)


# VMEM the sweep body's temporaries take per pixel, beyond the in/out
# blocks: 12 B/px at a 1024 px side and 16 B/px at 2048, measured by
# compiling for v5e.
_CCL_TEMP_BYTES_PER_PX = 16


@functools.partial(jax.jit, static_argnames=("interpret",))
def labels_pallas(stack: Array, *, interpret: bool | None = None) -> CCLSummary:
    """Pallas path: per-image fixpoint kernel + shared jnp canonicalization.

    The kernel holds one full (H, W) image in VMEM per grid step (CCL needs
    global connectivity, so unlike the yCHG colscan there is no independent
    column tiling to stream); re-ranking runs outside the kernel where the
    gather is cheap. Bit-identical to :func:`labels`.
    """
    b, h, w = stack.shape
    if b == 0 or h * w == 0:
        return labels(stack)
    if stack.dtype == jnp.bool_:
        stack = stack.astype(jnp.uint8)   # Mosaic loads no i1 blocks
    limit = whole_image_vmem_limit("ccl", (1, h, w), stack.dtype, jnp.int32,
                                   _CCL_TEMP_BYTES_PER_PX)
    raw = pl.pallas_call(
        _ccl_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        name="ccl_labels",
        interpret=resolve_interpret(interpret),
    )(stack)
    return _canonicalize(raw, stack != 0)
