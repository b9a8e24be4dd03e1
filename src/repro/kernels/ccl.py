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

The kernel path holds a whole image in VMEM where its block fits, and
otherwise labels it in bands of rows with a merge across the seams (see
the ``bands`` section below); the minimum and the re-ranking are the same.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import (VmemBudgetError, max_block_rows,
                                    resolve_interpret, whole_image_vmem_limit)

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
    # band path only: (B, 2) int32 per image, the band kernel's passes
    # (summed over bands) and the seam merge's rounds; read by the engine
    diag: Array | None = None


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
def _whole_image_labels(stack: Array, *,
                        interpret: bool | None = None) -> CCLSummary:
    """Per-image fixpoint kernel + shared jnp canonicalization.

    The kernel holds one full (H, W) image in VMEM per grid step (CCL needs
    global connectivity, so unlike the yCHG colscan there is no independent
    column tiling to stream); re-ranking runs outside the kernel where the
    gather is cheap.
    """
    b, h, w = stack.shape
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


def labels_pallas(stack: Array, *, interpret: bool | None = None) -> CCLSummary:
    """Pallas path, bit-identical to :func:`labels`.

    An image whose whole (1, H, W) block fits the VMEM cap goes through
    the whole-image kernel; a larger one is labelled in bands of rows
    (:func:`labels_bands`), whose height :func:`band_rows` derives from
    the same VMEM arithmetic. The choice is made from the shape and dtype.
    """
    b, h, w = stack.shape
    if b == 0 or h * w == 0:
        return labels(stack)
    rows = band_rows(h, w, jnp.dtype(stack.dtype))
    if rows is None:
        return _whole_image_labels(stack, interpret=interpret)
    return labels_bands(stack, rows, interpret=interpret)


# ----------------------------------------------------------------- bands
#
# An image too large for one block is cut into full-width bands of rows.
# One Pallas kernel takes a band of int32 seeds per grid step and brings
# each of the band's components to its least seed. Seeded with every
# pixel's global linear index + 1 (``ccl_band_labels``), a pixel's label
# becomes the least index + 1 of its component inside the band. The labels
# of vertically adjacent foreground pixels across each seam between bands
# are pairs of one component; resolving them (``ccl_seam_merge``) gives
# every band label the least label linked to it, the root of the whole
# component. The roots' rank in row-major order is a running count, which
# never falls along the order; seeded with it, and each seam row with the
# rank of its component's root, the kernel runs again (``ccl_relabel``)
# and every pixel ends at its component's rank, the labels of
# :func:`labels`. (The gathers of :func:`_canonicalize` over a MOD44W
# tile, 24 M entries, take ~0.34 s each on a v5e.) The three are programs
# of their own, so a device trace names each.

# Rows of one step of the band kernel's raster: an int32 sublane tile.
_SLAB_ROWS = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=256)
def band_rows(h: int, w: int, dtype) -> int | None:
    """Rows per band for an (H, W) image of ``dtype``; None where the
    whole-image kernel's block fits. Bands are as few and as even as
    whole slabs of rows allow, and each band of int32 seeds in and labels
    out fits the block arithmetic of :func:`whole_image_vmem_limit`;
    raises ``VmemBudgetError`` where not even one slab does."""
    try:
        whole_image_vmem_limit("ccl", (1, h, w), dtype, jnp.int32,
                               _CCL_TEMP_BYTES_PER_PX)
        return None
    except VmemBudgetError:
        pass
    most = max_block_rows("ccl", _round_up(w, 128), jnp.int32, jnp.int32,
                          _CCL_TEMP_BYTES_PER_PX, _SLAB_ROWS)
    n_bands = -(-h // most)
    return _round_up(-(-h // n_bands), _SLAB_ROWS)


def _run_ends(fg: Array, axis: int) -> tuple[Array, Array]:
    """int32 flags of the pixels that start and end a run of foreground
    along ``axis`` (every background pixel is a run of its own)."""
    n = fg.shape[axis]
    f = fg.astype(jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.int32, fg.shape, axis)
    before = pltpu.roll(f, 1, axis)       # element i <- i - 1
    after = pltpu.roll(f, n - 1, axis)    # element i <- i + 1
    head = (f == 0) | (before == 0) | (idx == 0)
    tail = (f == 0) | (after == 0) | (idx == n - 1)
    return head.astype(jnp.int32), tail.astype(jnp.int32)


def _run_min(v: Array, head: Array, tail: Array, axis: int) -> Array:
    """Min of ``v`` over each run along ``axis``, in log2(n) doubling
    steps: a segmented prefix min from each run's head and a segmented
    suffix min from its tail (``head``/``tail`` turn 1 once the window
    reaches the run's end, and the pixel stops taking values)."""
    n = v.shape[axis]
    fwd = bwd = v
    d = 1
    while d < n:
        fwd = jnp.where(head != 0, fwd,
                        jnp.minimum(fwd, pltpu.roll(fwd, d, axis)))
        head = head | pltpu.roll(head, d, axis)
        bwd = jnp.where(tail != 0, bwd,
                        jnp.minimum(bwd, pltpu.roll(bwd, n - d, axis)))
        tail = tail | pltpu.roll(tail, n - d, axis)
        d *= 2
    return jnp.minimum(fwd, bwd)


def _settle(v: Array, fg: Array) -> Array:
    """Labels of one slab of rows at the slab's own fixpoint: the min over
    vertical runs, then over horizontal runs, again while the vertical
    step still lowers a label. A step costs a turn of a path, not a pixel
    of its length; the last vertical step, which finds nothing, is the
    check."""
    horiz = _run_ends(fg, 1)
    if v.shape[0] == 1:
        return _run_min(v, *horiz, axis=1)
    vert = _run_ends(fg, 0)

    def across(x):
        return _run_min(_run_min(x, *vert, axis=0), *horiz, axis=1)

    def down(x):
        return _run_min(x, *vert, axis=0)

    def body(state):
        new = _run_min(state[1], *horiz, axis=1)
        return new, down(new)

    cur = across(v)
    return jax.lax.while_loop(lambda s: jnp.any(s[0] != s[1]), body,
                              (cur, down(cur)))[0]


def _band_kernel(seed_ref, lab_ref, passes_ref, *, slab: int):
    """One (1, band_rows, Wp) band of int32 seeds per grid step
    (``_INF`` on background).

    Rasters over slabs of ``slab`` rows, down and up in turn: a slab
    takes the neighbouring slab's edge row into its own edge row and,
    where that lowered a label (or on the first pass, where it holds
    foreground), settles to its own fixpoint (:func:`_settle`). A pass
    that lowers nothing ends the loop: every slab is then settled and
    agrees with its neighbours, so each component of the band holds its
    least seed. The passes, counted, go to ``passes_ref``.
    """
    _, bh, wp = lab_ref.shape
    n = bh // slab

    def load(i, carry):
        r = pl.multiple_of(i * slab, slab)
        lab_ref[0, pl.ds(r, slab), :] = seed_ref[0, pl.ds(r, slab), :]
        return carry

    jax.lax.fori_loop(0, n, load, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (slab, wp), 0)

    def sweep(state):
        passes, _ = state
        up = passes % 2 == 1
        first = passes == 0
        into = jnp.where(up, slab - 1, 0)   # the edge row a slab takes

        def step(i, carry):
            edge, lowered = carry
            r = pl.multiple_of(jnp.where(up, n - 1 - i, i) * slab, slab)
            v = lab_ref[0, pl.ds(r, slab), :]
            fg = v < _INF
            new = jnp.where(fg & (row == into), jnp.minimum(v, edge), v)
            hit = jnp.any(new != v)

            @pl.when(hit | (first & jnp.any(fg)))
            def _():
                lab_ref[0, pl.ds(r, slab), :] = _settle(new, fg)

            cur = lab_ref[0, pl.ds(r, slab), :]
            edge = jnp.min(jnp.where(row == slab - 1 - into, cur, _INF),
                           axis=0, keepdims=True)
            return edge, lowered | hit

        _, lowered = jax.lax.fori_loop(
            0, n, step, (jnp.full((1, wp), _INF, jnp.int32), jnp.bool_(False)))
        return passes + 1, lowered | first

    passes, _ = jax.lax.while_loop(lambda s: s[1], sweep,
                                   (jnp.int32(0), jnp.bool_(True)))
    passes_ref[...] = jnp.full(passes_ref.shape, passes, jnp.int32)


def _spread(seeds: Array, *, band_rows: int, name: str,
            interpret: bool) -> tuple[Array, Array]:
    """(B, Hp, Wp) int32 seeds, ``_INF`` on background -> each band's
    components at their least seed (same shape, in the seeds' buffer),
    and the kernel's passes per band (B, n_bands, 8, 128)."""
    b, hp, wp = seeds.shape
    n_bands = hp // band_rows
    limit = whole_image_vmem_limit("ccl", (1, band_rows, wp), jnp.int32,
                                   jnp.int32, _CCL_TEMP_BYTES_PER_PX)
    block = pl.BlockSpec((1, band_rows, wp), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_band_kernel, slab=math.gcd(band_rows, _SLAB_ROWS)),
        grid=(b, n_bands),
        in_specs=[block],
        out_specs=[block,
                   pl.BlockSpec((1, 1, 8, 128), lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, hp, wp), jnp.int32),
                   jax.ShapeDtypeStruct((b, n_bands, 8, 128), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        name=name,
        interpret=interpret,
    )(seeds)


@functools.partial(jax.jit, static_argnames=("band_rows", "interpret"))
def ccl_band_labels(stack: Array, *, band_rows: int,
                    interpret: bool) -> tuple[Array, Array]:
    """(B, H, W) stack -> band labels (B, Hp, Wp) int32, ``_INF`` on
    background, and the kernel's passes per band.

    The mask is zero-padded to whole bands and to a width of whole
    128-lane tiles; padding on the right and at the bottom starts no
    component and keeps the row-major order of the image's pixels, so the
    ranks crop back exactly. Seeds are linear indices in the padded
    width."""
    b, h, w = stack.shape
    hp, wp = _round_up(h, band_rows), _round_up(w, 128)
    fg = jnp.pad(stack != 0, ((0, 0), (0, hp - h), (0, wp - w)))
    pos = (jax.lax.broadcasted_iota(jnp.int32, (hp, wp), 0) * wp
           + jax.lax.broadcasted_iota(jnp.int32, (hp, wp), 1) + 1)
    return _spread(jnp.where(fg, pos[None], _INF), band_rows=band_rows,
                   name="ccl_band_labels", interpret=interpret)


def _resolve(a: Array, c: Array, *, size: int) -> tuple[Array, Array]:
    """Union of the label pairs ``(a[k], c[k])`` over a table of ``size``
    labels: min-hooking of both ends and their current roots, then one
    pointer jump of each end, round after round until every pair agrees
    on a root that is its own. Each entry only falls, to a label linked to
    it, so a pair's root is then the least label of its component.
    Returns the table and the rounds taken."""

    def settled(t):
        ta, tc = t[a], t[c]
        return jnp.all(ta == tc) & jnp.all(t[ta] == ta)

    def one_round(state):
        t, rounds = state
        ta, tc = t[a], t[c]
        m = jnp.minimum(ta, tc)
        t = t.at[a].min(m).at[c].min(m).at[ta].min(m).at[tc].min(m)
        t = t.at[a].set(t[t[a]])
        t = t.at[c].set(t[t[c]])
        return t, rounds + 1

    return jax.lax.while_loop(
        lambda s: ~settled(s[0]), one_round,
        (jnp.arange(size, dtype=jnp.int32), jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("band_rows",))
def ccl_seam_merge(lab: Array, *, band_rows: int) -> tuple[Array, Array]:
    """Band labels -> per image, a table from each label to the least
    label of its component, and the merge rounds it took ((B,) int32).

    The pairs are the labels of vertically adjacent foreground pixels
    across each seam, at most W per seam; label 0 pairs with itself
    where a seam pixel is background."""
    b, hp, wp = lab.shape
    above = lab[:, band_rows - 1:hp - 1:band_rows, :]
    below = lab[:, band_rows::band_rows, :]
    both = (above < _INF) & (below < _INF)
    a = jnp.where(both, above, 0).reshape(b, -1)
    c = jnp.where(both, below, 0).reshape(b, -1)
    return jax.vmap(functools.partial(_resolve, size=hp * wp + 1))(a, c)


@functools.partial(jax.jit, static_argnames=("band_rows", "h", "w",
                                              "interpret"))
def ccl_relabel(lab: Array, table: Array, passes: Array, rounds: Array, *,
                band_rows: int, h: int, w: int,
                interpret: bool) -> CCLSummary:
    """Band labels and the merge's table -> the labels of :func:`labels`,
    cropped to (H, W). A root is a band label at its own pixel that the
    merge left at itself; ``rank`` counts roots in row-major order, and
    the band kernel spreads each component's least rank, its root's.
    ``diag`` carries each image's labelling passes (summed over bands)
    and merge rounds."""
    b, hp, wp = lab.shape
    fg = lab < _INF
    pos = (jax.lax.broadcasted_iota(jnp.int32, (hp, wp), 0) * wp
           + jax.lax.broadcasted_iota(jnp.int32, (hp, wp), 1) + 1)
    is_root = fg & (lab == pos) & (table[:, 1:].reshape(lab.shape) == pos)
    rank = jnp.cumsum(is_root.reshape(b, -1), axis=1, dtype=jnp.int32)
    seeds = jnp.where(fg, rank.reshape(lab.shape), _INF)
    # a component's pixels on a seam row start from its root's rank
    for rows in (slice(band_rows - 1, hp - 1, band_rows),
                 slice(band_rows, hp, band_rows)):
        part = lab[:, rows, :]
        keys = jnp.where(part < _INF, part, 0).reshape(b, -1)
        root = jnp.take_along_axis(table, keys, axis=1)
        at_root = jnp.take_along_axis(rank, jnp.maximum(root - 1, 0), axis=1)
        seeds = seeds.at[:, rows, :].set(
            jnp.where(part < _INF, at_root.reshape(part.shape), _INF))
    spread, _ = _spread(seeds, band_rows=band_rows, name="ccl_rank_spread",
                        interpret=interpret)
    diag = jnp.stack([passes[:, :, 0, 0].sum(axis=1), rounds], axis=1)
    return CCLSummary(labels=jnp.where(fg, spread, 0)[:, :h, :w],
                      n_components=rank[:, -1], diag=diag)


def labels_bands(stack: Array, rows: int, *,
                 interpret: bool | None = None) -> CCLSummary:
    """The band path of :func:`labels_pallas` with bands of ``rows`` rows
    (any height in interpret mode; a multiple of 8 rows on the chip)."""
    _, h, w = stack.shape
    interpret = resolve_interpret(interpret)
    lab, passes = ccl_band_labels(stack, band_rows=rows, interpret=interpret)
    table, rounds = ccl_seam_merge(lab, band_rows=rows)
    return ccl_relabel(lab, table, passes, rounds, band_rows=rows, h=h, w=w,
                       interpret=interpret)
