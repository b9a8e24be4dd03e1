"""Seeded MOD44W-like water tiles, made on the accelerator.

A MOD44W v6 tile is 4800 x 4800 pixels of 250 m on the sinusoidal grid,
one bit a pixel, 1 = water. ``tile`` makes one from a seed in two parts,
both computed on the default JAX device:

  lakes   the octave-noise field of ``masks.device_scene`` (``octaves``
          octaves) thresholded so that ``lake_share`` of the pixels are
          water: many small lakes, few large ones;
  rivers  ``rivers`` per 4800 pixels of side, each a sine-generated curve
          (Langbein and Leopold's model of meanders: the heading turns as
          ``omega * sin(2 pi s / wavelength)`` along the arc ``s``, here
          with a slower seeded wobble on top), ``river_width`` pixels wide,
          from one edge of the tile to the opposite one. Rivers alternate
          between north-south and west-east. Their headings swing past 90
          degrees, so each river's path is at least twice the side it
          crosses, and a north-south river crosses every band of rows.

A river joins the lakes it runs through. The count scales with the side
(a tile of side ``s`` is crossed by ``round(rivers * s / 4800)``), so the
rivers' share of the pixels is the same at every size.
"""

from __future__ import annotations

import functools

import numpy as np

import masks

SIDE = 4800      # the MOD44W tile side that ``rivers`` is given for
STEP = 0.5       # arc length between the points a river is drawn through


@functools.lru_cache(maxsize=None)
def _drawer(length: int, across: int, width: int, n_points: int):
    """The jitted drawing of one river running along axis 0 of a
    (length, across) mask, through ``n_points`` points of the curve."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(params):
        """``params``: omega, wavelength, phase, wobble, wobble wavelength,
        wobble phase, start across. The curve starts a wavelength before
        row 0 and runs until it first reaches the last row; the part after
        its last point above row 0 is drawn, so the river is one piece
        from edge to edge. Returns the mask and the drawn path's length."""
        omega, lam, phi, wob, lam2, phi2, start = (params[i] for i in range(7))
        s = jnp.arange(n_points, dtype=jnp.float32) * STEP
        theta = (omega * jnp.sin(2 * jnp.pi * s / lam + phi)
                 + wob * jnp.sin(2 * jnp.pi * s / lam2 + phi2))
        along = jnp.cumsum(jnp.cos(theta)) * STEP - lam
        side = start + jnp.cumsum(jnp.sin(theta)) * STEP
        idx = jnp.arange(n_points)
        end = jnp.argmax(along >= length - 1)
        begin = jnp.max(jnp.where((along < 0) & (idx < end), idx, -1))
        keep = (idx > begin) & (idx <= end)
        r = jnp.clip(jnp.floor(along), 0, length - 1).astype(jnp.int32)
        c = jnp.clip(jnp.floor(side), 0, across - width).astype(jnp.int32)
        r = jnp.where(keep, r, length)          # out of range: dropped
        out = jnp.zeros((length, across), jnp.uint8)
        for dr in range(width):
            for dc in range(width):
                out = out.at[r + dr, c + dc].set(1, mode="drop")
        return out, (end - begin) * STEP

    return draw


def river_params(seed: int, n: int, h: int, w: int) -> list:
    """Per river: whether it runs north-south, and its seven parameters."""
    rng = np.random.default_rng([seed, 1])
    out = []
    for i in range(n):
        ns = i % 2 == 0
        length, across = (h, w) if ns else (w, h)
        lam = rng.uniform(0.08, 0.14) * length
        out.append((ns, np.array([
            rng.uniform(1.55, 1.75), lam, rng.uniform(0, 2 * np.pi),
            rng.uniform(0.1, 0.25), rng.uniform(2.5, 4.0) * lam,
            rng.uniform(0, 2 * np.pi), rng.uniform(0.2, 0.8) * across],
            np.float32)))
    return out


def river_count(rivers: int, h: int, w: int) -> int:
    return int(round(rivers * min(h, w) / SIDE))


def draw_river(ns: bool, params, h: int, w: int, width: int):
    """One river as an (h, w) uint8 host mask, and its path's length."""
    length, across = (h, w) if ns else (w, h)
    n_points = int(12 * 1.14 * length / STEP)   # reaches the far edge
    river, path = _drawer(length, across, width, n_points)(params)
    river = np.asarray(river)
    return (river if ns else river.T), float(path)


def tile(seed: int, traffic: dict) -> np.ndarray:
    """One (height, width) uint8 water tile of the traffic mix from
    ``seed``."""
    h, w = traffic["height"], traffic["width"]
    out = masks.device_scene(seed, h, w, traffic["lake_share"],
                             traffic["octaves"])
    for ns, p in river_params(seed, river_count(traffic["rivers"], h, w),
                              h, w):
        out |= draw_river(ns, p, h, w, traffic["river_width_px"])[0]
    return out
