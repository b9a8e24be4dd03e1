"""Seeded snow-cover-like masks, made on the accelerator.

They follow the shape of the MODIS snow-cover grids the paper analyses
(arXiv:1307.2560): several octaves of smooth noise, bilinearly upsampled
and summed with halving weights, thresholded so that ``coverage`` of the
pixels are foreground. ``device_scene`` computes the field on the default
JAX device in row blocks and brings the uint8 mask to the host, so a
21000 x 21000 scene takes seconds, not minutes.
"""

from __future__ import annotations

import functools

import numpy as np


def _octave_shapes(h: int, w: int, octaves: int):
    return tuple((max(2, h >> (octaves - o + 2)), max(2, w >> (octaves - o + 2)))
                 for o in range(octaves))


def _axis(n_coarse, n, idx):
    import jax.numpy as jnp

    pos = idx.astype(jnp.float32) * ((n_coarse - 1) / max(n - 1, 1))
    i0 = jnp.floor(pos).astype(jnp.int32)
    return i0, jnp.minimum(i0 + 1, n_coarse - 1), pos - i0


def _field(coarse, rows, cols, h, w):
    import jax.numpy as jnp

    acc = jnp.zeros((rows.shape[0], cols.shape[0]), jnp.float32)
    for o, c in enumerate(coarse):
        y0, y1, fy = _axis(c.shape[0], h, rows)
        x0, x1, fx = _axis(c.shape[1], w, cols)
        mixed = c[y0] * (1 - fy)[:, None] + c[y1] * fy[:, None]
        acc += (mixed[:, x0] * (1 - fx)[None] + mixed[:, x1] * fx[None]) / 2.0**o
    return acc


def device_scene(seed: int, h: int, w: int, coverage: float = 0.45,
                 octaves: int = 4, block_px: int = 64 << 20) -> np.ndarray:
    """(h, w) uint8 host mask made on the default JAX device from ``seed``.

    The field is evaluated in equal blocks of rows of at most ``block_px``
    pixels, so one program serves every block; the threshold is the
    ``1 - coverage`` quantile of the field on a strided grid.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1,))
    def coarse_fields(key, shapes):
        return tuple(jax.random.normal(k, s, jnp.float32)
                     for k, s in zip(jax.random.split(key, len(shapes)), shapes))

    @jax.jit
    def threshold(coarse, rows, cols):
        return jnp.quantile(_field(coarse, rows, cols, h, w), 1.0 - coverage)

    @jax.jit
    def block(coarse, rows, cols, thr):
        return (_field(coarse, rows, cols, h, w) > thr).astype(jnp.uint8)

    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))
    coarse = coarse_fields(key, _octave_shapes(h, w, octaves))
    stride = max(1, int(np.ceil(np.sqrt(h * w / 4e6))))
    thr = threshold(coarse, np.arange(0, h, stride, dtype=np.int32),
                    np.arange(0, w, stride, dtype=np.int32))
    n_blocks = -(-h * w // block_px)
    rows_per = -(-h // n_blocks)
    cols = np.arange(w, dtype=np.int32)
    out = np.empty((h, w), np.uint8)
    for r0 in range(0, h, rows_per):
        rows = np.minimum(np.arange(r0, r0 + rows_per, dtype=np.int32), h - 1)
        part = np.asarray(block(coarse, rows, cols, thr))
        out[r0:r0 + rows_per] = part[: h - r0]
    return out
