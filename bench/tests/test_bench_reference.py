"""The benchmark's NumPy reference against the program's own paths (CPU).

The reference is written from the paper and imports nothing of the
program; here it is held to ``core.serial.analyze_numpy`` and to the jnp
engine, exactly (values, dtypes, shapes), on the kinds of masks the cells
send, and on one granule stitched through ``SceneRunner``.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import masks  # noqa: E402
import reference  # noqa: E402
from repro.core import serial  # noqa: E402
from repro.data import modis  # noqa: E402
from repro.engine import Engine, YCHGConfig  # noqa: E402


@pytest.fixture(scope="module")
def jnp_engine():
    return Engine(YCHGConfig(backend="jax"))


def agree(mask, engine):
    want = reference.analyze(mask)
    assert reference.same(serial.analyze_numpy(mask), want)
    assert reference.same(engine.analyze(mask).to_host(), want)
    return want


@pytest.mark.parametrize("shape,seed", [((128, 128), 1), ((256, 200), 2),
                                        ((512, 512), 2**31 + 5)])
def test_snowfields(shape, seed, jnp_engine):
    agree(masks.device_scene(seed, *shape), jnp_engine)


@pytest.mark.parametrize("shape", [(1, 1), (1, 37), (53, 1), (112, 120),
                                   (231, 200), (17, 300)])
def test_ragged_shapes(shape, jnp_engine):
    rng = np.random.default_rng(shape[0] * 1000 + shape[1])
    agree(rng.integers(0, 2, shape, np.uint8), jnp_engine)


@pytest.mark.parametrize("fill", [0, 1])
def test_all_zero_and_all_one(fill, jnp_engine):
    want = agree(np.full((64, 96), fill, np.uint8), jnp_engine)
    assert int(want["n_hyperedges"]) == fill
    assert int(want["runs"].sum()) == 96 * fill


@pytest.mark.parametrize("res,count", [(64, 0), (64, 147), (512, 1000),
                                       (1024, 10_000)])
def test_striped_exact_counts(res, count, jnp_engine):
    want = agree(modis.striped(res, count), jnp_engine)
    assert int(want["n_hyperedges"]) == count


def test_blocks_carry_runs_across_block_rows():
    rng = np.random.default_rng(7)
    mask = (rng.random((1000, 90)) < 0.7).astype(np.uint8)
    whole = reference.analyze(mask)
    for rows in (1, 3, 64, 999):
        assert reference.same(reference.from_runs(
            reference.column_runs(mask, block_rows=rows)), whole)


def test_granule_through_scene_runner():
    from repro.scene import GranuleReader, SceneRunner

    granule = masks.device_scene(11, 555, 431)
    got = SceneRunner(Engine(YCHGConfig(backend="jax")), stack_tiles=3) \
        .analyze_scene(GranuleReader.from_array(granule, tile_h=64))
    assert reference.same(got.to_host(), reference.analyze(granule))


def test_int8_control_wraps_where_int32_does_not():
    mask = modis.striped(256, 300)
    assert not reference.same(reference.analyze(mask, np.int8),
                              reference.analyze(mask))
    small = modis.striped(256, 20)
    assert reference.same(reference.analyze(small, np.int8),
                          reference.analyze(small))
