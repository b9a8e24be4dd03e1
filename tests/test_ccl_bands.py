"""The band path of ``kernels.ccl``: images no whole-image block holds.

``labels_pallas`` keeps the whole-image kernel where its block fits the
VMEM cap and labels larger images in bands of rows, merging labels across
the seams. Forced small bands run the band path in interpret mode at test
sizes, and must equal the jnp reference :func:`ccl.labels` bit for bit;
the choice itself is checked on shapes alone (nothing at 4800² runs here).
Through the engine, the band path's pass, merge-round and seam-label
counts reach the ``engine.fetch`` span and nothing else.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.engine import Engine, YCHGConfig
from repro.kernels import ccl, ingest
from repro.kernels.platform import VMEM_CAP_BYTES, max_block_rows
from repro.service import Service, ServiceConfig


def _spiral(n: int) -> np.ndarray:
    """One path winding inward: it crosses every seam many times."""
    m = np.zeros((n, n), np.uint8)
    y0 = x0 = 0
    y1 = x1 = n - 1
    while y0 <= y1 and x0 <= x1:
        m[y0, x0:x1 + 1] = 1
        m[y0:y1 + 1, x1] = 1
        m[y1, x0:x1 + 1] = 1
        m[y0 + 2:y1 + 1, x0] = 1
        if y0 + 2 <= y1:
            m[y0 + 2, x0:x0 + 3] = 1
        y0, x0, y1, x1 = y0 + 4, x0 + 4, y1 - 4, x1 - 4
    return m


def _u(h: int = 30, w: int = 40) -> np.ndarray:
    """Two arms that join only at the bottom: in the top bands they are
    two components, and the merge makes them one."""
    m = np.zeros((h, w), np.uint8)
    m[2:h - 2, 3:6] = 1
    m[2:h - 2, w - 6:w - 3] = 1
    m[h - 4:h - 2, 3:w - 3] = 1
    return m


def _stripes(h: int = 40, w: int = 128) -> np.ndarray:
    """Vertical 1-px stripes: every foreground pixel of a seam row is a
    label of its own, and each stripe chains every band together."""
    m = np.zeros((h, w), np.uint8)
    m[:, ::2] = 1
    return m


def _bare_seams(h: int = 40, w: int = 60) -> np.ndarray:
    """Foreground only off the seam rows of bands of 8: the merge's table
    then holds label 0 alone."""
    m = (np.random.default_rng(2).random((h, w)) < 0.6).astype(np.uint8)
    m[7::8] = m[8::8] = 0
    return m


MASKS = {
    "random": (np.random.default_rng(1).random((2, 37, 130)) < 0.55
               ).astype(np.uint8),
    "stripes": _stripes()[None],
    "bare_seams": _bare_seams()[None],
    # three images whose seams hold full, no and random populations
    "stack3": np.stack([_stripes(40, 60), _bare_seams(),
                        (np.random.default_rng(3).random((40, 60)) < 0.5
                         ).astype(np.uint8)]),
    # a width padded past w to the next 128 lanes, the padding on seams
    "padded": (np.random.default_rng(4).random((1, 29, 131)) < 0.6
               ).astype(np.uint8),
    "spiral": _spiral(41)[None],
    "u": _u()[None],
    "checkerboard": (np.indices((21, 34)).sum(0) % 2).astype(np.uint8)[None],
    "all_water": np.ones((1, 19, 27), np.uint8),
    "all_land": np.zeros((1, 19, 27), np.uint8),
}


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  np.asarray(want.labels))
    np.testing.assert_array_equal(np.asarray(got.n_components),
                                  np.asarray(want.n_components))
    assert got.labels.dtype == want.labels.dtype == jnp.int32


def _seam_labels(mask: np.ndarray, rows: int) -> int:
    """Distinct components, each band labelled on its own, that touch a
    seam row: a band's last row where a band follows, its first where
    one comes before."""
    n_bands = -(-mask.shape[0] // rows)
    total = 0
    for k in range(n_bands):
        band = np.asarray(ccl.labels(jnp.asarray(
            mask[k * rows:(k + 1) * rows])[None]).labels[0])
        edges = ([band[0]] if k else []) + ([band[-1]] if k < n_bands - 1
                                             else [])
        total += len(np.setdiff1d(np.concatenate(edges or [[0]]), [0]))
    return total


@pytest.mark.parametrize("rows", [1, 3, 8, 13, 32])
@pytest.mark.parametrize("name", sorted(MASKS))
def test_band_path_bit_identical_to_reference(name, rows):
    x = jnp.asarray(MASKS[name])
    got = ccl.labels_bands(x, rows, interpret=True)
    _same(got, ccl.labels(x))
    passes, rounds, seam_labels = np.asarray(got.diag).T
    assert got.diag.shape == (x.shape[0], 3)
    assert np.all(passes >= 2 * -(-x.shape[1] // rows)) and np.all(rounds >= 0)
    assert seam_labels.tolist() == [_seam_labels(m, rows) for m in MASKS[name]]


def test_u_merges_through_a_lower_band_only():
    """Bands of 8 rows: the arms meet in the last band, so the merge
    needs a round, and the reference agrees on one component."""
    x = jnp.asarray(_u()[None])
    got = ccl.labels_bands(x, 8, interpret=True)
    _same(got, ccl.labels(x))
    assert int(got.n_components[0]) == 1 and int(got.diag[0, 1]) >= 1


def _full_table_resolve(a, c, size):
    """The seam merge as it was over every label of the image: a table of
    ``size`` entries indexed by the label itself (the oracle)."""

    def settled(t):
        ta, tc = t[a], t[c]
        return jnp.all(ta == tc) & jnp.all(t[ta] == ta)

    def one_round(state):
        t, rounds = state
        ta, tc = t[a], t[c]
        m = jnp.minimum(ta, tc)
        t = t.at[a].min(m).at[c].min(m).at[ta].min(m).at[tc].min(m)
        t = t.at[a].set(t[t[a]])
        return t.at[c].set(t[t[c]]), rounds + 1

    return jax.lax.while_loop(lambda s: ~settled(s[0]), one_round,
                              (jnp.arange(size, dtype=jnp.int32),
                               jnp.int32(0)))


@pytest.mark.parametrize("rows", [3, 8])
@pytest.mark.parametrize("name", ["spiral", "u", "random_a", "random_b",
                                  "stripes_wide"])
def test_compact_merge_matches_the_full_table_merge(name, rows):
    """Over the seam rows' own labels the merge gives every seam pixel
    the root the full label table gave it, in as many rounds. The wide
    stripes link 13 x 150 pairs at bands of 3 rows, more than the loop's
    short prefix holds, and 4 x 150 at bands of 8."""
    masks = dict(MASKS, random_a=(np.random.default_rng(5).random(
        (2, 44, 70)) < 0.6).astype(np.uint8), random_b=(
        np.random.default_rng(6).random((1, 50, 129)) < 0.45).astype(np.uint8),
        stripes_wide=_stripes(40, 300)[None])
    lab, _ = ccl.ccl_band_labels(jnp.asarray(masks[name]), band_rows=rows,
                                 interpret=True)
    root, counts = ccl.ccl_seam_merge(lab, band_rows=rows)
    lab = np.asarray(lab)
    keys = np.concatenate([lab[:, rows - 1:-1:rows], lab[:, rows::rows]],
                          axis=1).reshape(len(lab), -1)
    keys = np.where(keys < ccl._INF, keys, 0)
    above, below = np.split(keys, 2, axis=1)
    both = (above > 0) & (below > 0)
    for i in range(len(lab)):
        table, rounds = _full_table_resolve(
            jnp.asarray(np.where(both[i], above[i], 0)),
            jnp.asarray(np.where(both[i], below[i], 0)), lab[i].size + 1)
        np.testing.assert_array_equal(np.asarray(root[i]),
                                      np.asarray(table)[keys[i]])
        assert int(counts[i, 0]) == int(rounds)
        assert int(counts[i, 1]) == len(np.setdiff1d(keys[i], [0]))


@pytest.mark.parametrize("side,bands", [(1024, False), (1900, False),
                                        (2048, True), (4800, True)])
def test_chooser_keeps_whole_image_below_the_cap(side, bands):
    rows = ccl.band_rows(side, side, jnp.dtype(jnp.uint8))
    assert (rows is not None) is bands
    out = jax.eval_shape(ccl.labels_pallas,
                         jax.ShapeDtypeStruct((1, side, side), jnp.uint8))
    assert out.labels.shape == (1, side, side)
    assert (out.diag is not None) is bands
    if bands:
        # as few bands as the tallest block allows, evened out in whole
        # slabs of 8 rows
        most = max_block_rows("ccl", -(-side // 128) * 128, jnp.int32,
                              jnp.int32, ccl._CCL_TEMP_BYTES_PER_PX, 8)
        assert rows % 8 == 0 and rows <= most
        assert -(-side // rows) == -(-side // most)
        assert -(-side // rows) * rows - side < 8 * -(-side // rows)


def test_chooser_sizes_a_mod44w_tile_into_eight_bands():
    rows = ccl.band_rows(4800, 4800, jnp.dtype(jnp.uint8))
    assert rows == 600 and 4800 % rows == 0
    need = rows * 4864 * (2 * (4 + 4) + ccl._CCL_TEMP_BYTES_PER_PX)
    assert need < VMEM_CAP_BYTES


@pytest.fixture
def forced_bands(monkeypatch):
    """Bands of 8 rows for any image, and words for any byte mask."""
    monkeypatch.setattr(ccl, "band_rows", lambda h, w, dtype: 8)
    monkeypatch.setattr(ingest, "MIN_BYTES", 0)


def _spans(name):
    return [(n, meta) for tr in obs.recorder().traces()
            for n, _, _, meta in tr.spans() if n == name]


def test_engine_band_path_through_words_equals_reference(forced_bands):
    mask = (np.random.default_rng(7).random((45, 68)) < 0.6).astype(np.uint8)
    obs.recorder().clear()
    eng = Engine(YCHGConfig(backend="pallas"))
    got = eng.analyze(mask, op="ccl").to_host()
    want = ccl.labels(jnp.asarray(mask)[None])
    assert set(got) == {"labels", "n_components"}
    np.testing.assert_array_equal(got["labels"], np.asarray(want.labels[0]))
    np.testing.assert_array_equal(got["n_components"],
                                  np.asarray(want.n_components[0]))
    assert [m["words"] for _, m in _spans("engine.put")] == [1]
    (_, fetch), = _spans("engine.fetch")
    assert fetch["sweeps"] >= 2 * 6 and fetch["merge_rounds"] >= 1
    # 6 bands of 8 rows: 5 seams of two rows, 128 lanes wide
    assert 0 < fetch["seam_labels"] <= 2 * 5 * 128


def test_whole_image_fetch_carries_no_band_meta():
    obs.recorder().clear()
    mask = (np.random.default_rng(8).random((24, 31)) < 0.5).astype(np.uint8)
    Engine(YCHGConfig(backend="pallas")).analyze(mask, op="ccl").to_host()
    (_, fetch), = _spans("engine.fetch")
    assert not {"sweeps", "merge_rounds", "seam_labels"} & set(fetch)


def test_served_ccl_answers_are_unchanged_by_the_band_path(forced_bands):
    mask = (np.random.default_rng(9).random((20, 20)) < 0.6).astype(np.uint8)
    cfg = ServiceConfig(bucket_sides=(32,))
    with Service(Engine(YCHGConfig(backend="pallas")), cfg) as svc:
        got = svc.submit(mask, op="ccl").result(timeout=600).to_host()
    want = ccl.labels(jnp.asarray(mask)[None])
    assert list(got) == ["labels", "n_components"]
    np.testing.assert_array_equal(got["labels"], np.asarray(want.labels[0]))
    np.testing.assert_array_equal(got["n_components"],
                                  np.asarray(want.n_components[0]))
