"""The ``ccl.mod44w`` cell's pieces on the CPU: the NumPy reference, the
water tiles, the driver's comparison, the readers of its metrics, and its
place in ``BENCHMARK.json`` beside the cells that were there before it.
"""

import argparse
import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402
import observe  # noqa: E402
import reference_ccl  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import water  # noqa: E402

SPEC = harness.load_spec()
CELL = "ccl.mod44w"
METRICS = ["ccl_band_ms.water", "ccl_merge_ms.water", "ccl_sweeps.water",
           "ccl_merge_rounds.water", "ccl_roofline.water"]
DEV = "/device:TPU:0"
PEAKS = {"hbm_bytes_per_s": 819e9}


def _jnp_labels(mask):
    import jax.numpy as jnp

    from repro.kernels import ccl

    s = ccl.labels(jnp.asarray(mask)[None])
    return {"labels": np.asarray(s.labels[0]),
            "n_components": np.asarray(s.n_components[0])}


def _traffic(h, w):
    return dict(harness.resolve(SPEC, CELL).traffic, height=h, width=w)


@pytest.mark.parametrize("shape,density,seed", [
    ((1, 1), 1.0, 0), ((1, 40), 0.6, 1), ((37, 1), 0.6, 2),
    ((64, 64), 0.5, 3), ((61, 130), 0.6, 4), ((90, 33), 0.45, 5)])
def test_reference_equals_the_program_reference_on_random_masks(
        shape, density, seed):
    mask = (np.random.default_rng(seed).random(shape) < density
            ).astype(np.uint8)
    assert reference_ccl.same(reference_ccl.labels(mask), _jnp_labels(mask))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_equals_the_program_reference_on_water_tiles(seed):
    mask = water.tile(seed, _traffic(480, 448))
    want = _jnp_labels(mask)
    assert reference_ccl.same(reference_ccl.labels(mask), want)
    assert int(want["n_components"]) > 1


@pytest.mark.parametrize("fill", [0, 1])
def test_reference_all_land_and_all_water(fill):
    got = reference_ccl.labels(np.full((20, 30), fill, np.uint8))
    assert int(got["n_components"]) == fill
    assert got["labels"].dtype == np.int32 and got["labels"].sum() == 600 * fill


def test_control_with_int16_counters_is_wrong_past_32767_runs():
    """The control narrows every run number, parent and id to int16; a
    checkerboard of 80000 runs wraps it, as a MOD44W tile does."""
    mask = (np.indices((400, 400)).sum(0) % 2).astype(np.uint8)
    want = reference_ccl.labels(mask)
    assert int(want["n_components"]) == 80000
    assert not reference_ccl.same(reference_ccl.labels(mask, np.int16), want)
    small = (np.indices((40, 40)).sum(0) % 2).astype(np.uint8)
    assert reference_ccl.same(reference_ccl.labels(small, np.int16),
                              reference_ccl.labels(small))


def test_water_tiles_are_a_function_of_the_seed():
    tr_ = _traffic(320, 352)
    a, b, c = (water.tile(s, tr_) for s in (2**31 + 5, 2**31 + 5, 6))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.uint8 and a.shape == (320, 352)
    assert abs(a.mean() - tr_["coverage"]) < 0.03


@pytest.mark.parametrize("side", [800, 1600])
def test_every_river_runs_edge_to_edge_in_one_piece(side):
    """Each river touches both edges it runs between, is one component,
    and its path is at least twice the side it crosses."""
    params = water.river_params(2**31 + 3, water.river_count(6, side, side),
                                side, side)
    assert len(params) == round(6 * side / 4800)
    for ns, p in params:
        river, path = water.draw_river(ns, p, side, side, 2)
        edges = (river[0], river[-1]) if ns else (river[:, 0], river[:, -1])
        assert all(e.any() for e in edges)
        assert int(reference_ccl.labels(river)["n_components"]) == 1
        assert path >= 2 * side


def test_driver_counts_a_planted_one_pixel_fault(tmp_path, capsys,
                                                 monkeypatch):
    """One pixel of one window call's labels is altered where the engine
    produces it: ``bad_results`` reads 1, and ``correct`` is false."""
    from repro.engine import Engine

    cell = harness.resolve(SPEC, CELL)
    cell = dataclasses.replace(cell, traffic=_traffic(96, 80))
    orig, calls = Engine._run, []

    def run_(self, imgs, *, batched, op):
        out = orig(self, imgs, batched=batched, op=op)
        calls.append(op)
        if len(calls) == cell.traffic["distinct_tiles"] + 1:
            out = dataclasses.replace(out, labels=out.labels.at[0, 5, 5].add(1))
        return out

    monkeypatch.setattr(Engine, "_run", run_)
    args = argparse.Namespace(seed=2**31 + 99, seconds=0.5, trace=0)
    run.run_cell(cell, args, {"platform": "cpu", "kind": "none", "count": 1},
                 tmp_path)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["checks"]["bad_results"] == {"value": 1, "limit": 0}
    assert out["correct"] is False and out["attempted"] > 1
    assert set(out["metrics"]) == {"mpx_s", "setup_s"}
    assert set(calls) == {"ccl"}


def test_cell_resolves_to_mpx_s_setup_s_and_its_own_metrics():
    cell = harness.resolve(SPEC, CELL)
    assert cell.chips == 1 and cell.kind == "ccl_tiles"
    assert [m["name"] for m in cell.end_to_end] == ["mpx_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == METRICS
    assert all(m["layer"] == "kernels" and m["workloads"] == [CELL]
               for m in cell.per_layer)
    assert cell.config["reduced"] == [] and cell.traffic["height"] == 4800


def test_the_cells_before_it_resolve_as_before():
    bulk = harness.resolve(SPEC, "bulk.250m")
    scene = harness.resolve(SPEC, "scene.21k")
    assert [m["name"] for m in bulk.end_to_end] == ["mpx_s", "setup_s"]
    assert [m["name"] for m in scene.end_to_end] == ["scene_mpx_s", "setup_s"]
    for cell, tag in ((bulk, ".mpx"), (scene, ".scene")):
        names = [m["name"] for m in cell.per_layer]
        assert names and all(n.endswith(tag) for n in names)
    assert len(bulk.per_layer) == 11 and len(scene.per_layer) == 9


def _obs(trace, spans):
    return observe.Observed(list(spans), trace, 10.0, 0, PEAKS)


def _trace():
    """Two calls: band kernel 2 + 3 ms, merge 1 ms each, relabel 2 ms each,
    inside their programs; an unpack program that is not ccl's."""
    band = "%ccl_band_labels.1 = (s32[1,4928,4864]) custom-call(%pad)"
    ops, mods = [], []
    for t0, kern in ((0.0, 0.002), (1.0, 0.003)):
        ops += [("%pad_convert_fusion = s8[1,4928,4864]", t0, t0 + 0.001),
                (band, t0 + 0.001, t0 + 0.001 + kern)]
        mods += [("jit_ccl_band_labels(1)", t0, t0 + 0.001 + kern),
                 ("jit_ccl_seam_merge(2)", t0 + 0.01, t0 + 0.011),
                 ("jit_ccl_relabel(3)", t0 + 0.02, t0 + 0.022),
                 ("jit_unpack_words(4)", t0 - 0.005, t0 - 0.004)]
    return tr.DeviceTrace({DEV: ops}, {DEV: mods}, [])


SPANS = [("engine.dispatch", 0.0, 0.01, {"op": "ccl", "px": 4800 * 4800}),
         ("engine.dispatch", 1.0, 1.01, {"op": "ccl", "px": 4800 * 4800}),
         ("engine.dispatch", 2.0, 2.01, {"op": "ychg", "px": 99}),
         ("engine.fetch", 0.0, 0.1, {"bytes": 1, "sweeps": 20,
                                     "merge_rounds": 3}),
         ("engine.fetch", 1.0, 1.1, {"bytes": 1, "sweeps": 30,
                                     "merge_rounds": 5})]


@pytest.mark.parametrize("metric,want", [
    ("ccl_band_ms.water", 2.5), ("ccl_merge_ms.water", 3.0),
    ("ccl_sweeps.water", 25.0), ("ccl_merge_rounds.water", 4.0),
    # two tiles' floor bytes over 13 ms of ccl programs: band 3 + 4,
    # merge 1 + 1, relabel 2 + 2
    ("ccl_roofline.water",
     100 * 2 * (5 * 4800 * 4800 + 4) / 819e9 / 0.013)])
def test_readers_on_a_hand_built_traced_run(metric, want):
    got = harness.load_reader(metric)(_obs(_trace(), SPANS))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_are_silent_on_a_program_without_the_band_path(metric):
    """The parent has no band kernel, programs or span meta: nothing to
    read, and nothing raised, on the recorded chip trace or none."""
    chip = tr.read(BENCH / "testdata" / "serve_small.xplane.pb")
    spans = [("engine.fetch", 1.0, 1.1, {"bytes": 4})]
    assert harness.load_reader(metric)(_obs(chip, spans)) is None
    assert harness.load_reader(metric)(_obs(None, spans)) is None
