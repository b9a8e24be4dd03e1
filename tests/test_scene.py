"""`repro.scene` suite: granule windowing, exact stitching, resumable bulk.

Policy (tests/README.md §Scene tests): scenes are tiny (tens of rows) but
always exercise the ragged last strip; no wall-clock assertions — resume
points are pinned with ``max_stacks``, never with timers or signals. Two
bars, both exact:

  * **stitch bit-identity** — every field of a stitched scene result
    (values, dtypes, shapes) equals one whole-scene ``engine.analyze``;
  * **resume byte-identity** — an interrupted-and-resumed ``BulkJob``
    writes files byte-for-byte equal to an uninterrupted run's.

Sockets follow the frontend policy: loopback only, ephemeral ports.
"""

import dataclasses
import os

import numpy as np
import pytest

from repro.data import scenes
from repro.engine import Engine, YCHGConfig
from repro.scene import (
    BulkJob,
    BulkJobConfig,
    GranuleReader,
    GranuleSpec,
    SceneProgress,
    SceneResult,
    SceneRunner,
    manifest_from_json,
    manifest_to_json,
    read_scene_result,
    seam_joins,
    stitch_tile_runs,
    synthetic_manifest,
    write_scene_result,
)

TIMEOUT = 300.0


def _assert_host_identical(got, want, context=""):
    """Dict-of-arrays parity bar: values, dtypes, and shapes all equal."""
    assert set(got) == set(want)
    for field in want:
        g, w = np.asarray(got[field]), np.asarray(want[field])
        assert g.dtype == w.dtype, f"{context}{field}: {g.dtype} != {w.dtype}"
        assert g.shape == w.shape, f"{context}{field}: {g.shape} != {w.shape}"
        np.testing.assert_array_equal(g, w, err_msg=context + field)


# -------------------------------------------------------- synthetic scenes


def test_scene_rows_compose_to_whole_scene():
    """Windowed reads are exact row slices of the materialised scene —
    the determinism GranuleReader (and resume byte-identity) rests on."""
    whole = scenes.scene(50, 40, seed=9, cell=8)
    for row0, row1 in [(0, 50), (0, 7), (7, 20), (49, 50), (13, 13)]:
        np.testing.assert_array_equal(
            scenes.scene_rows(50, 40, row0, row1, seed=9, cell=8),
            whole[row0:row1])


def test_scene_is_binary_and_seed_sensitive():
    a = scenes.scene(32, 32, seed=0, cell=8)
    b = scenes.scene(32, 32, seed=1, cell=8)
    assert a.dtype == np.uint8 and set(np.unique(a)) <= {0, 1}
    assert not np.array_equal(a, b)


# ----------------------------------------------------------------- reader


def test_reader_tiles_cover_scene_with_inert_padding():
    mask = scenes.scene(21, 16, seed=2, cell=4)
    reader = GranuleReader.from_array(mask, 8)
    assert reader.n_tiles == 3
    assert reader.tile_rows(2) == (16, 21)
    rebuilt = np.concatenate([reader.read_tile(t) for t in range(3)])
    np.testing.assert_array_equal(rebuilt[:21], mask)
    assert not rebuilt[21:].any()   # zero padding only


def test_read_stack_matches_individual_tiles():
    mask = scenes.scene(30, 12, seed=3, cell=4)
    reader = GranuleReader.from_array(mask, 7)
    stack = reader.read_stack(1, 3)
    for i in range(3):
        np.testing.assert_array_equal(stack[i], reader.read_tile(1 + i))
    with pytest.raises(IndexError):
        reader.read_stack(3, 3)


def test_memmap_reader_matches_in_memory(tmp_path):
    mask = scenes.scene(25, 10, seed=4, cell=4)
    path = os.path.join(tmp_path, "granule.npy")
    np.save(path, mask)
    mem = GranuleReader.from_array(mask, 6)
    mm = GranuleReader.from_npy(path, 6)
    for t in range(mem.n_tiles):
        np.testing.assert_array_equal(mm.read_tile(t), mem.read_tile(t))


def test_spec_open_memmap_validates_shape(tmp_path):
    path = os.path.join(tmp_path, "g.npy")
    np.save(path, scenes.scene(20, 10, seed=0))
    spec = GranuleSpec(granule_id="g", height=99, width=10, kind="memmap",
                       path=path)
    with pytest.raises(ValueError, match="manifest says"):
        GranuleReader.open(spec, 8)


def test_manifest_json_round_trip():
    manifest = synthetic_manifest(3, 64, 32, seed=5, cell=16, coverage=0.3)
    assert manifest_from_json(manifest_to_json(manifest)) == manifest
    ids = [s.granule_id for s in manifest]
    assert len(set(ids)) == 3   # distinct ids, distinct seeds
    assert len({s.seed for s in manifest}) == 3


def test_spec_validation():
    with pytest.raises(ValueError, match="memmap"):
        GranuleSpec(granule_id="g", height=4, width=4, kind="memmap")
    with pytest.raises(ValueError, match="kind"):
        GranuleSpec(granule_id="g", height=4, width=4, kind="tarball")
    with pytest.raises(ValueError):
        GranuleSpec(granule_id="g", height=0, width=4)


# ----------------------------------------------------------------- stitch


def test_seam_joins_counts_crossing_runs_only():
    bottom = np.array([1, 0, 1, 0, 5], np.uint8)
    top = np.array([1, 1, 0, 0, 1], np.uint8)
    np.testing.assert_array_equal(seam_joins(bottom, top),
                                  np.array([1, 0, 0, 0, 1], np.int32))


@pytest.mark.parametrize("h,w,tile_h,stack", [
    (45, 32, 16, 2),   # ragged last strip, mid stack
    (37, 51, 8, 4),    # ragged, stack > strips per granule end
    (64, 24, 64, 1),   # one strip == whole scene
    (5, 9, 2, 3),      # tiny, stack overshoots
    (33, 16, 1, 4),    # single-row strips: every boundary is a seam
])
def test_stitched_scene_bit_identical_to_whole_scene(h, w, tile_h, stack):
    """The tentpole bar: streaming + seam stitching reproduces the
    whole-scene analysis exactly, every field, dtypes included."""
    mask = scenes.scene(h, w, seed=h * 100 + w, cell=8)
    engine = Engine()
    reader = GranuleReader.from_array(mask, tile_h)
    got = SceneRunner(engine, stack_tiles=stack).analyze_scene(reader)
    _assert_host_identical(got.to_host(), engine.analyze(mask).to_host(),
                           context=f"{h}x{w}/{tile_h}: ")


def test_stitched_scene_bit_identical_under_mesh():
    """Same bar with a mesh attached: stacks go through shard_map."""
    from repro.sharding import make_batch_mesh

    mask = scenes.scene(40, 16, seed=11, cell=8)
    engine = Engine(YCHGConfig(backend="auto"), mesh=make_batch_mesh())
    reader = GranuleReader.from_array(mask, 8)
    got = SceneRunner(engine, stack_tiles=3).analyze_scene(reader)
    _assert_host_identical(got.to_host(),
                           Engine().analyze(mask).to_host())


def test_stitch_tile_runs_matches_scene_runs():
    """Per-tile runs analysed independently (the online/NDJSON replay
    path) stitch to the same run vector the streaming runner produces."""
    mask = scenes.scene(29, 14, seed=6, cell=4)
    engine = Engine()
    reader = GranuleReader.from_array(mask, 6)
    tiles = [reader.read_tile(t) for t in range(reader.n_tiles)]
    tile_runs = [np.asarray(engine.analyze(t).to_host()["runs"])
                 for t in tiles]
    whole = np.asarray(engine.analyze(mask).to_host()["runs"])
    np.testing.assert_array_equal(stitch_tile_runs(tile_runs, tiles), whole)
    with pytest.raises(ValueError, match="run vectors"):
        stitch_tile_runs(tile_runs[:-1], tiles)


def test_progress_counters_accumulate():
    progress = SceneProgress()
    mask = scenes.scene(24, 8, seed=7, cell=4)
    reader = GranuleReader.from_array(mask, 8)
    SceneRunner(stack_tiles=2).analyze_scene(reader, progress=progress)
    snap = progress.snapshot()
    assert snap.tiles_done == reader.n_tiles
    assert snap.stitch_time_s > 0.0
    assert snap.resumes == 0


# ------------------------------------------------------------ result files


def test_scene_result_bytes_round_trip_and_deterministic(tmp_path):
    mask = scenes.scene(20, 12, seed=8, cell=4)
    result = SceneRunner().analyze_scene(GranuleReader.from_array(mask, 8))
    blob = result.to_bytes()
    assert blob == result.to_bytes()   # content-determined, no timestamps
    back = SceneResult.from_bytes(blob)
    _assert_host_identical(back.to_host(), result.to_host())
    assert (back.granule_id, back.height, back.width, back.tile_h,
            back.n_tiles) == (result.granule_id, result.height,
                              result.width, result.tile_h, result.n_tiles)

    path = os.path.join(tmp_path, "a", "r.ychg")
    write_scene_result(path, result)
    write_scene_result(path, result)   # rewrite: same bytes, atomic
    with open(path, "rb") as f:
        assert f.read() == blob
    _assert_host_identical(read_scene_result(path).to_host(),
                           result.to_host())
    with pytest.raises(ValueError, match="magic"):
        SceneResult.from_bytes(b"not a scene result")
    with pytest.raises(ValueError, match="trailing"):
        SceneResult.from_bytes(blob + b"x")


# -------------------------------------------------------------- bulk jobs


def _job(tmp_path, tag, manifest, progress=None, **cfg):
    knobs = dict(out_dir=os.path.join(tmp_path, tag, "out"),
                 ckpt_dir=os.path.join(tmp_path, tag, "ckpt"),
                 tile_h=8, stack_tiles=1, checkpoint_every=1)
    knobs.update(cfg)
    return BulkJob(Engine(), manifest, BulkJobConfig(**knobs),
                   progress=progress)


def _read_bytes(path):
    with open(path, "rb") as f:
        return f.read()


def test_bulk_job_outputs_match_direct_analysis(tmp_path):
    manifest = synthetic_manifest(2, 21, 10, seed=20, cell=4)
    job = _job(tmp_path, "direct", manifest)
    report = job.run()
    assert report.completed and report.granules_done == 2
    engine = Engine()
    for spec in manifest:
        got = read_scene_result(job.output_path(spec))
        whole = scenes.scene(spec.height, spec.width, seed=spec.seed,
                             cell=spec.cell, coverage=spec.coverage)
        _assert_host_identical(got.to_host(),
                               engine.analyze(whole).to_host(),
                               context=spec.granule_id + ": ")


@pytest.mark.parametrize("stop_after", [1, 3, 5])
def test_bulk_job_resume_is_byte_identical(tmp_path, stop_after):
    """Kill anywhere (granule boundary, mid-granule, first stack): the
    resumed job's output files are byte-for-byte the uninterrupted run's."""
    manifest = synthetic_manifest(2, 20, 12, seed=30, cell=4)
    straight = _job(tmp_path, "straight", manifest)
    assert straight.run().completed

    progress = SceneProgress()
    interrupted = _job(tmp_path, f"kill{stop_after}", manifest, progress)
    first = interrupted.run(max_stacks=stop_after)
    assert first.status == "interrupted"
    second = _job(tmp_path, f"kill{stop_after}", manifest, progress).run()
    assert second.completed
    assert second.resumes == 1
    assert progress.snapshot().resumes == 1
    for spec in manifest:
        assert _read_bytes(interrupted.output_path(spec)) == \
            _read_bytes(straight.output_path(spec)), spec.granule_id


def test_bulk_job_resume_after_corrupt_newest_checkpoint(tmp_path):
    """A torn newest checkpoint costs one interval, not the job: resume
    warns, falls back to the previous step, and stays byte-identical."""
    manifest = synthetic_manifest(1, 40, 10, seed=40, cell=4)
    straight = _job(tmp_path, "straight", manifest)
    assert straight.run().completed

    killed = _job(tmp_path, "killed", manifest)
    assert killed.run(max_stacks=3).status == "interrupted"
    ckpt_dir = os.path.join(tmp_path, "killed", "ckpt")
    newest = sorted(d for d in os.listdir(ckpt_dir)
                    if d.startswith("step_"))[-1]
    shard = [f for f in os.listdir(os.path.join(ckpt_dir, newest))
             if f.endswith(".npz")][0]
    with open(os.path.join(ckpt_dir, newest, shard), "r+b") as f:
        f.truncate(8)
    with pytest.warns(RuntimeWarning):
        second = _job(tmp_path, "killed", manifest).run()
    assert second.completed and second.resumes == 1
    spec = manifest[0]
    assert _read_bytes(killed.output_path(spec)) == \
        _read_bytes(straight.output_path(spec))


def test_bulk_job_checkpoints_are_gced_to_keep(tmp_path):
    manifest = synthetic_manifest(1, 48, 8, seed=50, cell=4)
    job = _job(tmp_path, "gc", manifest, keep=2)
    assert job.run().completed
    steps = [d for d in os.listdir(os.path.join(tmp_path, "gc", "ckpt"))
             if d.startswith("step_") and not d.endswith(".tmp")]
    assert len(steps) == 2


def test_bulk_job_finished_job_reruns_as_noop(tmp_path):
    manifest = synthetic_manifest(1, 16, 8, seed=60, cell=4)
    job = _job(tmp_path, "done", manifest)
    assert job.run().completed
    before = _read_bytes(job.output_path(manifest[0]))
    again = _job(tmp_path, "done", manifest).run()
    assert again.completed and again.stacks_done == 0
    assert _read_bytes(job.output_path(manifest[0])) == before


def test_bulk_job_rejects_bad_manifests(tmp_path):
    cfg = BulkJobConfig(out_dir=str(tmp_path / "o"),
                        ckpt_dir=str(tmp_path / "c"))
    with pytest.raises(ValueError, match="empty"):
        BulkJob(Engine(), [], cfg)
    spec = synthetic_manifest(1, 8, 8)[0]
    with pytest.raises(ValueError, match="duplicate"):
        BulkJob(Engine(), [spec, spec], cfg)


def test_bulk_job_detects_manifest_width_change(tmp_path):
    manifest = synthetic_manifest(1, 32, 8, seed=70, cell=4)
    job = _job(tmp_path, "w", manifest)
    assert job.run(max_stacks=1).status == "interrupted"
    wider = [dataclasses.replace(manifest[0], width=16)]
    with pytest.raises(ValueError, match="wide"):
        _job(tmp_path, "w", wider).run()


# -------------------------------------------- online/offline (loopback)


def test_online_tiles_agree_with_offline_scene():
    """Tiles replayed through the HTTP front end (NDJSON batch endpoint)
    are per-tile bit-identical to engine.analyze, and their stitched runs
    equal the offline streaming result — the scene-smoke leg as a test."""
    from repro.frontend import ServerThread, YCHGClient
    from repro.service import ServiceConfig, YCHGService

    mask = scenes.scene(20, 16, seed=80, cell=8)
    engine = Engine()
    reader = GranuleReader.from_array(mask, 8)
    tiles = [reader.read_tile(t) for t in range(reader.n_tiles)]
    offline = SceneRunner(engine).analyze_scene(reader)

    progress = SceneProgress()
    progress.set_totals(tiles=reader.n_tiles, granules=1)
    progress.note_tiles(reader.n_tiles)
    cfg = ServiceConfig(bucket_sides=(16,), max_batch=len(tiles))
    with YCHGService(engine, cfg) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        svc.attach_scene_progress(progress)
        items = {it.id: it for it in client.analyze_batch(tiles)}
        assert all(it.ok for it in items.values())
        for i, tile in enumerate(tiles):
            _assert_host_identical(items[i].result,
                                   engine.analyze(tile).to_host(),
                                   context=f"tile {i}: ")
        online_runs = stitch_tile_runs(
            [items[i].result["runs"] for i in range(len(tiles))], tiles)
        np.testing.assert_array_equal(online_runs,
                                      np.asarray(offline.runs))
        m = svc.metrics()
        assert m.scene_tiles_done == reader.n_tiles
        assert m.scene_tiles_total == reader.n_tiles
        text = client.metrics_text()
    assert "ychg_scene_tiles_done" in text
    assert "ychg_scene_resumes_total" in text


# ------------------------------------------------------------- stage spans


@pytest.fixture
def traced():
    from repro import obs

    obs.configure(enabled=True)
    obs.recorder().clear()
    yield obs
    obs.configure(enabled=True)
    obs.recorder().clear()


def test_bulk_job_stage_spans_nest_per_stack(tmp_path, traced):
    """Each stack: read, then put / dispatch / sync inside compute, then
    stitch, all on the granule's trace; the engine opens none of its own."""
    manifest = synthetic_manifest(1, 21, 10, seed=21, cell=4)
    _job(tmp_path, "spans", manifest, tile_h=8, stack_tiles=2,
         checkpoint_every=8).run()
    (tr,) = traced.recorder().traces()
    assert tr.process == "scene"
    spans = sorted(tr.spans(), key=lambda s: (s[1], -s[2]))
    names = [s[0] for s in spans]
    stack = ["scene.read", "scene.compute", "engine.put",
             "engine.dispatch", "scene.sync", "scene.stitch"]
    # 3 strips of 8 rows in stacks of 2: two stacks, then the granule ends
    assert names == stack * 2 + ["scene.write", "scene.checkpoint"]
    for i in (0, 6):
        _, c0, c1, meta = spans[i + 1]
        assert meta == {"granule": "granule_0021", "tiles": (2, 1)[i // 6]}
        for name, a, b, _ in spans[i + 2:i + 5]:
            assert c0 <= a <= b <= c1, name
        assert spans[i][2] <= c0 and c1 <= spans[i + 5][1]
    assert spans[4][3] == {"bytes": 2 * 10 * 4}        # the (2, W) runs
    # 10 columns are not whole words: the stacks went as bytes
    assert spans[2][3] == {"bytes": 2 * 8 * 10, "words": 0}


def test_scene_runner_stage_spans(traced):
    reader = GranuleReader.open(GranuleSpec("s", 21, 10, seed=3, cell=4), 8)
    SceneRunner(Engine(), stack_tiles=2).analyze_scene(reader)
    (tr,) = traced.recorder().traces()
    names = [s[0] for s in tr.spans()]
    assert names.count("scene.sync") == names.count("scene.stitch") == 2
    assert names.count("engine.put") == names.count("engine.dispatch") == 2
    assert names[-1] == "scene.finalize"
    by = {}
    for name, a, b, _ in tr.spans():
        by.setdefault(name, []).append((a, b))
    for (a, b), (s0, s1) in zip(by["scene.sync"], by["scene.stitch"]):
        assert s0 <= a <= b <= s1               # the wait is part of stitch
