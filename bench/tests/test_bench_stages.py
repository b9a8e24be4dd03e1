"""The readers of the program's stages and named kernels.

Hand-built traces with known gaps, stages and kernel events pin the
arithmetic; the recorded chip trace (a program from before the stages
were annotated and the kernels named) pins that each reader is silent,
and does not raise, where there is nothing to read.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import harness  # noqa: E402
import observe  # noqa: E402
import stages  # noqa: E402
import trace_reduce as tr  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}
DEV = "/device:TPU:0"

NEW_METRICS = [
    "engine_put_ms.mpx", "engine_put_ms.scene", "engine_dispatch_ms.mpx",
    "engine_dispatch_ms.scene", "engine_fetch_ms.scene", "scene_sync_ms.mpx",
    "ychg_kernel_ms.mpx", "ychg_kernel_ms.scene", "ychg_prep_ms.mpx",
    "ychg_prep_ms.scene", "idle_outside_stages.mpx",
    "idle_outside_stages.scene",
]


def _trace():
    """Two launches of the fused program, 0-4 s and 10-13 s; a layout
    copy before each kernel; an unrelated op at 20-21 s. Host: stages
    cover 4-8 s and 13-15 s of the gaps; a runtime event covers 8-9 s."""
    k = "%ychg_fused_full.1 = (s32[4,1,5504]) custom-call(%pad)"
    ops = [("%copy.2 = u8[4,256,5416] copy(%imgs)", 0.0, 1.0),
           (k, 1.0, 4.0),
           ("%copy.2 = u8[4,256,5416] copy(%imgs)", 10.0, 10.5),
           (k, 10.5, 13.0),
           ("%reduce = s32[4] reduce(%get-tuple-element.3)", 20.0, 21.0)]
    mods = [("jit_fused_analyze_pallas(1)", 0.0, 4.0),
            ("jit_fused_analyze_pallas(1)", 10.0, 13.0),
            ("jit_multiply(2)", 20.0, 21.0)]
    host = [("scene.compute", 3.0, 8.0), ("engine.put", 3.5, 7.0),
            ("XlaLinearize", 8.0, 9.0), ("scene.sync", 13.0, 15.0),
            ("scene.stitch", 30.0, 31.0)]     # after the last op: not a gap
    return tr.DeviceTrace({DEV: ops}, {DEV: mods}, host)


def _obs(trace, window_s=25.0, spans=()):
    return observe.Observed(list(spans), trace, window_s, 0, PEAKS)


def test_kernel_ms_is_total_over_launches():
    assert stages.kernel_ms(_obs(_trace())) == pytest.approx(1e3 * 5.5 / 2)


def test_prep_ms_is_program_time_outside_the_kernel_per_launch():
    # programs 7 s, kernel 5.5 s, two launches; jit_multiply is not yCHG
    assert stages.prep_ms(_obs(_trace())) == pytest.approx(1e3 * 1.5 / 2)


def test_idle_outside_stages_counts_only_unnamed_gaps():
    # device busy 0-4, 10-13, 20-21; gaps 4-10 and 13-20 (13 s);
    # stages name 4-8 and 13-15 (6 s): 7 s of 25 s unnamed
    got = stages.idle_outside_stages_pct(_obs(_trace()))
    assert got == pytest.approx(100.0 * 7 / 25)
    assert got < observe.idle_pct(_obs(_trace()))


def test_idle_outside_stages_is_zero_when_stages_cover_every_gap():
    t = _trace()
    t.host.append(("engine.fetch", 4.0, 20.0))
    assert stages.idle_outside_stages_pct(_obs(t)) == 0.0


def test_only_engine_and_scene_events_are_stages():
    t = _trace()
    assert stages.stage_intervals(_obs(t)) == [
        (3.0, 8.0), (3.5, 7.0), (13.0, 15.0), (30.0, 31.0)]
    assert stages.is_kernel("%ychg_fused_streamed.1 = (s32[1,1,21120])")
    assert not stages.is_kernel("%copy_bitcast_fusion(%ychg_fused_full.1)")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_reads_a_traced_run(metric):
    spans = [("engine.put", 1.0, 1.004, {}), ("engine.dispatch", 1.004,
                                             1.005, {}),
             ("engine.fetch", 1.005, 1.006, {}), ("scene.sync", 2.0, 2.002,
                                                  {})]
    value = harness.load_reader(metric)(_obs(_trace(), spans=spans))
    assert value is not None and value >= 0.0


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_is_silent_on_an_unannotated_program(metric):
    """The recorded chip trace has neither stage annotations nor named
    kernels, and a program without the spans gives none to read."""
    chip = tr.read(BENCH / "testdata" / "serve_small.xplane.pb")
    spans = [("scene.read", 1.0, 1.5, {}), ("scene.checkpoint", 2.0, 2.5, {})]
    assert harness.load_reader(metric)(_obs(chip, 0.05, spans)) is None
    assert harness.load_reader(metric)(_obs(None, 0.05, spans)) is None


def test_new_metrics_are_in_the_benchmark_with_their_cells():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        cell = "bulk.250m" if name.endswith(".mpx") else "scene.21k"
        assert entries[name]["workloads"] == [cell]
        assert name in [m["name"] for m in
                        harness.resolve(spec, cell).per_layer]


def test_stages_reach_the_profilers_host_plane(tmp_path):
    """Under a ``jax.profiler`` trace, a small bulk job and a ``to_host``
    call leave their stage names on a ``/host:`` plane, on the profiler's
    clock, where ``trace_reduce.read`` finds them."""
    import jax
    import numpy as np

    from repro import obs
    from repro.engine import Engine
    from repro.scene import BulkJob, BulkJobConfig, synthetic_manifest

    obs.configure(enabled=True)
    engine = Engine()
    job = BulkJob(engine, synthetic_manifest(1, 21, 10, seed=5, cell=4),
                  BulkJobConfig(out_dir=str(tmp_path / "out"),
                                ckpt_dir=str(tmp_path / "ckpt"), tile_h=8,
                                stack_tiles=2))
    img = np.ones((8, 16), np.uint8)
    engine.analyze(img).to_host()             # compiled outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "profile"),
                             profiler_options=opts)
    try:
        job.run()
        engine.analyze(img).to_host()
    finally:
        jax.profiler.stop_trace()
    trace = tr.read(tmp_path / "profile")
    got = {}
    for name, a, b in trace.host:
        if name.startswith(stages.STAGE_PREFIXES):
            got.setdefault(name, []).append((a, b))
    assert {n: len(v) for n, v in got.items()} == {
        "scene.read": 2, "scene.compute": 2, "engine.put": 3,
        "engine.dispatch": 3, "scene.sync": 2, "scene.stitch": 2,
        "scene.write": 1, "scene.checkpoint": 1, "engine.fetch": 1}
    # nesting holds on the profiler's clock too
    for (a, b), (c0, c1) in zip(got["scene.sync"], got["scene.compute"]):
        assert c0 <= a <= b <= c1
    (ckpt,), (fetch,) = got["scene.checkpoint"], got["engine.fetch"]
    assert ckpt[1] <= fetch[0]
