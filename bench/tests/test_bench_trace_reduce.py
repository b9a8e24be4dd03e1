"""The trace reduction on synthetic intervals and on one chip trace.

``testdata/serve_small.xplane.pb`` was recorded on one TPU v5e chip: six
``Engine.analyze_batch`` calls on (4, 256, 256) uint8 stacks, each
followed by the service's crop of one (224, 240) request.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import observe  # noqa: E402
import trace_reduce as tr  # noqa: E402

CHIP_TRACE = BENCH / "testdata" / "serve_small.xplane.pb"


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),                 # a gap
    ([(0, 2), (1, 3)], [(0, 3)]),                         # overlap
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),               # nesting
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),         # touching, unsorted
    ([(0, 1), (3, 2)], [(0, 1)]),                         # reversed: dropped
])
def test_merge(intervals, merged):
    assert tr.merge(intervals) == merged
    assert tr.covered(intervals) == sum(b - a for a, b in merged)


def test_gaps_longest_first():
    assert tr.gaps([(0, 1), (1.5, 2), (4, 5), (4.5, 4.8)]) == [(2, 4), (1, 1.5)]


def test_nested_events_count_once_in_busy_and_module_time():
    ops = [("a", 0.0, 4.0), ("b", 1.0, 2.0), ("c", 6.0, 7.0)]
    mods = [("jit_fused_analyze_pallas(1)", 0.0, 4.0),
            ("jit__crop_row(2)", 6.0, 7.0),
            ("jit_fused_analyze_streamed(3)", 3.0, 5.0)]
    t = tr.DeviceTrace({"/device:TPU:0": ops}, {"/device:TPU:0": mods},
                       host=[("outer", 3.5, 6.5), ("sleep", 4.0, 6.0)])
    assert t.busy_s() == 5.0
    assert t.module_s("jit_fused_analyze") == 5.0
    out = t.breakdown()
    assert out["device_ops"] == [["a", 4.0], ["b", 1.0], ["c", 1.0]]
    assert out["idle_gaps"] == [["sleep", 2.0]]


def test_busy_averages_over_devices():
    t = tr.DeviceTrace({"/device:TPU:0": [("x", 0, 2)],
                        "/device:TPU:1": [("x", 0, 4)]}, {}, [])
    assert t.busy_s() == 3.0


def test_attribute_prefers_overlap_then_shorter_event():
    host = [("long", 0.0, 10.0), ("short", 2.0, 3.0), ("edge", 2.9, 3.0)]
    assert tr.attribute((2.0, 3.0), host) == "short"
    assert tr.attribute((11.0, 12.0), host) == "no host event"


@pytest.fixture(scope="module")
def chip():
    return tr.read(CHIP_TRACE)


def test_chip_trace_has_the_fused_programs(chip):
    (plane,) = chip.ops
    assert plane == "/device:TPU:0"
    names = [tr.module_base(n) for n, _, _ in chip.modules[plane]]
    assert names.count("jit_fused_analyze_pallas") == 6
    assert names.count("jit__crop_row") == 6
    assert 0 < chip.module_s("jit_fused_analyze") < chip.busy_s()


def test_chip_trace_breakdown_and_roofline(chip):
    out = chip.breakdown()
    assert 0 < len(out["device_ops"]) <= 10
    assert 0 < len(out["idle_gaps"]) <= 10
    # device and host events share one clock: every long gap has a host event
    assert all(name != "no host event" for name, _ in out["idle_gaps"])
    floor = 6 * observe.ychg_floor_bytes(4 * 256 * 256, 4, 256)
    obs = observe.Observed(spans=[], trace=chip, window_s=0.05,
                           ychg_bytes=floor, peaks=observe.peaks("TPU v5 lite"))
    assert 1.0 < observe.ychg_roofline_pct(obs) < 100.0
    assert 0.0 < observe.idle_pct(obs) < 100.0


def test_roofline_is_silent_without_work_or_programs(chip):
    peaks = observe.peaks("TPU v5 lite")
    assert observe.ychg_roofline_pct(
        observe.Observed([], chip, 0.05, 0, peaks)) is None
    empty = tr.DeviceTrace({"/device:TPU:0": []}, {"/device:TPU:0": []}, [])
    assert observe.ychg_roofline_pct(
        observe.Observed([], empty, 0.05, 64 + 8 * 13 + 8, peaks)) is None


def test_floor_counts_unpadded_pixels_and_outputs_per_image():
    # two strips of a 5416-wide granule, the second cut short at 184 rows
    assert observe.ychg_floor_bytes(440 * 5416, 2, 5416) == (
        440 * 5416 + 2 * (5416 * 13 + 8))


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        observe.peaks("TPU v9 imaginary")
