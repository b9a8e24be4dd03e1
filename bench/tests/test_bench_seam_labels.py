"""The reader of ``ccl_seam_labels.water``: the mean ``seam_labels`` meta
of ``engine.fetch``, on hand-built spans, and silent on a program whose
fetch carries no such meta."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import harness  # noqa: E402
import observe  # noqa: E402
import trace_reduce as tr  # noqa: E402

METRIC = "ccl_seam_labels.water"


def _read(spans, trace=None):
    obs = observe.Observed(list(spans), trace, 10.0, 0,
                           {"hbm_bytes_per_s": 819e9})
    return harness.load_reader(METRIC)(obs)


@pytest.mark.parametrize("values,want", [([40, 90], 65.0), ([0], 0.0),
                                         ([68096, 68096], 68096.0)])
def test_reader_means_the_fetch_meta(values, want):
    spans = [("engine.dispatch", 0.0, 0.01, {"op": "ccl", "px": 1})]
    spans += [("engine.fetch", float(i), i + 0.1,
               {"bytes": 1, "sweeps": 20, "merge_rounds": 3,
                "seam_labels": v}) for i, v in enumerate(values)]
    spans += [("engine.fetch", 9.0, 9.1, {"bytes": 4})]
    assert _read(spans) == pytest.approx(want)


def test_reader_is_silent_without_the_meta():
    """A parent's fetch has sweeps and merge rounds but no seam labels:
    nothing to read, and nothing raised, on the recorded chip trace or
    none."""
    chip = tr.read(BENCH / "testdata" / "serve_small.xplane.pb")
    spans = [("engine.fetch", 1.0, 1.1, {"bytes": 4, "sweeps": 20,
                                         "merge_rounds": 3})]
    assert _read(spans, chip) is None and _read(spans) is None
