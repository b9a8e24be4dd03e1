"""The readers of the share of ``engine.put`` spans shipped as words."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import harness  # noqa: E402
import observe  # noqa: E402

METRICS = {"put_words_share.scene": "scene.21k",
           "put_words_share.mpx": "bulk.250m"}


def _read(metric, spans):
    obs = observe.Observed(list(spans), None, 1.0, 0, {})
    return harness.load_reader(metric)(obs)


def _put(t, **meta):
    return ("engine.put", t, t + 0.001, dict(bytes=16, **meta))


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("words, share", [
    ([1, 1, 1, 1], 100.0), ([1, 0, 1, 1], 75.0), ([0, 0], 0.0)])
def test_share_of_put_spans_with_words(metric, words, share):
    spans = [_put(i, words=w) for i, w in enumerate(words)]
    spans.append(("engine.dispatch", 9.0, 9.1, {"words": 0}))  # not a put
    assert _read(metric, spans) == pytest.approx(share)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_silent_on_a_program_without_the_meta(metric):
    """A program from before word shipping records puts without it."""
    assert _read(metric, [_put(0.0), _put(1.0)]) is None
    assert _read(metric, []) is None


def test_metrics_are_in_the_benchmark_with_their_cells():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name, cell in METRICS.items():
        assert entries[name]["workloads"] == [cell]
        assert entries[name]["layer"] == "engine"
        assert name in [m["name"] for m in
                        harness.resolve(spec, cell).per_layer]
