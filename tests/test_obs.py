"""`repro.obs` suite: histograms, Prometheus text, traces, recorder.

Per the timing policy in tests/README.md: no wall-clock assertions —
histogram *structure* (cumulative buckets, exact merges, quantile
bracketing) and span *ordering/nesting* are the bars; the `mpx_per_s`
active-time estimator is tested with injected timestamps, never sleeps.
"""

import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import (
    DEFAULT_LATENCY_BOUNDS,
    Histogram,
    PromBuilder,
    Trace,
    base_family,
    empty_snapshot,
    escape_label_value,
    format_value,
    maybe_trace,
    mono_to_wall_us,
    parse_prom_text,
    unescape_label_value,
)
from repro.service.metrics import MetricsRecorder, bucket_labels


@pytest.fixture
def tracing():
    """Tracing on, a clean recorder, and full state restore afterwards."""
    obs.configure(enabled=True, dump_path=None)
    obs.recorder().clear()
    yield
    obs.configure(enabled=True, dump_path=None)
    obs.recorder().clear()


# ------------------------------------------------------------- histogram


def test_histogram_counts_sum_and_cumulative():
    h = Histogram((0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    s = h.snapshot()
    # le-inclusive binning: 0.1 lands in the <=0.1 bucket, 1.0 in <=1.0
    assert s.counts == (2, 2, 1, 1)
    assert s.count == 6
    assert s.sum == pytest.approx(106.65)
    assert s.cumulative() == (2, 4, 5, 6)


def test_histogram_single_sample_p50_equals_p95():
    h = Histogram(DEFAULT_LATENCY_BOUNDS)
    h.observe(0.003)
    s = h.snapshot()
    assert s.quantile(0.50) == s.quantile(0.95) == 0.005


def test_histogram_quantile_is_upper_edge_and_bounds_bracket():
    h = Histogram((0.1, 1.0, 10.0))
    values = [0.05] * 50 + [5.0] * 50
    for v in values:
        h.observe(v)
    s = h.snapshot()
    # nearest-rank p50 = the 50th sample -> the <=0.1 bucket's upper edge
    assert s.quantile(0.50) == 0.1
    lo, hi = s.quantile_bounds(0.50)
    assert lo <= np.percentile(values, 50, method="inverted_cdf") <= hi
    lo, hi = s.quantile_bounds(0.95)
    assert (lo, hi) == (1.0, 10.0)
    assert lo <= np.percentile(values, 95, method="inverted_cdf") <= hi


def test_histogram_overflow_bucket_reports_finite_bounds():
    h = Histogram((0.1, 1.0))
    h.observe(50.0)
    s = h.snapshot()
    assert s.quantile_bounds(0.5) == (1.0, 1.0)
    assert math.isfinite(s.quantile(0.99))


def test_histogram_merge_is_exact_and_checks_bounds():
    a, b = Histogram((0.1, 1.0)), Histogram((0.1, 1.0))
    for v in (0.05, 0.5):
        a.observe(v)
    for v in (0.5, 5.0):
        b.observe(v)
    m = a.snapshot().merge(b.snapshot())
    assert m.counts == (1, 2, 1)
    assert m.count == 4
    assert m.sum == pytest.approx(6.05)
    with pytest.raises(ValueError):
        a.snapshot().merge(empty_snapshot((0.2, 2.0)))


def test_histogram_rejects_bad_bounds():
    for bad in ((), (1.0, 0.5), (1.0, 1.0)):
        with pytest.raises(ValueError):
            Histogram(bad)


def test_empty_snapshot_quantiles_are_zero():
    s = empty_snapshot((0.1, 1.0))
    assert s.quantile(0.5) == 0.0
    assert s.quantile_bounds(0.95) == (0.0, 0.0)


# ---------------------------------------------------------- prom text


def test_escape_label_value_roundtrip():
    for raw in ('plain', 'quo"te', 'back\\slash', 'new\nline',
                'all\\"of\nit', ''):
        esc = escape_label_value(raw)
        assert "\n" not in esc
        assert unescape_label_value(esc) == raw
    # escaping order: backslash first, so a literal \n survives as \\n
    assert escape_label_value("\\n") == "\\\\n"
    assert escape_label_value("\n") == "\\n"


def test_format_value_int_rendering():
    assert format_value(3) == "3"
    assert format_value(3.0) == "3"
    assert format_value(3.5) == "3.5"
    assert format_value(math.inf) == "+Inf"
    assert format_value(True) == "1"


def test_prombuilder_roundtrips_through_parser():
    h = Histogram((0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    b = PromBuilder()
    b.counter("t_requests_total", 7, "requests")
    b.gauge("t_depth", 2.5, labels=(("worker", 'w"0\n'),))
    b.histogram("t_latency_seconds",
                [((("side", "64"),), h.snapshot())], "latency")
    page = parse_prom_text(b.render())
    assert page.types == {"t_requests_total": "counter", "t_depth": "gauge",
                          "t_latency_seconds": "histogram"}
    assert page.get("t_requests_total") == 7
    # escaped label values come back as the original string
    assert page.get("t_depth", (("worker", 'w"0\n'),)) == 2.5
    buckets = page.series("t_latency_seconds_bucket")
    assert [dict(s.labels)["le"] for s in buckets] == ["0.1", "1", "+Inf"]
    assert [s.value for s in buckets] == [1, 2, 3]   # cumulative
    assert page.get("t_latency_seconds_count", (("side", "64"),)) == 3
    assert page.get("t_latency_seconds_sum",
                    (("side", "64"),)) == pytest.approx(5.55)


def test_parser_rejects_malformed_lines():
    for bad in ("no_value_here", "name{unclosed 1", 'name{a="x"y="z"} 1',
                "name notanumber"):
        with pytest.raises(ValueError):
            parse_prom_text(bad)
    # comments and blanks are fine
    page = parse_prom_text("# arbitrary comment\n\nok_total 1\n")
    assert page.get("ok_total") == 1


def test_base_family():
    assert base_family("x_seconds_bucket") == "x_seconds"
    assert base_family("x_seconds_sum") == "x_seconds"
    assert base_family("x_seconds_count") == "x_seconds"
    assert base_family("x_total") == "x_total"


# ----------------------------------------------------------------- trace


def test_trace_spans_nest_and_order(tracing):
    tr = Trace(process="test")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.add("explicit", 1.0, 2.0, detail="x")
    spans = tr.spans()
    names = [s[0] for s in spans]
    # ctx managers record at __exit__, so inner lands before outer
    assert names == ["inner", "outer", "explicit"]
    (in_n, in_t0, in_t1, _), (out_n, out_t0, out_t1, _) = spans[0], spans[1]
    assert out_t0 <= in_t0 <= in_t1 <= out_t1     # proper nesting
    assert spans[2][3] == {"detail": "x"}


def test_trace_add_clamps_reversed_timestamps(tracing):
    tr = Trace()
    tr.add("weird", 5.0, 3.0)
    _, t0, t1, _ = tr.spans()[0]
    assert t1 == t0 == 5.0            # never a negative duration


def test_maybe_trace_disabled_returns_null(tracing):
    obs.configure(enabled=False)
    tr = maybe_trace("deadbeef")
    assert tr is obs.NULL_TRACE
    assert not tr.enabled
    tr.add("x", 0.0, 1.0)
    with tr.span("y"):
        pass
    tr.finish()
    assert obs.recorder().traces() == []
    obs.configure(enabled=True)
    assert maybe_trace("deadbeef").enabled


def test_trace_finish_records_once_and_empty_traces_never(tracing):
    tr = Trace()
    tr.add("s", 0.0, 1.0)
    tr.finish()
    tr.finish()
    assert len(obs.recorder().traces()) == 1
    empty = Trace()
    empty.finish()
    assert len(obs.recorder().traces()) == 1   # empty trace not recorded


def test_recorder_ring_capacity(tracing):
    obs.configure(capacity=4)
    try:
        ids = []
        for _ in range(10):
            tr = Trace()
            tr.add("s", 0.0, 1.0)
            tr.finish()
            ids.append(tr.trace_id)
        kept = [t.trace_id for t in obs.recorder().traces()]
        assert kept == ids[-4:]       # most recent N, in order
    finally:
        obs.configure(capacity=256)


def test_chrome_export_fields_and_valid_json(tracing):
    tr = Trace("feedc0de", process="worker")
    tr.add("engine.compute", 1.0, 1.5, rows=3)
    tr.finish()
    payload = json.loads(obs.recorder().to_chrome_json())
    events = [e for e in payload["traceEvents"]
              if e["args"].get("trace_id") == "feedc0de"]
    assert len(events) == 1
    e = events[0]
    assert e["name"] == "engine.compute"
    assert e["cat"] == "worker"
    assert e["ph"] == "X"
    assert e["dur"] == pytest.approx(0.5e6)     # us
    assert e["ts"] == pytest.approx(mono_to_wall_us(1.0))
    assert e["tid"] == "feedc0de"
    assert e["args"]["rows"] == "3"
    assert isinstance(e["pid"], int)


def test_auto_dump_writes_configured_path(tracing, tmp_path):
    path = str(tmp_path / "flight.json")
    obs.configure(dump_path=path)
    tr = Trace()
    tr.add("s", 0.0, 1.0)
    tr.finish()
    assert obs.auto_dump("test") == path
    with open(path) as fh:
        assert json.load(fh)["traceEvents"]
    # no dump path -> None, never raises
    obs.configure(dump_path=None)
    assert obs.auto_dump("test") is None


def test_concurrent_span_adds_are_safe(tracing):
    tr = Trace()

    def add_many(k):
        for i in range(200):
            tr.add(f"t{k}", float(i), float(i + 1))

    threads = [threading.Thread(target=add_many, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans()) == 800


# ------------------------------------------------- service metrics seam


def test_mpx_active_time_ignores_idle_gaps():
    """The satellite bar: two bursts separated by a long idle gap must
    report the same Mpx/s as one contiguous burst (the old wall-span
    estimator diluted the rate ~100x here). Timestamps injected — no
    sleeps."""
    px, lat = 1_000_000, 0.1

    def run(times):
        r = MetricsRecorder()
        for now in times:
            r.record_complete(lat, px, now=now)
        return r.snapshot(queue_depth=0, cache_hits=0, cache_misses=len(times),
                          backend="x").mpx_per_s

    one_burst = run([100.0, 100.1, 100.2, 100.3])
    two_bursts = run([100.0, 100.1, 150.2, 150.3])   # 50 s idle in between
    assert one_burst > 0
    assert two_bursts == pytest.approx(one_burst)


def test_mpx_dense_burst_not_overcounted():
    """Completions arriving closer together than their latency credit
    only the inter-arrival gap — active time can never exceed the span
    of the burst plus one latency."""
    r = MetricsRecorder()
    for i in range(100):
        r.record_complete(0.5, 1000, now=200.0 + i * 0.001)
    assert r._active_s == pytest.approx(0.5 + 99 * 0.001)


def test_latency_hist_count_equals_completed_minus_cached():
    r = MetricsRecorder()
    r.record_complete(0.01, 100, n_requests=3, bucket=(64, "uint8"))
    r.record_complete(0.02, 100, bucket=(128, "uint8"))
    r.record_cache_hit(100)
    m = r.snapshot(queue_depth=0, cache_hits=1, cache_misses=4, backend="x")
    assert m.completed == 5
    assert m.completed_from_cache == 1
    assert sum(s.count for _, s in m.latency_hists) == 4
    assert m.latency_hist().count == m.completed - m.completed_from_cache


def test_snapshot_percentiles_come_from_histogram():
    r = MetricsRecorder()
    lats = [0.001] * 90 + [0.2] * 10
    for lat in lats:
        r.record_complete(lat, 10, bucket=(64, "uint8"))
    m = r.snapshot(queue_depth=0, cache_hits=0, cache_misses=100,
                   backend="x")
    merged = m.latency_hist()
    assert m.p50_latency_ms == merged.quantile(0.50) * 1e3
    lo, hi = merged.quantile_bounds(0.50)
    assert lo * 1e3 <= np.percentile(lats, 50) * 1e3 <= m.p50_latency_ms
    lo95, hi95 = merged.quantile_bounds(0.95)
    assert lo95 <= np.percentile(lats, 95, method="inverted_cdf") <= hi95
    assert m.p95_latency_ms >= m.p50_latency_ms


def test_stage_histograms_and_bucket_labels():
    r = MetricsRecorder()
    r.observe_stage("queue_wait", (64, "uint8"), 0.004)
    r.observe_stage("queue_wait", (64, "uint8"), 0.006)
    r.observe_stage("compute", None, 0.1)
    m = r.snapshot(queue_depth=0, cache_hits=0, cache_misses=0, backend="x")
    by_labels = dict(m.stage_hists)
    qw = by_labels[(("stage", "queue_wait"), ("side", "64"),
                    ("dtype", "uint8"))]
    assert qw.count == 2
    assert by_labels[(("stage", "compute"),)].count == 1
    assert bucket_labels((64, "uint8")) == (("side", "64"),
                                            ("dtype", "uint8"))
    assert bucket_labels(None) == ()
    assert bucket_labels("odd") == (("bucket", "odd"),)


# ------------------------------------- profiler annotations, current trace


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what opens."""

    log: list = []

    def __init__(self, name, **kwargs):
        assert not kwargs          # the name alone: the cheap native path
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    from repro.obs import trace as trace_mod

    _FakeAnnotation.log = []
    monkeypatch.setattr(trace_mod, "_ANNOTATION", _FakeAnnotation)
    return _FakeAnnotation.log


def test_live_span_is_a_profiler_annotation(tracing, fake_annotation):
    tr = Trace(process="test")
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    tr.add("retro", 1.0, 2.0)      # after the fact: no annotation
    assert fake_annotation == [("enter", "outer"), ("enter", "inner"),
                               ("exit", "inner"), ("exit", "outer")]
    assert [s[0] for s in tr.spans()] == ["inner", "outer", "retro"]


def test_tracing_off_records_nothing_and_opens_no_annotation(
        tracing, fake_annotation):
    obs.configure(enabled=False)
    with obs.join_trace("engine") as tr:
        assert tr is obs.NULL_TRACE
    with maybe_trace().span("x") as sp:
        sp.stamp(1.0, 2.0)
    with obs.use_trace(maybe_trace()), obs.join_trace("engine") as tr:
        with tr.span("y"):
            pass
    assert fake_annotation == []
    assert obs.recorder().traces() == []


def test_stamp_keeps_the_callers_clock_reads(tracing):
    tr = Trace()
    with tr.span("timed") as sp:
        sp.stamp(3.0, 4.5)
    assert tr.spans() == [("timed", 3.0, 4.5, {})]


def _joined():
    with obs.join_trace("engine") as tr:
        tr.add("s", 0.0, 1.0)
    return tr


def test_join_trace_prefers_the_current_trace(tracing):
    own = _joined()
    assert own.process == "engine"
    assert obs.recorder().traces() == [own]     # finished by the block
    granule = Trace(process="scene")
    with obs.use_trace(granule) as cur:
        assert cur is granule and _joined() is granule
        with obs.use_trace(obs.NULL_TRACE):    # a tier may opt out
            assert _joined() is obs.NULL_TRACE
        assert _joined() is granule
    assert obs.recorder().traces() == [own]     # the tier finishes its own
    assert _joined() not in (own, granule)


def test_current_trace_is_per_thread(tracing):
    seen = []
    granule = Trace()
    with obs.use_trace(granule):
        t = threading.Thread(target=lambda: seen.append(_joined()))
        t.start()
        t.join(timeout=60)
    assert not t.is_alive() and seen[0] is not granule


def test_obs_imports_and_traces_without_jax():
    """The frontend and router import ``repro.obs`` without jax: with
    jax blocked, it imports, spans record, and no annotation is sought."""
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None          # any import of jax now fails
        from repro import obs
        tr = obs.Trace()
        with obs.use_trace(tr), obs.join_trace("engine") as cur:
            with cur.span("s"):
                pass
        assert [s[0] for s in tr.spans()] == ["s"]
        assert not any(m.startswith("jax") and sys.modules[m] is not None
                       for m in sys.modules)
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60,
                         env={"PYTHONPATH": str(src), "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
