"""`repro.service` suite: the batching/caching/overlap layers above the engine.

Policy (tests/README.md §Service tests): no wall-clock assertions — the
threaded scheduler is verified through *parity* (every served result
bit-identical to ``engine.analyze`` on the raw mask, through padding,
bucketing, arrival order, duplicates, and caching), *counters* (registry
backend call counts prove cache hits skip compute; metrics prove the
compiled-shape bound), and *determinism knobs* (long ``max_delay_ms`` +
under-full buckets pin scheduling where a test needs it). Futures always
``result(timeout=...)`` with a generous bound so a scheduler bug fails,
never hangs, the suite.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ychg
from repro.engine import Engine, YCHGConfig, registry
from repro.service import (
    ResultCache,
    ServiceConfig,
    ServiceOverloaded,
    YCHGService,
    make_key,
    pick_bucket_side,
    sub_batch_ladder,
)
from ychg_invariants import assert_bit_identical

TIMEOUT = 300.0  # generous future bound: fail, never hang


def _mask(shape, seed=0, density=0.5):
    rng = np.random.default_rng(seed)
    return (rng.random(shape) < density).astype(np.uint8)


def _assert_result_matches_analyze(result, mask):
    """Service result == engine.analyze(mask): dtypes, shapes, values."""
    assert_bit_identical(result.to_summary(), ychg.analyze(jnp.asarray(mask)))


# ------------------------------------------------------------------ parity


def test_service_parity_mixed_shapes_and_duplicates():
    """The tentpole bar: ragged shapes, interleaved arrival order, duplicate
    masks — every future resolves to exactly engine.analyze(mask)."""
    masks = [
        _mask((17, 23), seed=1),
        _mask((64, 64), seed=2),
        _mask((33, 40), seed=3),
        _mask((128, 100), seed=4),
        _mask((5, 128), seed=5),
        _mask((1, 1), seed=6),
        np.zeros((30, 30), np.uint8),          # blank: zero hyperedges
        np.ones((16, 48), np.uint8),           # full coverage
    ]
    masks += [masks[0].copy(), masks[3].copy()]  # duplicates, far apart
    with YCHGService(config=ServiceConfig(
            bucket_sides=(64, 128), max_batch=4, max_delay_ms=1.0)) as svc:
        futures = [svc.submit(m) for m in masks]
        for mask, fut in zip(masks, futures):
            res = fut.result(timeout=TIMEOUT)
            assert not res.batched and res.batch_size == 1
            _assert_result_matches_analyze(res, mask)


def test_service_parity_matches_plain_analyze_batch():
    """Satellite: the overlapped/bucketed path == one plain
    engine.analyze_batch over the same masks (same shape, so the comparison
    is a direct stack)."""
    masks = [_mask((48, 64), seed=s) for s in range(6)]
    engine = Engine()
    want = engine.analyze_batch(np.stack(masks))
    with YCHGService(engine, ServiceConfig(
            bucket_sides=(64,), max_batch=3, max_delay_ms=1.0)) as svc:
        outs = [f.result(timeout=TIMEOUT) for f in map(svc.submit, masks)]
    got = np.concatenate([np.asarray(o.runs) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(want.runs))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(o.n_hyperedges) for o in outs]),
        np.asarray(want.n_hyperedges))


def test_service_parity_ragged_arrival_order():
    """Shuffled interleaving across buckets must not cross results over."""
    rng = np.random.default_rng(11)
    masks = [_mask(((s % 60) + 4, ((s * 7) % 90) + 4), seed=100 + s)
             for s in range(24)]
    order = rng.permutation(len(masks))
    with YCHGService(config=ServiceConfig(
            bucket_sides=(32, 64, 128), max_batch=4, max_delay_ms=1.0)) as svc:
        futures = {}
        for i in order:
            futures[i] = svc.submit(masks[i])
        for i, fut in futures.items():
            _assert_result_matches_analyze(fut.result(timeout=TIMEOUT), masks[i])


def test_service_nonbinary_and_nonuint8_masks():
    """int32 masks with values > 1 keep nonzero-is-foreground semantics
    through pad_stack (zero padding is inert for any dtype)."""
    mask = (np.arange(20 * 17).reshape(20, 17) % 5).astype(np.int32) * 7
    with YCHGService(config=ServiceConfig(
            bucket_sides=(32,), max_batch=2, max_delay_ms=1.0)) as svc:
        _assert_result_matches_analyze(svc.analyze(mask, timeout=TIMEOUT), mask)


# ------------------------------------------------------------------- cache


def test_cache_hit_skips_backend():
    """Satellite: a hit must not invoke the backend — asserted via the
    registry call counter the engine bumps on every dispatch."""
    mask = _mask((40, 40), seed=20)
    engine = Engine()
    backend = engine.resolve_backend()
    with YCHGService(engine, ServiceConfig(
            bucket_sides=(64,), max_batch=1, max_delay_ms=1.0)) as svc:
        first = svc.analyze(mask, timeout=TIMEOUT)
        n_after_miss = registry.call_count(backend)
        again = svc.analyze(mask.copy(), timeout=TIMEOUT)  # same bytes
        assert registry.call_count(backend) == n_after_miss
        assert again is first  # the cached object itself, no copy
        m = svc.metrics()
        assert m.cache_hits == 1 and m.cache_misses == 1


def test_cache_same_bytes_different_shape_or_dtype_misses():
    """Satellite: the key is content + shape + dtype — equal byte strings
    with different interpretation are different requests."""
    payload = (np.arange(32) % 2).astype(np.uint8)
    variants = [
        payload.reshape(4, 8),
        payload.reshape(8, 4),            # same bytes, different shape
        payload.reshape(4, 8).view(np.int8),  # same bytes, different dtype
    ]
    assert variants[0].tobytes() == variants[1].tobytes() == variants[2].tobytes()
    engine = Engine()
    backend = engine.resolve_backend()
    with YCHGService(engine, ServiceConfig(
            bucket_sides=(16,), max_batch=1, max_delay_ms=1.0)) as svc:
        before = registry.call_count(backend)
        for v in variants:
            _assert_result_matches_analyze(svc.analyze(v, timeout=TIMEOUT), v)
        assert registry.call_count(backend) == before + 3  # all misses
        assert svc.metrics().cache_hits == 0


def test_cache_different_engine_config_misses_in_shared_cache():
    """Keys embed (resolved backend, engine config): two services sharing
    one ResultCache never serve each other's entries."""
    mask = _mask((24, 24), seed=21)
    shared = ResultCache(64)
    cfg = ServiceConfig(bucket_sides=(32,), max_batch=1, max_delay_ms=1.0)
    with YCHGService(Engine(YCHGConfig(backend="jax")), cfg,
                     cache=shared) as a, \
         YCHGService(Engine(YCHGConfig(backend="fused")), cfg,
                     cache=shared) as b:
        ra = a.analyze(mask, timeout=TIMEOUT)
        n_fused = registry.call_count("fused")
        rb = b.analyze(mask, timeout=TIMEOUT)   # must MISS a's entry
        assert registry.call_count("fused") == n_fused + 1
        assert shared.misses == 2 and shared.hits == 0 and len(shared) == 2
        assert_bit_identical(ra.to_summary(), rb.to_summary())


def test_result_cache_lru_eviction_and_disable():
    cache = ResultCache(2)
    cfg = YCHGConfig()
    keys = [make_key(np.full((2, 2), i, np.uint8), "jax", cfg) for i in range(3)]
    cache.put(keys[0], "a"); cache.put(keys[1], "b")
    assert cache.get(keys[0]) == "a"      # refresh 0 -> 1 is now LRU
    cache.put(keys[2], "c")               # evicts 1
    assert cache.get(keys[1]) is None and cache.get(keys[2]) == "c"
    assert len(cache) == 2 and cache.hits == 2 and cache.misses == 1
    off = ResultCache(0)
    off.put(keys[0], "a")
    assert off.get(keys[0]) is None and len(off) == 0
    with pytest.raises(ValueError):
        ResultCache(-1)


def test_make_key_discriminates_every_component():
    from repro.sharding import make_batch_mesh

    a = _mask((4, 6), seed=1)
    base = make_key(a, "jax", YCHGConfig())
    assert make_key(a.copy(), "jax", YCHGConfig()) == base  # content-addressed
    assert make_key(a, "fused", YCHGConfig()) != base
    assert make_key(a, "jax", YCHGConfig(block_w=64)) != base
    assert make_key(1 - a, "jax", YCHGConfig()) != base     # different bytes
    # a meshed engine's results carry a different device layout: never
    # interchangeable with unmeshed entries through a shared cache
    assert make_key(a, "jax", YCHGConfig(), make_batch_mesh()) != base


# ------------------------------------------------- coalescing / scheduling


def test_duplicate_in_flight_coalesces_to_one_slot():
    """While a mask is queued, an identical submit joins the leader: one
    backend computation, both futures get the same result object."""
    mask = _mask((20, 20), seed=30)
    # max_batch=8 + long delay: both submits land in the same pending bucket
    with YCHGService(config=ServiceConfig(
            bucket_sides=(32,), max_batch=8, max_delay_ms=400.0)) as svc:
        f1 = svc.submit(mask)
        f2 = svc.submit(mask.copy())
        r1 = f1.result(timeout=TIMEOUT)
        r2 = f2.result(timeout=TIMEOUT)
        assert r1 is r2
        m = svc.metrics()
        assert m.coalesced == 1 and m.batches == 1 and m.completed == 2
        _assert_result_matches_analyze(r1, mask)


def test_compiled_shapes_bounded_by_bucket_and_sub_batch_ladders():
    """Acceptance bar: arbitrary traffic shapes never dispatch more distinct
    compiled shapes than bucket_sides x the power-of-two sub-batch ladder
    (one dtype) — sub-bucket padding must not unbound the shape budget."""
    rng = np.random.default_rng(31)
    sides = (32, 64, 128)
    max_batch = 4
    masks = [_mask((int(rng.integers(2, 128)), int(rng.integers(2, 128))),
                   seed=200 + s) for s in range(30)]
    with YCHGService(config=ServiceConfig(
            bucket_sides=sides, max_batch=max_batch, max_delay_ms=1.0)) as svc:
        for f in [svc.submit(m) for m in masks]:
            f.result(timeout=TIMEOUT)
        m = svc.metrics()
    ladder = sub_batch_ladder(max_batch)
    assert len(ladder) == int(np.log2(max_batch)) + 1
    assert m.n_compiled_shapes <= len(sides) * len(ladder)
    assert set(m.compiled_shapes) <= {
        (b, s, s) for s in sides for b in ladder}


def test_low_occupancy_flush_pads_to_sub_batch_not_max_batch():
    """A lone request must dispatch a (1, side, side) stack, not pay for
    max_batch - 1 blank images (the pad-to-max_batch regression)."""
    mask = _mask((40, 40), seed=90)
    with YCHGService(config=ServiceConfig(
            bucket_sides=(64,), max_batch=8, max_delay_ms=1.0)) as svc:
        _assert_result_matches_analyze(svc.analyze(mask, timeout=TIMEOUT),
                                       mask)
        m = svc.metrics()
    assert m.compiled_shapes == ((1, 64, 64),)
    # pad fraction is now only the side padding, not 8x image blanks
    assert m.pad_fraction == 1.0 - mask.size / (64 * 64)


def test_sub_batches_off_restores_pad_to_max_batch():
    """The sub_batches=False knob keeps the old policy available so
    benchmarks can compare both on one schedule."""
    mask = _mask((40, 40), seed=91)
    with YCHGService(config=ServiceConfig(
            bucket_sides=(64,), max_batch=8, max_delay_ms=1.0,
            sub_batches=False)) as svc:
        svc.analyze(mask, timeout=TIMEOUT)
        m = svc.metrics()
    assert m.compiled_shapes == ((8, 64, 64),)


def test_submit_validation_and_lifecycle():
    svc = YCHGService(config=ServiceConfig(
        bucket_sides=(16,), max_batch=1, max_delay_ms=1.0))
    with pytest.raises(ValueError, match=r"\(H, W\)"):
        svc.submit(np.zeros((2, 3, 4), np.uint8))
    with pytest.raises(ValueError, match="largest service bucket"):
        svc.submit(np.zeros((17, 4), np.uint8))
    res = svc.analyze(np.zeros((8, 8), np.uint8), timeout=TIMEOUT)
    assert int(np.asarray(res.n_hyperedges)[0]) == 0
    svc.close()
    svc.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(np.zeros((8, 8), np.uint8))


def test_close_drains_queued_requests():
    """Requests still pending at close() are flushed, not dropped."""
    masks = [_mask((12, 12), seed=40 + i) for i in range(3)]
    svc = YCHGService(config=ServiceConfig(
        bucket_sides=(16,), max_batch=8, max_delay_ms=10_000.0))
    futures = [svc.submit(m) for m in masks]  # sit in the delay window
    svc.close()
    for mask, fut in zip(masks, futures):
        _assert_result_matches_analyze(fut.result(timeout=TIMEOUT), mask)


def test_cancelled_future_does_not_kill_scheduler():
    """A client cancelling its future must not crash the scheduler thread
    (set_result on a cancelled future raises InvalidStateError): the rest of
    the batch and all later requests must still resolve."""
    with YCHGService(config=ServiceConfig(
            bucket_sides=(16,), max_batch=8, max_delay_ms=200.0)) as svc:
        doomed = svc.submit(_mask((8, 8), seed=70))   # parked in the window
        survivor_mask = _mask((8, 8), seed=71)
        survivor = svc.submit(survivor_mask)
        assert doomed.cancel()                        # never marked running
        _assert_result_matches_analyze(
            survivor.result(timeout=TIMEOUT), survivor_mask)
        # scheduler is still alive: a fresh request completes too
        after = _mask((8, 8), seed=72)
        _assert_result_matches_analyze(svc.analyze(after, timeout=TIMEOUT),
                                       after)


def test_analyze_stream_bad_item_still_delivers_prior_results():
    """The one-item lookahead must not swallow a computed result when the
    NEXT item is invalid: the valid result is yielded first, then the
    ValueError surfaces on the following pull (the pre-lookahead contract)."""
    engine = Engine()
    good = _mask((6, 7), seed=73)
    gen = engine.analyze_stream([good, np.zeros((2, 2, 2, 2), np.uint8)])
    first = next(gen)
    _assert_result_matches_analyze(first, good)
    with pytest.raises(ValueError, match="stream items"):
        next(gen)


def test_service_config_validation():
    with pytest.raises(ValueError, match="ascending ladder"):
        ServiceConfig(bucket_sides=(128, 64))
    with pytest.raises(ValueError, match="ascending ladder"):
        ServiceConfig(bucket_sides=())
    with pytest.raises(ValueError, match="max_batch"):
        ServiceConfig(max_batch=0)
    with pytest.raises(ValueError, match="inflight_buckets"):
        ServiceConfig(inflight_buckets=0)
    with pytest.raises(ValueError, match="max_queue_depth"):
        ServiceConfig(max_queue_depth=0)
    with pytest.raises(ValueError, match="overload_policy"):
        ServiceConfig(overload_policy="drop")
    assert pick_bucket_side((5, 100), (64, 128)) == 128


def test_metrics_snapshot_consistency():
    masks = [_mask((40, 40), seed=50 + i) for i in range(5)]
    with YCHGService(config=ServiceConfig(
            bucket_sides=(64,), max_batch=2, max_delay_ms=1.0)) as svc:
        for f in [svc.submit(m) for m in masks + [masks[0]]]:
            f.result(timeout=TIMEOUT)
        m = svc.metrics()
    assert m.submitted == 6 and m.completed == 6
    assert m.cache_hits + m.cache_misses == 6
    assert m.queue_depth == 0
    assert 0.0 <= m.pad_fraction < 1.0
    assert m.p95_latency_ms >= m.p50_latency_ms >= 0.0
    assert m.backend in registry.backend_names()


# ------------------------------------- scheduler bugfix regressions (PR 4)


class _WindowCache(ResultCache):
    """Intercepts the first ``put`` so the test can run code inside the
    completion window (result ready, cache insert in progress)."""

    def __init__(self, capacity=64):
        super().__init__(capacity)
        self.entered = threading.Event()
        self.resume = threading.Event()
        self._intercepted = False

    def put(self, key, value):
        if not self._intercepted:
            self._intercepted = True
            self.entered.set()
            assert self.resume.wait(TIMEOUT), "window gate never released"
        super().put(key, value)


def test_duplicate_in_completion_window_never_redispatches():
    """Regression (coalescing/cache race): a duplicate submitted while the
    leader's completion is mid-flight must hit the cache or the leader —
    the pre-fix code popped the leader BEFORE the cache insert, so the
    duplicate saw neither and re-dispatched the whole computation."""
    mask = _mask((24, 24), seed=80)
    engine = Engine()
    backend = engine.resolve_backend()
    cache = _WindowCache()
    svc = YCHGService(engine, ServiceConfig(
        bucket_sides=(32,), max_batch=1, max_delay_ms=1.0), cache=cache)
    try:
        f1 = svc.submit(mask)
        # completion is now parked inside the cache insert: the result is
        # computed, the leader not yet retired — the pre-fix window
        assert cache.entered.wait(TIMEOUT)
        n_dispatched = registry.call_count(backend)
        box = {}
        t = threading.Thread(
            target=lambda: box.update(fut=svc.submit(mask.copy())),
            daemon=True)
        t.start()          # duplicate lands in the window
        cache.resume.set()
        t.join(TIMEOUT)
        r1 = f1.result(timeout=TIMEOUT)
        r2 = box["fut"].result(timeout=TIMEOUT)
        # the duplicate was served without moving the backend call counter
        assert registry.call_count(backend) == n_dispatched
        assert r2 is r1
        _assert_result_matches_analyze(r1, mask)
    finally:
        svc.close()


def test_cache_hits_do_not_skew_latency_percentiles():
    """Regression (metrics skew): repeat traffic served from the cache must
    not push ~0 ms samples into the latency window — pre-fix, nine hits
    dragged p50 to 0 and hid what a compute miss actually costs."""
    mask = _mask((32, 32), seed=81)
    with YCHGService(config=ServiceConfig(
            bucket_sides=(64,), max_batch=1, max_delay_ms=1.0)) as svc:
        svc.analyze(mask, timeout=TIMEOUT)              # one compute miss
        for _ in range(9):
            svc.analyze(mask.copy(), timeout=TIMEOUT)   # nine cache hits
        m = svc.metrics()
    assert m.completed == 10 and m.completed_from_cache == 9
    assert m.cache_hits == 9
    # the window holds exactly the one compute sample: both percentiles
    # equal it, and it is the real (nonzero) submit->ready latency
    assert m.p50_latency_ms == m.p95_latency_ms
    assert m.p50_latency_ms > 0.0


# --------------------------------------------- admission control (PR 4)


def test_overload_shed_raises_typed_error_and_counts():
    """At max_queue_depth under policy "shed", submit fails fast with
    ServiceOverloaded; admitted requests still resolve, and freed slots
    re-admit. The long delay window holds the admitted requests pending so
    the bound is deterministically occupied."""
    masks = [_mask((16, 16), seed=100 + i) for i in range(6)]
    svc = YCHGService(config=ServiceConfig(
        bucket_sides=(16,), max_batch=8, max_delay_ms=10_000.0,
        max_queue_depth=2, overload_policy="shed"))
    try:
        admitted = [svc.submit(m) for m in masks[:2]]
        for m_ in masks[2:]:
            with pytest.raises(ServiceOverloaded, match="max_queue_depth=2"):
                svc.submit(m_)
        met = svc.metrics()
        assert met.shed == 4 and met.blocked == 0
    finally:
        svc.close()   # drains the two admitted requests
    for mask, fut in zip(masks, admitted):
        _assert_result_matches_analyze(fut.result(timeout=TIMEOUT), mask)


def test_overload_admits_cache_hits_and_coalesces_for_free():
    """Cache hits and in-flight duplicates consume no queue slot: at a full
    queue they are still served, while a distinct mask sheds."""
    leader_mask = _mask((16, 16), seed=110)
    svc = YCHGService(config=ServiceConfig(
        bucket_sides=(16,), max_batch=8, max_delay_ms=10_000.0,
        max_queue_depth=1, overload_policy="shed"))
    try:
        f1 = svc.submit(leader_mask)              # occupies the only slot
        f2 = svc.submit(leader_mask.copy())       # coalesces: no slot needed
        with pytest.raises(ServiceOverloaded):
            svc.submit(_mask((16, 16), seed=111))  # distinct: shed
        m = svc.metrics()
        assert m.coalesced == 1 and m.shed == 1
    finally:
        svc.close()
    assert f2.result(timeout=TIMEOUT) is f1.result(timeout=TIMEOUT)
    _assert_result_matches_analyze(f1.result(timeout=TIMEOUT), leader_mask)


def test_per_bucket_bound_sheds_flood_not_minority():
    """Satellite (per-bucket fairness): under a skewed two-bucket load
    with ``bucket_queue_depth`` set, the flooded bucket sheds against its
    own allowance while the minority bucket's shed count stays ZERO and
    all its requests resolve. Determinism per the no-wall-clock policy:
    a long delay window holds admitted requests pending, so the flooded
    bucket's bound is occupied exactly when the excess submits arrive."""
    flood = [_mask((16, 16), seed=200 + i) for i in range(6)]
    minority = [_mask((32, 32), seed=300 + i) for i in range(2)]
    svc = YCHGService(config=ServiceConfig(
        bucket_sides=(16, 32), max_batch=8, max_delay_ms=10_000.0,
        bucket_queue_depth=2, overload_policy="shed"))
    try:
        admitted = [svc.submit(m) for m in flood[:2]]   # fill the 16-bucket
        for m_ in flood[2:]:
            with pytest.raises(ServiceOverloaded,
                               match="bucket_queue_depth=2"):
                svc.submit(m_)
        # the minority bucket admits freely while the flood is shedding
        minority_futs = [svc.submit(m) for m in minority]
        met = svc.metrics()
        assert met.shed == 4 and met.blocked == 0
        assert met.shed_by_bucket == ((("ychg", 16, "uint8"), 4),)
    finally:
        svc.close()   # drains everything admitted
    for mask, fut in zip(flood[:2] + minority, admitted + minority_futs):
        _assert_result_matches_analyze(fut.result(timeout=TIMEOUT), mask)


class _GatedEngine(Engine):
    """Holds every dispatch at the analyze_batch door until released —
    pins "the queue is full because work is genuinely in flight"."""

    def __init__(self):
        super().__init__()
        self.entered = threading.Event()
        self.resume = threading.Event()

    def analyze_batch(self, stack):
        result = super().analyze_batch(stack)
        self.entered.set()
        assert self.resume.wait(TIMEOUT), "engine gate never released"
        return result


def test_overload_block_applies_backpressure_then_admits():
    """Policy "block": at the bound, submit waits (counted in blocked) and
    is admitted once a completion frees a slot — nothing is lost."""
    engine = _GatedEngine()
    m1, m2 = _mask((16, 16), seed=120), _mask((16, 16), seed=121)
    svc = YCHGService(engine, ServiceConfig(
        bucket_sides=(16,), max_batch=1, max_delay_ms=1.0,
        max_queue_depth=1, overload_policy="block"))
    try:
        f1 = svc.submit(m1)
        assert engine.entered.wait(TIMEOUT)   # m1 holds the only slot
        box = {}
        t = threading.Thread(target=lambda: box.update(fut=svc.submit(m2)),
                             daemon=True)
        t.start()
        # the submitter is parked at the admission gate, not shed
        deadline = time.monotonic() + TIMEOUT
        while svc.metrics().blocked < 1:
            assert time.monotonic() < deadline, "submitter never blocked"
            time.sleep(0.001)
        assert "fut" not in box
        engine.resume.set()                   # m1 completes -> slot frees
        t.join(TIMEOUT)
        _assert_result_matches_analyze(box["fut"].result(timeout=TIMEOUT), m2)
        _assert_result_matches_analyze(f1.result(timeout=TIMEOUT), m1)
        m = svc.metrics()
        assert m.blocked == 1 and m.shed == 0
    finally:
        engine.resume.set()
        svc.close()


def test_rider_on_shed_leader_fails_and_is_not_counted_as_accepted():
    """A duplicate that coalesces onto a leader still waiting at the
    admission gate shares the leader's fate: if the leader is rejected
    (here by close() waking the gate), the rider's future fails too and
    its submit/coalesce counts are backed out — submitted - completed must
    keep tracking real outstanding work."""
    engine = _GatedEngine()
    m1, m2 = _mask((16, 16), seed=130), _mask((16, 16), seed=131)
    svc = YCHGService(engine, ServiceConfig(
        bucket_sides=(16,), max_batch=1, max_delay_ms=1.0,
        max_queue_depth=1, overload_policy="block"))
    f1 = svc.submit(m1)
    assert engine.entered.wait(TIMEOUT)       # m1 holds the only slot
    box = {}

    def leader_submit():
        try:
            svc.submit(m2)
        except RuntimeError as e:
            box["exc"] = e

    t = threading.Thread(target=leader_submit, daemon=True)
    t.start()
    deadline = time.monotonic() + TIMEOUT     # leader parks at the gate
    while svc.metrics().blocked < 1:
        assert time.monotonic() < deadline, "leader never blocked"
        time.sleep(0.001)
    rider = svc.submit(m2.copy())             # coalesces onto parked leader
    assert svc.metrics().coalesced == 1
    # close() wakes the admission gate immediately (the leader fails before
    # any drain), but itself blocks joining the scheduler thread until the
    # engine gate opens — so run it aside and release the engine after the
    # leader's rejection is in hand, keeping the slot occupied throughout
    closer = threading.Thread(target=svc.close, daemon=True)
    closer.start()
    t.join(TIMEOUT)
    assert "closed" in str(box["exc"])
    engine.resume.set()                       # let m1 finish and close drain
    closer.join(TIMEOUT)
    with pytest.raises(RuntimeError, match="closed"):
        rider.result(timeout=TIMEOUT)         # rider shares the rejection
    _assert_result_matches_analyze(f1.result(timeout=TIMEOUT), m1)
    m = svc.metrics()
    # only m1 was ever accepted: the rider's submit/coalesce backed out
    assert m.submitted == 1 and m.completed == 1 and m.coalesced == 0


# ------------------------------------------- engine stream double-buffering


def test_analyze_stream_order_and_parity_through_lookahead():
    """The double-buffered stream (one-item lookahead) still yields strictly
    in order, one result per item, bit-identical per item."""
    rng = np.random.default_rng(60)
    items = [(rng.random((10 + i, 14)) < 0.5).astype(np.uint8)
             for i in range(7)]
    engine = Engine()
    outs = list(engine.analyze_stream(iter(items)))
    assert len(outs) == len(items)
    for item, out in zip(items, outs):
        assert_bit_identical(out.to_summary(), ychg.analyze(jnp.asarray(item)))


def test_analyze_stream_empty_and_singleton():
    engine = Engine()
    assert list(engine.analyze_stream(iter([]))) == []
    img = _mask((9, 9), seed=61)
    (only,) = engine.analyze_stream([img])
    _assert_result_matches_analyze(only, img)


def test_analyze_stream_bad_rank_raises():
    engine = Engine()
    with pytest.raises(ValueError, match="stream items"):
        list(engine.analyze_stream([np.zeros((2, 2, 2, 2), np.uint8)]))


def test_analyze_stream_raising_iterator_still_delivers_prior_results():
    """A source iterator that raises (e.g. a failing loader) must not
    swallow the previous item's computed result either."""
    engine = Engine()
    good = _mask((6, 7), seed=74)

    def loader():
        yield good
        raise OSError("load failed")

    gen = engine.analyze_stream(loader())
    _assert_result_matches_analyze(next(gen), good)
    with pytest.raises(OSError, match="load failed"):
        next(gen)


# ------------------------------------------------------ registry counters


def test_registry_call_counters():
    registry.reset_call_counts()
    assert registry.call_count() == 0
    engine = Engine(YCHGConfig(backend="jax"))
    engine.analyze(np.zeros((4, 4), np.uint8))
    assert registry.call_count("jax") == 1
    assert registry.call_count() == 1
    engine.analyze_batch(np.zeros((2, 4, 4), np.uint8))
    assert registry.call_count("jax") == 2
    assert registry.call_count("fused") == 0


def test_flush_dispatch_span_joins_the_riders_trace():
    """The engine's dispatch span of a flush lands on its first rider's
    trace: served requests open no engine traces of their own."""
    from repro import obs

    obs.configure(enabled=True)
    obs.recorder().clear()
    try:
        with YCHGService(config=ServiceConfig(
                bucket_sides=(64,), max_batch=4, max_delay_ms=1.0)) as svc:
            trace = obs.Trace(process="test")
            svc.submit(_mask((20, 30), seed=41), trace=trace).result(
                timeout=TIMEOUT)
            stages = ("engine.put", "engine.dispatch", "engine.fetch")
            (name, a, b, meta), = [s for s in trace.spans()
                                   if s[0] in stages]
            assert name == "engine.dispatch" and meta["op"] == "ychg"
            flush = [s for s in trace.spans() if s[0] == "scheduler.flush"]
            assert flush[0][1] <= a <= b <= flush[0][2]
        assert all(t.process != "engine" for t in obs.recorder().traces())
    finally:
        obs.recorder().clear()
