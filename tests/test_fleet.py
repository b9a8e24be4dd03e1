"""`repro.fleet` suite: ring placement, key stability, peering, routing.

Per the fleet policy in tests/README.md: loopback only, every port
ephemeral, no wall-clock assertions (gates and bounded polls pin the
interleavings), and the bit-identity bar applies through the router path
exactly as it does one layer down. "Workers" here are in-process
service + ServerThread pairs — subprocess workers (spawn, handshake,
restart) are exercised end to end by the fleet-smoke CI leg, not per-test.

The cross-process key-stability test is the exception that NEEDS a
subprocess: `serialize_key` exists precisely because tuple keys lean on
per-process `hash()`, so the test re-renders the same key under two
different ``PYTHONHASHSEED`` values and holds the bytes equal to the
parent's — the property consistent-hash placement (and every worker
restart) rides on.
"""

import json
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.engine import Engine, YCHGConfig
from repro.fleet import (
    FleetRouter,
    HashRing,
    PeeredResultCache,
    RouterConfig,
    RouterThread,
    WorkerLink,
)
from repro.fleet.router import routing_key
from repro.frontend import (
    FrontendOverloaded,
    ServerThread,
    YCHGClient,
    protocol,
)
from repro.service import ServiceConfig, YCHGService
from repro.service.cache import make_key, serialize_key

from test_service import _GatedEngine  # noqa: E402  (established pattern)

TIMEOUT = 300.0


def _mask(shape, seed=0, density=0.5):
    rng = np.random.default_rng(seed)
    return (rng.random(shape) < density).astype(np.uint8)


def _assert_host_equal(got, want):
    assert set(got) == set(want)
    for field in want:
        a, b = np.asarray(want[field]), np.asarray(got[field])
        assert a.shape == b.shape, field
        assert a.dtype == b.dtype, field
        assert np.array_equal(a, b), field


# ------------------------------------------------------------ hash ring


def test_ring_is_deterministic_and_balanced():
    nodes = ["w0", "w1", "w2", "w3"]
    ring_a, ring_b = HashRing(nodes), HashRing(nodes)
    keys = [serialize_key(make_key(_mask((16, 16), seed=s), "cpu", None))
            for s in range(200)]
    owners = [ring_a.node_for(k) for k in keys]
    # same nodes -> same ring -> same placement, in any process
    assert owners == [ring_b.node_for(k) for k in keys]
    counts = {n: owners.count(n) for n in nodes}
    # virtual nodes keep the split rough but never degenerate
    assert all(counts[n] > 0 for n in nodes), counts


def test_ring_removal_moves_only_the_dead_nodes_keys():
    nodes = ["w0", "w1", "w2", "w3"]
    ring = HashRing(nodes)
    keys = [serialize_key(make_key(_mask((16, 16), seed=s), "cpu", None))
            for s in range(200)]
    before = {k: ring.node_for(k) for k in keys}
    up = [n for n in nodes if n != "w1"]
    for k, owner in before.items():
        after = ring.node_for(k, up=up)
        if owner != "w1":
            assert after == owner   # survivors' keys never move
        else:
            assert after in up      # w1's keys land on live nodes only
    # failover is deterministic: the preference walk always names the
    # same successor for the same key
    for k in keys[:20]:
        assert ring.node_for(k, up=up) == [
            n for n in ring.preference(k) if n in up][0]


def test_ring_all_down_and_bad_construction():
    ring = HashRing(["w0", "w1"])
    key = b"anything"
    assert ring.node_for(key, up=[]) is None
    with pytest.raises(ValueError):
        HashRing([])
    with pytest.raises(ValueError):
        HashRing(["w0", "w0"])


# ------------------------------------------------------- key serialization


def test_serialize_key_distinguishes_every_component():
    mask = _mask((4, 8), seed=1)
    cfg = YCHGConfig()
    base = serialize_key(make_key(mask, "cpu", cfg))
    # same bytes, different shape: (4, 8) vs (8, 4)
    reshaped = np.ascontiguousarray(mask.reshape(8, 4))
    assert serialize_key(make_key(reshaped, "cpu", cfg)) != base
    # same bytes, different dtype view
    as_int8 = mask.view(np.int8)
    assert serialize_key(make_key(as_int8, "cpu", cfg)) != base
    # different backend / different config / different content
    assert serialize_key(make_key(mask, "ref", cfg)) != base
    cfg2 = YCHGConfig(block_w=cfg.block_w * 2)
    assert serialize_key(make_key(mask, "cpu", cfg2)) != base
    assert serialize_key(
        make_key(_mask((4, 8), seed=2), "cpu", cfg)) != base
    # different op on the same mask: per-op cache namespaces never alias
    assert serialize_key(make_key(mask, "cpu", cfg, op="ccl")) != base
    # and the rendering is pure: same inputs, same bytes
    assert serialize_key(make_key(mask, "cpu", YCHGConfig())) == base


def test_serialize_key_is_versioned_and_op_prefixed():
    """The v2 rendering leads with a version tag and a length-prefixed op
    component, so mixed-version fleet caches can never alias: a v1 key's
    first length-prefixed part was a 32-byte digest, a v2 key's is the
    11-byte version tag — differing first components, never equal bytes.
    The op part is length-prefixed, so ("ab", mask) and ("a", b-ish
    content) cannot collide by concatenation either."""
    mask = _mask((4, 8), seed=1)
    cfg = YCHGConfig()
    for op in ("ychg", "ccl", "denoise", "denoise+ychg"):
        skey = serialize_key(make_key(mask, "cpu", cfg, op=op))
        assert skey.startswith(
            len(b"ychg-key-v2").to_bytes(4, "big") + b"ychg-key-v2")
        # the op component follows, length-prefixed
        off = 4 + len(b"ychg-key-v2")
        n = int.from_bytes(skey[off:off + 4], "big")
        assert skey[off + 4:off + 4 + n] == op.encode()


_CHILD_SCRIPT = textwrap.dedent("""
    import sys
    import numpy as np
    from repro.engine import YCHGConfig
    from repro.service.cache import make_key, serialize_key
    rng = np.random.default_rng(7)
    mask = (rng.random((32, 48)) < 0.5).astype(np.uint8)
    for op in ("ychg", "ccl", "denoise+ychg"):
        key = make_key(mask, "cpu", YCHGConfig(), op=op)
        sys.stdout.write(serialize_key(key).hex() + "\\n")
""")


def test_serialized_key_is_stable_across_processes():
    """The satellite bar: the serialized key must be byte-identical in
    processes with different hash seeds — tuple keys are not (hash()
    randomisation), which is exactly why routing serializes first. Since
    the v2 op component, every op's key (pipeline keys included) holds
    the same bar."""
    import os

    rng = np.random.default_rng(7)
    mask = (rng.random((32, 48)) < 0.5).astype(np.uint8)
    want = "".join(
        serialize_key(make_key(mask, "cpu", YCHGConfig(), op=op)).hex() + "\n"
        for op in ("ychg", "ccl", "denoise+ychg"))
    assert len(set(want.split())) == 3   # op-distinct, never aliased
    for seed in ("0", "1"):
        env = dict(os.environ, PYTHONHASHSEED=seed)
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_SCRIPT], env=env,
            capture_output=True, text=True, timeout=TIMEOUT)
        assert out.returncode == 0, out.stderr
        assert out.stdout == want, (
            f"serialized key drifted under PYTHONHASHSEED={seed}")


# ------------------------------------------------------------- peering


def test_peer_probe_adopts_siblings_entry_without_recompute():
    """Worker B misses locally, finds the entry in sibling A's cache over
    the RPC probe, and serves it WITHOUT dispatching a batch — B's batch
    counter stays 0 and the result is bit-identical to A's."""
    mask = _mask((24, 24), seed=30)
    cfg = ServiceConfig(bucket_sides=(32,), max_batch=2, max_delay_ms=1.0)
    cache_a = PeeredResultCache(64)
    svc_a = YCHGService(Engine(), cfg, cache=cache_a)
    with svc_a, ServerThread(svc_a, rpc_port=0) as srv_a:
        want = svc_a.submit(mask).result(timeout=TIMEOUT).to_host()
        cache_b = PeeredResultCache(64)
        cache_b.set_peers([("127.0.0.1", srv_a.rpc_port)])
        svc_b = YCHGService(Engine(), cfg, cache=cache_b)
        with svc_b:
            got = svc_b.submit(mask).result(timeout=TIMEOUT).to_host()
            m = svc_b.metrics()
    _assert_host_equal(got, want)
    assert cache_b.peer_hits == 1
    assert m.peer_hits == 1
    assert m.batches == 0          # the whole point: no compute on B
    assert m.completed == 1
    # the adopted entry is now LOCAL: a repeat hits B's own cache
    assert cache_b.get(
        make_key(np.ascontiguousarray(mask),
                 svc_b.engine.resolve_backend(), svc_b.engine.config,
                 svc_b.engine.mesh)) is not None


def test_peer_probe_miss_and_dead_peer_fall_back_to_compute():
    """A sibling without the entry, then a dead peer: both are just
    misses — the service computes as if unpeered, and peering never
    makes a request fail."""
    mask = _mask((24, 24), seed=31)
    cfg = ServiceConfig(bucket_sides=(32,), max_batch=2, max_delay_ms=1.0)
    empty_cache = PeeredResultCache(64)
    svc_empty = YCHGService(Engine(), cfg, cache=empty_cache)
    with svc_empty, ServerThread(svc_empty, rpc_port=0) as srv_empty:
        # a dead port: bind-then-close guarantees nothing listens there
        s = socket.create_server(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        cache = PeeredResultCache(64, probe_timeout_s=0.1)
        cache.set_peers([("127.0.0.1", dead_port),
                         ("127.0.0.1", srv_empty.rpc_port)])
        svc = YCHGService(Engine(), cfg, cache=cache)
        with svc:
            out = svc.submit(mask).result(timeout=TIMEOUT)
            m = svc.metrics()
    assert out.to_host()["runs"].shape == (24,)
    assert cache.peer_hits == 0
    assert cache.peer_misses == 1
    assert m.peer_misses == 1
    assert m.batches == 1          # computed locally


def test_cache_probe_rpc_verb_is_local_only():
    """The inbound probe answers from the local index and NEVER computes:
    probing a cold worker is a miss even though the worker could have
    computed the answer."""
    mask = _mask((16, 16), seed=32)
    cfg = ServiceConfig(bucket_sides=(16,), max_batch=1, max_delay_ms=1.0)
    cache = PeeredResultCache(64)
    svc = YCHGService(Engine(), cfg, cache=cache)
    with svc, ServerThread(svc, rpc_port=0) as srv:
        from repro.fleet.peering import probe_peer

        key = make_key(np.ascontiguousarray(mask),
                       svc.engine.resolve_backend(), svc.engine.config,
                       svc.engine.mesh)
        skey = serialize_key(key)
        assert probe_peer("127.0.0.1", srv.rpc_port, skey,
                          timeout=5.0) is None
        assert svc.metrics().batches == 0    # the probe computed nothing
        svc.submit(mask).result(timeout=TIMEOUT)
        frame = probe_peer("127.0.0.1", srv.rpc_port, skey, timeout=5.0)
        assert frame is not None and frame["hit"]
        # stored layout rides the wire: B=1 arrays, not the squeezed host view
        runs = protocol.decode_array(frame["result"]["runs"])
        assert runs.shape == (1, 16)


# ------------------------------------------------------------- the router


def _two_worker_fleet(cfg=None, engines=None):
    """Two in-process 'workers' (service + ServerThread with RPC) plus
    their links; caller closes via the returned closers list."""
    cfg = cfg or ServiceConfig(
        bucket_sides=(32,), max_batch=4, max_delay_ms=1.0)
    links, closers = [], []
    for i in range(2):
        engine = engines[i] if engines else Engine()
        cache = PeeredResultCache(64)
        svc = YCHGService(engine, cfg, cache=cache)
        srv = ServerThread(svc, rpc_port=0)
        links.append(WorkerLink(name=f"w{i}", host="127.0.0.1",
                                rpc_port=srv.rpc_port,
                                http_port=srv.port))
        closers.append((svc, srv))
    return links, closers


def _close_fleet(closers):
    for svc, srv in closers:
        srv.close()
        svc.close()


def test_router_path_is_bit_identical_and_uses_both_workers():
    masks = [_mask((28, 28), seed=40 + i) for i in range(8)]
    links, closers = _two_worker_fleet()
    try:
        cfg = ServiceConfig(bucket_sides=(32,), max_batch=4,
                            max_delay_ms=1.0)
        with YCHGService(Engine(), cfg) as ref:
            want = [ref.submit(m).result(timeout=TIMEOUT).to_host()
                    for m in masks]
        router = FleetRouter(links, RouterConfig(bucket_sides=(32,),
                                                 max_batch=4))
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            # single analyzes + a streamed batch, all through the router
            got0 = client.analyze(masks[0])
            _assert_host_equal(got0, want[0])
            items = {it.id: it for it in client.analyze_batch(masks)}
            for i, want_res in enumerate(want):
                assert items[i].ok, items[i].error
                _assert_host_equal(items[i].result, want_res)
            health = client.health()
            assert health["workers"] == {"w0": True, "w1": True}
        # placement actually spread over the ring for this mask set
        ring = HashRing(["w0", "w1"])
        owners = {ring.node_for(routing_key(m)) for m in masks}
        assert owners == {"w0", "w1"}, (
            "seed set no longer exercises both workers; pick new seeds")
    finally:
        _close_fleet(closers)


def test_router_reroutes_to_survivor_when_a_worker_dies():
    masks = [_mask((28, 28), seed=50 + i) for i in range(9)]
    links, closers = _two_worker_fleet()
    try:
        ring = HashRing(["w0", "w1"])
        # a mask owned by w1, so killing w1 forces a reroute
        victim_mask = next(m for m in masks
                           if ring.node_for(routing_key(m)) == "w1")
        cfg = ServiceConfig(bucket_sides=(32,), max_batch=4,
                            max_delay_ms=1.0)
        with YCHGService(Engine(), cfg) as ref:
            want = ref.submit(victim_mask).result(timeout=TIMEOUT).to_host()
        router = FleetRouter(links, RouterConfig(bucket_sides=(32,),
                                                 max_batch=4))
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            _assert_host_equal(client.analyze(victim_mask), want)
            svc1, srv1 = closers[1]
            srv1.close()           # w1's listeners vanish mid-fleet
            svc1.close()
            _assert_host_equal(client.analyze(victim_mask), want)
            metrics = client.metrics_text()
            assert "ychg_fleet_rerouted_total 1" in metrics
            assert 'ychg_fleet_worker_up{worker="w1"} 0' in metrics
            assert 'ychg_fleet_worker_up{worker="w0"} 1' in metrics
            health = client.health()
            assert health["workers"] == {"w0": True, "w1": False}
    finally:
        _close_fleet(closers)


def test_router_admission_sheds_429_when_workers_are_saturated():
    """Router-side DRR admission: one queue slot, held by a request
    parked in a gated worker engine — the second request sheds at the
    ROUTER with HTTP 429 before ever reaching a worker."""
    engines = [_GatedEngine(), _GatedEngine()]
    links, closers = _two_worker_fleet(engines=engines)
    holder_fut = {}
    try:
        router = FleetRouter(links, RouterConfig(
            bucket_sides=(32,), max_batch=4, max_queue_depth=1,
            overload_policy="shed"))
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            holder_mask, shed_mask = (_mask((28, 28), seed=60),
                                      _mask((28, 28), seed=61))
            t = threading.Thread(
                target=lambda: holder_fut.update(
                    out=client.analyze(holder_mask)),
                daemon=True)
            t.start()
            # the holder is admitted once it reaches a worker's engine
            deadline = time.monotonic() + TIMEOUT
            while not any(e.entered.is_set() for e in engines):
                assert time.monotonic() < deadline, "holder never arrived"
                time.sleep(0.005)
            with YCHGClient("127.0.0.1", rt.port) as shed_client:
                with pytest.raises(FrontendOverloaded) as exc_info:
                    shed_client.analyze(shed_mask)
            assert exc_info.value.status == 429
            assert exc_info.value.retry_after_s > 0
            for e in engines:
                e.resume.set()
            t.join(TIMEOUT)
            assert "runs" in holder_fut.get("out", {})
    finally:
        for e in engines:
            e.resume.set()
        _close_fleet(closers)


def test_router_429_retry_after_reflects_measured_drain_rate():
    """The 429 hint comes from the router's drain-rate estimator, not the
    old hardcoded 1.0 s: seed the estimator white-box with a known rate
    (10 completions/s) and the Retry-After must be (backlog + 1) / 10 =
    0.1 s — the parked holder is in flight at the worker, not queued, so
    backlog is 0 at shed time."""
    engines = [_GatedEngine(), _GatedEngine()]
    links, closers = _two_worker_fleet(engines=engines)
    holder_fut = {}
    try:
        router = FleetRouter(links, RouterConfig(
            bucket_sides=(32,), max_batch=4, max_queue_depth=1,
            overload_policy="shed"))
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            holder_mask, shed_mask = (_mask((28, 28), seed=62),
                                      _mask((28, 28), seed=63))
            t = threading.Thread(
                target=lambda: holder_fut.update(
                    out=client.analyze(holder_mask)),
                daemon=True)
            t.start()
            deadline = time.monotonic() + TIMEOUT
            while not any(e.entered.is_set() for e in engines):
                assert time.monotonic() < deadline, "holder never arrived"
                time.sleep(0.005)
            # seed: 10 completions over the last second; the huge interval
            # pins the samples against the loop's own observe() calls
            now = time.monotonic()
            router._drain._interval = 1e9
            router._drain._samples = [(now - 1.0, 0), (now, 10)]
            with YCHGClient("127.0.0.1", rt.port) as shed_client:
                with pytest.raises(FrontendOverloaded) as exc_info:
                    shed_client.analyze(shed_mask)
            assert exc_info.value.status == 429
            assert exc_info.value.retry_after_s == pytest.approx(
                0.1, abs=0.001)
            for e in engines:
                e.resume.set()
            t.join(TIMEOUT)
            assert "runs" in holder_fut.get("out", {})
    finally:
        for e in engines:
            e.resume.set()
        _close_fleet(closers)


def test_rollup_sums_worker_histograms_exactly():
    """Fixed bucket boundaries make the fleet rollup exact arithmetic:
    every ychg_request_latency_seconds series on the router's /metrics
    page equals the plain sum of the two workers' series, and the summed
    histogram stays internally consistent (_count == +Inf bucket)."""
    from repro.obs import base_family, parse_prom_text

    masks = [_mask((28, 28), seed=70 + i) for i in range(6)]
    links, closers = _two_worker_fleet()
    n_requests = len(masks) + 2
    try:
        router = FleetRouter(links, RouterConfig(bucket_sides=(32,),
                                                 max_batch=4))
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            items = {it.id: it for it in client.analyze_batch(masks)}
            assert all(it.ok for it in items.values())
            # a mixed-op recording: the rollup must stay exact arithmetic
            # when series carry distinct op label sets
            client.analyze(_mask((28, 28), seed=80), op="ccl")
            client.analyze(_mask((28, 28), seed=81), op="ccl")
            worker_pages = []
            for link in links:
                with YCHGClient("127.0.0.1", link.http_port) as wc:
                    worker_pages.append(parse_prom_text(wc.metrics_text()))
            page = parse_prom_text(client.metrics_text())
        fam = "ychg_request_latency_seconds"
        assert page.types.get(fam) == "histogram"

        def hist_series(p):
            return {(s.name, s.labels): s.value for s in p.samples
                    if base_family(s.name) == fam}

        want = {}
        for wp in worker_pages:
            for key, v in hist_series(wp).items():
                want[key] = want.get(key, 0.0) + v
        got = hist_series(page)
        assert want, "workers exported no latency histogram series"
        for key, v in want.items():
            assert got.get(key) == v, key
        inf = sum(v for (n, labels), v in got.items()
                  if n.endswith("_bucket") and dict(labels)["le"] == "+Inf")
        counts = sum(v for (n, _), v in got.items()
                     if n.endswith("_count"))
        assert inf == counts == n_requests
        # both ops' label sets survive the rollup distinctly
        ops_seen = {dict(labels).get("op") for (n, labels) in got
                    if n.endswith("_count")}
        assert {"ychg", "ccl"} <= ops_seen
        # the plain-counter legacy rollup behaviour still holds alongside
        assert page.get("ychg_completed_total") == n_requests
    finally:
        _close_fleet(closers)


# ------------------------------------------------------------- supervision


def test_supervisor_refuses_more_workers_than_tpu_chips(monkeypatch):
    """A chip serves one process: on a host with 2 TPU chips a fleet of 3
    is refused before anything spawns, and a fleet of 2 pins slot i to
    chip i (a restart reclaims the same chip)."""
    import repro.fleet.router as router_mod
    from repro.fleet import FleetSupervisor

    monkeypatch.setattr(router_mod, "host_tpu_chips", lambda: 2)
    with pytest.raises(ValueError, match="at most 2 workers"):
        FleetSupervisor(3)
    assert [l.chip for l in FleetSupervisor(2).links] == [0, 1]
    monkeypatch.setattr(router_mod, "host_tpu_chips", lambda: 0)
    assert [l.chip for l in FleetSupervisor(3).links] == [None] * 3


def test_pinned_env_shows_each_worker_one_chip():
    from repro.fleet.chips import pinned_env

    a, b = pinned_env(0), pinned_env(3)
    assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0", "3")
    assert a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
    # pinned processes run side by side without lifting libtpu's lock
    assert "ALLOW_MULTIPLE_LIBTPU_LOAD" not in a


def test_worker_that_dies_before_ready_says_why():
    """The supervisor keeps a worker's stderr: a worker that exits before
    its READY handshake surfaces its own last words in the error."""
    from repro.fleet import FleetSupervisor

    sup = FleetSupervisor(1, worker_args=["--no-such-flag"],
                          start_timeout_s=60.0)
    with pytest.raises(RuntimeError,
                       match=r"(?s)READY handshake.*--no-such-flag"):
        sup.start()
