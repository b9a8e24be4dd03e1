"""Load generator for the network front end + the fairness policies.

Two scenario families, written to ``BENCH_frontend.json``:

  **wire_vs_inprocess** — the SAME schedule through (a) in-process
  ``YCHGService.submit`` and (b) the loopback HTTP transport (streamed
  batch + sequential closed-loop round trips), so the wire tax is
  measured directly: batch-throughput ratio and per-request added
  latency. The transport must stay a thin edge, not a second service.

  **fair_vs_unfair_skew** — open-loop traffic offered at 3x measured
  capacity, 1-in-6 requests in a minority bucket and the rest flooding a
  hot bucket, through two admission configurations on one schedule:

    unfair  the PR-4 policy: one bucket-blind global ``max_queue_depth``
            + arrival-order flushes (``fair=False``) — the flood owns the
            queue, so the bound sheds minority requests too;
    fair    per-bucket ``bucket_queue_depth`` + deficit-round-robin
            flushes (``fair=True``) — the flood sheds against its own
            allowance only.

  The acceptance bar (asserted here, recorded in the JSON): under the
  fair policy the minority bucket sheds NOTHING and its client-observed
  p95 stays bounded, while the flooded bucket sheds; under the unfair
  policy the minority bucket demonstrably sheds with the flood.

Run:  PYTHONPATH=src python benchmarks/bench_frontend.py [--out BENCH_frontend.json]
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from typing import Dict, List

import numpy as np

import jax

from repro.data import modis
from repro.engine import Engine
from repro.frontend import ServerThread, YCHGClient
from repro.launch.compilecache import enable_compile_cache
from repro.service import ServiceConfig, ServiceOverloaded, YCHGService


def _pace(t0: float, n: int, rate: float) -> None:
    due = t0 + n / rate
    while True:
        remaining = due - time.perf_counter()
        if remaining <= 0:
            return
        time.sleep(min(1e-3, remaining))


def _warm_rungs(engine: Engine, res: int, max_batch: int = 8) -> None:
    """Compile every sub-batch rung's batch + crop shape outside timing."""
    from repro.service import crop_result, sub_batch_ladder

    for b in sub_batch_ladder(max_batch):
        r = engine.analyze_batch(np.zeros((b, res, res), np.uint8))
        crop_result(r, 0, res).block_until_ready()


# ------------------------------------------------------ wire vs in-process


def run_wire_vs_inprocess() -> dict:
    res, n_requests, pool_size = 128, 48, 8
    pool = [modis.snowfield(res, seed=900 + i) for i in range(pool_size)]
    rng = np.random.default_rng(7)
    schedule = rng.choice(pool_size, size=n_requests)
    engine = Engine()
    cfg = ServiceConfig(bucket_sides=(res,), max_batch=8, max_delay_ms=2.0)

    with YCHGService(engine, cfg) as svc:
        svc.analyze(pool[0], timeout=600)           # warm outside timing
        # in-process arm: submit all, await all (the batch twin)
        t0 = time.perf_counter()
        for f in [svc.submit(pool[i]) for i in schedule]:
            f.result(timeout=600)
        inproc_batch_s = time.perf_counter() - t0
        # in-process sequential arm: per-request closed loop
        t0 = time.perf_counter()
        for i in schedule[:16]:
            svc.analyze(pool[i], timeout=600)
        inproc_seq_ms = (time.perf_counter() - t0) / 16 * 1e3

    with YCHGService(engine, cfg) as svc, ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        client.analyze(pool[0])                     # warm (incl. keep-alive)
        t0 = time.perf_counter()
        items = list(client.analyze_batch([pool[i] for i in schedule]))
        wire_batch_s = time.perf_counter() - t0
        assert all(it.ok for it in items), "wire batch had failures"
        t0 = time.perf_counter()
        for i in schedule[:16]:
            client.analyze(pool[i])
        wire_seq_ms = (time.perf_counter() - t0) / 16 * 1e3

    return {
        "scenario": "wire_vs_inprocess",
        "n_requests": n_requests,
        "resolutions": [res],
        "inprocess_rps": round(n_requests / inproc_batch_s, 1),
        "wire_rps": round(n_requests / wire_batch_s, 1),
        "wire_throughput_ratio": round(inproc_batch_s / wire_batch_s, 2),
        "inprocess_seq_ms": round(inproc_seq_ms, 3),
        "wire_seq_ms": round(wire_seq_ms, 3),
        "wire_overhead_ms_per_request": round(wire_seq_ms - inproc_seq_ms, 3),
    }


# ------------------------------------------------------ fair vs unfair skew


def _run_skew_arm(engine: Engine, knobs: dict,
                  requests: List[tuple], rate: float) -> dict:
    """One admission policy under the shared skewed open-loop schedule.

    ``requests`` is [(kind, mask), ...] with every mask DISTINCT — repeat
    masks would coalesce onto in-flight leaders (consuming no queue slot)
    and the admission bounds would never engage.
    """
    base = dict(bucket_sides=(64, 128), max_batch=8, max_delay_ms=2.0,
                cache_entries=0, overload_policy="shed")
    shed = {"minority": 0, "flood": 0}
    latencies: Dict[str, list] = {"minority": [], "flood": []}
    lock = threading.Lock()
    with YCHGService(engine, ServiceConfig(**base, **knobs)) as svc:
        futures = []
        t0 = time.perf_counter()
        for n, (kind, mask) in enumerate(requests):
            _pace(t0, n, rate)
            try:
                fut = svc.submit(mask)
            except ServiceOverloaded:
                shed[kind] += 1
                continue

            # stamp completion in the done callback: awaiting futures in
            # submit order would charge each request for every slower
            # predecessor and corrupt the per-bucket percentiles
            def _stamp(f, kind=kind, t_sub=time.perf_counter()):
                lat = (time.perf_counter() - t_sub) * 1e3
                with lock:
                    latencies[kind].append(lat)

            fut.add_done_callback(_stamp)
            futures.append(fut)
        for fut in futures:
            fut.result(timeout=600)
    out = {}
    for kind in ("minority", "flood"):
        lat = np.asarray(latencies[kind])
        out[f"{kind}_served"] = int(lat.size)
        out[f"{kind}_shed"] = shed[kind]
        out[f"{kind}_p95_ms"] = (round(float(np.percentile(lat, 95)), 3)
                                 if lat.size else None)
    return out


def run_fair_vs_unfair_skew() -> dict:
    n_requests = 120
    # 1 in 6 requests is minority traffic; deterministic interleave; every
    # mask distinct so nothing coalesces and admission truly engages
    requests = [
        ("minority" if n % 6 == 0 else "flood",
         modis.snowfield(64 if n % 6 == 0 else 128, seed=1000 + n))
        for n in range(n_requests)
    ]
    engine = Engine()
    # compile every ladder rung (batch + crop) for both buckets up front
    for res in (64, 128):
        _warm_rungs(engine, res)
    # probe flood-bucket capacity closed-loop on distinct masks, offer 3x
    probe = [modis.snowfield(128, seed=2000 + i) for i in range(24)]
    with YCHGService(engine, ServiceConfig(
            bucket_sides=(64, 128), max_batch=8, max_delay_ms=2.0,
            cache_entries=0)) as svc:
        svc.analyze(probe[0], timeout=600)
        t0 = time.perf_counter()
        for f in [svc.submit(m) for m in probe]:
            f.result(timeout=600)
        capacity_rps = 24 / (time.perf_counter() - t0)
    rate = 3.0 * capacity_rps
    out = {"scenario": "fair_vs_unfair_skew", "n_requests": n_requests,
           "resolutions": [64, 128],
           "traffic": "open-loop 3x capacity, 1-in-6 minority (64), "
                      "rest flood (128)",
           "capacity_rps": round(capacity_rps, 1),
           "offered_rps": round(rate, 1)}
    arms = (
        # PR-4 policy: bucket-blind global bound, arrival-order flushes
        ("unfair", {"max_queue_depth": 16, "fair": False}),
        # this PR: per-bucket bounds + deficit-round-robin flushes
        ("fair", {"bucket_queue_depth": 24, "fair": True}),
    )
    for label, knobs in arms:
        arm = _run_skew_arm(engine, knobs, requests, rate)
        for k, v in arm.items():
            out[f"{label}_{k}"] = v
    # the acceptance bar: fairness isolates the minority bucket completely
    assert out["fair_minority_shed"] == 0, out
    assert out["fair_flood_shed"] > 0, out          # the flood still sheds
    assert out["unfair_minority_shed"] > 0, out     # bucket-blind shed it
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_frontend.json")
    args = ap.parse_args()
    enable_compile_cache()
    rows = [run_wire_vs_inprocess(), run_fair_vs_unfair_skew()]
    for row in rows:
        print(json.dumps(row), flush=True)
    report = {
        "bench": "frontend_load_sweep",
        "platform": jax.default_backend(),
        "backend": Engine().resolve_backend(),
        "note": (
            "wire_vs_inprocess drives one schedule through in-process "
            "submit and through loopback HTTP (streamed batch + "
            "per-request closed loop) — the wire tax, measured; "
            "fair_vs_unfair_skew offers 3x-capacity open-loop traffic, "
            "1-in-6 minority-bucket, under the PR-4 bucket-blind global "
            "bound with arrival-order flushes vs per-bucket bounds with "
            "deficit-round-robin: fairness must keep minority sheds at "
            "ZERO (and its p95 bounded) while the flood sheds"
        ),
        "scenarios": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(rows)} scenarios)")


if __name__ == "__main__":
    main()
