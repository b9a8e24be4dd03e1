"""Synthetic load generator + sweep for the yCHG ROI service.

Each scenario builds a mask pool (`data.modis.snowfield`/`striped`), draws a
request schedule over it (unique traffic, zipf-ish repeated traffic, mixed
resolutions, optionally paced to an open-loop arrival rate), then drives the
SAME schedule through two paths:

  naive    one blocking ``engine.analyze(mask)`` per request, in order —
           the pre-service serving strategy (what launch/serve.py used to
           approximate with one hand-built batch);
  service  ``YCHGService.submit`` per request, futures awaited at the end —
           micro-batching + bucket padding + result cache + overlap.

Both paths are warmed first (compile time is a separate, known cost — see
``launch/serve.py``'s cold/warm split), so the comparison is steady-state.
Per scenario we record naive/service throughput, speedup, p50/p95 latency,
cache hit rate, Mpx/s, and the compiled-shape count, and write the table to
``BENCH_service.json`` for later PRs to track.

Run:  PYTHONPATH=src python benchmarks/bench_service.py [--out BENCH_service.json]

``--quick`` swaps in a seconds-not-minutes scenario set (same shapes of
traffic, smaller pools/schedules) shared by the CI ``bench-gate`` job and
local smoke runs: the committed ``BENCH_service.json`` carries the quick
baselines under ``"quick"`` plus the gate's tolerances under ``"gate"``,
and ``benchmarks/check_bench_regression.py`` fails CI when a fresh quick
run regresses past them.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import List, Optional, Sequence

import numpy as np

import jax

from repro.data import modis
from repro.engine import Engine
from repro.launch.compilecache import enable_compile_cache
from repro.service import (
    ServiceConfig,
    ServiceOverloaded,
    YCHGService,
    sub_batch_ladder,
)


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    resolutions: Sequence[int]     # pool mask sides (mixed-res traffic)
    pool_size: int                 # distinct masks in the pool
    n_requests: int
    repeat_alpha: Optional[float]  # zipf-ish skew; None = all-unique schedule
    rate: Optional[float] = None   # open-loop arrivals/s; None = closed-loop
    seed: int = 0


SCENARIOS = (
    # the acceptance scenario: repeated-mask traffic, closed loop
    Scenario("repeat_small", (128,), pool_size=8, n_requests=160,
             repeat_alpha=1.2),
    # worst case for the cache: every request distinct
    Scenario("unique_small", (128,), pool_size=160, n_requests=160,
             repeat_alpha=None),
    # mixed resolutions exercise the bucket ladder + striped masks the
    # hyperedge-count invariance (paper knob (b)) inside the pool
    Scenario("mixed_res", (64, 128, 256), pool_size=24, n_requests=120,
             repeat_alpha=1.0),
    # paced open-loop traffic: latency under a sustainable arrival rate
    Scenario("paced_repeat", (128,), pool_size=8, n_requests=100,
             repeat_alpha=1.2, rate=200.0),
)

# the --quick set: same traffic shapes, schedules small enough for CI
# (seconds, warmup included) — these are what the bench-gate compares
QUICK_SCENARIOS = (
    Scenario("repeat_small", (128,), pool_size=6, n_requests=48,
             repeat_alpha=1.2),
    Scenario("unique_small", (128,), pool_size=48, n_requests=48,
             repeat_alpha=None),
    Scenario("mixed_res", (64, 128), pool_size=12, n_requests=36,
             repeat_alpha=1.0),
)


def build_pool(sc: Scenario) -> List[np.ndarray]:
    rng = np.random.default_rng(sc.seed)
    pool = []
    for i in range(sc.pool_size):
        res = sc.resolutions[i % len(sc.resolutions)]
        if i % 3 == 2:  # striped masks pin an exact hyperedge count
            pool.append(modis.striped(res, int(rng.integers(10, 200))))
        else:
            pool.append(modis.snowfield(res, seed=sc.seed * 1000 + i))
    return pool


def build_schedule(sc: Scenario, rng: np.random.Generator) -> np.ndarray:
    if sc.repeat_alpha is None:
        assert sc.pool_size >= sc.n_requests
        return rng.permutation(sc.n_requests)
    weights = 1.0 / np.arange(1, sc.pool_size + 1) ** sc.repeat_alpha
    return rng.choice(sc.pool_size, size=sc.n_requests, p=weights / weights.sum())


def run_naive(engine: Engine, pool, schedule, rate) -> float:
    """Per-request blocking engine.analyze over the schedule; returns rps."""
    t0 = time.perf_counter()
    for n, i in enumerate(schedule):
        if rate is not None:
            _pace(t0, n, rate)
        engine.analyze(pool[i]).block_until_ready()
    return len(schedule) / (time.perf_counter() - t0)


def run_service(svc: YCHGService, pool, schedule, rate) -> float:
    t0 = time.perf_counter()
    futures = []
    for n, i in enumerate(schedule):
        if rate is not None:
            _pace(t0, n, rate)
        futures.append(svc.submit(pool[i]))
    for f in futures:
        f.result(timeout=600)
    return len(schedule) / (time.perf_counter() - t0)


def _pace(t0: float, n: int, rate: float) -> None:
    due = t0 + n / rate
    while True:
        remaining = due - time.perf_counter()
        if remaining <= 0:
            return
        time.sleep(min(1e-3, remaining))


def _warm_rungs(engine: Engine, res: int, max_batch: int = 8) -> None:
    """Compile every sub-batch ladder rung's batch computation AND the
    service's per-request crop fan-out for it, outside any timed region."""
    from repro.service import crop_result

    for b in sub_batch_ladder(max_batch):
        r = engine.analyze_batch(np.zeros((b, res, res), np.uint8))
        crop_result(r, 0, res).block_until_ready()


def run_scenario(sc: Scenario) -> dict:
    pool = build_pool(sc)
    schedule = build_schedule(sc, np.random.default_rng(sc.seed + 1))
    sides = tuple(sorted(set(sc.resolutions)))
    max_batch = 8
    engine = Engine()
    svc = YCHGService(engine, ServiceConfig(bucket_sides=sides,
                                            max_batch=max_batch,
                                            max_delay_ms=2.0))
    with svc:
        # warm both paths: compile each distinct shape once, outside timing.
        # The service now dispatches (b, side, side) for every sub-batch
        # ladder rung b — and fans out through a (b, side)-shaped crop —
        # so warm each rung's batch AND crop, not just the full batch.
        for res in sides:
            warm = pool[next(i for i, m in enumerate(pool)
                             if m.shape[0] == res)]
            engine.analyze(warm).block_until_ready()
            svc.submit(warm).result(timeout=600)
            _warm_rungs(engine, res, max_batch)
        naive_rps = run_naive(engine, pool, schedule, sc.rate)
        service_rps = run_service(svc, pool, schedule, sc.rate)
        m = svc.metrics()
    row = {
        "scenario": sc.name,
        "n_requests": sc.n_requests,
        "resolutions": list(sides),
        "traffic": "unique" if sc.repeat_alpha is None
        else f"zipf(a={sc.repeat_alpha})",
        "rate_rps": sc.rate,
        "naive_rps": round(naive_rps, 1),
        "service_rps": round(service_rps, 1),
        "speedup": round(service_rps / naive_rps, 2),
        "p50_latency_ms": round(m.p50_latency_ms, 3),
        "p95_latency_ms": round(m.p95_latency_ms, 3),
        "cache_hit_rate": round(m.hit_rate, 3),
        "coalesced": m.coalesced,
        "mpx_per_s": round(m.mpx_per_s, 2),
        "compiled_shapes": m.n_compiled_shapes,
        "shape_budget": len(sides) * len(sub_batch_ladder(max_batch)),
        "pad_fraction": round(m.pad_fraction, 3),
    }
    # acceptance bar: bucket ladder x sub-batch ladder bounds the shapes
    assert m.n_compiled_shapes <= len(sides) * len(sub_batch_ladder(max_batch)), row
    return row


def run_low_occupancy(pool_size: int = 24) -> dict:
    """Closed-loop B=1 traffic (submit one, await it, submit the next):
    every flush has occupancy 1, the worst case for pad-to-max_batch. The
    SAME schedule runs under sub-bucket padding and under the old
    pad-to-max policy; sub-buckets must dispatch ~max_batch x fewer pixels
    (pad_fraction) and be no slower end to end."""
    res, max_batch = 128, 8
    pool = [modis.snowfield(res, seed=500 + i) for i in range(pool_size)]
    out = {"scenario": "low_occupancy", "n_requests": len(pool),
           "resolutions": [res], "traffic": "closed-loop B=1",
           "max_batch": max_batch}
    for label, sub in (("sub_buckets", True), ("pad_to_max", False)):
        cfg = ServiceConfig(bucket_sides=(res,), max_batch=max_batch,
                            max_delay_ms=2.0, cache_entries=0,
                            sub_batches=sub)
        with YCHGService(Engine(), cfg) as svc:
            svc.analyze(pool[0], timeout=600)   # warm: compile outside timing
            t0 = time.perf_counter()
            for m in pool:
                svc.analyze(m, timeout=600)
            dt = time.perf_counter() - t0
            met = svc.metrics()
        out[f"{label}_rps"] = round(len(pool) / dt, 1)
        out[f"{label}_pad_fraction"] = round(met.pad_fraction, 3)
        out[f"{label}_p95_latency_ms"] = round(met.p95_latency_ms, 3)
    out["speedup_sub_vs_padmax"] = round(
        out["sub_buckets_rps"] / out["pad_to_max_rps"], 2)
    # the acceptance bar: strictly less pad compute, no slower end to end
    # (5% wall-clock tolerance: at this size the delay window dominates
    # both arms, so "no slower" means within run-to-run noise)
    assert out["sub_buckets_pad_fraction"] < out["pad_to_max_pad_fraction"], out
    assert out["speedup_sub_vs_padmax"] >= 0.95, out
    return out


def run_overload() -> dict:
    """Open-loop traffic offered well past capacity. Unbounded queue: every
    request is admitted and p95 balloons with the backlog. Bounded queue
    with overload_policy="shed": excess submits fail fast with
    ServiceOverloaded, and the p95 of what IS served stays flat."""
    res, n_requests = 128, 120
    pool = [modis.snowfield(res, seed=700 + i) for i in range(n_requests)]
    base = dict(bucket_sides=(res,), max_batch=8, max_delay_ms=2.0,
                cache_entries=0)
    # compile every ladder rung (batch + crop) once, outside every
    # measurement below
    _warm_rungs(Engine(), res)
    # probe steady-state capacity, then offer a multiple of it
    with YCHGService(Engine(), ServiceConfig(**base)) as svc:
        svc.analyze(pool[0], timeout=600)
        t0 = time.perf_counter()
        for f in [svc.submit(m) for m in pool[:40]]:
            f.result(timeout=600)
        capacity_rps = 40 / (time.perf_counter() - t0)
    rate = 3.0 * capacity_rps
    out = {"scenario": "overload", "n_requests": n_requests,
           "resolutions": [res], "traffic": "open-loop 3x capacity",
           "capacity_rps": round(capacity_rps, 1),
           "offered_rps": round(rate, 1)}
    for label, knobs in (
        ("unbounded", {}),
        ("bounded_shed", {"max_queue_depth": 16, "overload_policy": "shed"}),
    ):
        shed = 0
        with YCHGService(Engine(),
                         ServiceConfig(**base, **knobs)) as svc:
            svc.analyze(pool[0], timeout=600)
            futures = []
            t0 = time.perf_counter()
            for n, m in enumerate(pool):
                _pace(t0, n, rate)
                try:
                    futures.append(svc.submit(m))
                except ServiceOverloaded:
                    shed += 1
            for f in futures:
                f.result(timeout=600)
            met = svc.metrics()
        out[f"{label}_p95_latency_ms"] = round(met.p95_latency_ms, 3)
        out[f"{label}_served"] = len(futures)
        if knobs:
            out[f"{label}_shed"] = shed
            assert shed > 0 and shed == met.shed, out   # admission worked
    # the acceptance bar: a bounded queue keeps tail latency flat under
    # the same offered load, at the price of shedding the excess
    assert (out["bounded_shed_p95_latency_ms"]
            <= out["unbounded_p95_latency_ms"]), out
    return out


EXTRA_SCENARIOS = {
    "low_occupancy": run_low_occupancy,
    "overload": run_overload,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_service.json")
    ap.add_argument("--scenario", default=None,
                    help="run a single scenario by name")
    ap.add_argument("--quick", action="store_true",
                    help="the small scenario set the CI bench-gate runs "
                         "(seconds, not minutes); writes mode='quick'")
    args = ap.parse_args()
    enable_compile_cache()
    scenarios = QUICK_SCENARIOS if args.quick else SCENARIOS
    extras = (
        {"low_occupancy": lambda: run_low_occupancy(pool_size=10)}
        if args.quick else EXTRA_SCENARIOS
    )
    rows = []
    for sc in scenarios:
        if args.scenario and sc.name != args.scenario:
            continue
        row = run_scenario(sc)
        rows.append(row)
        print(json.dumps(row), flush=True)
    for name, runner in extras.items():
        if args.scenario and name != args.scenario:
            continue
        row = runner()
        rows.append(row)
        print(json.dumps(row), flush=True)
    report = {
        "bench": "service_load_sweep",
        "mode": "quick" if args.quick else "full",
        "platform": jax.default_backend(),
        "backend": Engine().resolve_backend(),
        "note": (
            "steady-state (both paths warmed); naive = blocking per-request "
            "engine.analyze on the same schedule; latency percentiles are "
            "service submit->ready times (compute misses only — cache hits "
            "are excluded from the window); low_occupancy compares sub-"
            "bucket padding vs pad-to-max_batch on one schedule; overload "
            "offers 3x capacity open-loop, unbounded vs bounded+shed"
        ),
        "scenarios": rows,
    }
    # re-recording over an existing baseline must not destroy the CI
    # bench-gate's contract: a full re-run carries the committed "quick"
    # baselines and "gate" tolerances forward, and a quick re-run aimed at
    # the baseline file refreshes ONLY its "quick" section (never clobbers
    # the full table). Point --out at a fresh path for a standalone report.
    try:
        with open(args.out) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = None
    if existing is not None:
        if args.quick and existing.get("mode") != "quick":
            existing["quick"] = {
                "note": existing.get("quick", {}).get(
                    "note", "baselines for the CI bench-gate"),
                "scenarios": rows,
            }
            report = existing
        elif not args.quick:
            for section in ("quick", "gate"):
                if section in existing:
                    report[section] = existing[section]
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(rows)} scenarios)")


if __name__ == "__main__":
    main()
