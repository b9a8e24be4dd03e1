"""Fleet scaling benchmark: router over N worker processes vs one process.

One scenario family, written to ``BENCH_fleet.json``:

  **fleet_vs_single** — the SAME pool of distinct masks (caches disabled
  where they would flatter: the timed masks are never pre-cached) served
  two ways: (a) a single in-process ``YCHGService`` behind its own
  ``ServerThread`` (today's one-process ceiling) and (b) the
  ``repro.fleet`` router fanning over ``--workers`` subprocess workers.
  The fleet arm runs first and the single-process arm after its workers
  are gone, so no two processes ever claim one chip. Both arms are
  warmed on a DISJOINT warm mask set (same bucket, so the
  ladder rungs compile outside timing, but no timed mask is ever served
  from a cache). The row records throughput for both arms, the ratio,
  and a bit-identity verdict (every field of every result compared
  against the single-process arm).

  **Honesty about cores**: fanning over processes buys nothing a single
  core can't give. The row records ``cores`` (``os.cpu_count()``); the
  ``>= 2x`` acceptance bar is asserted only when ``cores >= 4`` — on
  smaller boxes the measured ratio is recorded with a ``cpu_limited``
  note instead of a fake pass or a guaranteed failure.

  A final **peering leg** (recorded, always asserted) replays the
  smoke's death -> reroute -> restart -> repeat sequence and requires the
  rolled-up ``ychg_cache_peer_hits_total`` > 0: repeat traffic after a
  worker restart must be served from a sibling's cache, not recomputed.

Run:  PYTHONPATH=src python benchmarks/bench_fleet.py [--out BENCH_fleet.json]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import time
from typing import Dict, List

import numpy as np

import jax

from repro.core import serial
from repro.data import modis
from repro.engine import Engine
from repro.fleet import FleetRouter, FleetSupervisor, HashRing, RouterConfig, RouterThread
from repro.fleet.router import routing_key
from repro.frontend import ServerThread, YCHGClient
from repro.launch.compilecache import enable_compile_cache
from repro.service import ServiceConfig, YCHGService

RES = 64
MAX_BATCH = 8


def _masks(n: int, seed0: int) -> List[np.ndarray]:
    return [modis.snowfield(RES, seed=seed0 + i) for i in range(n)]


def _timed_batch(client: YCHGClient, masks) -> tuple:
    t0 = time.perf_counter()
    items = {it.id: it for it in client.analyze_batch(masks)}
    dt = time.perf_counter() - t0
    bad = [i for i, it in items.items() if not it.ok]
    assert not bad, f"batch failures: {bad}"
    return dt, items


def _identical(items: Dict, want: List[Dict[str, np.ndarray]]) -> bool:
    for i, want_res in enumerate(want):
        got = items[i].result
        for field, arr in want_res.items():
            a, b = np.asarray(arr), got[field]
            if not (np.array_equal(a, b) and a.dtype == b.dtype
                    and a.shape == b.shape):
                return False
    return True


def run_fleet_vs_single(n_workers: int, n_requests: int) -> dict:
    timed = _masks(n_requests, seed0=3000)
    warm = _masks(n_requests, seed0=9000)     # disjoint: warms compiles only
    cores = os.cpu_count() or 1

    cfg = ServiceConfig(bucket_sides=(RES,), max_batch=MAX_BATCH,
                        max_delay_ms=2.0)

    # ---- fleet arm first: router over n_workers subprocess workers. This
    # process stays off JAX's backend until the workers are gone, since on
    # a TPU host each worker owns a chip.
    worker_args = ["--buckets", str(RES), "--max-batch", str(MAX_BATCH),
                   "--max-delay-ms", "2.0", "--cache-entries", "1024"]
    sup = FleetSupervisor(n_workers, worker_args=worker_args)
    peer_hits = 0.0
    try:
        links = sup.start()
        router = FleetRouter(
            links,
            RouterConfig(bucket_sides=(RES,), max_batch=MAX_BATCH,
                         max_delay_ms=2.0, health_interval_s=3600.0),
            supervisor=sup)
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            client.wait_ready(timeout=180.0)
            list(client.analyze_batch(warm))
            fleet_s, fleet_items = _timed_batch(client, timed)

            # ---- peering leg: kill a mask's owner, reroute (survivor
            # caches it), restart the slot, repeat -> sibling-cache hit
            ring = HashRing([l.name for l in links])
            probe = timed[0]
            want_probe = serial.analyze_numpy(probe)
            owner = ring.node_for(routing_key(probe))
            sup._by_name[owner].process.kill()
            got = client.analyze(probe)                 # reroutes
            assert all(
                np.array_equal(np.asarray(want_probe[f]), got[f])
                for f in want_probe), "rerouted result not identical"
            asyncio.run_coroutine_threadsafe(
                router.check_workers(), rt._loop).result(timeout=300)
            client.analyze(probe)                       # restarted owner peers
            for line in client.metrics_text().splitlines():
                if line.startswith("ychg_cache_peer_hits_total "):
                    peer_hits = float(line.rsplit(" ", 1)[1])
    finally:
        sup.stop()

    # ---- single-process arm (its results are the identity bar)
    with YCHGService(Engine(), cfg) as svc, ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        list(client.analyze_batch(warm))
        single_s, single_items = _timed_batch(client, timed)
    want = [single_items[i].result for i in range(n_requests)]
    bit_identical = _identical(fleet_items, want)

    assert bit_identical, "fleet arm not bit-identical to single process"
    assert peer_hits > 0, "repeat traffic after restart never hit a sibling"

    ratio = round((n_requests / fleet_s) / (n_requests / single_s), 2)
    row = {
        "scenario": "fleet_vs_single",
        "n_requests": n_requests,
        "n_workers": n_workers,
        "cores": cores,
        "resolutions": [RES],
        "single_rps": round(n_requests / single_s, 1),
        "fleet_rps": round(n_requests / fleet_s, 1),
        "fleet_throughput_ratio": ratio,
        "bit_identical": bit_identical,
        "peer_hits": peer_hits,
    }
    if cores >= 4:
        assert ratio >= 2.0, (
            f"router over {n_workers} workers on {cores} cores only "
            f"{ratio}x a single process (bar: 2x)")
    else:
        row["note"] = (
            f"cpu_limited: {cores} core(s) — {n_workers} worker processes "
            "time-slice one CPU, so the >= 2x bar is asserted only on "
            ">= 4 cores; ratio recorded as measured")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args()
    enable_compile_cache()
    row = run_fleet_vs_single(args.workers, args.requests)
    print(json.dumps(row), flush=True)
    report = {
        "bench": "fleet_scaling",
        "platform": jax.default_backend(),
        "backend": Engine().resolve_backend(),
        "note": (
            "fleet_vs_single serves one pool of distinct masks through a "
            "single-process front end and through the fleet router over "
            f"{args.workers} subprocess workers (warm masks disjoint from "
            "timed masks; no timed mask pre-cached). Bit-identity and the "
            "sibling-cache (peering) leg are hard-asserted everywhere; the "
            ">= 2x throughput bar is asserted only when cores >= 4, "
            "recorded as measured (cpu_limited) otherwise."
        ),
        "scenarios": [row],
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} (1 scenario)")


if __name__ == "__main__":
    main()
