"""Multi-op platform benchmark: per-op serving + compound pipeline.

Two scenario families, written to ``BENCH_ops.json``:

  **{op}_serving** (one row per registered op: ychg, ccl, denoise) —
  N distinct inputs served through the HTTP front end with
  ``POST /v1/{op}``, every wire result compared bit for bit against the
  op's in-repo jnp reference (``OpSpec.reference``) — the same parity
  bar the tests hold every backend to, re-checked here on the numbers
  the bench is about to publish. The row records throughput and the
  ``bit_identical`` verdict (hard-asserted: a bench that serves wrong
  answers fast is not a result).

  **pipeline_vs_sequential** — the payoff row. The SAME pool of
  speckled float images pushed through ``denoise -> ychg`` two ways:
  (a) two wire requests per image, the host feeding stage 1's filtered
  image back in for stage 2 (today's compose-by-hand path), and (b) one
  ``POST /v1/pipeline`` compound request per image, the stages chained
  device-resident by the engine. Both arms are warmed on a DISJOINT
  image set (rungs compile outside timing; no timed input pre-cached)
  and every compound result is compared bit for bit against its
  sequential twin.

  **Honesty about cores**: the compound path saves a host round trip
  and a second scheduler pass, not CPU work — on a core-starved box the
  timings are noise-dominated. The row records ``cores``
  (``os.cpu_count()``); the ``>= 1.0x`` acceptance bar is asserted only
  when ``cores >= 4`` — smaller boxes record the measured ratio with a
  ``cpu_limited`` note instead of a fake pass or a guaranteed failure.

Run:  PYTHONPATH=src python benchmarks/bench_ops.py [--out BENCH_ops.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.data import modis
from repro.engine import Engine
from repro.engine.ops import get_op, op_names
from repro.frontend import ServerThread, YCHGClient
from repro.launch.compilecache import enable_compile_cache
from repro.service import Service, ServiceConfig

RES = 64
MAX_BATCH = 8


def _mask_inputs(n: int, seed0: int) -> List[np.ndarray]:
    return [modis.snowfield(RES, seed=seed0 + i) for i in range(n)]


def _float_inputs(n: int, seed0: int) -> List[np.ndarray]:
    """Speckled smooth fields: the denoise stage has real outliers to
    strike and the filtered image still has structure for yCHG."""
    out = []
    for i in range(n):
        rng = np.random.default_rng(seed0 + i)
        yy, xx = np.mgrid[0:RES, 0:RES]
        img = np.maximum(
            0.0, 0.55 * np.sin(yy / 9.0) * np.cos(xx / 13.0) - 0.05
        ).astype(np.float32)
        spikes = rng.random(img.shape) < 0.02
        img[spikes] = rng.random(spikes.sum()).astype(np.float32) * 4.0
        out.append(img)
    return out


def _inputs(op: str, n: int, seed0: int) -> List[np.ndarray]:
    return (_float_inputs(n, seed0) if op == "denoise"
            else _mask_inputs(n, seed0))


def _host_equal(got: Dict[str, np.ndarray],
                want: Dict[str, np.ndarray]) -> bool:
    if set(got) != set(want):
        return False
    for field in want:
        a, b = np.asarray(want[field]), np.asarray(got[field])
        if not (np.array_equal(a, b) and a.dtype == b.dtype
                and a.shape == b.shape):
            return False
    return True


def run_op_serving(op: str, client: YCHGClient, n_requests: int) -> dict:
    spec = get_op(op)
    timed = _inputs(op, n_requests, seed0=3000)
    warm = _inputs(op, n_requests, seed0=9000)   # compiles only
    # the parity bar: single-request (batched=False) reference layout,
    # exactly what the wire hands back
    want = [spec.from_summary(spec.reference(jnp.asarray(x)[None]),
                              False).to_host()
            for x in timed]
    for x in warm:
        client.analyze(x, op=op)
    t0 = time.perf_counter()
    got = [client.analyze(x, op=op) for x in timed]
    dt = time.perf_counter() - t0
    bit_identical = all(_host_equal(g, w) for g, w in zip(got, want))
    assert bit_identical, f"{op}: wire results drifted from the reference"
    return {
        "scenario": f"{op}_serving",
        "op": op,
        "n_requests": n_requests,
        "resolutions": [RES],
        "rps": round(n_requests / dt, 1),
        "bit_identical": bit_identical,
    }


def run_pipeline_vs_sequential(client: YCHGClient, n_requests: int) -> dict:
    stages = ["denoise", "ychg"]
    timed = _float_inputs(n_requests, seed0=3000)
    warm = _float_inputs(n_requests, seed0=9000)
    cores = os.cpu_count() or 1

    def sequential(img: np.ndarray) -> Dict[str, np.ndarray]:
        filtered = client.analyze(img, op="denoise")
        return client.analyze(filtered["image"], op="ychg")

    # warm both arms (disjoint images: compiles land, no timed input cached)
    for img in warm:
        sequential(img)
        client.pipeline(img, stages)

    t0 = time.perf_counter()
    want = [sequential(img) for img in timed]
    sequential_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    got = [client.pipeline(img, stages) for img in timed]
    pipeline_s = time.perf_counter() - t0

    bit_identical = all(_host_equal(g, w) for g, w in zip(got, want))
    assert bit_identical, (
        "compound pipeline drifted from the stages issued sequentially")

    ratio = round((n_requests / pipeline_s) / (n_requests / sequential_s), 2)
    row = {
        "scenario": "pipeline_vs_sequential",
        "stages": stages,
        "n_requests": n_requests,
        "cores": cores,
        "resolutions": [RES],
        "sequential_rps": round(n_requests / sequential_s, 1),
        "pipeline_rps": round(n_requests / pipeline_s, 1),
        "pipeline_vs_sequential_ratio": ratio,
        "bit_identical": bit_identical,
    }
    if cores >= 4:
        assert ratio >= 1.0, (
            f"compound pipeline only {ratio}x the sequential arm on "
            f"{cores} cores (bar: 1x — it removes a host round trip, it "
            "must never be slower)")
    else:
        row["note"] = (
            f"cpu_limited: {cores} core(s) — timings noise-dominated, so "
            "the >= 1x bar is asserted only on >= 4 cores; ratio recorded "
            "as measured")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_ops.json")
    ap.add_argument("--requests", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ServiceConfig(bucket_sides=(RES,), max_batch=MAX_BATCH,
                        max_delay_ms=2.0)
    rows = []
    with Service(Engine(), cfg) as svc, ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        for op in sorted(op_names()):
            rows.append(run_op_serving(op, client, args.requests))
            print(json.dumps(rows[-1]), flush=True)
        rows.append(run_pipeline_vs_sequential(client, args.requests))
        print(json.dumps(rows[-1]), flush=True)

    report = {
        "bench": "multi_op_platform",
        "platform": jax.default_backend(),
        "backend": Engine().resolve_backend(),
        "note": (
            "per-op serving rows hold every wire result to the op's jnp "
            "reference (bit-identical, hard-asserted); "
            "pipeline_vs_sequential pushes the same image pool through "
            "denoise->ychg as two wire requests per image and as one "
            "compound POST /v1/pipeline request (warm images disjoint "
            "from timed; compound results compared bit for bit against "
            "their sequential twins). The >= 1x throughput bar is "
            "asserted only when cores >= 4, recorded as measured "
            "(cpu_limited) otherwise."
        ),
        "scenarios": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(rows)} scenarios)")


if __name__ == "__main__":
    main()
