"""Scene-scale streaming benchmark: stitched strips vs the whole scene.

Two scenario families, written to ``BENCH_scene.json``:

  **scene_stitch** — one synthetic granule analysed three ways: (a)
  whole-scene, one ``engine.analyze`` call on the full (H, W) mask (the
  ceiling when the scene *fits*); (b) streamed, ``SceneRunner`` over
  ``tile_h``-row strips in stacks of ``stack_tiles`` with exact seam
  stitching — the only arm that works when the scene does not fit; (c)
  per-tile-naive, one ``engine.analyze`` call per strip (what tiling
  costs without batching). Records Mpx/s for all three, the
  stitched/whole and stitched/naive ratios, and a ``bit_identical``
  verdict comparing every stitched field against the whole-scene arm —
  the number that makes the speed numbers mean anything.

  **checkpoint_overhead** — the same manifest run as a ``BulkJob`` twice:
  checkpointing every stack (the paranoid setting) vs only at granule
  boundaries. Records the elapsed ratio as
  ``checkpoint_overhead_fraction`` — the price of kill-anywhere
  resumability at its most aggressive — plus a ``resume_bit_identical``
  verdict from an interrupt-and-resume pass compared byte-for-byte
  against the uninterrupted output files.

  Both gated ratios are *same-box relative* (stitched vs naive, per-stack
  checkpointing vs none), so they hold on any machine; ``cores`` is
  recorded for context, and a recording made under pathological
  conditions can carry a ``cpu_limited`` note the gate honours instead of
  its ratio bars (``check_bench_regression.py --scene``). The
  ``bit_identical`` / ``resume_bit_identical`` verdicts have no escape.

Run:  PYTHONPATH=src python benchmarks/bench_scene.py [--out BENCH_scene.json]
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time
from typing import List

import numpy as np

import jax

from repro.data import scenes
from repro.engine import Engine
from repro.launch.compilecache import enable_compile_cache
from repro.scene import (
    BulkJob,
    BulkJobConfig,
    GranuleReader,
    SceneRunner,
    synthetic_manifest,
)


def _best_of(n: int, fn) -> float:
    """Best wall time of n calls — rewards steady state, tolerates noise."""
    return min(_timed(fn) for _ in range(n))


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _identical(got: dict, want: dict) -> bool:
    for field, arr in want.items():
        a, b = np.asarray(arr), np.asarray(got[field])
        if not (np.array_equal(a, b) and a.dtype == b.dtype
                and a.shape == b.shape):
            return False
    return True


def run_scene_stitch(height: int, width: int, tile_h: int,
                     stack_tiles: int, repeats: int) -> dict:
    engine = Engine()
    mask = scenes.scene(height, width, seed=42, cell=64)
    reader = GranuleReader.from_array(mask, tile_h, granule_id="bench")
    runner = SceneRunner(engine, stack_tiles=stack_tiles)
    px = height * width

    def whole():
        # to_host() so the timing includes materialising the result, like
        # the stitched/naive arms do (asynchronous dispatch would
        # otherwise flatter this arm enormously)
        return engine.analyze(mask).to_host()

    def streamed():
        return runner.analyze_scene(reader)

    def naive():
        # one device call per strip, stitched the same exact way
        state = None
        from repro.scene import SceneState
        state = SceneState.fresh(reader.width)
        for t in range(reader.n_tiles):
            tile = reader.read_tile(t)
            res = engine.analyze(tile)
            runner.update(state, tile[None], np.asarray(res.runs))
        return runner.finalize(reader, state)

    whole(), streamed(), naive()          # warmup: compile all three shapes
    t_whole = _best_of(repeats, whole)
    t_stream = _best_of(repeats, streamed)
    t_naive = _best_of(repeats, naive)
    bit_identical = _identical(streamed().to_host(), whole())
    return {
        "scenario": "scene_stitch",
        "height": height,
        "width": width,
        "tile_h": tile_h,
        "stack_tiles": stack_tiles,
        "n_tiles": reader.n_tiles,
        "cores": os.cpu_count() or 1,
        "whole_scene_mpx_s": round(px / t_whole / 1e6, 1),
        "stitched_mpx_s": round(px / t_stream / 1e6, 1),
        "per_tile_naive_mpx_s": round(px / t_naive / 1e6, 1),
        "stitched_vs_whole_ratio": round(t_whole / t_stream, 3),
        "stitched_vs_naive_ratio": round(t_naive / t_stream, 3),
        "bit_identical": bool(bit_identical),
    }


def run_checkpoint_overhead(height: int, width: int, tile_h: int,
                            stack_tiles: int, n_granules: int) -> dict:
    engine = Engine()
    manifest = synthetic_manifest(n_granules, height, width, seed=7,
                                  cell=64)
    px = n_granules * height * width

    def run_job(tmp: str, tag: str, every: int, **kw) -> "tuple":
        job = BulkJob(engine, manifest, BulkJobConfig(
            out_dir=os.path.join(tmp, tag, "out"),
            ckpt_dir=os.path.join(tmp, tag, "ckpt"),
            tile_h=tile_h, stack_tiles=stack_tiles,
            checkpoint_every=every))
        return job, job.run(**kw)

    with tempfile.TemporaryDirectory() as tmp:
        run_job(tmp, "warm", 10**9)       # warmup: compile the stack shape
        _, r_none = run_job(tmp, "none", 10**9)   # boundary ckpts only
        _, r_every = run_job(tmp, "every", 1)     # ckpt per stack
        overhead = (r_every.elapsed_s - r_none.elapsed_s) / r_none.elapsed_s

        # resume verdict: interrupt the per-stack job mid-manifest,
        # resume, and compare output bytes against the "none" arm
        kill_job, first = run_job(tmp, "kill", 1,
                                  max_stacks=max(1, r_every.stacks_done // 2))
        _, second = run_job(tmp, "kill", 1)
        resume_ok = (first.status == "interrupted" and second.completed
                     and second.resumes == 1)
        if resume_ok:
            for spec in manifest:
                a = os.path.join(tmp, "none", "out",
                                 f"{spec.granule_id}.ychg")
                b = kill_job.output_path(spec)
                with open(a, "rb") as fa, open(b, "rb") as fb:
                    if fa.read() != fb.read():
                        resume_ok = False
                        break
    return {
        "scenario": "checkpoint_overhead",
        "n_granules": n_granules,
        "height": height,
        "width": width,
        "tile_h": tile_h,
        "stack_tiles": stack_tiles,
        "cores": os.cpu_count() or 1,
        "no_ckpt_mpx_s": round(px / r_none.elapsed_s / 1e6, 1),
        "ckpt_every_stack_mpx_s": round(px / r_every.elapsed_s / 1e6, 1),
        "checkpoint_overhead_fraction": round(max(0.0, overhead), 3),
        "resume_bit_identical": bool(resume_ok),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_scene.json")
    ap.add_argument("--height", type=int, default=8192)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--tile-h", type=int, default=512)
    ap.add_argument("--stack", type=int, default=4)
    ap.add_argument("--granules", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    scenarios: List[dict] = []
    print(f"scene_stitch: {args.height}x{args.width}, tile_h {args.tile_h}, "
          f"stacks of {args.stack} [{jax.default_backend()}]", flush=True)
    row = run_scene_stitch(args.height, args.width, args.tile_h,
                           args.stack, args.repeats)
    scenarios.append(row)
    print(f"  whole {row['whole_scene_mpx_s']} Mpx/s, stitched "
          f"{row['stitched_mpx_s']} Mpx/s "
          f"({row['stitched_vs_whole_ratio']}x whole, "
          f"{row['stitched_vs_naive_ratio']}x naive), naive "
          f"{row['per_tile_naive_mpx_s']} Mpx/s, "
          f"bit_identical={row['bit_identical']}", flush=True)

    print(f"checkpoint_overhead: {args.granules} granules of "
          f"{args.height}x{args.width}", flush=True)
    row = run_checkpoint_overhead(args.height, args.width, args.tile_h,
                                  args.stack, args.granules)
    scenarios.append(row)
    print(f"  no-ckpt {row['no_ckpt_mpx_s']} Mpx/s, per-stack ckpt "
          f"{row['ckpt_every_stack_mpx_s']} Mpx/s (overhead "
          f"{row['checkpoint_overhead_fraction']:.1%}), "
          f"resume_bit_identical={row['resume_bit_identical']}", flush=True)

    report = {
        "bench": "scene_streaming",
        "platform": jax.default_backend(),
        "backend": "auto",
        "note": (
            "scene_stitch analyses one synthetic granule whole, streamed "
            "(SceneRunner strips + exact seam stitching), and "
            "per-tile-naive; bit_identical compares every stitched field "
            "against the whole-scene call. checkpoint_overhead runs the "
            "same manifest checkpointing every stack vs boundaries only, "
            "and proves interrupt->resume writes byte-identical outputs. "
            "Gated ratios are same-box relative; bit-identity verdicts "
            "have no escape hatch."
        ),
        "scenarios": scenarios,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
