"""Bulk cells: ``BulkJob`` over a manifest of memory-mapped granules.

Set-up makes ``distinct_granules`` seeded granules on the device
(``make_inputs``, which the control reads too), writes them as ``.npy``
files, runs one granule through a throwaway job (which
compiles the stack program and warms the result and checkpoint writers),
and builds a manifest that cycles the files under distinct ids, long
enough that the window never runs out of work.

The window is one ``BulkJob.run`` with ``should_stop`` true once
``--seconds`` have passed; the job checkpoints and returns at the next
stack boundary. End-to-end: ``mpx_s``, megapixels of granule rows folded
into the job's state by then, over the seconds until that stop. Every
granule whose strips were all folded is due as a result file: each is
read back and compared with the NumPy reference of its granule. The job
folds every stack it analyses before it polls ``should_stop``, so the
rows folded are the yCHG work of the window, without the zero rows that
pad a granule's last strip.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

import harness
import masks
import observe
import reference

# granule pixels a second of window can need: some 30x the rate one chip
# reaches, so the manifest never runs out
PX_PER_S = 2e10


def make_inputs(traffic: dict, seed: int) -> list:
    """The cell's distinct granules, (height, width) uint8 host arrays."""
    return [masks.device_scene(seed * 7919 + i, traffic["height"],
                               traffic["width"], traffic["coverage"],
                               traffic["octaves"])
            for i in range(traffic["distinct_granules"])]


def write_granules(granules: list, where: Path) -> list:
    paths = []
    for i, g in enumerate(granules):
        path = where / f"granule_{i}.npy"
        np.save(path, g)
        # on disk before the window: otherwise the kernel writes these
        # pages back some 30 s later, in the middle of the window
        with open(path, "rb+") as fh:
            os.fsync(fh.fileno())
        paths.append(str(path))
    return paths


def run(ctx) -> harness.Outcome:
    from repro.engine import Engine, YCHGConfig
    from repro.scene import (BulkJob, BulkJobConfig, GranuleSpec,
                             read_scene_result)

    cell = ctx.cell
    tr = cell.traffic
    h, w = tr["height"], tr["width"]
    job_cfg = cell.config["bulk_job"]
    granules = make_inputs(tr, ctx.seed)
    paths = write_granules(granules, ctx.tmp)
    harness.log(f"bench: {len(granules)} granules made and written")
    engine = Engine(YCHGConfig(**cell.config.get("engine", {})))

    def spec(j: int) -> GranuleSpec:
        return GranuleSpec(f"g{j:06d}", h, w, kind="memmap",
                           path=paths[j % len(paths)])

    n_tiles = -(-h // job_cfg["tile_h"])
    BulkJob(engine, [spec(0)], BulkJobConfig(
        out_dir=str(ctx.tmp / "warm_out"), ckpt_dir=str(ctx.tmp / "warm_ckpt"),
        **job_cfg)).run(max_stacks=-(-n_tiles // job_cfg["stack_tiles"]))
    n_manifest = int(PX_PER_S * ctx.seconds / (h * w)) + 8
    job = BulkJob(engine, [spec(j) for j in range(n_manifest)], BulkJobConfig(
        out_dir=str(ctx.tmp / "out"), ckpt_dir=str(ctx.tmp / "ckpt"),
        **job_cfg))
    harness.log(f"bench: backend {engine.resolve_backend()}; "
                f"{len(granules)} granules of {h}x{w}; set-up compiles "
                f"{ctx.clock.count} in {ctx.clock.seconds:.1f}s")

    with ctx.window() as win:
        setup_s = win.t0 - ctx.t_start

        def should_stop() -> bool:
            t = time.monotonic()
            win.marks.append(t)
            if t - win.t0 >= ctx.seconds:
                win.t1 = t
                return True
            return False

        report = job.run(should_stop=should_stop)
    if report.status != "interrupted":
        raise RuntimeError(f"the manifest of {n_manifest} granules ran out "
                           f"inside the window")
    harness.log(f"bench: {report.granules_done} granules and "
                f"{report.tiles_done} strips in {win.t1 - win.t0:.3f}s")

    full, rest = divmod(report.tiles_done, n_tiles)
    rows = full * h + min(rest * job_cfg["tile_h"], h)

    # every granule whose strips were all folded is due as a result file
    wants = [reference.analyze(g) for g in granules]
    wrong = missing = 0
    for j in range(full):
        path = job.output_path(spec(j))
        if not Path(path).is_file():
            missing += 1
        elif not reference.same(read_scene_result(path).to_host(),
                                wants[j % len(wants)]):
            wrong += 1
    harness.log(f"bench: {wrong} wrong and {missing} missing results of "
                f"{full}")
    return harness.Outcome(
        metrics={"mpx_s": rows * w / 1e6 / (win.t1 - win.t0),
                 "setup_s": setup_s},
        attempted=full, failed=wrong + missing,
        checks={"bad_results": (wrong + missing, 0)}, window=win,
        ychg_bytes=observe.ychg_floor_bytes(rows * w, report.tiles_done, w))
