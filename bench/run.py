#!/usr/bin/env python3
"""Run one benchmark cell on the TPU and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell's configuration, traffic mix and
per-layer metrics are found by name from ``BENCHMARK.json`` (see
``bench/harness.py``); the traffic file's ``kind`` picks the driver.
Each run sets up (data from ``--seed``, every shape the cell uses warmed),
measures for ``--seconds``, checks every answer of the window against the
NumPy reference, and prints one JSON line last on stdout:
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from the program's spans and a device trace of the
window. Earlier lines on stderr give the device, the set-up's compiles,
compiles inside the window (there should be none) and the numbers
compared for ``correct`` with their limits, last.

It exits nonzero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def tpu_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        harness.log(f"bench: JAX gives {len(devs)} {devs[0].platform} "
                    f"device(s); this cell needs {chips} TPU chip(s)")
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_spec(), args.workload)
    # JAX reads the directory from the environment; a fixed path inside
    # the checkout otherwise, so a second run finds every program
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(BENCH.parent / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = tpu_device(cell.chips)
    harness.log(f"bench: {cell.name} on {device['count']} x {device['kind']}; "
                f"config {cell.config['name']}, traffic {cell.traffic['name']}")

    tmp = Path(tempfile.mkdtemp(prefix="bench_"))
    try:
        run_cell(cell, args, device, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_cell(cell, args, device: dict, tmp: Path) -> None:
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), tmp=tmp,
                      clock=harness.CompileClock(), t_start=T_START)
    out = harness.load_driver(cell.kind).run(ctx)
    device = dict(device, memory_peak_bytes=out.window.memory_peak_bytes)
    per_layer = breakdown = None
    if ctx.trace:
        import observe
        import trace_reduce

        t0, t1 = ctx.profile_window
        trace = trace_reduce.read(ctx.profile_dir)
        device.update(busy_s=trace.busy_s(), window_s=t1 - t0)
        obs = observe.Observed(spans=out.window.spans, trace=trace,
                               window_s=t1 - t0, ychg_bytes=out.ychg_bytes,
                               peaks=observe.peaks(device["kind"]))
        per_layer = {m["name"]: harness.load_reader(m["name"])(obs)
                     for m in cell.per_layer}
        breakdown = trace.breakdown()
    for name, (value, limit) in out.checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr,
              flush=True)
    print(harness.result_line(cell, out, device, per_layer, breakdown),
          flush=True)


if __name__ == "__main__":
    main()
