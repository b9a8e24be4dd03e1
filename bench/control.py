#!/usr/bin/env python3
"""The control of ``correct``: the reference with int8 counters in the
program's place, judged as a run's answers are judged.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

The configurations state exact int32 counts; the control narrows every
counter to int8 (``reference.analyze(mask, np.int8)``), the step below
that a kernel keeping its int8 input type would take. For each seed it
builds the inputs a run of the cell builds, with the driver's own
``make_inputs`` on the default JAX device, lets the control answer them,
and prints the number a run compares, which must come out above its
limit of 0: ``bad_results`` over ``results`` results (a run's granule
files and scene calls cycle the distinct inputs).

The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402

CONTROL_ACC = np.int8


def differs(mask: np.ndarray) -> bool:
    return not reference.same(reference.analyze(mask, CONTROL_ACC),
                              reference.analyze(mask))


def readings(cell: harness.Cell, seed: int, results: int = 100) -> dict:
    inputs = harness.load_driver(cell.kind).make_inputs(cell.traffic, seed)
    bad = [differs(x) for x in inputs]
    return {"bad_results": sum(bad[j % len(bad)] for j in range(results)),
            "results": results}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = harness.resolve(harness.load_spec(), args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **readings(cell, seed)}), flush=True)


if __name__ == "__main__":
    main()
