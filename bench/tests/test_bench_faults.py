"""``correct`` comes out false when the timed path is broken underneath.

Each case skips the harness's look for a chip and drives the rest of a
run on the CPU, at a size a test run can hold, with one fault planted in
the program: an answer altered where it is produced, half of a batch
left out, or a bulk step that returns its state unchanged. The sound run
of each cell must come out correct, and the control (the reference with
int8 counters in the program's place) must fail a number it compares.
"""

import argparse
import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import control  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
from repro.engine import Engine  # noqa: E402
from repro.scene import SceneRunner  # noqa: E402

SPEC = harness.load_spec()


def tiny(name: str) -> harness.Cell:
    cell = harness.resolve(SPEC, name)
    tr, cfg = dict(cell.traffic), dict(cell.config)
    if cell.kind == "bulk":
        tr.update(height=1000, width=640)
        cfg["bulk_job"] = dict(cfg["bulk_job"], tile_h=64)
    else:
        tr.update(height=300, width=260)
    return dataclasses.replace(cell, traffic=tr, config=cfg)


def outcome(name, tmp_path, capsys) -> dict:
    args = argparse.Namespace(seed=2**31 + 99, seconds=1.0, trace=0)
    run.run_cell(tiny(name), args, {"platform": "cpu", "kind": "none",
                                    "count": 1}, tmp_path)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def altered(monkeypatch):
    orig = Engine._run

    def run_(self, imgs, *, batched, op):
        out = orig(self, imgs, batched=batched, op=op)
        return dataclasses.replace(out, runs=out.runs.at[:, 0].add(1))
    monkeypatch.setattr(Engine, "_run", run_)


def half_batch(monkeypatch):
    orig = Engine._run

    def run_(self, imgs, *, batched, op):
        b = imgs.shape[0]
        return orig(self, imgs.at[b // 2:].set(0), batched=batched, op=op)
    monkeypatch.setattr(Engine, "_run", run_)


def state_unchanged(monkeypatch):
    monkeypatch.setattr(SceneRunner, "update",
                        lambda self, state, stack, runs_b: state)


@pytest.mark.parametrize("name,fault", [
    ("bulk.250m", None), ("bulk.250m", altered), ("bulk.250m", half_batch),
    ("bulk.250m", state_unchanged),
    ("scene.21k", None), ("scene.21k", altered),
])
def test_fault_makes_the_run_incorrect(name, fault, tmp_path, capsys,
                                       monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    out = outcome(name, tmp_path, capsys)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0


@pytest.mark.parametrize("name,size", [
    ("bulk.250m", (1015, 677)),
    ("scene.21k", (700, 700)),
])
def test_control_fails_a_compared_number(name, size):
    cell = harness.resolve(SPEC, name)
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, height=size[0], width=size[1]))
    got = control.readings(cell, seed=3, results=8)
    assert got["bad_results"] > 0, got
