"""The harness: BENCHMARK.json resolves by name, data files are found
without code changes, a cell's inputs are a function of the seed, and
``bench/run.py`` refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = harness.load_spec()


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves_to_files_and_readers(workload):
    cell = harness.resolve(SPEC, workload)
    assert (BENCH / "drivers" / f"{cell.kind}.py").is_file()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]))


def test_every_end_to_end_metric_has_a_bound():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_a_dropped_in_traffic_file_and_reader_are_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    small = dict(json.loads((BENCH / "traffic" / "scene-21k.json").read_text()),
                 name="scene-8k", height=8192, width=8192)
    (tmp_path / "bench" / "traffic" / "scene-8k.json").write_text(
        json.dumps(small))
    (tmp_path / "bench" / "metrics" / "calls.small.py").write_text(
        "def read(obs):\n    return 1.0\n")
    spec["workloads"].append({"name": "scene.8k", "config": "modis-bulk",
                              "traffic": "scene-8k", "chips": 1,
                              "why": "a later cell"})
    spec["per_layer"].append({"name": "calls.small", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "kernels", "moves": "scene_mpx_s",
                              "workloads": ["scene.8k"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "scene.21k" in m["workloads"]:
            m["workloads"].append("scene.8k")
    cell = harness.resolve(spec, "scene.8k", bench=tmp_path / "bench")
    assert (cell.kind, cell.traffic["height"]) == ("scene", 8192)
    assert "calls.small" in [m["name"] for m in cell.per_layer]
    assert harness.load_reader("calls.small", tmp_path / "bench")(None) == 1.0


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_inputs_are_a_function_of_the_seed(workload):
    cell = harness.resolve(SPEC, workload)
    tr = dict(cell.traffic, height=96, width=80)
    make = harness.load_driver(cell.kind).make_inputs
    a, b, c = make(tr, 2**31 + 17), make(tr, 2**31 + 17), make(tr, 5)
    assert len(a) > 1 and all(x.shape == (96, 80) for x in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(x, y) for x, y in zip(a, c))
    # distinct inputs within a run, each near the stated coverage
    assert not np.array_equal(a[0], a[1])
    assert all(abs(x.mean() - tr["coverage"]) < 0.05 for x in a)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "scene.21k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    out = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {"PYTHONPATH": "", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")}
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
