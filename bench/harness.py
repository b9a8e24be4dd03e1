"""Shared plumbing of the benchmark: files by name, the chip, the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found here by the
name ``BENCHMARK.json`` gives it:

  configuration  the ``file`` of its ``configs`` entry (bench/configs/);
  traffic mix    bench/traffic/<traffic>.json, whose ``kind`` names the
                 driver that runs it (bench/drivers/<kind>.py);
  metric         bench/metrics/<metric>.py, whose ``read(observed)``
                 returns a number or None.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import resource
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
T_IMPORT = time.monotonic()


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def resolve(spec: dict, name: str, bench: Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((bench.parent / cfg_entry["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer)


def load_driver(kind: str, bench: Path = BENCH):
    return _load_module(bench / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def load_reader(metric: str, bench: Path = BENCH) -> Callable:
    mod = _load_module(bench / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def log(msg: str) -> None:
    """A progress line on stderr, stamped with seconds since start-up."""
    print(f"[{time.monotonic() - T_IMPORT:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


class CompileClock:
    """Seconds and count of XLA backend compiles in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self) -> Tuple[float, int]:
        return self.seconds, self.count


@dataclasses.dataclass
class Window:
    """One measured window. The driver sets ``t1``, its close, and appends
    to ``marks`` the instant each unit of work (a stack, a call) is done;
    the rest is filled in when the window closes."""

    t0: float
    t1: float = 0.0
    marks: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    spans: List[Tuple[str, float, float, dict]] = dataclasses.field(
        default_factory=list)
    memory_peak_bytes: int = 0


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell, the run's arguments, and tools."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    tmp: Path
    clock: Any
    t_start: float
    profile_dir: Optional[Path] = None
    profile_window: Tuple[float, float] = (0.0, 0.0)

    @contextlib.contextmanager
    def window(self) -> Iterator[Window]:
        """Bracket the measured window.

        In a traced run the device trace and the program's span capture
        run over it. On close: the compiles inside it, the spans inside
        [t0, t1], the memory peak, and on stderr the host's CPU seconds and
        the work done in each fifth of the window, which tell a slow host
        (more CPU seconds for the same work) from a slow stretch.
        """
        win = Window(time.monotonic())
        if self.trace:
            self._profile_start()
        count0 = self.clock.count
        use0 = resource.getrusage(resource.RUSAGE_SELF)
        try:
            yield win
        finally:
            use1 = resource.getrusage(resource.RUSAGE_SELF)
            if self.trace:
                self._profile_stop()
        win.t1 = win.t1 or time.monotonic()
        win.compiles = self.clock.count - count0
        win.spans = window_spans(win.t0, win.t1) if self.trace else []
        win.memory_peak_bytes = memory_peak(self.cell.chips)
        log(f"bench: compiles inside the window: {win.compiles}")
        log(f"bench: host CPU in the window: "
            f"user {use1.ru_utime - use0.ru_utime:.3f}s, "
            f"sys {use1.ru_stime - use0.ru_stime:.3f}s")
        if win.marks:
            fifth = (win.t1 - win.t0) / 5
            per = np.bincount(np.minimum(
                ((np.asarray(win.marks) - win.t0) / fifth).astype(int), 4),
                minlength=5)
            log(f"bench: work done in each fifth of the window: {per.tolist()}")

    def _profile_start(self) -> None:
        import jax
        from repro import obs

        obs.configure(capacity=1 << 20)
        obs.recorder().clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.profile_dir = self.tmp / "profile"
        jax.profiler.start_trace(str(self.profile_dir), profiler_options=opts)
        self.profile_window = (time.monotonic(), 0.0)

    def _profile_stop(self) -> None:
        import jax

        self.profile_window = (self.profile_window[0], time.monotonic())
        jax.profiler.stop_trace()


def window_spans(t0: float, t1: float) -> List[Tuple[str, float, float, dict]]:
    """Spans of the program's flight recorder that lie inside [t0, t1]."""
    from repro import obs

    return [(name, a, b, meta)
            for tr in obs.recorder().traces()
            for name, a, b, meta in tr.spans() if a >= t0 and b <= t1]


@dataclasses.dataclass
class Outcome:
    """What a driver returns.

    ``metrics`` holds the cell's end-to-end values by name (``setup_s``
    included); ``checks`` the numbers compared for ``correct``, each with
    its limit (a run is correct when no number is above its limit);
    ``ychg_bytes`` the bytes the window's yCHG work had to move, from the
    unpadded inputs (``observe.ychg_floor_bytes``).
    """

    metrics: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]
    window: Window
    ychg_bytes: int = 0


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def result_line(cell: Cell, out: Outcome, device: dict,
                per_layer: Optional[Dict[str, float]] = None,
                breakdown: Optional[dict] = None) -> str:
    """The JSON result; the compared numbers come last, under ``checks``."""
    if per_layer is None:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = out.metrics
    else:
        names = {m["name"]: m["unit"] for m in cell.per_layer}
        values = per_layer
    metrics = {k: {"value": values[k], "unit": u}
               for k, u in names.items() if values.get(k) is not None}
    line = {
        "correct": all(v <= lim for v, lim in out.checks.values()),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return json.dumps(line)
