"""From a ``jax.profiler`` trace to device busy time, op times and idle gaps.

Kept as code with the benchmark so that every run reduces a trace the same
way. The trace is the ``.xplane.pb`` the profiler writes; it is read with
``jax.profiler.ProfileData``. Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
executed HLO op (a Pallas kernel is one ``custom-call``) and the
``XLA Modules`` line one event per executed program, named after the
jitted function (``jit_<name>(<id>)``). Host planes (``/host:...``) hold
the runtime's and the frameworks's own events, which say what the host
was doing while the device waited.

Busy time is the union of the op intervals, so nested and overlapping
events count once; idle is the rest of the traced window.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end), seconds
Event = Tuple[str, float, float]        # (name, start, end), seconds

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals (touching ones are joined)."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b < a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(intervals: Iterable[Interval]) -> List[Interval]:
    """The holes between consecutive busy intervals, longest first."""
    m = merge(intervals)
    holes = [(m[i][1], m[i + 1][0]) for i in range(len(m) - 1)]
    return sorted(holes, key=lambda g: g[0] - g[1])


def totals(events: Iterable[Event]) -> Dict[str, float]:
    """Summed duration per event name."""
    out: Dict[str, float] = {}
    for name, a, b in events:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def module_base(name: str) -> str:
    """``jit_fused_analyze_pallas(42)`` -> ``jit_fused_analyze_pallas``."""
    return name.split("(", 1)[0]


def attribute(gap: Interval, host: Sequence[Event]) -> str:
    """What the host did in an idle gap: the host event that overlaps it
    most, the shorter one on a tie (the more specific of nested events)."""
    a, b = gap
    best, best_key = "no host event", (0.0, 0.0)
    for name, s, e in host:
        ov = min(b, e) - max(a, s)
        key = (ov, -(e - s))
        if ov > 0 and key > best_key:
            best, best_key = name, key
    return best


@dataclasses.dataclass
class DeviceTrace:
    """The events of one trace, on the profiler's clock, in seconds."""

    ops: Dict[str, List[Event]]       # device plane -> op events
    modules: Dict[str, List[Event]]   # device plane -> program events
    host: List[Event]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        per = [covered((a, b) for _, a, b in ev) for ev in self.ops.values() if ev]
        return sum(per) / len(per) if per else 0.0

    def module_s(self, prefix: str) -> float:
        """Device seconds of the programs whose jitted name starts with
        ``prefix``, averaged over devices: every op inside them counts."""
        per = [covered((a, b) for name, a, b in ev
                       if module_base(name).startswith(prefix))
               for ev in self.modules.values() if ev]
        return sum(per) / len(per) if per else 0.0

    def breakdown(self, k: int = 10) -> dict:
        """Top ``k`` device ops by time and the ``k`` longest idle gaps,
        each gap named by what the host was doing in it."""
        op_s: Dict[str, float] = {}
        for ev in self.ops.values():
            for name, sec in totals(ev).items():
                op_s[name] = op_s.get(name, 0.0) + sec
        top = sorted(op_s.items(), key=lambda kv: -kv[1])[:k]
        holes = []
        for ev in self.ops.values():
            holes += gaps((a, b) for _, a, b in ev)
        holes = sorted(holes, key=lambda g: g[0] - g[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[attribute(g, self.host), g[1] - g[0]]
                              for g in holes]}


def _events(line) -> List[Event]:
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def read(where: Path) -> DeviceTrace:
    """Load an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    where = Path(where)
    files = [where] if where.is_file() else sorted(
        where.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {where}")
    pd = ProfileData.from_file(str(files[-1]))
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            ops[plane.name] = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            modules[plane.name] = (_events(lines[MODULES_LINE])
                                   if MODULES_LINE in lines else [])
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host += _events(line)
    return DeviceTrace(ops, modules, host)
