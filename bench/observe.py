"""What a traced run saw, and the arithmetic the per-layer readers share.

A reader (bench/metrics/<metric>.py) gets one :class:`Observed` and
returns a number, or None when the run gave it nothing to read: a share
of a roofline is never reported as 0 for want of events.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Observed:
    spans: List[Tuple[str, float, float, dict]]
    trace: Optional[object]              # trace_reduce.DeviceTrace
    window_s: float                      # length of the traced window
    ychg_bytes: int                      # yCHG floor bytes of the window
    peaks: dict


def span_mean_ms(obs: Observed, name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans, in ms."""
    iv = [(a, b) for n, a, b, _ in obs.spans if n == name]
    if not iv:
        return None
    return 1e3 * sum(b - a for a, b in iv) / len(iv)


def ychg_floor_bytes(pixels: int, images: int, width: int) -> int:
    """Bytes yCHG has to move for ``images`` uint8 masks ``width`` wide
    holding ``pixels`` pixels in all, unpadded: the mask in, and the
    program's outputs out: runs, births and deaths (int32) and transitions
    (bool) per column, two int32 totals per image."""
    return pixels + images * (width * (4 + 4 + 4 + 1) + 8)


YCHG_PROGRAM = "jit_fused_analyze"


def ychg_roofline_pct(obs: Observed) -> Optional[float]:
    """Least time the window's yCHG work needs at peak HBM bandwidth, over
    the device time of the programs that ran it, in %."""
    if obs.trace is None or obs.ychg_bytes <= 0:
        return None
    device_s = obs.trace.module_s(YCHG_PROGRAM)
    if device_s <= 0:
        return None
    floor_s = obs.ychg_bytes / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / device_s


def idle_pct(obs: Observed) -> Optional[float]:
    if obs.trace is None or obs.window_s <= 0:
        return None
    return 100.0 * (1.0 - obs.trace.busy_s() / obs.window_s)
