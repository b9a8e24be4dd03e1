"""The program's stages and named kernels, read from a device trace.

Every live span of the program (``repro.obs``) is also a
``jax.profiler`` annotation of the same name, so its stages lie on the
host planes of the trace, on the clock of the device ops: ``engine.*``
(put, dispatch, fetch) and ``scene.*`` (read, compute, sync, stitch,
checkpoint, write). The yCHG kernels carry stable ``pallas_call`` names,
which head their ``XLA Ops`` events (``%ychg_fused_full.1 = ...``).

Each reader returns None where the trace holds nothing to read: a
program without the annotations or the kernel names gives no number.
"""

from __future__ import annotations

from typing import List, Optional

from observe import YCHG_PROGRAM, Observed
from trace_reduce import Interval, covered, merge, module_base

STAGE_PREFIXES = ("engine.", "scene.")
YCHG_KERNEL = "ychg_fused"


def is_kernel(op_name: str, kernel: str = YCHG_KERNEL) -> bool:
    """An ``XLA Ops`` event of the named kernel (its HLO text starts with
    the instruction's name, which the kernel's name heads)."""
    return op_name.lstrip("%").startswith(kernel)


def stage_intervals(obs: Observed) -> List[Interval]:
    return [(a, b) for name, a, b in obs.trace.host
            if name.startswith(STAGE_PREFIXES)]


def kernel_ms(obs: Observed, kernel: str = YCHG_KERNEL) -> Optional[float]:
    """Device ms of one launch of ``kernel``: its events' total over
    their count, on every device."""
    if obs.trace is None:
        return None
    ev = [b - a for ops in obs.trace.ops.values()
          for name, a, b in ops if is_kernel(name, kernel)]
    if not ev:
        return None
    return 1e3 * sum(ev) / len(ev)


def prep_ms(obs: Observed, program: str = YCHG_PROGRAM,
            kernel: str = YCHG_KERNEL) -> Optional[float]:
    """Device ms a launch of the ``program*`` programs spends outside
    ``kernel``: the casts, layout copies and pads around it."""
    if obs.trace is None:
        return None
    rest, launches = 0.0, 0
    for plane, mods in obs.trace.modules.items():
        runs = [(a, b) for name, a, b in mods
                if module_base(name).startswith(program)]
        kern = [(a, b) for name, a, b in obs.trace.ops.get(plane, [])
                if is_kernel(name, kernel)]
        if runs and kern:
            rest += covered(runs) - covered(kern)
            launches += len(runs)
    if not launches:
        return None
    return 1e3 * rest / launches


def idle_outside_stages_pct(obs: Observed) -> Optional[float]:
    """Share of the window, in %, in which no device op runs and the host
    is inside no stage: the gaps between the first and the last device
    op that no stage covers, averaged over the devices that ran."""
    if obs.trace is None or obs.window_s <= 0:
        return None
    stages = stage_intervals(obs)
    if not stages:
        return None
    per = []
    for ops in obs.trace.ops.values():
        busy = merge((a, b) for _, a, b in ops)
        if not busy:
            continue
        d0, d1 = busy[0][0], busy[-1][1]
        named = [(max(a, d0), min(b, d1)) for a, b in stages
                 if b > d0 and a < d1]
        per.append((d1 - d0) - covered(busy + named))
    if not per:
        return None
    return 100.0 * (sum(per) / len(per)) / obs.window_s
