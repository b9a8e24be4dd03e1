"""One fleet worker per TPU chip.

A chip belongs to one process at a time, and a process that starts JAX's
TPU runtime claims every chip it can see. So the supervisor counts the
host's chips without starting the runtime (the parent never claims one),
refuses a fleet larger than that count, and starts each worker with the
libtpu settings that make exactly one chip visible to it.
"""

from __future__ import annotations

import glob
import os
import socket
from pathlib import Path
from typing import Dict

_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v4, v5p, v5e, v6e, 7x); other Google
# devices on a TPU host (NICs, disks) share the vendor id
_TPU_PCI_DEVICES = {"0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}


def _host_has_tpu() -> bool:
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        dev = Path(vendor).with_name("device")
        if (Path(vendor).read_text().strip() == _GOOGLE_PCI_VENDOR
                and dev.read_text().strip() in _TPU_PCI_DEVICES):
            return True
    return False


def host_tpu_chips() -> int:
    """TPU chips this machine can open, counted without loading the TPU
    runtime; 0 where ``JAX_PLATFORMS`` keeps JAX off the TPU.

    The PCI bus may list every chip of the physical host while the
    machine is given only some of them, so the count is of the device
    nodes it can open: ``/dev/accel*`` (v4 and older) or the numbered
    ``/dev/vfio`` groups (v5e and newer, one per chip)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    if not _host_has_tpu():
        return 0
    vfio = [p for p in glob.glob("/dev/vfio/*") if Path(p).name.isdigit()]
    return len(glob.glob("/dev/accel*")) + len(vfio)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pinned_env(chip: int) -> Dict[str, str]:
    """Environment that pins one worker process to TPU chip ``chip``: a
    one-chip, one-process slice with its own runtime port. On a v5e host
    four such processes run at once, each seeing one device, without
    lifting libtpu's one-process lock."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(_free_port()),
    }
