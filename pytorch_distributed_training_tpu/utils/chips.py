"""One process per chip, decided without touching jax.

A TPU chip belongs to one process at a time: a second process that asks
libtpu for the same chip fails or hangs. The processes that SPAWN
chip-using children (the fleet coordinator, ``cli.launch``) are jax-free by
design — a parent that touched the backend would hold the chips itself — so
they count the host's chips the way jax does before it starts a backend
(PCI vendor/device ids) and hand each child its own chips through the
variables libtpu reads at start-up.
"""

from __future__ import annotations

import glob
import os

_GOOGLE_PCI_VENDOR_ID = "0x1ae0"
#: v3, v4, v5p, v5e, v6e, 7x (jax/_src/hardware_utils.py)
_TPU_PCI_DEVICE_IDS = (
    "0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076",
)


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def host_tpu_chips() -> int:
    """TPU chips attached to this host through PCI (0 = not a TPU host)."""
    return sum(
        1
        for vendor in glob.glob("/sys/bus/pci/devices/*/vendor")
        if _read(vendor) == _GOOGLE_PCI_VENDOR_ID
        and _read(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICE_IDS
    )


def chip_env(index: int) -> dict:
    """Environment that makes chip ``index`` of this host the only TPU one
    child process sees: an independent one-chip, one-process slice with its
    own runtime port (two such children ran side by side on a four-chip
    v5e host, PR 21). Means nothing to a backend other than libtpu."""
    port = 8476 + index
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
    }


def require_chips(what: str, processes: int, chips_each: int = 1) -> int:
    """Chips on this host, after refusing — with a message, before any
    child starts — a plan that would put two processes on one chip. Only
    one chip per child can be handed out: the two-chip layout
    (``TPU_CHIPS_PER_PROCESS_BOUNDS=1,2,1``) failed to start on the v5e
    host (PR 21), so a multi-chip child on a TPU host is refused too."""
    have = host_tpu_chips()
    if have and chips_each > 1:
        raise SystemExit(
            f"{what}: {chips_each} chips per process on a TPU host is not "
            f"supported (only one-chip children can be given chips of "
            f"their own); run the {chips_each}-chip program as ONE process"
        )
    if have and processes > have:
        raise SystemExit(
            f"{what}: {processes} process(es) need {processes} chips and "
            f"this host has {have}; a chip belongs to one process at a time"
        )
    return have
