"""The chip a run stands on: its description, and its peaks by kind."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int):
    """The TPU devices this run uses; raises ``NoAccelerator`` when JAX finds
    no TPU or fewer than ``chips`` of them.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r}); nothing was measured")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]
