"""The accelerator a run uses, its peak memory, and the table of peaks
(``benchmark/peaks.json``, keyed by ``device_kind``)."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices; raises NoAccelerator."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def peaks(kind: str, path: str = PEAKS) -> dict:
    """The published peaks of ``kind``; a kind missing from the table is
    an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]
