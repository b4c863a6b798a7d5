"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default: a roofline share against a guessed peak is not a measurement."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {_TABLE} (known: {sorted(table)});"
            " add its published peaks with their source"
        )
    return table[device_kind]


def least_seconds(*, flops: float, bytes_moved: float, flops_peak: float,
                  bytes_peak: float) -> tuple[float, str]:
    """The roofline's least time for a call and which bound sets it."""
    by_compute = flops / flops_peak
    by_memory = bytes_moved / bytes_peak
    return (by_compute, "compute") if by_compute >= by_memory else (by_memory, "memory")
