"""The chip a run is on: the platform gate, device facts, memory, peaks."""

from __future__ import annotations

import json
import os

import jax

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The platform every measured run must be on.  Only the CPU tests of the
# harness set it to "cpu"; nothing else does, and nothing falls back.
PLATFORM = "tpu"


class NoChip(SystemExit):
    """Raised (exit code 3) when JAX finds no accelerator or too few."""

    def __init__(self, msg: str):
        super().__init__(3)
        self.msg = msg


def require(chips: int) -> list:
    """The first ``chips`` devices, or :class:`NoChip` when JAX finds
    another platform than :data:`PLATFORM` or fewer devices."""
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise NoChip(f"needs a {PLATFORM.upper()}; JAX found {len(devs)} "
                     f"{devs[0].platform!r} device(s) ({devs[0].device_kind})"
                     f"; nothing was run")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak_bytes(devices)}


def peaks(kind: str) -> dict:
    """The published peaks of ``kind`` from ``peaks.json``; a device that
    is not in the table is an error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]
