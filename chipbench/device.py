"""The device a run is on, its published peaks, and its memory readings.

A run names the platform, ``device_kind`` and device count JAX reports.
A run that finds no TPU, or fewer chips than its cell asks for, or a
device that ``peaks.json`` does not list, stops with ``NoChip``: nothing
falls back to the CPU.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """The accelerator a cell needs is not there."""


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS.name}")
    return table[kind]


def require_chips(devices: list, chips: int) -> dict:
    """The peaks of the TPU the run found; ``NoChip`` if it found none,
    too few, or an unknown kind."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else None
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found {len(devices)}")
    return peaks_for(devices[0].device_kind)


def describe(devices: list, chips: int) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(chips, len(devices))}


def memory(dev) -> dict:
    """The allocator's readings (empty where the backend gives none)."""
    try:
        return dict(dev.memory_stats() or {})
    except Exception:               # backends without memory statistics
        return {}
