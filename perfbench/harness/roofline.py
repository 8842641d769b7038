"""Peaks of the devices this benchmark may run on, and the bytes a kernel
must move, computed from a cell's shapes.  A device that is not in
`peaks.json` is an error, never a default."""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "peaks.json")


def peak(device_kind: str, what: str) -> float:
    with open(_PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"(it has {sorted(table)}); add its published peaks with their source"
        )
    return float(table[device_kind][what])


def round_trip_bytes(shapes: dict) -> float:
    """Bytes one trip of the round kernel's placement loop must read and
    write, whatever its layout: to place one job it has to look at every
    node's allocatable amount of each resource the world uses at one priority
    level (f32) and at the node's schedulable flag (one byte), and at each
    queue's allocation to pick the queue (f32 per resource); it writes one
    node's row and one queue's row back.  Operations per byte are a handful
    of compares and adds, far under the chip's ratio, so bytes bound it."""
    n, q, r = shapes["nodes"], shapes["queues"], shapes["resources"]
    reads = n * (4 * r + 1) + q * 4 * r
    writes = 4 * r + 4 * r
    return float(reads + writes)
