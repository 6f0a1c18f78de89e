"""Bytes a mining job has to move, counted from the graph and the
reference alone (never from the program's padded shapes), and the peaks
of the chip they are divided by.

Expanding an embedding reads the adjacency list of each of its members,
4 bytes per neighbour id; a child that is expanded in turn is written
and read back, 4 bytes per vertex. Whatever implements the expansion has
to move at least that much, so the least time at the chip's memory
bandwidth over the measured device time is a share of the memory
roofline that no padding or layout change can push past 100 %.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes of one vertex id, as the adjacency lists and frontiers hold it
ID_BYTES = 4


def expansion_bytes(deg, parents) -> int:
    """``parents`` lists, level by level, the (count, k) vertex rows of
    every embedding that is expanded; the first level's rows are single
    vertices, which need no write. Returns the bytes the expansions
    have to move: each parent's members' adjacency lists, plus every
    parent of the second level on written and read once."""
    deg = np.asarray(deg, np.int64)
    total = 0
    for level, rows in enumerate(parents):
        rows = np.asarray(rows, np.int64)
        if not len(rows):
            continue
        total += int(deg[rows].sum()) * ID_BYTES
        if level > 0:
            total += rows.size * ID_BYTES
    return total


def load_peaks(device_kind: str, path: str = None) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; a kind that is
    not in the table is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(kinds)})"
        )
    return kinds[device_kind]
