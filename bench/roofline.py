"""Peaks of the chip and the least work of the kernels the benchmark
rates against them.

``peaks.json`` holds the published peaks of each device kind, as JAX
reports ``device_kind``, with their source.  A kind that is not in the
table is an error: a share of an unknown peak means nothing.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


def expand_step_bytes(n: int, w: int, b: int, s: int) -> int:
    """Least HBM bytes of one expansion super-step, whatever implements
    it: the ``[n, w]`` 32-bit extension table, the ``n`` 32-bit vertex
    weights and the ``b`` dequeued ``s``-word states must each be read
    once.  The chip publishes no integer vector peak, so this byte count
    at the HBM bandwidth is the bound the step is rated against."""
    return 4 * (n * w + n + b * s)


def least_seconds(n_bytes: float, peaks: dict) -> float:
    return n_bytes / peaks["hbm_bytes_per_s"]
