"""The stencil's own work, whatever implements it, and its roofline bound.

Per call (per request, or per shard on a mesh): the useful flops are one
multiply and one add per tap, per point, per step; the least bytes are one
read and one write of the state.  Counting bytes per step, or the zeros of
a banded Toeplitz operand, would change with the implementation and read
over 100% once steps are fused.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def taps(config: dict) -> int:
    return int(np.count_nonzero(np.asarray(config["stencil"]["gather_coeffs"])))


def bytes_per_point(config: dict) -> int:
    return int(np.dtype(config["dtype"]).itemsize)


def call_work(config: dict, points: int, steps: int) -> tuple[int, int]:
    """``(flops, bytes)`` of advancing ``points`` grid points ``steps``
    steps in one call."""
    flops = 2 * taps(config) * int(points) * int(steps)
    nbytes = 2 * bytes_per_point(config) * int(points)
    return flops, nbytes


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(kinds)}")
    return kinds[device_kind]


def bound_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak binds."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
