"""Operations and bytes that the exact algorithm needs for a kernel call,
from its shapes alone, whatever the implementation does, and the roofline
time they set against a device's peaks.

Operations count 2·D per query–candidate pair (a difference and a
multiply-add per dim).  Bytes count each candidate row and each query row
read once, as float32, plus the outputs: k float32 distances and k int32
ids per query row.
"""
from __future__ import annotations

import json
import os

F32 = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def device_peak(device_kind: str) -> dict:
    """The row of ``peaks.json`` for a device; a device missing from it is
    an error, not a default."""
    with open(PEAKS) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add its published figures with a source")
    return peaks[device_kind]


def scan_topk(rows: int, corpus_rows: int, dim: int, k: int):
    """The brute lane (``knn_topk``): every query row against every corpus
    row.  Returns (operations, bytes)."""
    flops = 2 * dim * rows * corpus_rows
    nbytes = F32 * dim * (rows + corpus_rows) + 2 * F32 * k * rows
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict):
    """The least time the device could take, and which bound sets it."""
    t_compute = flops / peak["flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (
        t_memory, "memory")
