"""Work counted from shapes: FLOPs and bytes of the kernels the per-layer
metrics divide by device time, and the table of chip peaks.

Every count here is a lower bound on what any implementation must do, so a
share of a peak or of a roofline computed from it cannot pass 100 % unless
the time leaves out part of the work.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time of (flops, bytes) on the chip and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ------------------------------------------------------------- scheduler

def collection_kernel(k: int, n: int, m: int) -> tuple[float, float]:
    """Skew-aware collection (P1') for K slices of N x M: the (N, M) f32
    log-weights read once and the (N, M) f32 alpha written once; one
    operation per weight. (The greedy itself does O(N^2 M) work; this is the
    floor no implementation can go under.)"""
    elems = k * n * m
    return float(elems), float(8 * elems)
