"""Logical work of a configuration's GEMMs and the least time a chip
could spend on it.

Work is counted from the published GEMM table (``network.logical_gemms``),
never from the layout the program executes, so it reads the same
whatever implements it:

  * operations: 2 * M * K * D * count per image;
  * bytes: the 4-bit activation and weight codes held as one byte each,
    and float32 outputs; weights are read once per call;
  * sampled noise: no bytes.

Least time of a call = sum over GEMMs of max(ops / peak ops, bytes /
peak bandwidth), each GEMM bounded on its own.
"""
from __future__ import annotations

import os
from typing import List

from bench import network

PEAKS_FILE = os.path.join(network.BENCH_DIR, "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device not in the
    table is an error, never a default."""
    table = network.load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table {PEAKS_FILE}")
    return table[device_kind]


def gemm_ops(g: network.Gemm, images: int) -> float:
    return 2.0 * images * g.m * g.k * g.d * g.count


def gemm_bytes(g: network.Gemm, images: int) -> float:
    x = images * g.m * g.k * g.count
    w = g.k * g.d * g.count
    out = 4 * images * g.m * g.d * g.count
    return float(x + w + out)


def ops_per_image(gemms: List[network.Gemm]) -> float:
    return sum(gemm_ops(g, 1) for g in gemms)


def least_time_s(gemms: List[network.Gemm], images: int,
                 peak: dict) -> float:
    """Least time of one call on ``images`` images."""
    return sum(max(gemm_ops(g, images) / peak["ops_per_s"],
                   gemm_bytes(g, images) / peak["hbm_bytes_per_s"])
               for g in gemms)
