"""The chip's peaks, and the least work of each roofline'd operation.

A roofline share is the least time the chip could take for the work,
max(operations / peak FLOP/s, bytes / peak HBM bytes/s), over the time it
took.  The operations and bytes counted here are those of the work, not
of an implementation: one pass over the stored operand, and the products
the mathematics needs.  No implementation can read over 100% against them.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device) -> dict:
    """The peaks row of ``device.device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    kind = device.device_kind
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE}")
    return table[kind]


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def mu_iteration_work(*, k: int, r: int, stored_entries: int,
                      operand_bytes: int, chips: int = 1
                      ) -> tuple[float, float]:
    """(FLOPs, bytes) per chip of one MU iteration of an r-member unit.

    Bytes: one pass over the stored operand (dense m n^2 entries, or the
    BCSR blocks with their coordinates), shared by the members.  FLOPs:
    the two X-sized products of each member, X A and X^T A (R's update
    and A's use the same X A), at 2 FLOPs a product: 4 (stored entries,
    m included) k r.  The k-sized products are left out."""
    flops = 4.0 * stored_entries * k * r
    return flops / chips, operand_bytes / chips
