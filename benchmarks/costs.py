"""Compulsory bytes of the served kernels, and the table of peaks.

The byte count is copied from the program's ops/roofline.py
(`_c_rank_join_bm`: its compulsory `bytes`, not the XLA fusion-boundary
model), so that a later PR cannot move a roofline share's numerator. It
is fed REAL list lengths, never padded buckets or batch slots.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

NF = 17
ROW_BYTES = NF * 2 + 4 + 4       # int16 features + int32 flags + docid
ROW_BYTES_DEAD = ROW_BYTES + 1   # + the tombstone byte gathered per row


def peak(device_kind: str) -> dict:
    """Peaks of one chip by `device_kind`; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


def join_bitmap_bytes(r: int, partners: int, k: int = 128) -> float:
    """Bitmap-membership conjunction: the rare list's r rows once, two
    gathers (20 B) per row per partner, the top-k out."""
    return ROW_BYTES_DEAD * r + max(partners, 1) * 20 * r + 8 * k


def share_pct(least_s: float, measured_s: float, what: str) -> float:
    """A share of the roofline in percent; over 100 is a fault of the
    count, never clipped."""
    pct = 100.0 * least_s / measured_s
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.1f}% of the roofline — the bytes are counted "
            f"too high or the device time leaves out part of the work")
    return pct
