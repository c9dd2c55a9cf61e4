"""Compulsory bytes of the sort-merge join kernel.

Copied from the program's ops/roofline.py (`_c_rank_join`: its
compulsory `comp`, not the XLA fusion-boundary model), beside
costs.join_bitmap_bytes and for the same reason: a later PR cannot move
a roofline share's numerator. Fed REAL list lengths, never the padded
windows (r, m) the kernel compiles for, nor batch slots.
"""

from __future__ import annotations

from benchmarks.costs import ROW_BYTES_DEAD


def join_sortmerge_bytes(r: int, ms, k: int = 128) -> float:
    """Sort-merge conjunction: the rare list's r rows once (43 B each);
    per partner of m rows 12 B of gathered columns per rare row and the
    partner's (docid, pos) pairs, 8 B each, streamed for the (r + m)
    sort; the top-k out."""
    ms = list(ms)
    if not ms:
        raise ValueError("a conjunction has at least one partner")
    return ROW_BYTES_DEAD * r + sum(12 * r + 8 * m for m in ms) + 8 * k
