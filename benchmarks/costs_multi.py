"""Compulsory bytes of a bitmap-membership conjunction with two partners
or more (the cell `wiki.multi`).

Whatever implements the join, the chip must read the rare list's rows
once; for each partner it must look every rare row up in the partner's
membership table (one interleaved bitmap word and rank prefix, without
which the partner's row cannot be found), and read the partner's joined
columns (row position, first position, hit count, flags) at the rows the
partner holds; and write the page of k rows. Kept with the benchmark,
beside costs.join_bitmap_bytes, costs_join.join_sortmerge_bytes and
costs_mesh.mesh_join_bytes and for the same reason: a later PR cannot
move a roofline share's numerator. Fed REAL list lengths and the hits
they give, never the padded window the program compiles for, nor batch
slots.
"""

from __future__ import annotations

from benchmarks.costs import ROW_BYTES_DEAD

BITMAP_ENTRY_BYTES = 4 + 4          # a bitmap word + its rank prefix
JOINED_BYTES = 4 + 2 + 2 + 4        # jpos, posintext, hitcount, flags


def join_multi_bytes(r: int, hits, k: int = 128) -> float:
    """One conjunction: r rare rows at the arena's row bytes (43: int16
    features, flags, docid, the tombstone byte); per partner r membership
    entries (8 B) and the joined columns (12 B) at the `hits` rare rows
    that partner holds (one number a partner); the k (score, docid) rows
    out."""
    hits = list(hits)
    if len(hits) < 2:
        raise ValueError("a multi-partner conjunction has two partners "
                         "or more")
    if any(not 0 <= h <= r for h in hits):
        raise ValueError("a partner holds at most every rare row")
    return ROW_BYTES_DEAD * r + sum(
        BITMAP_ENTRY_BYTES * r + JOINED_BYTES * h for h in hits) + 8 * k
