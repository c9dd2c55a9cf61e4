"""Compulsory bytes of the mesh store's SPMD conjunction, a chip.

A 1 x n mesh holds every list split by document over n doc columns
(docid % n), a chip a column. Whatever implements the join, a chip must
read its column's share of the rare list's rows once, its share of each
partner's docid-sorted join side table once, and write the fused page of
k rows. Kept with the benchmark, beside costs.join_bitmap_bytes and
costs_join.join_sortmerge_bytes and for the same reason: a later PR
cannot move a roofline share's numerator. Fed REAL list lengths, never
the padded windows (r, m) the program compiles for.
"""

from __future__ import annotations

from benchmarks.costs import ROW_BYTES_DEAD

JOIN_ENTRY_BYTES = 4 + 4        # a side-table entry: docid + row position


def mesh_join_bytes(r: int, ms, chips: int = 4, k: int = 128) -> float:
    """One chip's bytes for one conjunction: r / chips rare rows at the
    arena's row bytes (43: int16 features, flags, docid, the tombstone
    byte), per partner of m rows m / chips side-table entries (8 B),
    the k fused (score, docid) rows out."""
    ms = list(ms)
    if not ms:
        raise ValueError("a conjunction has at least one partner")
    if chips < 1:
        raise ValueError("a mesh has at least one chip")
    return (ROW_BYTES_DEAD * r + sum(JOIN_ENTRY_BYTES * m for m in ms)) \
        / chips + 8 * k
