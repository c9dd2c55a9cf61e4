"""Share of the rare list's rows still live after a device join's first
membership (`join_survivors` over `join_compact_rows`, the window's
joins of two memberships or more): what the later memberships run over
since the join intersects progressively. About 1/8 where the first
partner is a regular term of the question's topic. None where the
program does not count it."""

from ._shared import share_of


def read(ctx):
    c = ctx["counters"]
    if "join_survivors" not in c or "join_compact_rows" not in c:
        return None
    return share_of(c["join_survivors"], c["join_compact_rows"],
                    "join_survivor_pct")
