"""Share of the window's device conjunctions that took at least one
sort-merge membership (`join_sm_served` over `join_served`): what the
traffic meets when the vocabulary outgrows the join-bitmap slots. None
where the program does not count it."""

from ._shared import share_of


def read(ctx):
    c = ctx["counters"]
    if "join_sm_served" not in c:
        return None
    return share_of(c["join_sm_served"], c.get("join_served", 0),
                    "join_sortmerge_pct")
