"""Include partners a device conjunction joined its rare list with, the
mean over the window's served joins (`join_partners` over `join_served`):
1 where every conjunction has two words, 2.5 where half have three and
half four. None where the program does not count partners."""


def read(ctx):
    c = ctx["counters"]
    if "join_partners" not in c or not c.get("join_served"):
        return None
    return c["join_partners"] / c["join_served"]
