"""Share of the device-eligible conjunctions sent in the window that the
mesh store declined to the host (`join_fallbacks`: a list in more than
one span, a RAM delta, a window the tables cannot cover, a lost mesh, a
failed transfer). The shape of `join_declined_pct`; 0 in a healthy run.
None where the store does not count it (the parent of the PR that gave
the mesh store the counter)."""

from ._join import device_conjunction
from ._shared import share_of


def read(ctx):
    c = ctx["counters"]
    if "join_fallbacks" not in c:
        return None
    sent = sum(device_conjunction(ctx, r[0]) for r in ctx["rows"])
    return share_of(c["join_fallbacks"], sent, "mesh_declined_pct")
