"""Share of the device-eligible conjunctions sent in the window that the
store declined to the host (`join_fallbacks`: a list in more than one
span, a partner window the join table cannot cover, a lost device). The
guarantees make a host fallback a thing to report; 0 in a healthy
run."""

from ._join import device_conjunction
from ._shared import share_of


def read(ctx):
    c = ctx["counters"]
    if "join_fallbacks" not in c:
        return None
    sent = sum(device_conjunction(ctx, r[0]) for r in ctx["rows"])
    return share_of(c["join_fallbacks"], sent, "join_declined_pct")
