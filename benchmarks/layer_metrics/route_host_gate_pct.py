"""Share of the window's searches that the host gate kept on the host:
the count of `search.route.host_gate` over the counts of all five
routes, each counted where the routing happens. At most one search per
client, finished after the close, is counted besides (`_spans`)."""

from ._spans import route_pct


def read(ctx):
    return route_pct("host_gate", "route_host_gate_pct")
