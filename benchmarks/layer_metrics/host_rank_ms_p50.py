"""Median wall of the ranking stage of the searches the host gate kept
(`search.route.host_gate`: term_search, constraint mask and the NumPy
ranker, searchevent.py) — the program's own clock over what
`host_gate_ms_p50` sees from the client. Of the window's requests and at
most one per client finished after its close (`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("search.route.host_gate")
