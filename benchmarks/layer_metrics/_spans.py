"""What the readers of the program's own spans share.

benchmarks/run.py runs the node in its own process and calls
`histogram.reset_windows()` as the window opens, so after the window a
span family's windowed counts ARE the window's distribution (the ring
retains 180 s; the readers run well inside that). Three things are in a
family that are not the window's: the requests a client finishes after
the close (at most one per client), what the node records between the
reset and the first request (3 s, no request), and, for the `runtime.*`
families alone, what it records between the close and the reading.

Every function returns None where the program has no such family (the
parent of the PR that added it) or the family holds nothing: a reader
leaves its metric out, it never raises for that.
"""

from ._shared import share_of

ROUTES = ("event_cache", "topk_cache", "device", "host_gate", "host_other")


def family(name: str):
    """The node's windowed histogram of that name, or None."""
    try:
        from yacy_search_server_tpu.utils import histogram
    except ImportError:
        return None
    return histogram.get(name)


def count(name: str):
    h = family(name)
    return None if h is None else h.windowed_count()


def median_ms(name: str):
    """Median of the family over the window: the bucket that holds the
    middle value, interpolated linearly (as servlet_ms_p50 does)."""
    h = family(name)
    if h is None:
        return None
    from yacy_search_server_tpu.utils.histogram import BUCKET_BOUNDS_MS
    counts, bounds = h.windowed_counts(), BUCKET_BOUNDS_MS
    total = sum(counts)
    if total <= 0:
        return None
    rank, cum = total // 2, 0
    for i, c in enumerate(counts):
        if c > 0 and cum + c > rank:
            if i >= len(bounds):
                return bounds[-1]
            lo = bounds[i - 1] if i > 0 else 0.0
            return lo + (bounds[i] - lo) * ((rank - cum) + 0.5) / c
        cum += c
    return None


def route_counts():
    """{route: searches that took it in the window}, or None where the
    program counts no routes. A search takes exactly one
    (`search.route.<route>`, searchevent.py)."""
    got = {r: count("search.route." + r) for r in ROUTES}
    if all(v is None for v in got.values()):
        return None
    return {r: v or 0 for r, v in got.items()}


def route_pct(route: str, what: str):
    routes = route_counts()
    if routes is None:
        return None
    return share_of(routes[route], sum(routes.values()), what)


def spent_ms_per_s(names):
    """Time the families' spans took, per second of what their windows
    cover: sum over the families of windowed sum / covered seconds."""
    try:
        from yacy_search_server_tpu.utils import tracing
        tracing.flush_gc()      # collections queued since the last span
    except (ImportError, AttributeError):
        pass
    rate, seen = 0.0, False
    for name in names:
        h = family(name)
        if h is None or not hasattr(h, "windowed_sum"):
            continue
        span_s = h.windowed_span_s()
        if span_s > 0:
            rate += h.windowed_sum() / span_s
            seen = True
    return rate if seen else None
