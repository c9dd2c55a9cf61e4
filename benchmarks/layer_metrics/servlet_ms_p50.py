"""Median of the node's own `servlet.serving` histogram over the window
(host clock inside the server): the bucket that holds the middle
request, interpolated linearly."""


def read(ctx):
    counts, bounds = ctx["servlet_counts"], ctx["servlet_bounds_ms"]
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
