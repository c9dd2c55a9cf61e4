"""Median wall of one join dispatch with two partners or more as the
batcher stamps it, issue to fetched (the family `kernel.join_multi`,
recorded where `kernel.<name>` is: one observation per conjunction that
rode the dispatch). Of the window's device answers (`_spans`); None
where the program has no such family."""

from ._spans import median_ms


def read(ctx):
    return median_ms("kernel.join_multi")
