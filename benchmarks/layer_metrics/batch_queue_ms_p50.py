"""Median wait of a query in the batcher, enqueue to the moment a
dispatcher takes its part (`batcher.queue`, devstore._QueryBatcher).
Of the window's device answers and at most one per client finished
after its close (`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("batcher.queue")
