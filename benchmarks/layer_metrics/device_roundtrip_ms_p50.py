"""Median wall a request's thread spends in the batcher, enqueue to
result (`devstore.batch`: queue, issue, device, fetch, wake-up). Of the
window's device answers and at most one per client finished after its
close (`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("devstore.batch")
