"""Median wall of one sort-merge join dispatch as the batcher stamps it,
issue to fetched (`kernel._rank_join_batch_packed_kernel`: one
observation per conjunction that rode it, so a wave of four counts four
times). Of the window's device answers (`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("kernel._rank_join_batch_packed_kernel")
