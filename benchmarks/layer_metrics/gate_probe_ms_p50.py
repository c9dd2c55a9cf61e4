"""Median wall of a host join that probed two lists or more (the family
`search.join.multiprobe`, recorded where `search.join` is): a question
under the host gate whose rare list drives and whose longer lists are
looked up at its documents. Of the window's requests (`_spans`); None
where the program has no such family."""

from ._spans import median_ms


def read(ctx):
    return median_ms("search.join.multiprobe")
