"""Median wall of `SearchEvent.results()` (`search.page`: the metadata
join of what the page still lacks, the page, its snippets). Of the
window's requests and at most one per client finished after its close
(`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("search.page")
