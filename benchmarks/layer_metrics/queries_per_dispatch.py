"""Queries that rode a batch dispatch in the window (served by the
device store, less those its top-k cache answered) over the batcher's
dispatches."""


def read(ctx):
    c = ctx["counters"]
    n = c.get("batch_dispatches", 0)
    if n <= 0:
        return None
    rode = c.get("queries_served", 0) - c.get("rank_cache_hits", 0)
    if rode < 0:
        raise ValueError(f"queries_per_dispatch: {rode} queries rode")
    return rode / n
