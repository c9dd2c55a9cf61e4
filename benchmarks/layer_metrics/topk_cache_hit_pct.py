"""Share of the window's requests that the device store's top-k cache
answered (its `rank_cache_hits` counter). The search-event cache in front
of it has the metric `event_cache_hit_pct`."""

from ._shared import share_of


def read(ctx):
    return share_of(ctx["counters"].get("rank_cache_hits", 0),
                    ctx["attempted"], "topk_cache_hit_pct")
