"""Share of the window's searches that a live search event answered:
the count of `search.route.event_cache` (SearchEventCache.get_event)
over the counts of all five routes — a count, where
`event_cache_hit_pct` is a difference of two. At most one search per
client, finished after the close, is counted besides (`_spans`)."""

from ._spans import route_pct


def read(ctx):
    return route_pct("event_cache", "route_event_cache_pct")
