"""Share of the window's device-eligible requests (shortest list over
the shipped host gate) that never reached the device store: the
search-event cache answered them. The program counts no event-cache hits,
so this is the difference of two exact counts: the requests the benchmark
sent that the gate lets through, less those the store counted
(`queries_served`, and `fallbacks` / `join_fallbacks`, which it declined).
A request in flight when the window closes is sent but not yet counted:
at most one per client, and the reading is that much too high."""

from ._shared import device_query, share_of


def read(ctx):
    eligible = sum(1 for r in ctx["rows"] if device_query(ctx, r[0]))
    c = ctx["counters"]
    reached = c.get("queries_served", 0) + c.get("fallbacks", 0) \
        + c.get("join_fallbacks", 0)
    return share_of(eligible - reached, eligible, "event_cache_hit_pct")
