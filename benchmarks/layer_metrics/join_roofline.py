"""The join kernels' share of their roofline in the traced slices.

Measured: device time of every executed program whose name holds
"rank_join" (the trace's "XLA Modules" events). Least time: the
compulsory bytes of the conjunctions the device served there over the
chip's peak bandwidth. The bytes are the benchmark's own: the mean, over
the device-eligible conjunctions sent in the traced slices, of
costs.join_bitmap_bytes(real rare length, partners) — every list of
65,536 rows or more holds a join bitmap in these deployments — times the
store's `join_served` count over the same slices."""

from benchmarks import costs

from ._shared import device_query, program_seconds


def read(ctx):
    if ctx["trace"] is None:
        return None
    seconds = program_seconds(ctx, "rank_join")
    served = ctx["trace_counters"].get("join_served", 0)
    per_query = []
    for r in ctx["trace_rows"]:
        ls = ctx["lengths"](r[0])
        if len(ls) >= 2 and device_query(ctx, r[0]):
            per_query.append(costs.join_bitmap_bytes(min(ls), len(ls) - 1))
    if seconds <= 0 or served <= 0 or not per_query:
        return None
    least = served * (sum(per_query) / len(per_query)) \
        / ctx["peak"]["bytes_per_s"]
    return costs.share_pct(least, seconds, "join_roofline")
