"""The sort-merge join kernel's share of its roofline in the traced
slices.

Measured: device time of every executed program whose name holds
"rank_join_batch_packed" (the bitmap kernel's holds "rank_join_bm_" and
does not match). Least time: the compulsory bytes of the conjunctions it
served there over the chip's peak bandwidth: the mean, over the
device-eligible conjunctions sent in the traced slices whose partner
holds no bitmap, of costs_join.join_sortmerge_bytes(real rare length,
real partner lengths), times the store's `join_sm_served` count over
the same slices. None where the program does not count that."""

from benchmarks import costs, costs_join

from ._join import sortmerge_shapes
from ._shared import program_seconds


def read(ctx):
    if ctx["trace"] is None:
        return None
    seconds = program_seconds(ctx, "rank_join_batch_packed")
    served = ctx["trace_counters"].get("join_sm_served", 0)
    per_query = [costs_join.join_sortmerge_bytes(r, ms)
                 for r, ms in sortmerge_shapes(ctx, ctx["trace_rows"])]
    if seconds <= 0 or served <= 0 or not per_query:
        return None
    least = served * (sum(per_query) / len(per_query)) \
        / ctx["peak"]["bytes_per_s"]
    return costs.share_pct(least, seconds, "join_sm_roofline")
