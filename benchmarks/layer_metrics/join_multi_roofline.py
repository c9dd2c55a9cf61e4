"""The bitmap join kernel's share of its roofline in the traced slices,
where every device conjunction has two partners or more.

Measured: device time of every executed program whose name holds
"rank_join_bm" (the trace's "XLA Modules" events). Least time: the
compulsory bytes of the multi-partner conjunctions the device served
there over the chip's peak bandwidth: the mean, over the device-eligible
conjunctions of three words or more sent in the traced slices, of
costs_multi.join_multi_bytes(real rare length, hits a partner), times
the store's `join_multi_served` count over the same slices. None where
the program does not count that. The device reader divides a program's
time by the number of `/device:*` planes, of which one is empty on a v5e
(PERF.md 7.17): this share reads twice what it is, as `join_roofline`
and `join_sm_roofline` do."""

from benchmarks import costs, costs_multi

from ._multi import multi_shapes
from ._shared import program_seconds


def read(ctx):
    if ctx["trace"] is None:
        return None
    seconds = program_seconds(ctx, "rank_join_bm")
    served = ctx["trace_counters"].get("join_multi_served", 0)
    per_query = [costs_multi.join_multi_bytes(r, hits)
                 for r, hits in multi_shapes(ctx, ctx["trace_rows"])]
    if seconds <= 0 or served <= 0 or not per_query:
        return None
    least = served * (sum(per_query) / len(per_query)) \
        / ctx["peak"]["bytes_per_s"]
    return costs.share_pct(least, seconds, "join_multi_roofline")
