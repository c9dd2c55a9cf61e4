"""The mesh store's join program's share of its roofline in the traced
slices, a chip.

Measured: device time of every executed program whose name holds
"_mesh_join_shard", as trace_reduce averages it over the device planes
(a chip's time: the SPMD program runs on every chip at once). Least
time: the compulsory bytes A CHIP reads for the conjunctions the store
served there over one chip's peak bandwidth: the mean, over the
device-eligible conjunctions sent in the traced slices, of
costs_mesh.mesh_join_bytes(real rare length, real partner lengths, the
cell's chips), times the store's `join_served` count over the same
slices. None where the program does not count that."""

from benchmarks import costs, costs_mesh

from ._mesh import JOIN_PROGRAM, cell_chips, conjunction_shapes
from ._shared import program_seconds


def read(ctx):
    if ctx["trace"] is None:
        return None
    seconds = program_seconds(ctx, JOIN_PROGRAM)
    served = ctx["trace_counters"].get("join_served", 0)
    chips = cell_chips(ctx)
    per_query = [costs_mesh.mesh_join_bytes(r, ms, chips)
                 for r, ms in conjunction_shapes(ctx, ctx["trace_rows"])]
    if seconds <= 0 or served <= 0 or not per_query:
        return None
    least = served * (sum(per_query) / len(per_query)) \
        / ctx["peak"]["bytes_per_s"]
    return costs.share_pct(least, seconds, "mesh_join_roofline")
