"""Share of the device's busy time in the traced slices that collective
operations took (all-gather, all-reduce, collective-permute, all-to-all,
reduce-scatter, by the operation of each "XLA Ops" event): what the
fusion of the four columns' answers costs where nothing hides it. Both
times are trace_reduce's averages over the same device planes. None
without a trace or where the trace carries no operations."""

from ._mesh import is_collective
from ._shared import share_of


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["by_op"]:
        return None
    took = sum(s for name, s in tr["by_op"].items() if is_collective(name))
    return share_of(took, tr["busy_s"], "mesh_collective_pct")
