"""What the readers of the mesh store (cell `mesh4.tasks`) share."""

import json
import os
import re

from ._join import device_conjunction

JOIN_PROGRAM = "_mesh_join_shard"       # the jitted shard_map body's name
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")
# an "XLA Ops" event of a TPU plane is named by its HLO text,
# "%pmax.14 = s32[17]{0:T(128)S(1)} all-reduce(s32[17]{...} %x), ...":
# the operation is the word before the first "(" that follows a space
# (layouts write "T(128)" after ":" or ")", tuples "(s32[" after "= ")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_NUMBERED = re.compile(r"\.\d+$")


def operation(event_name: str) -> str:
    """"%all-gather.1 = s32[4,1,128]{...} all-gather(s32[..." ->
    "all-gather"; a plain name ("all-reduce.3") loses its number."""
    if " = " in event_name:
        m = _OPCODE.search(event_name.split(" = ", 1)[1])
        if m is not None:
            return m.group(1)
    return _NUMBERED.sub("", event_name.lstrip("%"))


def is_collective(event_name: str) -> bool:
    """The operation itself, or the -start / -done half of an
    asynchronous one."""
    return operation(event_name).startswith(COLLECTIVES)


def cell_chips(ctx) -> int:
    """The chips BENCHMARK.json gives the cell: the doc columns of its
    1 x n mesh."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    return int(cells[ctx["workload"]]["chips"])


def conjunction_shapes(ctx, rows) -> list:
    """[(rare length, [partner lengths])] of the device-eligible
    conjunctions among `rows`: the rare list is the first of the
    shortest, as `MeshSegmentStore._rank_join_impl` picks it."""
    out = []
    for r in rows:
        if device_conjunction(ctx, r[0]):
            ls = ctx["lengths"](r[0])
            rare = min(range(len(ls)), key=ls.__getitem__)
            out.append((ls[rare],
                        [m for i, m in enumerate(ls) if i != rare]))
    return out
