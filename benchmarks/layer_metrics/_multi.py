"""What the readers of the multi-partner join (cell `wiki.multi`) share."""

import json
import os

from benchmarks import corpus

from ._mesh import conjunction_shapes


def densities(ctx) -> dict:
    """{list length: share of its window's documents a list of that
    length holds}, from the configuration of the cell (a High list's
    window is the corpus; a tier is known by its length). A list holds a
    row of another list's window with that probability: the lists of one
    question cover one topic."""
    with open(os.path.join(corpus.HERE, "workloads",
                           ctx["workload"] + ".json"),
              encoding="utf-8") as f:
        c = corpus.load_config(json.load(f)["config"])["corpus"]
    out = {s["length"]: s["length"] / (s["window"] or c["docs"])
           for s in c["tiers"].values()}
    if len(out) != len(c["tiers"]):
        raise ValueError("two tiers of one length: a partner's tier "
                         "cannot be told from its length")
    return out


def multi_shapes(ctx, rows) -> list:
    """[(rare length, [hits a partner])] of the device-eligible
    conjunctions among `rows` with two partners or more (the rare list
    as `conjunction_shapes` picks it); a partner's hits are the rare
    rows it is expected to hold."""
    shapes = [(r, ms) for r, ms in conjunction_shapes(ctx, rows)
              if len(ms) >= 2]
    if not shapes:
        return []
    dens = densities(ctx)
    return [(r, [r * dens[m] for m in ms]) for r, ms in shapes]
