"""What the readers of the sort-merge join share.

A conjunction takes the sort-merge kernel where a partner of its rare
list (the FIRST of its shortest lists, in the query's word order, as
`devstore._rank_join_impl` picks it) holds no join bitmap. In the
deployments whose vocabulary outgrows the slots the store hands them out
in load order (benchmarks/run.py loads corpus.layout's terms in order,
the configuration's `assumed.load_order`): the High lists numbered
`BITMAP_SLOTS` and up carry none.
"""

import re

from ._shared import device_query

BITMAP_SLOTS = 64       # DeviceArena.JOIN_BITMAP_SLOTS at 2.5M documents
_HIGH = re.compile(r"zh(\d+)$")


def device_conjunction(ctx, qi) -> bool:
    """Two lists or more, the shortest over the host gate."""
    return len(ctx["lengths"](qi)) >= 2 and device_query(ctx, qi)


def sortmerge_shapes(ctx, rows) -> list:
    """[(rare length, [partner lengths])] of the device-eligible
    conjunctions among `rows` that have a partner without a bitmap."""
    sent = {r[0]: q for r, q in zip(ctx["rows"], ctx["queries"])}
    out = []
    for r in rows:
        if not device_conjunction(ctx, r[0]):
            continue
        words, ls = sent[r[0]].split(), ctx["lengths"](r[0])
        rare = min(range(len(ls)), key=ls.__getitem__)
        partners = [i for i in range(len(ls)) if i != rare]
        if any((m := _HIGH.match(words[i])) and int(m.group(1))
               >= BITMAP_SLOTS for i in partners):
            out.append((ls[rare], [ls[i] for i in partners]))
    return out
