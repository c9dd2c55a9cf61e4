"""The join kernels' share of their roofline in the traced slices of
the cell `wiki.full` (10M documents, a 2^26-row arena).

Measured: device time of every executed program whose name holds
"rank_join" (the bitmap and the sort-merge kernel). Least time: the
compulsory bytes of the conjunctions the device served there over the
chip's peak bandwidth. Of the device-eligible conjunctions sent in the
traced slices, those whose partners all hold a join bitmap count the
mean of costs.join_bitmap_bytes(real rare length, partners) times the
store's `join_served` less `join_sm_served` over the same slices; the
others the mean of costs_join.join_sortmerge_bytes(real rare length,
partner lengths) times `join_sm_served`. Which partner holds a bitmap
follows from the join bitmaps the arena handed out (the event tracker's
`join_bitmap_slots`: 32 at 10M documents, where `_join.BITMAP_SLOTS`
says 64 for 2.5M): the store hands them out in load order to the lists
of `BITMAP_MIN_ROWS` or more. The device
reader divides a program's time by the number of `/device:*` planes, of
which one is empty on a v5e (PERF.md 7.17): this share reads twice what
it is, as `join_roofline` does. None where the program does not count
the bitmaps it hands out (no `arena_grow` events either)."""

import json
import os

from benchmarks import corpus, costs, costs_join

from ._arena import index_items
from ._join import device_conjunction
from ._shared import program_seconds

BITMAP_MIN_ROWS = 65_536    # DeviceSegmentStore.JOIN_BITMAP_MIN


def slot_holders(ctx, slots: int) -> set:
    """Names of the lists that hold a join bitmap: the first `slots` of
    bitmap size in load order (corpus.layout's, as run.py loads)."""
    with open(os.path.join(corpus.HERE, "workloads",
                           ctx["workload"] + ".json"),
              encoding="utf-8") as f:
        c = corpus.load_config(json.load(f)["config"])["corpus"]
    names = [f"z{tier[0]}{i}" for tier in corpus.TIERS
             for i in range(int(c["tiers"][tier]["lists"]))
             if int(c["tiers"][tier]["length"]) >= BITMAP_MIN_ROWS]
    return set(names[:slots])


def conjunction_bytes(ctx, rows, holders: set) -> tuple[list, list]:
    """Compulsory bytes of the device-eligible conjunctions among `rows`
    (the rare list is the first of the shortest, as
    devstore._rank_join_impl picks it): (every partner on a bitmap, a
    partner on the sort-merge)."""
    sent = {r[0]: q for r, q in zip(ctx["rows"], ctx["queries"])}
    bitmap, sortmerge = [], []
    for r in rows:
        if not device_conjunction(ctx, r[0]):
            continue
        words, ls = sent[r[0]].split(), ctx["lengths"](r[0])
        rare = min(range(len(ls)), key=ls.__getitem__)
        partners = [i for i in range(len(ls)) if i != rare]
        if all(words[i] in holders for i in partners):
            bitmap.append(costs.join_bitmap_bytes(ls[rare], len(partners)))
        else:
            sortmerge.append(costs_join.join_sortmerge_bytes(
                ls[rare], [ls[i] for i in partners]))
    return bitmap, sortmerge


def read(ctx):
    if ctx["trace"] is None or index_items("arena_grow") is None:
        return None     # a program that does not count its bitmaps
    slots = index_items("join_bitmap_slots") or 0
    seconds = program_seconds(ctx, "rank_join")
    served = ctx["trace_counters"].get("join_served", 0)
    served_sm = ctx["trace_counters"].get("join_sm_served", 0)
    bitmap, sortmerge = conjunction_bytes(
        ctx, ctx["trace_rows"], slot_holders(ctx, slots))
    least = 0.0
    for n, shapes in ((served - served_sm, bitmap), (served_sm, sortmerge)):
        if n and not shapes:
            return None      # served, and no conjunction of that mode sent
        if n:
            least += n * sum(shapes) / len(shapes)
    if seconds <= 0 or least <= 0:
        return None
    return costs.share_pct(least / ctx["peak"]["bytes_per_s"], seconds,
                           "full_join_roofline")
