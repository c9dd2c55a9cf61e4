"""What the readers of the device arena share (cell `wiki.full`, and
`arena_fill_pct` in every one-chip cell).

The node is stopped before the readers run, and a reader's context
holds the window's counter deltas only, so they read the process-wide
registries the program writes: the event tracker's cumulative totals of
the INDEX class (`devstore_pack` / `devstore_skip`: rows of runs packed
into the arena and rows of runs its budget refused; `arena_grow`: rows
allocated to arenas, the first allocation and every growth;
`join_bitmap_slots`: join bitmaps handed out) and the span family
`arena.grow`. In a cell, which loads one arena once and packs nothing
inside the window, these are the arena's figures at the window's start.
A program without one of them (the parent of the PR that added them)
gives None there: the reader leaves its metric out.
"""


def index_items(label: str):
    """Items of the event tracker's INDEX series `label` since the
    process started, or None where the program never wrote it."""
    try:
        from yacy_search_server_tpu.utils import eventtracker
    except ImportError:
        return None
    got = eventtracker.totals().get((eventtracker.EClass.INDEX, label))
    return None if got is None else got[1]


def grow_seconds():
    """Seconds the arena's row buffers spent growing since the process
    started (the `arena.grow` family's cumulative sum: set-up, where the
    load grows them), or None where the program has no such family."""
    try:
        from yacy_search_server_tpu.utils import histogram
    except ImportError:
        return None
    h = histogram.get("arena.grow")
    return None if h is None else h.snapshot()["sum_ms"] / 1000.0
