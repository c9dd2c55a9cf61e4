"""Share of the ingested rows that are on the device: rows of runs
packed into the arena (`devstore_pack`) over those and the rows of runs
the arena's budget refused (`devstore_skip`), which stay host-served.
The configuration `lucene-wikimedium10m-full` guarantees 100. None where
the program packed nothing."""

from ._arena import index_items
from ._shared import share_of


def read(ctx):
    packed = index_items("devstore_pack")
    if packed is None:
        return None
    return share_of(packed, packed + (index_items("devstore_skip") or 0),
                    "arena_resident_pct")
