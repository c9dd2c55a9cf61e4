"""Share of the arena's capacity that its rows use: the rows packed into
it (`devstore_pack`) over the rows allocated to it (`arena_grow`), as
the load left them for the window. Growth doubles the capacity, so a
fill under 50% is padding the admission never asked for. None where the
program does not track its allocation."""

from ._arena import index_items
from ._shared import share_of


def read(ctx):
    used, cap = index_items("devstore_pack"), index_items("arena_grow")
    if used is None or cap is None:
        return None
    return share_of(used, cap, "arena_fill_pct")
