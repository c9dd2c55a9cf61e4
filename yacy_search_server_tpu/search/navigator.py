"""Navigators — facet counters over the result candidate set.

Capability equivalent of the reference's navigator plugin registry
(reference: source/net/yacy/search/navigator/ — RestrictedStringNavigator,
HostNavigator, LanguageNavigator, YearNavigator, ...; assembled by
NavigatorPlugins.java and accumulated per result in
SearchEvent.java:1131+). Each navigator is a score map keyed by a facet
value; the UI renders the top entries as refinement links.
"""

from __future__ import annotations

import datetime

from ..index.metadata import split_multi
from ..utils.scoremap import ScoreMap

DEFAULT_NAVIGATORS = ("hosts", "language", "filetype", "authors", "year",
                      "dates")


class Navigator:
    """One facet dimension: counts of facet values over seen results."""

    def __init__(self, name: str, field: str):
        self.name = name
        self.field = field
        self.counts = ScoreMap()

    def add(self, value) -> None:
        if value is None:
            return
        v = str(value).strip()
        if v:
            self.counts.inc(v)

    def top(self, n: int = 10) -> list[tuple[str, int]]:
        return self.counts.top(n)

    def __len__(self) -> int:
        return len(self.counts)


def make_navigators(names=DEFAULT_NAVIGATORS) -> dict[str, Navigator]:
    fields = {
        "hosts": "host_s",
        "language": "language_s",
        "filetype": "url_file_ext_s",
        "authors": "author",
        "year": "last_modified_days_i",
        "collections": "collection_sxt",
        # dates mentioned IN the content (reference: DateNavigator over
        # dates_in_content_dts), distinct from the `year` modified-date facet
        "dates": "dates_in_content_dts",
    }
    return {n: Navigator(n, fields[n]) for n in names if n in fields}


_EPOCH_ORDINAL = datetime.date(1970, 1, 1).toordinal()


def _add_value(nav: Navigator, v) -> None:
    if nav.name == "year" and v:
        v = datetime.date.fromordinal(_EPOCH_ORDINAL + int(v)).year
    if nav.name == "dates" and v:
        for date in split_multi(str(v)):
            nav.add(date)
        return
    nav.add(v)


def accumulate(navigators: dict[str, Navigator], meta) -> None:
    """Count one result document into every active navigator."""
    for nav in navigators.values():
        _add_value(nav, meta.get(nav.field))


def accumulate_batch(navigators: dict[str, Navigator], cols: dict,
                     alive) -> None:
    """Count a CANDIDATE SET into every navigator from the columns one
    MetadataStore.rows_at gather read (`cols[nav.field]`, index for
    index with `alive`; a dead candidate counts nowhere)."""
    for nav in navigators.values():
        for ok, v in zip(alive, cols[nav.field]):
            if ok and v != "":          # an empty text counts nowhere
                _add_value(nav, v)
