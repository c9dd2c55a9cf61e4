"""The corpus of one deployment, made from (configuration, seed).

Pure NumPy, imports nothing of the program: the harness loads these lists
into the node, the load generator's children draw their queries from the
layout, and the plain reference regenerates the same lists after the
window. One term's list depends only on (seed, term index), so the
reference builds just the lists its sample needs.

Tiers are keyed to the system's own tile (32,768 rows): a High list
samples the whole corpus, a Med list a contiguous window of the corpus,
a Low list a sub-window inside one Med window. (Med window, Low
sub-window) is a "topic": lists that cover one topic intersect in a page
of results or more, as the terms of a real question do.

Title documents ("stars", `corpus.stars` of the configuration): in every
Med window a few documents carry the word in their title (appearance
flag 25, the ranking profile's heaviest coefficient) in every High and
Med list that holds them. Within one list their rows are alike but for
the domain length, which is the document's own and differs from star to
star. So the page of every query over High and Med lists is made of
title documents, as a search box's is, in an order that no two equal
rankings and no extreme of the term frequency can touch (PERF.md section
4 says why that matters on the chip).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# posting feature columns (the row format of the index under test:
# WordReferenceRow's attributes, one int column each)
NF = 17
F_WORDS_IN_TITLE, F_WORDS_IN_TEXT, F_HITCOUNT = 1, 2, 11
F_FLAGS, F_LANGUAGE, F_DOMLENGTH = 10, 5, 16
FLAG_IN_TITLE = 1 << 25         # WordReferenceRow.flag_app_dc_title
LANG_EN = (ord("e") << 8) | ord("n")
TIERS = ("high", "med", "low")


@dataclass(frozen=True)
class Term:
    index: int          # position in the load order (largest lists first)
    name: str           # the query word
    tier: str
    length: int         # postings
    start: int          # first document of its window
    window: int         # documents in its window
    med_window: int     # which Med window covers it (-1: whole corpus)
    sub_window: int     # which Low sub-window (-1: none)


@dataclass(frozen=True)
class Layout:
    docs: int
    hosts: int
    terms: tuple
    n_med_windows: int
    n_sub_windows: int
    # title documents: per Med window the documents (ascending) and their
    # domain lengths; how many of a window's a list leaves out
    star_docs: tuple = ()
    star_domlength: tuple = ()
    star_dropped: int = 0

    def tier(self, name: str) -> list:
        return [t for t in self.terms if t.tier == name]

    def by_name(self) -> dict:
        return {t.name: t for t in self.terms}

    def covering(self, tier: str, med_window: int, sub_window: int) -> list:
        """The lists of `tier` whose window covers the topic."""
        if tier == "high":
            return self.tier("high")
        if tier == "med":
            return [t for t in self.terms
                    if t.tier == "med" and t.med_window == med_window]
        return [t for t in self.terms if t.tier == "low"
                and t.med_window == med_window
                and t.sub_window == sub_window]

    @property
    def postings(self) -> int:
        return sum(t.length for t in self.terms)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def layout(cfg: dict, seed: int) -> Layout:
    c = cfg["corpus"]
    docs, tiers = int(c["docs"]), c["tiers"]
    med_w, low_w = int(tiers["med"]["window"]), int(tiers["low"]["window"])
    n_med = docs // med_w
    n_sub = med_w // low_w
    if n_med < 1 or n_sub < 1:
        raise ValueError("corpus smaller than one Med window")
    # the Med windows tile the corpus from a seeded offset
    slack = docs - n_med * med_w
    off = int(np.random.default_rng([int(seed), 104729]).integers(
        0, slack + 1))
    terms, idx = [], 0
    for tier in TIERS:
        spec = tiers[tier]
        for i in range(int(spec["lists"])):
            if tier == "high":
                start, window, mw, sw = 0, docs, -1, -1
            elif tier == "med":
                mw, sw = i % n_med, -1
                start, window = off + mw * med_w, med_w
            else:
                mw, sw = i % n_med, (i // n_med) % n_sub
                start, window = off + mw * med_w + sw * low_w, low_w
            length = int(spec["length"])
            if length > window:
                raise ValueError(f"{tier} list longer than its window")
            terms.append(Term(idx, f"z{tier[0]}{i}", tier, length, start,
                              window, mw, sw))
            idx += 1
    star_docs, star_dl, dropped = _stars(c.get("stars"), seed, off, med_w,
                                         n_med)
    return Layout(docs, int(c["hosts"]), tuple(terms), n_med, n_sub,
                  star_docs, star_dl, dropped)


def _stars(spec, seed: int, off: int, med_w: int, n_med: int):
    """The title documents of every Med window and their domain lengths:
    one value each, no two stars alike (256 values, so at most 256
    stars)."""
    if not spec:
        return (), (), 0
    per, dropped = int(spec["per_med_window"]), int(spec["dropped"])
    if per * n_med > 256 or not 0 <= dropped < per:
        raise ValueError("more title documents than domain lengths")
    lengths = np.random.default_rng([int(seed), 15485863]).permutation(
        256)[:per * n_med].astype(np.int32)
    docs, dls = [], []
    for w in range(n_med):
        pos = np.random.default_rng([int(seed), 15485863, w]).choice(
            med_w, per, replace=False)
        order = np.argsort(pos)
        docs.append((off + w * med_w + pos[order]).astype(np.int32))
        dls.append(lengths[w * per:(w + 1) * per][order])
    return tuple(docs), tuple(dls), dropped


def _stars_of(lay: Layout, term: Term, seed: int):
    """(title documents this list holds, their domain lengths, every
    title document inside its window), each ascending by document. A
    High or Med list leaves out a run of `star_dropped` of each window's
    (cyclic, from a seeded start), so two lists share most of them and
    pages differ; a Low list holds none. No list holds a title document
    as an ordinary row."""
    none = np.empty(0, np.int32)
    if not lay.star_docs:
        return none, none, none
    rng = np.random.default_rng([int(seed), 6700417, term.index])
    windows = range(lay.n_med_windows) if term.med_window < 0 \
        else [term.med_window]
    held, dls, inside = [], [], []
    for w in windows:
        docs = lay.star_docs[w]
        lo, hi = np.searchsorted(docs, [term.start,
                                        term.start + term.window])
        inside.append(docs[lo:hi])
        if term.tier == "low":
            continue
        first = int(rng.integers(len(docs)))
        keep = np.ones(len(docs), bool)
        keep[(first + np.arange(lay.star_dropped)) % len(docs)] = False
        held.append(docs[keep])
        dls.append(lay.star_domlength[w][keep])
    return (np.concatenate(held) if held else none,
            np.concatenate(dls) if dls else none, np.concatenate(inside))


def term_list(lay: Layout, term: Term, seed: int):
    """(docids int32 [n] ascending unique, feats int32 [n, NF]) of one
    term. Ordinary rows: uniform features as the repo's start check
    draws them, but a hit count of 1-32 in a text of 100-1,099 words
    under a title of 0-15, so that the term frequency stays under a
    half. Title documents: one row per list, drawn from the middle of
    every range (its term frequency is never a candidate set's largest
    or smallest), with the title flag and the document's domain length."""
    rng = np.random.default_rng([int(seed), 7919, term.index])
    stars, star_dl, inside = _stars_of(lay, term, seed)
    n = term.length - len(stars)
    # n ordinary documents of the window, none of them a title document
    at = np.sort(rng.choice(term.window - len(inside), n, replace=False))
    gaps = inside - term.start - np.arange(len(inside))
    docids = term.start + at + np.searchsorted(gaps, at, side="right")
    feats = rng.integers(0, 1000, (n, NF), dtype=np.int32)
    feats[:, F_FLAGS] = rng.integers(0, 2 ** 20, n, dtype=np.int32)
    feats[:, F_DOMLENGTH] = rng.integers(0, 256, n, dtype=np.int32)
    feats[:, F_HITCOUNT] = rng.integers(1, 33, n, dtype=np.int32)
    feats[:, F_WORDS_IN_TEXT] = rng.integers(100, 1100, n, dtype=np.int32)
    feats[:, F_WORDS_IN_TITLE] = rng.integers(0, 16, n, dtype=np.int32)
    feats[:, F_LANGUAGE] = LANG_EN
    if len(stars):
        row = rng.integers(250, 750, NF, dtype=np.int32)
        row[F_FLAGS] = FLAG_IN_TITLE | int(rng.integers(0, 2 ** 20))
        row[F_HITCOUNT] = rng.integers(8, 17)
        row[F_WORDS_IN_TEXT] = rng.integers(300, 601)
        row[F_WORDS_IN_TITLE] = rng.integers(4, 9)
        row[F_LANGUAGE] = LANG_EN
        star_feats = np.tile(row, (len(stars), 1))
        star_feats[:, F_DOMLENGTH] = star_dl
        docids = np.concatenate([docids, stars])
        order = np.argsort(docids, kind="stable")
        docids = docids[order]
        feats = np.concatenate([feats, star_feats])[order]
    return docids.astype(np.int32), feats


def url_of(doc: int, hosts: int) -> str:
    return f"http://h{doc % hosts}.example/d{doc}.html"


def doc_of(url: str) -> int:
    """Inverse of url_of; -1 for a link this corpus never made."""
    try:
        stem = url.rsplit("/d", 1)[1]
        return int(stem[:-len(".html")])
    except (IndexError, ValueError):
        return -1
