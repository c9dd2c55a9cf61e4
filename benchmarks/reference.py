"""The plain reference: YaCy's conjunctive ranked search in NumPy.

Imports nothing of the program and takes nothing the program made. It
regenerates the posting lists of a query's words from (configuration,
seed) through benchmarks/corpus.py, joins them, scores every joined
posting with the reference ranking order (ReferenceOrder.cardinal of the
upstream project: each attribute min/max-normalised over the candidates
to 0..256 and shifted by its ranking-profile coefficient), and returns
the page a client must see: the ten best (link, ranking), at most
`MAX_PER_HOST` links of one host, equal rankings by ascending document
id (the order the program pins in search/searchevent.py and
ops/ranking.py). Every number has one value: a served page is right only
where it equals the reference's page, link for link and ranking for
ranking, in order.

Semantics written down here, from the upstream description:

- join: the rows of the shortest list (the first such in query order)
  that every other list also holds; word distance = span of the words'
  first positions, hit count = the least over the words, flags = OR.
- normalised attribute: (x - min) * 256 // (max - min), 0 where all
  candidates agree; "lower is better" attributes score 256 - that.
- term frequency: hitcount / (words in text + words in title + 1) in
  float32, normalised over the candidates the same way in float32 with
  IEEE division (correctly rounded, as NumPy and the program's host path
  divide), truncated. The document with the largest term frequency has
  256 exactly.
- the default text ranking profile's coefficients; query language en.
"""

from __future__ import annotations

import numpy as np

from . import corpus

PAGE = 10
MAX_PER_HOST = 6

# posting attribute columns
(F_LASTMOD, F_WORDS_IN_TITLE, F_WORDS_IN_TEXT, F_PHRASES_IN_TEXT, F_DOCTYPE,
 F_LANGUAGE, F_LLOCAL, F_LOTHER, F_URL_LENGTH, F_URL_COMPS, F_FLAGS,
 F_HITCOUNT, F_POSINTEXT, F_POSINPHRASE, F_POSOFPHRASE, F_WORDDISTANCE,
 F_DOMLENGTH) = range(17)

# default ranking profile (RankingProfile.java defaults, text domain):
# column -> (shift coefficient, higher is better)
NORMALISED = {
    F_LASTMOD: (9, True), F_WORDS_IN_TITLE: (2, True),
    F_WORDS_IN_TEXT: (3, True), F_PHRASES_IN_TEXT: (0, True),
    F_LLOCAL: (0, True), F_LOTHER: (7, True), F_URL_LENGTH: (6, False),
    F_URL_COMPS: (7, False), F_HITCOUNT: (1, True), F_POSINTEXT: (4, False),
    F_POSINPHRASE: (0, False), F_POSOFPHRASE: (0, False),
    F_WORDDISTANCE: (10, False),
}
C_DOMLENGTH, C_TF, C_LANGUAGE = 10, 8, 2
# appearance / category flag bit -> shift coefficient
FLAG_SHIFTS = {28: 12, 25: 14, 26: 1, 27: 2, 24: 10, 29: 5,
               0: 0, 20: 0, 21: 0, 22: 0, 23: 0}
INT16_MAX = 32767


def join(lists):
    """[(docids, feats)] in query order -> (docids, merged feats)."""
    if len(lists) == 1:
        return lists[0]
    order = sorted(range(len(lists)), key=lambda i: len(lists[i][0]))
    base_d, base_f = lists[order[0]]
    common = base_d
    for i in order[1:]:
        common = np.intersect1d(common, lists[i][0], assume_unique=True)
    feats = base_f[np.searchsorted(base_d, common)].copy()
    pos_min = feats[:, F_POSINTEXT].copy()
    pos_max = pos_min.copy()
    hit_min = feats[:, F_HITCOUNT].copy()
    flags = feats[:, F_FLAGS].copy()
    for i in order[1:]:
        d, f = lists[i]
        other = f[np.searchsorted(d, common)]
        pos_min = np.minimum(pos_min, other[:, F_POSINTEXT])
        pos_max = np.maximum(pos_max, other[:, F_POSINTEXT])
        hit_min = np.minimum(hit_min, other[:, F_HITCOUNT])
        flags |= other[:, F_FLAGS]
    feats[:, F_WORDDISTANCE] = pos_max - pos_min
    feats[:, F_HITCOUNT] = hit_min
    feats[:, F_FLAGS] = flags
    return common, feats


def bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest, ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def cardinal(feats: np.ndarray, control: str | None = None) -> np.ndarray:
    """int64 ranking of every candidate row (all rows are candidates).
    `control` breaks the term frequency as CONTROLS describes."""
    flags = feats[:, F_FLAGS].astype(np.int64)
    # the index stores every attribute but the flags in 16 bits
    f = np.clip(feats, -INT16_MAX - 1, INT16_MAX).astype(np.int64)
    score = np.zeros(len(f), np.int64)
    for col, (shift, direct) in NORMALISED.items():
        x = f[:, col]
        lo, hi = int(x.min()), int(x.max())
        if hi == lo:
            continue
        norm = (x - lo) * 256 // (hi - lo)
        score += (norm if direct else 256 - norm) << shift
    score += (256 - f[:, F_DOMLENGTH]) << C_DOMLENGTH
    score += np.where(f[:, F_LANGUAGE] == corpus.LANG_EN,
                      255 << C_LANGUAGE, 0)
    for bit, shift in FLAG_SHIFTS.items():
        score += ((flags >> bit) & 1) * (255 << shift)
    rnd = bfloat16 if control == "tf_bfloat16" else np.float32
    tf = rnd(rnd(f[:, F_HITCOUNT].astype(np.float32)) / rnd(
        (f[:, F_WORDS_IN_TEXT] + f[:, F_WORDS_IN_TITLE] + 1)
        .astype(np.float32)))
    tlo, thi = np.float32(tf.min()), np.float32(tf.max())
    if not thi > tlo:
        return score
    tf_norm = rnd(rnd(rnd(tf - tlo) * np.float32(256.0))
                  / rnd(thi - tlo)).astype(np.int64)
    if control == "tf_max_255":
        tf_norm = np.minimum(tf_norm, 255)
    return score + (tf_norm << C_TF)


def page(docids: np.ndarray, scores: np.ndarray, hosts: int,
         ties_descending: bool = False):
    """Best-first page: equal rankings by ascending docid (descending
    only for the control `tie_desc`), host diversity."""
    if len(docids) == 0:
        return []
    order = np.lexsort((-docids if ties_descending else docids, -scores))
    out, per_host = [], {}
    for i in order[:PAGE * 8]:
        d = int(docids[i])
        # the node's host key: characters 6..12 of the 12-character url
        # hash the harness gives document d (last digit + host number)
        h = (d % 10, d % hosts)
        if per_host.get(h, 0) >= MAX_PER_HOST:
            continue
        per_host[h] = per_host.get(h, 0) + 1
        out.append((d, int(scores[i])))
        if len(out) == PAGE:
            break
    return out


class Reference:
    """Answers queries over one (configuration, seed) corpus; keeps the
    lists it has built, dropping them all when `budget_rows` is passed.
    `control` names one of CONTROLS or PROBES: the reference with that
    one thing broken, to be put in the program's place."""

    def __init__(self, lay, seed: int, budget_rows: int = 12_000_000,
                 control: str | None = None):
        if control is not None and control not in {**CONTROLS, **PROBES}:
            raise KeyError(f"no control {control!r}")
        self.lay, self.seed = lay, int(seed)
        self._by_name = lay.by_name()
        self._lists, self._rows = {}, 0
        self._budget = budget_rows
        self._control = control
        self._last = None

    def _list(self, word: str):
        got = self._lists.get(word)
        if got is None:
            term = self._by_name.get(word)
            if term is None:
                return None
            got = corpus.term_list(self.lay, term, self.seed)
            if self._control == "drop_tail":
                n = max(1, len(got[0]) * 15 // 16)
                got = (got[0][:n], got[1][:n])
            if self._rows + len(got[0]) > self._budget:
                self._lists.clear()
                self._rows = 0
            self._lists[word] = got
            self._rows += len(got[0])
        return got

    def scored(self, query: str):
        """(docids, rankings) of every candidate of the conjunction; the
        last answer is kept (a stream with repeats is compared in query
        order)."""
        if self._last is not None and self._last[0] == query:
            return self._last[1]
        words = []
        for w in query.lower().split():
            if w not in words:
                words.append(w)
        lists = [self._list(w) for w in words]
        if not lists or any(x is None or len(x[0]) == 0 for x in lists):
            got = (np.empty(0, np.int32), np.empty(0, np.int64))
        else:
            docids, feats = join(lists)
            got = (docids, cardinal(feats, self._control) if len(docids)
                   else np.empty(0, np.int64))
        self._last = (query, got)
        return got

    def answer(self, query: str):
        docids, scores = self.scored(query)
        return page(docids, scores, self.lay.hosts,
                    ties_descending=self._control == "tie_desc")

    def served(self, queries):
        """[(query, [(link, ranking)])] as a client would read them."""
        return [(q, [(corpus.url_of(d, self.lay.hosts), s)
                     for d, s in self.answer(q)]) for q in queries]


# -- the controls: the reference with one thing broken ----------------------

# Put in the program's place, each has to come out as not correct, on
# every seed (benchmarks/tests, and `run.py --control all` on the chip).
CONTROLS = {
    # THE control of a lower precision: the one floating-point attribute
    # computed in the nearest precision below the float32 that the
    # ranking order states
    "tf_bfloat16": "term frequency and its normalisation in bfloat16",
    # a stated guarantee broken: not ALL resident postings
    "drop_tail": "the last sixteenth of every list, by document id, "
                 "never scored",
}
# At the scale of the two faults the program shows on the chip (PERF.md
# section 7). They change an answer only where a page holds the document
# with the largest term frequency, or two equal rankings. The corpus's
# title documents keep both off every page that the device answers, so
# here they show on pages under the host gate alone: read beside the
# controls to show how fine the comparison is, not required to fail a
# sample that holds no such page.
PROBES = {
    "tf_max_255": "the largest normalised term frequency 255, not 256",
    "tie_desc": "equal rankings by descending document id",
}


# -- the comparison that decides `correct` ---------------------------------

LIMITS = {"wrong_answers": 0, "max_rank_gap": 0, "max_miss_gap": 0,
          "tie_order_answers": 0, "stale_served": 0}


def decide(numbers: dict, compared: int) -> bool:
    """`correct`: something was compared and no number is over its limit."""
    return compared > 0 and all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compare(ref: Reference, answers, hosts: int) -> dict:
    """answers: [(query, [(link, ranking)])] as the clients read them. A
    served page is right where it equals the reference's page; for one
    that is not, the numbers say how:

    - max_rank_gap: how far a served ranking lies from the reference's
      ranking of that document (2^31: a link the conjunction lacks).
    - max_miss_gap: how far the ranking the reference gives a served
      document lies below what the reference's page holds at that rank
      (a better document was left out), and how far the served rankings
      break their descent; 2^31 for a page of another length.
    - tie_order_answers: answers with both gaps 0 that still differ from
      the reference's page: equal rankings not in ascending document id.
    - wrong_answers: answers that differ from the reference's page."""
    wrong, rank_gap, miss_gap, tie_order, examples = 0, 0, 0, 0, []
    for query, served in sorted(answers, key=lambda a: a[0]):
        docids, scores = ref.scored(query)
        want = page(docids, scores, hosts)
        got = [(corpus.doc_of(link), int(r)) for link, r in served]
        if got == want:
            continue
        at = np.searchsorted(docids, [d for d, _r in got])
        rg, mg = 0, 0 if len(got) == len(want) else 2 ** 31
        for i, ((d, r), j) in enumerate(zip(got, at.tolist())):
            if j >= len(docids) or int(docids[j]) != d:
                rg = 2 ** 31                 # a link the conjunction lacks
                continue
            rg = max(rg, abs(r - int(scores[j])))
            if i < len(want):
                mg = max(mg, want[i][1] - int(scores[j]))
            if i and got[i - 1][1] < r:
                mg = max(mg, r - got[i - 1][1])
        wrong += 1
        tie_order += not (rg or mg)
        if len(examples) < 5:
            examples.append({"query": query, "served": got[:PAGE],
                             "reference": want})
        rank_gap, miss_gap = max(rank_gap, rg), max(miss_gap, mg)
    return {"numbers": {"wrong_answers": wrong, "max_rank_gap": rank_gap,
                        "max_miss_gap": miss_gap,
                        "tie_order_answers": tie_order},
            "compared": len(answers), "examples": examples}
