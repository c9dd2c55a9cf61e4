"""Dense structure-of-arrays postings — the device-native RWI row format.

TPU-first redesign of the reference's row-encoded posting
(reference: source/net/yacy/kelondro/data/word/WordReferenceRow.java:49-69,
the 20-column layout). Instead of b256-encoded byte rows decoded one at a
time (WordReferenceVars.transform), a term's postings are two numpy arrays:

    docids : int32 [n]        -- local doc ids, sorted ascending, unique
    feats  : int32 [n, NF]    -- the posting attributes, one column each

which upload to the device as-is and score as one batched kernel. The doc id
is an index into the columnar metadata store (index/metadata.py), which owns
the docid <-> 12-char url-hash mapping; DHT routing recovers url hashes from
there when postings move between peers.

Column meanings follow the reference's posting attributes 1:1 so the ranking
profile's signals stay comparable (see ops/ranking.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# feature column indices (NF columns, int32 each)
F_LASTMOD = 0        # last-modified, days since epoch (reference col "a")
F_WORDS_IN_TITLE = 1  # col "u"
F_WORDS_IN_TEXT = 2   # col "w"
F_PHRASES_IN_TEXT = 3  # col "p"
F_DOCTYPE = 4         # col "d"
F_LANGUAGE = 5        # col "l": 2 ascii chars packed big-endian
F_LLOCAL = 6          # outlinks to same domain, col "x"
F_LOTHER = 7          # outlinks to other domains, col "y"
F_URL_LENGTH = 8      # col "m"
F_URL_COMPS = 9       # col "n"
F_FLAGS = 10          # 30-bit appearance/category bitfield, col "z"
F_HITCOUNT = 11       # occurrences of the word in the doc, col "c"
F_POSINTEXT = 12      # first position of word in text, col "t"
F_POSINPHRASE = 13    # col "r"
F_POSOFPHRASE = 14    # col "o"
F_WORDDISTANCE = 15   # avg distance of query words, filled by the join, col "i"
F_DOMLENGTH = 16      # normalized domain length (derived from url-hash flag byte)
NF = 17

FEATURE_NAMES = [
    "lastmod", "words_in_title", "words_in_text", "phrases_in_text", "doctype",
    "language", "llocal", "lother", "url_length", "url_comps", "flags",
    "hitcount", "posintext", "posinphrase", "posofphrase", "worddistance",
    "domlength",
]


def pack_language(lang: str) -> int:
    """2-char ISO-639-1 code -> int (e.g. 'en' -> 0x656e); '' -> 0."""
    if not lang:
        return 0
    b = lang[:2].lower().encode("ascii", "replace")
    return (b[0] << 8) | (b[1] if len(b) > 1 else 0)


def unpack_language(v: int) -> str:
    if v == 0:
        return ""
    return bytes(((v >> 8) & 0xFF, v & 0xFF)).decode("ascii", "replace")


@dataclass
class PostingsList:
    """One term's postings: sorted-unique docids + aligned feature rows."""

    docids: np.ndarray  # int32 [n], ascending, unique
    feats: np.ndarray   # int32 [n, NF]

    def __post_init__(self):
        assert self.docids.ndim == 1 and self.feats.shape == (len(self.docids), NF)

    def __len__(self) -> int:
        return len(self.docids)

    @staticmethod
    def empty() -> "PostingsList":
        return PostingsList(np.empty(0, np.int32), np.empty((0, NF), np.int32))

    @staticmethod
    def from_rows(docids: list[int], feats: list[np.ndarray] | np.ndarray) -> "PostingsList":
        d = np.asarray(docids, dtype=np.int32)
        f = np.asarray(feats, dtype=np.int32).reshape(len(d), NF)
        return sort_dedupe(d, f)

    def select(self, mask: np.ndarray) -> "PostingsList":
        return PostingsList(self.docids[mask], self.feats[mask])


def sort_dedupe(docids: np.ndarray, feats: np.ndarray) -> PostingsList:
    """Sort by docid; on duplicates the *last* row wins (newest write)."""
    from ..utils import native
    if len(docids) > 1 and bool(np.all(docids[1:] > docids[:-1])):
        # already ascending without a repeat (a bulk import's lists): the
        # order is the identity
        return PostingsList(np.array(docids, np.int32),
                            np.array(feats, np.int32))
    order = native.sort_dedupe_order(docids)
    if order is not None:
        return PostingsList(docids[order].astype(np.int32, copy=False),
                            feats[order].astype(np.int32, copy=False))
    order = np.argsort(docids, kind="stable")
    d, f = docids[order], feats[order]
    if len(d) > 1:
        # keep last of each run of equal ids
        keep = np.empty(len(d), dtype=bool)
        keep[:-1] = d[1:] != d[:-1]
        keep[-1] = True
        d, f = d[keep], f[keep]
    return PostingsList(d.astype(np.int32), f.astype(np.int32))


def merge(lists: list[PostingsList]) -> PostingsList:
    """Merge runs; later lists override earlier ones on docid collision."""
    lists = [p for p in lists if len(p)]
    if not lists:
        return PostingsList.empty()
    if len(lists) == 1:
        return lists[0]
    d = np.concatenate([p.docids for p in lists])
    f = np.concatenate([p.feats for p in lists])
    return sort_dedupe(d, f)


def probe_rows(list_docids: np.ndarray, list_feats: np.ndarray,
               docids: np.ndarray, want_feats: bool = True):
    """Which of the sorted `docids` one term's list holds, and the feature
    rows of those: (found mask over `docids`, rows aligned to
    docids[found], or None when the membership alone is wanted). The list
    may be a map's slice: a binary search and a gather touch only the
    pages they land on, nothing is materialized."""
    if len(list_docids) == 0:
        found = np.zeros(len(docids), dtype=bool)
        return found, (list_feats[:0] if want_feats else None)
    idx = np.searchsorted(list_docids, docids)
    found = np.take(list_docids, idx, mode="clip") == docids
    if not want_feats:
        return found, None
    return found, np.take(list_feats, idx[found], axis=0)


def remove_docids(p: PostingsList, dead: np.ndarray) -> PostingsList:
    """Drop postings whose docid is in the sorted `dead` array (tombstones)."""
    if len(p) == 0 or len(dead) == 0:
        return p
    from ..utils import native
    alive = native.alive_mask(p.docids, dead)
    if alive is not None:
        return p.select(alive)
    idx = np.searchsorted(dead, p.docids)
    idx = np.clip(idx, 0, len(dead) - 1)
    alive = dead[idx] != p.docids
    return p.select(alive)
