"""SearchEvent — scatter-gather search orchestrator, TPU-first.

Capability equivalent of the reference's SearchEvent
(reference: source/net/yacy/search/query/SearchEvent.java:112-2563, the
2,563-line orchestrator) and SearchEventCache.java:42-199. The reference
runs a local Solr thread + a local RWI thread + N remote-peer threads, all
feeding two bounded priority heaps, then drains the heaps through filters,
doubledom diversion and post-ranking per `oneResult` call. Here the local
path is batched:

    term_search (sorted join)  →  constraint masks  →  device cardinal
    + top-K kernel (ops/ranking.score_topk)  →  metadata join  →
    host-diversity drain  →  post-ranking  →  result list

Remote feeders (M5, peers/) later call `add_remote_postings` /
`add_remote_results` on a live event — the heaps survive as host-side
fusion points for asynchronous WAN producers, exactly the straggler
strategy of SURVEY.md §7 ("deadline + late-merge into the cached event").

Filters are applied as masks BEFORE the kernel (the reference interleaves
them into its heap-insert loop, SearchEvent.java:673-836): contentdom
flag constraint, language, site host, tld, filetype, inurl/intitle/author
modifier checks. Host diversity (max N per host, then diversion —
`doubledom`, SearchEvent.java:1297-1412) runs host-side over the oversized
top-K so result *quality* matches, not just speed (SURVEY.md §7 hard part
#1: two-stage top-k with domain-diversity constraints).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

from ..index import postings as P
from ..index.segment import Segment
from ..ops.ranking import (CD_ALL, CD_APP, CD_AUDIO, CD_IMAGE, CD_TEXT,
                           CD_VIDEO, CardinalRanker)
from ..utils.bitfield import (FLAG_CAT_HASAPP, FLAG_CAT_HASAUDIO,
                              FLAG_CAT_HASIMAGE, FLAG_CAT_HASVIDEO)
from ..utils import profiling, tracing
from ..utils.eventtracker import EClass, StageTimer, update as track
from ..utils.hashes import hosthash
from ..utils.topk import WeakPriorityQueue
from .navigator import accumulate_batch, make_navigators
from .query import QueryParams
from .snippet import extract_snippet

# oversampling factor for the device top-k so host-side diversity/filter
# rechecks still fill the page (reference pulls from an unbounded-ish heap)
TOPK_OVERSAMPLE = 8

# a conjunction's word count is a stage-counter label (JOIN_TERMS_<n>) up
# to the device join's term cap (devstore.MAX_JOIN_TERMS); more words
# share JOIN_TERMS_MANY, so a hostile query mints no series
JOIN_TERMS_LABELS = 6

# the columns every ResultEntry is built from (SearchEvent._make_entry)
ENTRY_FIELDS = ("sku", "title", "host_s", "url_file_ext_s", "language_s",
                "size_i", "wordcount_i", "last_modified_days_i",
                "references_i")

_CD_FLAG = {CD_IMAGE: FLAG_CAT_HASIMAGE, CD_AUDIO: FLAG_CAT_HASAUDIO,
            CD_VIDEO: FLAG_CAT_HASVIDEO, CD_APP: FLAG_CAT_HASAPP}


def _unconstrained_single_term(q) -> bool:
    """THE predicate for "plain single term, no constraints of any
    kind" — the cacheable query shape.  One implementation shared by
    the device-path eligibility gate and the rung-3 cache-only path: a
    constraint gate added to one but not the other would serve a
    cached UNCONSTRAINED answer for a constrained query (wrong, not
    stale)."""
    m = q.modifier
    inc, exc = q.goal.include_hashes, q.goal.exclude_hashes
    return (len(inc) == 1 and not exc and not m.date_sort
            and not (m.sitehost or m.tld or m.filetype or m.protocol)
            and not m.language
            and _CD_FLAG.get(q.contentdom) is None
            and m.from_days is None and m.to_days is None
            and q.profile.authority <= 12)


@dataclass
class ResultEntry:
    """One search result row (URIMetadataNode-equivalent surface)."""

    docid: int
    urlhash: bytes
    score: int
    url: str = ""
    title: str = ""
    snippet: str = ""
    snippet_done: bool = False  # lazily extracted at page render
    host: str = ""
    filetype: str = ""
    language: str = ""
    size: int = 0
    wordcount: int = 0
    lastmod_days: int = 0
    references: int = 0
    source: str = "local"   # local | peer hash

    def to_json(self) -> dict:
        return {
            "link": self.url, "title": self.title, "description": self.snippet,
            "urlhash": self.urlhash.decode("ascii", "replace"),
            "host": self.host, "size": self.size, "sizename": _sizename(self.size),
            "ranking": int(self.score), "source": self.source,
        }


def _sizename(n: int) -> str:
    for unit in ("bytes", "kB", "MB", "GB"):
        if n < 1024:
            return f"{n} {unit}"
        n //= 1024
    return f"{n} TB"


@dataclass
class ImageResult:
    """One image search result (contentdom=image serving mode — the
    reference builds these from images_urlstub_sxt with source-page
    attribution, SearchEvent.java:2178-2280 / yacysearchitem image
    branch)."""

    image_url: str
    alt: str
    source_url: str          # the page the image appears on
    source_title: str
    source_urlhash: bytes
    host: str
    score: int
    filetype: str = ""
    source: str = "local"


class SearchEvent:
    """One live search: executes locally at construction, accepts remote
    feeder inserts afterwards, serves pages via `one_result`/`results`."""

    def __init__(self, query: QueryParams, segment: Segment, loader=None):
        self.query = query
        # the id the event cache keeps this event under and the page
        # hands out as `eventID`: taken once, here (`query` is shared by
        # every request the event answers and is not changed after)
        self.event_id = query.query_id()
        self.segment = segment
        # crawler loader for LIVE snippet production (None: cache-local
        # extraction only — embedded/federated events have no crawler)
        self.loader = loader
        self.snippet_evictions = 0
        self._snippet_evicted: set[bytes] = set()
        self.created = time.time()
        self.touched = time.time()
        self._lock = threading.RLock()
        self.navigators = make_navigators(query.facets)
        # host-side fusion heap for asynchronous (remote) producers; local
        # batched results are inserted at construction
        self.result_heap: WeakPriorityQueue[ResultEntry] = WeakPriorityQueue(
            max(query.max_results_node, query.item_count * 10))
        self._seen_urlhashes: set[bytes] = set()
        self._host_counts: dict[bytes, int] = {}
        self._diverted: list[tuple[int, ResultEntry]] = []
        self.local_rwi_considered = 0
        self.local_rwi_evicted = 0
        self.remote_peers_asked = 0
        self.remote_results = 0
        # which peers this event scattered to and which answered — the
        # live state behind the per-event network picture (reference:
        # htroot/SearchEventPicture.java over SearchEvent.primarySearch)
        self.asked_peers: list = []
        self.result_peer_hashes: set[bytes] = set()
        # one-shot latch for query-time heuristics: they fire when the
        # event is created, never again on cache hits/paging (the
        # reference's heuristics are per-search-event)
        self.heuristics_fired = False
        self._pending: list[tuple[int, int]] = []  # lazily-drained ranked
        self._drained = 0                          # local entries drained
        self._ranker = CardinalRanker(query.profile, query.lang)
        # what the metadata join reads for a candidate that becomes an
        # entry: the filter-only columns only when this query rechecks
        # them (_make_entry)
        self._entry_fields = ENTRY_FIELDS + tuple(
            f for f, asked in (("author", query.modifier.author),
                               ("keywords", query.modifier.keyword),
                               ("text_t", query.goal.phrases)) if asked)
        # the trace this event was born under: remote feeder threads and
        # late-merging producers parent their spans here (the contextvar
        # does not cross the fan-out thread boundary)
        self.trace_ctx = tracing.current()
        # degradation ladder rung (ISSUE 9, utils/actuator.LEVEL_*):
        # each rung serves a PREFIX of the full pipeline, so degraded
        # answers stay bit-identical in ordering to the corresponding
        # non-degraded stage outputs (tie discipline per stage)
        self.degrade_level = getattr(query, "degrade_level", 0)
        self._run_local()

    def _note_degraded(self, stage: str, n: int = 1) -> None:
        """Every downgraded stage is counted (eventtracker ->
        yacy_stage_events_total) and traced (a zero-length marker span
        when a trace is active)."""
        track(EClass.SEARCH, f"DEGRADED_{stage}", n)
        tracing.emit("search.degraded", 0.0, stage=stage,
                     level=self.degrade_level)

    # -- local batched path --------------------------------------------------

    def _run_local(self) -> None:
        # the ranking stage is ONE span, named on the way out by the
        # route that produced the candidates: the windowed count of
        # `search.route.<route>` IS the route counter, measured where
        # the routing happens (with `event_cache`, which
        # SearchEventCache.get_event records, exactly one per search)
        with tracing.timed("search.route") as rt:
            self._route = "host_other"
            try:
                ranked = self._rank_local()
            finally:
                rt.rename("search.route." + self._route)
        if ranked is not None:
            self._fill_results(*ranked)

    def _rank_local(self):
        """(scores, docids) of the local candidates, or None for an
        empty answer. Sets `self._route`: `topk_cache` and `device`
        where _device_local answered (from a peek, from the store),
        `host_gate` where its small-candidate gate sent the query to
        the NumPy ranker, `host_other` for every other host answer
        (modifiers, date sort, no store, the store declined) and for
        the cache-only rung's miss."""
        q = self.query
        k_need = max(q.item_count + q.offset, 10) * TOPK_OVERSAMPLE

        # ladder rung 3 (cache-only / stale-ok): answer from the
        # versioned top-k cache with ZERO ranking work; a miss returns
        # an empty page instead of paying device/host ranking — the
        # last line of defense before shedding outright
        if self.degrade_level >= 3:
            got = self._cache_only(k_need)
            if got is not None:
                self._route = "topk_cache"
                scores, docids, self.local_rwi_considered = got
                if len(docids):
                    return scores, docids
            return None

        # hybrid-cache plumbing: _device_local may serve a FULL cached
        # hybrid answer (rerank included, zero device work) or hand back
        # the put context for inserting the one computed below
        self._rerank_done = False
        self._hybrid_put = None
        # steady-state path: rank placed device blocks (uploads only the
        # RAM delta); None -> host path (term not resident / query shape
        # needs host-side data)
        placed = self._device_local(k_need)
        if placed is not None:
            scores, docids, self.local_rwi_considered = placed
            if len(docids) == 0:
                return None
            if q.hybrid and not self._rerank_done:
                scores, docids = self._second_stage(scores, docids,
                                                    k_need,
                                                    allow_put=True)
            return scores, docids

        # the stage says how the conjunction was read (`single` term,
        # long lists probed from the short one, or lists of a size
        # merged) and how many posting rows that took; each path is a
        # stage counter of its own (yacy_stage_events_total JOIN_<PATH>)
        # So is the number of lists the conjunction had (JOIN_TERMS_<n>);
        # a join that probed two lists or more is a family of its own
        # beside `search.join`, which a windowed reader cannot split by
        # attr
        how: dict = {}
        n_terms = len(q.goal.include_hashes)
        t0 = time.perf_counter()
        with StageTimer(EClass.SEARCH, "JOIN") as stage:
            joined = self.segment.term_search(
                include_hashes=q.goal.include_hashes or None,
                exclude_hashes=q.goal.exclude_hashes or None, how=how)
            stage.count = how["rows"]
            stage.set(path=how["path"], rows=how["rows"], terms=n_terms,
                      probes=how["probes"])
        if how["probes"] >= 2:
            tracing.record("search.join.multiprobe",
                           (time.perf_counter() - t0) * 1e3,
                           terms=n_terms, probes=how["probes"])
        track(EClass.SEARCH, "JOIN_" + how["path"].upper(), how["rows"])
        track(EClass.SEARCH, "JOIN_TERMS_" + (
            str(n_terms) if n_terms <= JOIN_TERMS_LABELS else "MANY"),
            how["rows"])
        self.local_rwi_considered = len(joined)
        if len(joined) == 0:
            return None

        with StageTimer(EClass.SEARCH, "PRESORT"):
            mask = self._constraint_mask(joined)
            cand = joined if mask is None else joined.select(mask)
        if len(cand) == 0:
            return None

        # the authority signal is the only hosthash consumer; the per-row
        # python loop must not run for profiles that never read it
        # (ReferenceOrder.java:255 guard — authority only when coeff > 12)
        hosthashes = None
        if q.profile.authority > 12:
            hosthashes = [hosthash(self.segment.metadata.urlhash_of(d))
                          for d in cand.docids.tolist()]
        k = min(len(cand), k_need)
        if q.modifier.date_sort:
            # /date modifier: recency replaces the cardinal as the sort key
            # (reference: QueryModifier /date -> Solr sort last_modified desc)
            lastmod = cand.feats[:, P.F_LASTMOD].astype(np.int64)
            order = np.argsort(-lastmod, kind="stable")[:k]
            scores, docids = lastmod[order], cand.docids[order]
        else:
            # which ranker answered (the fused native call, its NumPy
            # twin, the device kernel past SMALL_RANK_N) is an attr of
            # the stage and a stage counter each, as JOIN_<PATH> above
            by: dict = {}
            with StageTimer(EClass.SEARCH, "NORMALIZING", len(cand)) as stage:
                scores, docids = self._ranker.rank(cand, hosthashes, k=k,
                                                   how=by)
                stage.set(ranker=by["ranker"])
            track(EClass.SEARCH, "NORMALIZING_" + by["ranker"].upper(),
                  len(cand))

        if q.hybrid and len(docids) and not q.modifier.date_sort:
            # host-computed answers never enter the hybrid cache: they
            # are not bit-identical to device-path answers, and a
            # cached one would flap the versioned top-k contract
            scores, docids = self._second_stage(scores, docids, k_need,
                                                allow_put=False)
        return scores, docids

    def _cache_only(self, k: int):
        """Ladder rung 3 (ISSUE 9): the versioned top-k cache is the
        ONLY serving source — stale-ok, because at this rung an answer
        computed against a slightly older arena epoch beats paying any
        ranking work (and beats shedding).  Only the unconstrained
        single-term shape can answer from the cache (the cache key
        carries no constraints — serving a cached unconstrained answer
        for a constrained query would be wrong, not stale); everything
        else misses and returns empty, counted."""
        q = self.query
        ds = self.segment.devstore
        inc = q.goal.include_hashes
        peek = getattr(ds, "rank_cache_get", None) if ds is not None \
            else None
        if peek is not None and _unconstrained_single_term(q):
            try:
                got = peek(inc[0], q.profile, q.lang, k, stale_ok=True)
            except TypeError:
                # store without the stale_ok surface (mesh store, rank-
                # service client): the strict peek still serves hits
                got = peek(inc[0], q.profile, q.lang, k)
            if got is not None:
                self._note_degraded("CACHE_ONLY_HIT", len(got[1]))
                return got
        self._note_degraded("CACHE_ONLY_MISS")
        return None

    def _fill_results(self, scores, docids) -> None:
        """Queue the ranked candidates and materialize lazily: the page
        drain (results()) joins metadata only for as many entries as the
        page needs plus a post-ranking cushion — materializing the whole
        oversampled top-k per query was the serving path's python
        bottleneck. A cushion beyond the page keeps post-ranking boosts
        competing across the page boundary.

        Facets accumulate over the FULL ranked candidate set (the
        reference's facet counts also cover the whole query result, not
        the page — Solr facet counting), from the same gather that reads
        the first cushion's rows."""
        with self._lock:
            self._pending = list(zip(scores.tolist(), docids.tolist()))
            self._pending.reverse()      # pop() from the end = best-first
            self._drain(self.query.offset + self.query.item_count,
                        facets=bool(self.navigators))

    def _drain(self, need: int, facets: bool = False) -> None:
        """Materialize pending local candidates until `cushion` of them
        have been drained (counted independently of the heap, which remote
        feeders also fill — remote inserts must not starve better local
        candidates out of materialization). The metadata join is ONE
        gather per increment: what the cushion still lacks (an eviction
        refills with a gather of its own), and with `facets` the
        navigator columns of every pending candidate beside it."""
        cushion = need * 2 + 6
        with self._lock:
            if not self._pending:
                return
            with StageTimer(EClass.SEARCH, "RESULTLIST") as stage:
                gathered = 0
                while self._pending and self._drained < cushion:
                    take = min(cushion - self._drained, len(self._pending))
                    read = len(self._pending) if facets else take
                    batch = self._pending[-read:]
                    batch.reverse()                      # best-first
                    del self._pending[-take:]
                    rows = self._gather([d for _, d in batch], take, facets)
                    facets = False
                    gathered += read
                    for i in range(take):
                        score, docid = batch[i]
                        entry = self._make_entry(rows, i, int(docid),
                                                 int(score))
                        if entry is None:
                            self.local_rwi_evicted += 1
                            continue
                        self._drained += 1
                        stage.count += 1
                        self._insert(entry)
                stage.set(rows=stage.count, gathered=gathered)

    def _gather(self, docids: list, head: int, facets: bool):
        """The event's metadata join: ONE MetadataStore.rows_at read of
        the entry columns and url hashes of the first `head` docids and,
        with `facets`, of the navigator columns of all of them, counted
        into the navigators here (a column both need is read once)."""
        nav_fields = ([nav.field for nav in self.navigators.values()]
                      if facets else ())
        with StageTimer(EClass.SEARCH, "METAJOIN", len(docids)):
            rows = self.segment.metadata.rows_at(
                docids, nav_fields, head_fields=self._entry_fields,
                head=head)
            if facets:
                accumulate_batch(self.navigators, rows.cols, rows.alive)
        return rows

    def _device_local(self, k: int):
        """Eligibility gate + dispatch for the device-resident serving path
        (index/devstore.py). Plain single terms rank via the pruned span
        scan; conjunctions — and single terms with exclusions — via the
        device join (sort-merge over docid-sorted side-tables). Query
        shapes needing host-side data still fall back: metadata modifiers
        (site:/tld:/filetype:/protocol), date-sort, and authority-boosted
        profiles."""
        q = self.query
        ds = self.segment.devstore
        if ds is None:
            return None
        inc, exc = q.goal.include_hashes, q.goal.exclude_hashes
        if not inc:
            return None
        m = q.modifier
        from ..index.devstore import NO_FLAG, NO_LANG
        flag = _CD_FLAG.get(q.contentdom)
        lang_filter = (P.pack_language(m.language) if m.language
                       else NO_LANG)
        flag_bit = NO_FLAG if flag is None else flag
        facet_mods = bool(m.sitehost or m.tld or m.filetype or m.protocol)
        # ONE predicate for "plain single term, no constraints of any
        # kind" — the cacheable shape, shared with the rung-3 cache-only
        # path (module-level _unconstrained_single_term). A new routing
        # gate below that constrains results must extend that ONE
        # conjunction, not drift past a hand-copied list.
        unconstrained = _unconstrained_single_term(q)
        # cache-aware eligibility: a repeated hot term answers from the
        # store's versioned top-k result cache with ZERO device work, so
        # none of the cost-based gates below apply to it — in particular
        # the small-candidate host gate (count_upper takes the RWI lock
        # and a cache hit is cheaper than even that host scoring)
        if unconstrained:
            # hybrid queries peek the HYBRID cache first: a hit is the
            # full two-stage answer (sparse rank + dense rerank),
            # bit-identical with zero device work; keyed additionally
            # on (alpha, encoder version, vector version) so it can
            # never survive an encoder swap or a vector write. A miss
            # remembers the put context — the epoch BEFORE the sparse
            # stage runs, so a racing flush leaves the entry born-stale
            # (rung 2 skips the hybrid peek entirely: a cached HYBRID
            # answer would disagree with the rerank-skipped order every
            # computed answer serves while degraded)
            if q.hybrid and self.degrade_level < 2:
                hpeek = getattr(ds, "hybrid_cache_get", None)
                if hpeek is not None:
                    # dense-first answers live under their own key
                    # (candidate stream differs); a dense-first query
                    # that will SHED its probe (rung 1) serves the
                    # plain-hybrid key its computed answer will match
                    df = bool(getattr(q, "dense_first", False)) \
                        and self.degrade_level < 1
                    q0 = time.perf_counter()
                    got = hpeek(inc[0], q.profile, q.lang, k,
                                q.hybrid_alpha, dense_first=df)
                    if got is not None:
                        track(EClass.SEARCH, "DEVRANK", len(got[1]),
                              (time.perf_counter() - q0) * 1000.0)
                        self._route = "topk_cache"
                        self._rerank_done = True
                        return got
                    # the vector-content version is snapshotted HERE,
                    # with the epoch: a vector write racing the rerank
                    # below must leave the entry unreachable, not filed
                    # under the post-write key as if fresh (the ANN
                    # centroid version likewise, for dense-first)
                    self._hybrid_put = (ds, inc[0], ds.arena_epoch,
                                        ds.hybrid_vector_version(),
                                        ds.ann_centroid_version())
            # the sparse peek still serves hybrid queries' FIRST stage
            # (a hybrid-cache miss can ride a sparse hit into rerank)
            peek = getattr(ds, "rank_cache_get", None)
            if peek is not None:
                q0 = time.perf_counter()
                got = peek(inc[0], q.profile, q.lang, k)
                if got is not None:
                    # the stage still lands in the stage counters
                    # (attributable, with zero device work behind it;
                    # hit-only so misses don't double-count the real
                    # DEVRANK stage below); its wall is the route span's
                    track(EClass.SEARCH, "DEVRANK", len(got[1]),
                          (time.perf_counter() - q0) * 1000.0)
                    self._route = "topk_cache"
                    return got
        # tiny candidate sets: the host path scores them in microseconds
        # (ops/ranking.SMALL_RANK_N numpy twin); a device dispatch and
        # its round trip would dominate.
        # A conjunction's join size is bounded by its RAREST term, and so
        # is what the host join READS: Segment.term_search fetches that
        # list and probes the long ones at its docids.
        from ..ops.ranking import SMALL_RANK_N
        # store-overridable threshold: a mesh dryrun (or a locally
        # attached device with a ~0 dispatch floor) may lower it
        thresh = getattr(ds, "small_rank_n", None)
        if thresh is None:
            thresh = SMALL_RANK_N
        if min(self.segment.rwi.count_upper(th)
               for th in inc) <= thresh:
            self._route = "host_gate"
            return None
        if m.date_sort:
            return None
        # metadata-constrained modifiers (site:/tld:/filetype:/protocol)
        # serve on device for SINGLE-term queries via a cached facet
        # docid bitmap (VERDICT r3 #5 widening); conjunctions with them
        # keep the host join
        if facet_mods and (len(inc) != 1 or exc
                           or not getattr(ds, "supports_filter_bitmap",
                                          False)):
            return None
        if q.profile.authority > 12:
            return None
        if len(inc) == 1 and not exc:
            if facet_mods:
                # residency pre-check: building+uploading a bitmap for a
                # term the store will decline anyway is dead work (and
                # would trigger a pointless background prewarm)
                spans = ds.spans_for(inc[0])
                if spans is None or len(spans) > ds.MAX_SPANS:
                    return None
            # the kwarg only goes to stores that declared support (the
            # facet_mods gate above guarantees allow is None otherwise)
            extra = ({"allow_bitmap": self._facet_filter_bitmap(ds, m)}
                     if facet_mods else {})
            with StageTimer(EClass.SEARCH, "DEVRANK"):
                got = ds.rank_term(
                    inc[0], q.profile, q.lang, k=k,
                    lang_filter=lang_filter, flag_bit=flag_bit,
                    from_days=m.from_days, to_days=m.to_days, **extra)
        else:
            with StageTimer(EClass.SEARCH, "DEVJOIN"):
                got = ds.rank_join(
                    inc, exc, q.profile, q.lang, k=k,
                    lang_filter=lang_filter, flag_bit=flag_bit,
                    from_days=m.from_days, to_days=m.to_days)
        if got is not None:         # None: the store declined, host ranks
            self._route = "device"
        return got

    def _facet_filter_bitmap(self, ds, m):
        """Device filter bitmap for the active metadata modifiers —
        SAME membership semantics as the host path's _modifier_mask
        (site: exact host or subdomain; tld: suffix; filetype/protocol:
        equality), cached on device per (modifier combo, facet version,
        capacity)."""
        meta = self.segment.metadata
        parts = []
        if m.sitehost:
            parts.append(("site", m.sitehost.lower()))
        if m.tld:
            parts.append(("tld", m.tld.lower()))
        if m.filetype:
            parts.append(("ft", m.filetype.lower()))
        if m.protocol:
            parts.append(("proto", m.protocol.lower()))
        key = (tuple(parts), getattr(meta, "facet_version", 0),
               meta.capacity())

        def docids_fn():
            allowed = None
            for kind, val in parts:
                if kind == "site":
                    suffix = "." + val
                    got = meta.facet_docids(
                        "host_s",
                        lambda h: h == val or h.endswith(suffix))
                elif kind == "tld":
                    suffix = "." + val
                    got = meta.facet_docids(
                        "host_s", lambda h: h.endswith(suffix))
                elif kind == "ft":
                    got = meta.facet_docids("url_file_ext_s", val)
                else:
                    got = meta.facet_docids("url_protocol_s", val)
                allowed = got if allowed is None else \
                    np.intersect1d(allowed, got, assume_unique=False)
            return allowed if allowed is not None else np.empty(0, np.int64)

        return ds.filter_bitmap(key, docids_fn)

    def _second_stage(self, scores, docids, k_need: int,
                      allow_put: bool):
        """The hybrid second stage behind the degradation ladder
        (ISSUE 11): dense-first candidate generation + fusion (sheds at
        rung 1 — ONE rung before the rerank, utils/actuator
        .LEVEL_NO_DENSE_FIRST), the dense rerank (sheds at rung 2), or
        the sparse order as-is. Every rung's output keeps the pinned
        (score DESC, docid ASC) tie discipline, so degraded answers are
        bit-identical to the corresponding non-degraded stage prefix.
        With `allow_put`, files the computed answer in the hybrid top-k
        cache under the context _device_local snapshotted (device-path
        answers only — host-computed orders are not bit-identical)."""
        q = self.query
        if self.degrade_level >= 2:
            # ladder rung 2: skip the whole dense stage — the sparse
            # stage's pinned order serves as-is
            self._note_degraded("RERANK", len(docids))
            return scores, docids
        df_served = False
        if q.dense_first:
            if self.degrade_level >= 1:
                # dense-first sheds one rung BEFORE the rerank: the
                # candidate-generation probe is the more expensive
                # stage, and shedding it still leaves a full hybrid
                # (sparse + rerank) answer
                self._note_degraded("DENSEFIRST", len(docids))
            else:
                with StageTimer(EClass.SEARCH, "DENSEFIRST",
                                len(docids)):
                    got = self._dense_first(scores, docids, k_need)
                if got is not None:
                    scores, docids = got
                    df_served = True
                # None: no ANN index — the plain rerank below serves
                # (counted ann_fallbacks by the store)
        if not df_served:
            with StageTimer(EClass.SEARCH, "DENSERERANK", len(docids)):
                scores, docids = self._dense_rerank(scores, docids)
        if allow_put and self._hybrid_put is not None:
            ds, th, epoch0, dv0, cv0 = self._hybrid_put
            ds.hybrid_cache_put(
                th, q.profile, q.lang, k_need, q.hybrid_alpha,
                epoch0, scores, docids, self.local_rwi_considered,
                dv0=dv0, dense_first=df_served, cv0=cv0)
        return scores, docids

    def _dense_first(self, scores, docids, k: int):
        """Dense-first candidate generation (ISSUE 11): the IVF ANN
        index turns the query vector into a candidate stream that is
        fused with the sparse candidates in ONE cardinal score domain
        (sparse + fixed-scale dense boost) under the pinned (score
        DESC, docid ASC) tie discipline — a document sparse retrieval
        missed can now be recovered by the dense path. Steady state
        rides the devstore's batched `ann` kernel family
        (dense_first_topk); an event without a devstore probes the
        segment's index host-side. Returns None when no ANN index is
        attached (the caller keeps the plain rerank)."""
        q = self.query
        qtext = " ".join(q.include_words())
        qvec = self.segment.encoder.encode(qtext)
        sparse = np.asarray(scores, dtype=np.int64).astype(np.int32)
        dd = np.asarray(docids).astype(np.int32)
        ds = self.segment.devstore
        fn = getattr(ds, "dense_first_topk", None) \
            if ds is not None else None
        if fn is not None:
            got = fn(qvec, sparse, dd, q.hybrid_alpha, k)
            if got is not None:
                s, d = got
                return np.asarray(s, dtype=np.int64), np.asarray(d)
        ann = getattr(self.segment, "ann", None)
        if ann is not None and getattr(ann, "built", False):
            s, d = ann.search_host(qvec, dd, sparse, q.hybrid_alpha, k)
            return np.asarray(s, dtype=np.int64), np.asarray(d)
        return None

    def _dense_rerank(self, scores, docids):
        """M7 second stage: add dense cosine similarity into the sparse
        cardinal scores on device. One score domain throughout — the
        boost has a FIXED scale, so fusion with remote results never
        depends on the local batch's score range.

        Steady state rides the devstore's batched forward-index kernel
        (rerank_boost): candidates gather their doc vectors ON DEVICE
        and concurrent hybrid queries coalesce into one MXU dispatch
        through the pipelined batcher — the per-query get_block gather
        + solo dense_boost_topk hop only survives as the fallback for
        stores without a device path (mesh store, over-budget forward
        index). Both paths order ties by (score DESC, docid ASC) — the
        pinned discipline that keeps solo/batched/packed/cached rerank
        answers identical (arxiv 1807.05798)."""
        q = self.query
        qtext = " ".join(self.query.include_words())
        qvec = self.segment.encoder.encode(qtext)
        docids = np.asarray(docids)
        sparse = np.asarray(scores, dtype=np.int64)
        ds = self.segment.devstore
        rb = getattr(ds, "rerank_boost", None) if ds is not None else None
        if rb is not None:
            got = rb(qvec, sparse.astype(np.int32),
                     docids.astype(np.int32), q.hybrid_alpha)
            if got is not None:
                s, d = got
                return np.asarray(s, dtype=np.int64), np.asarray(d)
        # device lost (ISSUE 10c): the legacy path below still runs a
        # device kernel — on a REAL dead device it would crash the
        # query.  Serve the sparse order instead (the ladder's rung-2
        # prefix: deterministic, tie discipline already applied) and
        # count it as a degraded rerank
        if ds is not None and getattr(ds, "device_lost", False):
            self._note_degraded("RERANK", len(docids))
            return sparse, docids
        # host-gather legacy path (no device store / no device-resident
        # forward index): per-query block upload + solo kernel
        import jax.numpy as jnp

        from ..ops.dense import dense_boost_topk

        doc_vecs = self.segment.dense.get_block(docids)
        k = int(len(docids))
        final, order = dense_boost_topk(
            jnp.asarray(qvec), jnp.asarray(doc_vecs),
            jnp.asarray(sparse.astype(np.int32)),
            jnp.ones(k, dtype=bool), jnp.float32(q.hybrid_alpha), k)
        final = np.asarray(final, dtype=np.int64)
        dd = docids[np.asarray(order)]
        # re-assert the tie discipline (lax.top_k orders ties by input
        # position, i.e. sparse rank): score DESC, then docid ASC
        tie = np.lexsort((dd, -final))
        return final[tie], dd[tie]

    def _constraint_mask(self, plist) -> np.ndarray | None:
        """Vector filters replacing the reference's per-row checks in
        addRWIs (flags/contentdom/language constraints) and the metadata
        recheck in pullOneFilteredFromRWI (site/tld/filetype). None
        where nothing constrains: the caller then ranks the joined block
        as it is (an all-true select copies it in two array calls, each
        of which hands the interpreter lock away)."""
        q = self.query
        m = q.modifier
        flag = _CD_FLAG.get(q.contentdom)
        if flag is None and not (
                m.language or m.from_days is not None
                or m.to_days is not None or m.sitehost or m.tld
                or m.filetype or m.protocol):
            return None
        n = len(plist)
        mask = np.ones(n, dtype=bool)
        # contentdom flag constraint
        if flag is not None:
            mask &= (plist.feats[:, P.F_FLAGS] >> flag) & 1 == 1
        # language modifier is a hard filter (reference: language handled
        # both as filter for /language/ modifier and as ranking preference)
        if q.modifier.language:
            mask &= plist.feats[:, P.F_LANGUAGE] == P.pack_language(
                q.modifier.language)
        # daterange: inclusive bounds on last-modified days
        if q.modifier.from_days is not None:
            mask &= plist.feats[:, P.F_LASTMOD] >= q.modifier.from_days
        if q.modifier.to_days is not None:
            mask &= plist.feats[:, P.F_LASTMOD] <= q.modifier.to_days
        # metadata constraints via the facet inverted indexes: each
        # modifier resolves to a sorted docid set by iterating DISTINCT
        # field values (hosts/extensions/protocols — thousands at most),
        # then one vectorized isin over the candidates. Replaces the
        # per-candidate-row python loop that dominated 100k-row masks
        # (VERDICT r1 weak #5).
        meta = self.segment.metadata
        if m.sitehost:
            want = m.sitehost.lower()
            suffix = "." + want
            allowed = meta.facet_docids(
                "host_s", lambda h: h == want or h.endswith(suffix))
            mask &= np.isin(plist.docids, allowed, assume_unique=False)
        if m.tld:
            suffix = "." + m.tld.lower()
            allowed = meta.facet_docids(
                "host_s", lambda h: h.endswith(suffix))
            mask &= np.isin(plist.docids, allowed, assume_unique=False)
        if m.filetype:
            allowed = meta.facet_docids("url_file_ext_s",
                                        m.filetype.lower())
            mask &= np.isin(plist.docids, allowed, assume_unique=False)
        if m.protocol:
            allowed = meta.facet_docids("url_protocol_s",
                                        m.protocol.lower())
            mask &= np.isin(plist.docids, allowed, assume_unique=False)
        return mask

    def _make_entry(self, rows, i: int, docid: int, score: int):
        """Modifier recheck + ResultEntry over row `i` of a gather
        (_gather -> MetadataStore.rows_at); None when evicted. The entry
        is built from what the gather read: a row deleted since then is
        the race a reader always had with a writer."""
        q = self.query
        if not rows.alive[i]:
            return None
        cols = rows.cols
        url = cols["sku"][i]
        title = cols["title"][i] or url
        if q.url_filter is not None and q.url_filter(url):
            return None
        if q.modifier.inurl and q.modifier.inurl.lower() not in url.lower():
            return None
        if q.modifier.intitle and q.modifier.intitle.lower() not in title.lower():
            return None
        if q.modifier.author:
            if q.modifier.author.lower() not in (cols["author"][i] or "").lower():
                return None
        # metadata-facet recheck (site:/tld:/filetype:/protocol): the
        # device path filters by a facet BITMAP that may be up to
        # FILTER_TTL_S stale under active indexing (devstore
        # .filter_bitmap) — a stale false positive dies here, so staleness
        # only ever DELAYS inclusion (the reference's soft-commit lag)
        mod = q.modifier
        if mod.sitehost or mod.tld or mod.filetype or mod.protocol:
            host = (cols["host_s"][i] or "").lower()
            if mod.sitehost:
                want = mod.sitehost.lower()
                if host != want and not host.endswith("." + want):
                    return None
            if mod.tld and not host.endswith("." + mod.tld.lower()):
                return None
            if mod.filetype and (cols["url_file_ext_s"][i] or "").lower() \
                    != mod.filetype.lower():
                return None
            if mod.protocol and not url.lower().startswith(
                    mod.protocol.lower() + ":"):
                return None
        if q.modifier.keyword:
            if q.modifier.keyword.lower() not in (cols["keywords"][i] or "").lower():
                return None
        # quoted phrases must literally appear (QueryGoal phrase recheck)
        if q.goal.phrases:
            tl = cols["text_t"][i].lower()
            for ph in q.goal.phrases:
                if ph not in tl and ph not in title.lower():
                    return None
        # snippet extraction is deferred to page render (results()):
        # only the ~10 returned entries need one, not the whole
        # oversampled top-k — the drain loop is the serving hot path
        return ResultEntry(
            docid=docid, urlhash=rows.urlhashes[i],
            score=score, url=url, title=title, snippet="",
            host=cols["host_s"][i], filetype=cols["url_file_ext_s"][i],
            language=cols["language_s"][i], size=cols["size_i"][i],
            wordcount=cols["wordcount_i"][i],
            lastmod_days=cols["last_modified_days_i"][i],
            references=cols["references_i"][i])

    # -- fusion (local batch now, remote feeders in M5) ----------------------

    def _insert(self, entry: ResultEntry) -> bool:
        """Dedup + host-diversity + post-ranking + heap insert. Facet
        accumulation happens upstream over the whole candidate set
        (_fill_results), not per inserted entry."""
        q = self.query
        if q.url_filter is not None and entry.url and q.url_filter(entry.url):
            return False
        # remote entries never went through _constraint_mask: recheck the
        # daterange bounds on the metadata they carry (local entries were
        # already filtered; their recheck is a no-op)
        if q.modifier.from_days is not None \
                and entry.lastmod_days < q.modifier.from_days:
            return False
        if q.modifier.to_days is not None \
                and entry.lastmod_days > q.modifier.to_days:
            return False
        if q.modifier.date_sort:
            # one sort key for every producer: recency (remote cardinal
            # scores are on an incomparable scale)
            entry.score = entry.lastmod_days
        with self._lock:
            if entry.urlhash in self._seen_urlhashes:
                return False
            self._seen_urlhashes.add(entry.urlhash)
            hh = hosthash(entry.urlhash)
            cnt = self._host_counts.get(hh, 0)
            if cnt >= q.max_per_host:
                # doubledom diversion: parked, re-merged if page underfills
                self._diverted.append((entry.score, entry))
                return False
            self._host_counts[hh] = cnt + 1
            score = self._post_ranking(entry)
            entry.score = score
            self.result_heap.put(entry, score)
            return True

    def _post_ranking(self, entry: ResultEntry) -> int:
        """Post-sort boosts (reference: SearchEvent.postRanking,
        SearchEvent.java:1963-2021): query appearing in title/url and
        citation references raise the pre-sorted score."""
        q, score = self.query, entry.score
        if q.modifier.date_sort:
            return score  # recency IS the sort key; boosts would distort it
        prof = q.profile
        tl = entry.title.lower()
        ul = entry.url.lower()
        for w in q.goal.include_words:
            if w in tl:
                score += 128 << prof.descrcompintoplist
            if w in ul:
                score += 128 << prof.urlcompintoplist
        if entry.references > 0:
            score += min(entry.references, 255) << prof.citation
        return score

    def add_remote_results(self, entries: list[ResultEntry]) -> int:
        """Feeder entry point for remote peers (M5): merge asynchronously
        into the live event (the reference's addNodes path)."""
        added = 0
        src0 = entries[0].source if entries else ""
        with tracing.span_in(self.trace_ctx, "search.fusion_remote",
                             n=len(entries), peer=src0):
            added = self._add_remote_locked(entries)
        with self._lock:
            self.remote_results += added
            self.touched = time.time()
        return added

    def _add_remote_locked(self, entries: list[ResultEntry]) -> int:
        added = 0
        for e in entries:
            src = getattr(e, "source", None)
            if src and src != "local":
                try:
                    self.result_peer_hashes.add(
                        src.encode("ascii") if isinstance(src, str) else src)
                except UnicodeEncodeError:
                    pass  # non-hash source label: nothing to mark
            if self._insert(e):
                added += 1
        return added

    # -- consumption ---------------------------------------------------------

    def results_available(self) -> int:
        """Heap entries that are actually SERVABLE (snippet-evicted slots
        stay in the heap but never render — paging links must not point
        at pages made only of them)."""
        return max(0, self.result_heap.size_available()
                   - len(self._snippet_evicted))

    def results(self, offset: int | None = None,
                count: int | None = None,
                with_snippets: bool | None = None) -> list[ResultEntry]:
        """One page of results, best-first (oneResult loop equivalent).
        `with_snippets` overrides the query's snippet_fetch for THIS call
        (shared QueryParams on a cached event must never be mutated)."""
        # the metadata join of what the page still lacks, the page and
        # its snippets: one wall, traced or not (`search.page`)
        with tracing.timed("search.page"):
            return self._results(offset, count, with_snippets)

    def _results(self, offset, count, with_snippets) -> list[ResultEntry]:
        self.touched = time.time()
        q = self.query
        offset = q.offset if offset is None else offset
        count = q.item_count if count is None else count
        if with_snippets is None:
            with_snippets = q.snippet_fetch
        need = offset + count
        self._drain(need)
        with self._lock:
            avail = self.results_available()
            if avail < need and self._diverted and not self._pending:
                # page underfills: merge back diverted same-host entries
                # (the reference re-admits doubledom-parked results when the
                # drained stacks run dry, SearchEvent.java:1376-1412)
                self._diverted.sort(key=lambda t: -t[0])
                refill = need - avail
                for score, entry in self._diverted[:refill]:
                    self.result_heap.put(entry, score)
                del self._diverted[:refill]
        got = self._page_entries(offset, count)
        if with_snippets:
            # snippet production may EVICT entries (deleteIfSnippetFail);
            # backfill from the heap until the page fills or runs dry —
            # and RE-DRAIN: evictions consumed materialization cushion,
            # so _pending may still hold live candidates
            while True:
                evicted = self._produce_snippets(got)
                if not evicted:
                    break
                with self._lock:
                    self._drained = max(0, self._drained - evicted)
                self._drain(need)
                refill = self._page_entries(offset, count)
                if [e.urlhash for e in refill] == [e.urlhash for e in got]:
                    break
                got = refill
        return got

    def _page_entries(self, offset: int, count: int) -> list[ResultEntry]:
        """One page from the heap, skipping snippet-evicted entries (their
        heap slots stay; offsets count LIVE entries only)."""
        got: list[ResultEntry] = []
        live = 0
        i = 0
        while len(got) < count:
            el = self.result_heap.element(i, timeout_s=0)
            i += 1
            if el is None:
                break
            e = el.payload
            if e.urlhash in self._snippet_evicted:
                continue
            live += 1
            if live > offset:
                got.append(e)
        return got

    def _produce_snippets(self, entries: list[ResultEntry]) -> int:
        """Fill missing snippets; returns how many entries were evicted
        (reference: concurrent snippet workers + deleteIfSnippetFail,
        SearchEvent.java:1862-1948)."""
        with tracing.span("search.snippets", n=len(entries)) as sp:
            return self._produce_snippets_inner(entries, sp)

    def _produce_snippets_inner(self, entries: list[ResultEntry],
                                sp) -> int:
        from .snippet import (SNIPPET_DEAD, SNIPPET_OK, SnippetProducer)
        q = self.query
        words = q.goal.include_words
        sp.set(inline=0, pooled=0)
        live_jobs: list[ResultEntry] = []
        for e in entries:
            if e.snippet_done or e.snippet:
                continue
            if e.source == "local":
                text = self.segment.metadata.text_value(e.docid, "text_t")
                if text:
                    e.snippet, _ = extract_snippet(text, words)
                    e.snippet_done = True
                    continue
            # stored text gone (blanked row / imported metadata) or a
            # remote result without a peer snippet: live path
            live_jobs.append(e)
        # ladder rung 1 (ISSUE 9): skip LIVE snippet fetches — cache-
        # local extraction above already served what it could; the
        # network fetches (the expensive, latency-tailed part) are the
        # first thing the ladder sheds.  No eviction either: under
        # degradation a missing snippet proves nothing.
        if live_jobs and self.loader is not None \
                and self.degrade_level >= 1:
            self._note_degraded("SNIPPETS", len(live_jobs))
            for e in live_jobs:
                e.snippet_done = True
            return 0
        if not live_jobs or self.loader is None:
            for e in live_jobs:
                e.snippet_done = True
            return 0
        producer = SnippetProducer(self.loader, q.snippet_strategy)
        outcomes = producer.produce_many([e.url for e in live_jobs], words)
        # where the jobs ran: on this thread (cacheonly) or through the
        # pool (a strategy that may go to the network):
        # yacy_stage_events_total SNIPPETS_INLINE / SNIPPETS_POOLED
        inline = len(live_jobs) - producer.pooled
        sp.set(inline=inline, pooled=producer.pooled)
        if inline:
            track(EClass.SEARCH, "SNIPPETS_INLINE", inline)
        if producer.pooled:
            track(EClass.SEARCH, "SNIPPETS_POOLED", producer.pooled)
        evicted = 0
        # eviction applies only when verification was REQUESTED: under
        # cacheonly a missing cache entry proves nothing (the reference
        # keeps unverified results in its cacheonly default too)
        verifying = q.snippet_strategy != "cacheonly"
        for e, (snippet, outcome) in zip(live_jobs, outcomes):
            e.snippet_done = True
            if outcome == SNIPPET_OK:
                e.snippet = snippet
                continue
            if not (verifying and q.snippet_delete_on_fail):
                continue
            with self._lock:
                self._snippet_evicted.add(e.urlhash)
                self.snippet_evictions += 1
            evicted += 1
            if outcome == SNIPPET_DEAD and e.source == "local":
                # the fetch proved the document gone: purge it from the
                # local index (the reference's deleteIfSnippetFail index
                # hygiene; transport errors never purge)
                try:
                    self.segment.remove_document(e.urlhash)
                except Exception:
                    import logging
                    logging.getLogger("search.snippets").warning(
                        "dead-document purge failed for %r; the index "
                        "still claims a URL the snippet fetch proved gone",
                        e.urlhash, exc_info=True)
        return evicted

    def one_result(self, item: int) -> ResultEntry | None:
        page = self.results(offset=item, count=1)
        return page[0] if page else None

    def image_results(self, offset: int | None = None,
                      count: int | None = None) -> list["ImageResult"]:
        """One page of IMAGE results (contentdom=image serving mode).

        Ranked page documents — already constrained to HASIMAGE carriers
        by the contentdom flag filter — expand into one entry per image
        from their indexed ``images_urlstub_sxt``/``images_alt_sxt``
        arrays, deduplicated by image URL across source pages (the first,
        best-ranked, page wins attribution), paged over the expansion.
        Remote entries carry no local metadata row and contribute no
        images (the reference fetches their image fields from the peer's
        metadata lines; our remote ResultEntry surface has no image
        arrays yet). Match: reference SearchEvent.java:2178-2280."""
        from ..index.metadata import split_multi_positional
        from ..utils.hashes import url_file_ext
        q = self.query
        offset = q.offset if offset is None else offset
        count = q.item_count if count is None else count
        need = offset + count
        meta = self.segment.metadata
        out: list[ImageResult] = []
        seen: set[str] = set()
        doc_off = 0
        chunk = max(count, 10)
        # deterministic expansion from rank 0 every call: dedup must
        # see the same prefix regardless of the requested page.
        # with_snippets=False: image mode never shows page snippets, so
        # the carrier scan must not pay a text_t read per document.
        while len(out) < need:
            docs = self.results(offset=doc_off, count=chunk,
                                with_snippets=False)
            if not docs:
                break
            for r in docs:
                if r.source != "local":
                    continue
                stubs = split_multi_positional(
                    meta.text_value(r.docid, "images_urlstub_sxt"))
                if not any(stubs):
                    continue
                protos = split_multi_positional(
                    meta.text_value(r.docid, "images_protocol_sxt"))
                # legacy rows (indexed before the positional arrays)
                # have no protocol column and their alt array dropped
                # empty slots — alignment is unrecoverable, so alts are
                # omitted rather than misattributed (re-crawl restores)
                alts = (split_multi_positional(
                    meta.text_value(r.docid, "images_alt_sxt"))
                    if any(protos) else [])
                for j, stub in enumerate(stubs):
                    key = stub.lower()
                    if not stub or key in seen:
                        continue
                    seen.add(key)
                    proto = (protos[j] if j < len(protos)
                             and protos[j] else "http")
                    image_url = f"{proto}://{stub}"
                    out.append(ImageResult(
                        image_url=image_url,
                        alt=alts[j] if j < len(alts) else "",
                        source_url=r.url, source_title=r.title,
                        source_urlhash=r.urlhash, host=r.host,
                        score=r.score,
                        filetype=url_file_ext(image_url),
                        source=r.source))
            doc_off += len(docs)
            if len(docs) < chunk:
                break
        return out[offset:need]

    def facet(self, name: str, n: int = 10) -> list[tuple[str, int]]:
        nav = self.navigators.get(name)
        return nav.top(n) if nav else []


class SearchEventCache:
    """query-id → live SearchEvent, so paging reuses the executed search
    (reference: SearchEventCache.java:42-199, incl. memory-pressure
    cleanup — here a simple TTL + max-size policy)."""

    def __init__(self, max_events: int = 100, ttl_s: float = 600.0):
        self.max_events = max_events
        self.ttl_s = ttl_s
        self._events: dict[str, SearchEvent] = {}
        self._lock = profiling.ObservedLock("search_cache")
        # most recent event id — the default subject of the search-event
        # picture (reference: SearchEventCache.lastEventID)
        self.last_event_id: str | None = None

    def get_event(self, query: QueryParams, segment: Segment,
                  loader=None) -> SearchEvent:
        qid = query.query_id()
        t0 = time.perf_counter()
        with self._lock:
            ev = self._events.get(qid)
            if ev is not None:
                ev.touched = time.time()
        if ev is not None:
            # the fifth route, counted where it is taken: a live event
            # answers and nothing is ranked (SearchEvent._run_local
            # records the other four)
            tracing.record("search.route.event_cache",
                           (time.perf_counter() - t0) * 1000.0)
            return ev
        ev = SearchEvent(query, segment, loader=loader)
        with self._lock:
            self.cleanup_locked()
            self._events[qid] = ev
            self.last_event_id = qid
        return ev

    def event_by_id(self, qid: str) -> "SearchEvent | None":
        """Look up a LIVE event by its query id — the progressive
        per-item delivery surface (reference: htroot/yacysearchitem.java
        reads the cached event while feeders still run)."""
        with self._lock:
            ev = self._events.get(qid)
            if ev is not None:
                ev.touched = time.time()
            return ev

    def cleanup_locked(self) -> None:
        now = time.time()
        dead = [k for k, e in self._events.items()
                if now - e.touched > self.ttl_s]
        for k in dead:
            del self._events[k]
        while len(self._events) >= self.max_events:
            oldest = min(self._events, key=lambda k: self._events[k].touched)
            del self._events[oldest]

    def clear(self) -> None:
        """Drop every cached event (filter-set changes invalidate results
        computed under the old filter)."""
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)
