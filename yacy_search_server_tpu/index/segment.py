"""Segment — the index core: RWI + metadata + citations behind one facade.

Capability equivalent of the reference's Segment (reference:
source/net/yacy/search/index/Segment.java:135 bundling the RWI term index,
the Solr-backed fulltext store and the citation index; write path
`storeDocument` Segment.java:562-787; read path via kelondro/rwi/TermSearch).

Write path per document (storeDocument parity):
  1. condense -> per-word feature rows (document/condenser.py)
  2. metadata put (columnar store) -> docid
  3. citation index add for every outbound anchor
  4. postprocess references_i / references_exthosts_i for docs cited so far
  5. RWI per-word insert as one dense block append
  6. RAM-buffer flush when over threshold (IndexCell.FlushThread contract)

Read path `term_search` reproduces TermSearch semantics (reference:
kelondro/rwi/TermSearch.java:38-80): conjunction over all included terms
with the all-or-nothing subset rule (if any term has no postings the result
is empty), then destructive exclusion. The conjunctive join itself is a
sorted-docid intersection (the vectorized replacement of
ReferenceContainer.joinConstructive, ReferenceContainer.java:397-489), with
worddistance = span of first-appearance positions across the query terms.
"""

from __future__ import annotations

import re
import threading

import numpy as np

from ..document.condenser import Condenser
from ..document.document import Document
from ..document.langdetect import vote_language
from ..utils.eventtracker import EClass, StageTimer
from ..utils.hashes import url2hash, word2hash
from . import postings as P
from .citation import CitationIndex
from .metadata import DocumentMetadata, MetadataStore, metadata_from_parsed
from .postings import PostingsList
from .rwi import RWIIndex

# a conjunction probes a term's list at the docids that survive so far,
# instead of fetching it whole, when the list is at least this many times
# as long as the driving list; nearer in size it fetches and merges them.
# Read off a measurement (CHANGES.md, PR 26): from here up a probe loses
# little to a linear merge even where the long list is already resident,
# and wins several times that where it would have to be materialized
PROBE_MIN_RATIO = 16

# private-range catchall term: every document is indexed under it so a
# peer can enumerate/count its whole index (reference: Segment.java:766-768
# catchall term insert)
CATCHALL_WORD = "yacyall"


class Segment:
    def __init__(self, data_dir: str | None = None,
                 max_ram_postings: int | None = None):
        self.data_dir = data_dir
        rwi_dir = f"{data_dir}/rwi" if data_dir else None
        meta_dir = f"{data_dir}/meta" if data_dir else None
        kwargs = {}
        if max_ram_postings is not None:
            kwargs["max_ram_postings"] = max_ram_postings
        self.rwi = RWIIndex(rwi_dir, **kwargs)
        self.citations = CitationIndex()
        self.metadata = MetadataStore(meta_dir)
        # per-edge hyperlink store (reference: the webgraph Solr core,
        # search/schema/WebgraphSchema.java:34 — edges written as
        # subdocuments in Segment.storeDocument:642-659)
        from .webgraph import WebgraphStore
        self.webgraph = WebgraphStore(
            f"{data_dir}/webgraph" if data_dir else None)
        # M7 hybrid rerank: doc embeddings aligned to docids (new
        # capability beyond the reference; ops/dense.py)
        from ..ops.dense import HashingEncoder
        from .dense import DenseVectorStore
        self.encoder = HashingEncoder()
        self.dense = DenseVectorStore(
            f"{data_dir}/dense" if data_dir else None,
            dim=self.encoder.dim)
        # optional autotagging source (document/vocabulary.py); when set,
        # store_document writes vocabulary facets into vocabulary_sxt
        # (the reference's vocabulary_* Solr fields from Tokenizer tagging)
        self.vocabularies = None
        # optional synonym library (document/synonyms.py): indexing-time
        # term expansion inside the Condenser
        self.synonyms = None
        # optional gazetteer (document/geolocalization.py): fills missing
        # doc lat/lon from place names before condensing, so the
        # HASLOCATION flag and lat_d/lon_d columns light up
        self.gazetteer = None
        # device-resident serving (index/devstore.py): opt-in via
        # enable_device_serving; Switchboard turns it on by default
        self.devstore = None
        # dense-first IVF ANN index (index/annstore.py, ISSUE 11):
        # built on demand via build_ann_index — embeddings are
        # derivable data, so the index rebuilds rather than persists
        self.ann = None
        self._lock = threading.RLock()

    def enable_device_serving(self, budget_bytes: int = 2 << 30,
                              device=None, packed_residency: bool = False,
                              warm_budget_bytes: int = 1 << 30):
        """Pack frozen runs onto the device and serve eligible queries
        from placed blocks (VERDICT r1 #1: the product path must be the
        benchmark path — reference IndexCell ram/array split,
        kelondro/rwi/IndexCell.java:65-283). `packed_residency` packs
        runs as BIT-PACKED blocks with fused on-device decode and a
        hot/warm/cold tier ladder (index.device.packedResidency) —
        an order of magnitude more corpus per chip at the measured
        compression ratio."""
        from .devstore import DeviceSegmentStore
        if self.devstore is None:
            self.devstore = DeviceSegmentStore(
                self.rwi, device=device, budget_bytes=budget_bytes,
                packed_residency=packed_residency,
                warm_budget_bytes=warm_budget_bytes)
            # hybrid rerank serves from the device-resident forward
            # index of this segment's doc vectors (batched second stage)
            self.devstore.attach_dense(self.dense)
        return self.devstore

    def build_ann_index(self, n_clusters: int | None = None,
                        device_budget_bytes: int = 1 << 30,
                        warm_budget_bytes: int = 1 << 28,
                        **kw):
        """(Re)build the dense-first IVF ANN index over this segment's
        doc embeddings and attach it to the serving store (ISSUE 11).
        Rebuilding bumps the centroid-set version, which invalidates
        every cached dense-first answer through the hybrid cache key.
        Embeddings written AFTER the build have no slab row until the
        next rebuild (they still rank sparse + rerank; the dense-first
        stream just cannot generate them as candidates yet)."""
        from .annstore import AnnVectorIndex
        if len(self.dense) == 0:
            raise ValueError(
                "no dense vectors to index — store documents (or "
                "dense.put vectors) before build_ann_index")
        if self.ann is None:
            self.ann = AnnVectorIndex(
                self.encoder.dim,
                data_dir=f"{self.data_dir}/ann" if self.data_dir
                else None,
                device_budget_bytes=device_budget_bytes,
                warm_budget_bytes=warm_budget_bytes)
        self.ann.build_from_dense(self.dense, n_clusters=n_clusters,
                                  **kw)
        if self.devstore is not None \
                and hasattr(self.devstore, "attach_ann"):
            self.devstore.attach_ann(self.ann)
        return self.ann

    def enable_mesh_serving(self, devices=None, n_term: int = 1,
                            budget_bytes: int = 2 << 30):
        """Multi-chip serving: partition the arena over a ('term','doc')
        mesh and run eligible queries as one SPMD program
        (index/meshstore.py — VERDICT r2 #1: multi-chip is the product
        path, not a demo; reference DHT axes
        cora/federate/yacy/Distribution.java:35-93)."""
        from .meshstore import MeshSegmentStore
        if self.devstore is None:
            self.devstore = MeshSegmentStore(
                self.rwi, devices=devices, n_term=n_term,
                budget_bytes=budget_bytes)
        elif not isinstance(self.devstore, MeshSegmentStore):
            raise RuntimeError(
                "a single-device serving store is already attached; "
                "close it before enabling mesh serving")
        return self.devstore

    # -- write path ----------------------------------------------------------

    def store_document(self, doc: Document, crawldepth: int = 0,
                       collection: str = "user",
                       referrer_urlhash: bytes | None = None,
                       responsetime_ms: int = 0,
                       httpstatus: int = 200,
                       ingest_stamp: float | None = None) -> int:
        """Index one parsed document; returns its docid.

        `ingest_stamp` is the crawl-to-searchable SLO's pipeline-entry
        time (ISSUE 13a): Switchboard.to_indexer stamps it when the
        crawler hands the response over, and it rides here through the
        4-stage pipeline.  Direct callers (surrogate importers, tests)
        get a store-time stamp — the searchable latency they report is
        their own write wall, honestly small."""
        from ..ingest import slo as ingest_slo
        if ingest_stamp is None:
            ingest_stamp = ingest_slo.TRACKER.stamp()
        with StageTimer(EClass.INDEX, "storeDocument", 1):
            # bounded-buffer backpressure (ISSUE 13 satellite): a writer
            # may not outrun the flusher — at the hard cap this blocks
            # (counted, SLO-visible) until a flush drains the buffer.
            # BEFORE the segment lock: a blocked writer must not stall
            # the facade's other writers or the flush thread itself
            self.rwi.wait_capacity()
            urlhash = url2hash(doc.url)
            # language vote (Segment.java:492): metadata vs statistical
            # detection vs TLD hint — every doc gets its best-known lang
            doc.language = vote_language(doc.language, doc.text, doc.url)
            if self.gazetteer is not None and not doc.lat and not doc.lon:
                hit = self.gazetteer.locate_text(
                    f"{doc.title}\n{' '.join(doc.keywords)}\n{doc.text[:2048]}")
                if hit is not None:
                    doc.lat, doc.lon = hit
            condenser = Condenser(doc, synonyms=self.synonyms)

            vocab_sxt = ""
            if self.vocabularies is not None:
                tagmap = self.vocabularies.tag_document(
                    f"{doc.title}\n{doc.text[:8192]}")
                vocab_sxt = ",".join(
                    f"{voc}:{tag}" for voc in sorted(tagmap)
                    for tag in sorted(tagmap[voc]))
            host = _host_of(doc.url)
            meta = metadata_from_parsed(
                urlhash, doc.url, doc.title, doc.text,
                author=doc.author,
                description_txt=doc.description,
                keywords=",".join(doc.keywords),
                host_s=host,
                language_s=doc.language,
                url_file_ext_s=_ext_of(doc.url),
                collection_sxt=collection,
                size_i=len(doc.text),
                wordcount_i=condenser.word_count,
                phrasecount_i=condenser.phrase_count,
                imagescount_i=len(doc.images),
                linkscount_i=len(doc.anchors),
                crawldepth_i=crawldepth,
                doctype_i=doc.doctype,
                flags_i=condenser.content_flags.value,
                last_modified_days_i=doc.publish_date_days,
                **dict(zip(
                    ("references_i", "references_internal_i",
                     "references_external_i", "references_exthosts_i"),
                    self.citations.reference_counts(urlhash))),
                lat_d=doc.lat, lon_d=doc.lon,
                vocabulary_sxt=vocab_sxt,
                vocabularies_sxt=",".join(
                    sorted({v.split(":", 1)[0]
                            for v in vocab_sxt.split(",") if v})),
                fresh_date_days_i=doc.publish_date_days,
                synonyms_sxt=",".join(
                    getattr(condenser, "synonym_terms", [])),
                referrer_id_s=(referrer_urlhash or b"").decode("ascii",
                                                               "replace"),
                responsetime_i=responsetime_ms,
                httpstatus_i=httpstatus,
                **_schema_breadth_fields(doc, host),
            )
            with self._lock:
                # re-index: retire the previous version's identity so its
                # postings can never answer for the new version (put()
                # allocates a fresh docid and dead-marks the old row)
                old_docid = self.metadata.docid(urlhash)
                docid = self.metadata.put(meta)
                if old_docid is not None:
                    self.rwi.delete_doc(old_docid)
                    # targets the old version cited lose one reference;
                    # refresh their counts (the new version's own anchors are
                    # refreshed below)
                    for target in self.citations.remove_citing_doc(old_docid):
                        self._refresh_references(target)
                    self.webgraph.remove_source(old_docid)

                # citations: this doc cites its anchors
                for a in doc.anchors:
                    try:
                        target = url2hash(a.url)
                    except Exception:
                        continue
                    self.citations.add(target, docid, urlhash)
                    self._refresh_references(target)
                # webgraph: one edge row per anchor with link text/rel
                # (Segment.java:642-659 webgraph putEdges)
                self.webgraph.add_document_edges(
                    docid, doc.url, doc.anchors, crawldepth=crawldepth,
                    collection=collection,
                    load_date_days=meta.get("load_date_days_i", 0),
                    last_modified_days=meta.get("last_modified_days_i", 0),
                    host_ranks=getattr(self, "_host_ranks", None))

                # RWI block append; the catchall term gets the neutral
                # doc-level row (not any word's flags/positions)
                doc_row = condenser.doc_row(
                    {P.F_DOMLENGTH: meta.get("domlength_i")})
                term_hashes, rows = condenser.postings_rows(base_row=doc_row)
                seen_terms = set(term_hashes)
                for th, row in zip(term_hashes, rows):
                    self.rwi.add(th, docid, row)
                self.rwi.add(word2hash(CATCHALL_WORD), docid, doc_row)
                # inbound anchor texts make the page findable by what
                # OTHERS call it (reference: webgraph anchor text feeding
                # the target's index via CollectionConfiguration): terms
                # from links already pointing here index under this doc
                # with the description flag set
                self._index_anchor_terms(docid, urlhash, doc_row,
                                         seen_terms)
                self.dense.put(docid, self.encoder.encode(
                    f"{doc.title}\n{doc.text[:4096]}"))

            # the document is searchable from the RAM buffer: the first
            # crawl-to-searchable tier observation; the stamp queues for
            # the flush (-> ingest.flushed) and device pack (-> .device).
            # A flush racing the microseconds between the last rwi.add
            # and this registration claims the buffer WITHOUT this
            # stamp, which then rides the NEXT flush — deliberately
            # conservative: the flushed/device tiers may overstate by
            # one flush period in that window, never report a doc
            # flushed before all its postings froze
            ingest_slo.TRACKER.note_stored(self.rwi, ingest_stamp)
            # flush outside the segment lock: the compressed run write must
            # not stall concurrent readers/other writers on this facade.
            # Single-flight (ISSUE 13): concurrent writers skip instead
            # of stacking duplicate flushes
            self.rwi.maybe_flush()
            return docid

    MAX_ANCHOR_TEXTS = 50

    def _index_anchor_terms(self, docid: int, urlhash: bytes,
                            doc_row, seen_terms: set) -> None:
        """Index the target document under the words of its inbound
        anchor texts (skipping nofollow links and terms the body already
        carries). One posting per new term with FLAG_APP_DC_DESCRIPTION,
        like an in-description appearance."""
        from ..document.condenser import words_of
        from ..utils.bitfield import FLAG_APP_DC_DESCRIPTION
        texts = self.webgraph.anchor_texts(urlhash)[:self.MAX_ANCHOR_TEXTS]
        if not texts:
            return
        extra: set[str] = set()
        for text in texts:
            extra.update(words_of(text.lower()))
        row = doc_row.copy()
        row[P.F_FLAGS] |= 1 << FLAG_APP_DC_DESCRIPTION
        row[P.F_HITCOUNT] = 1
        for word in extra:
            th = word2hash(word)
            if th in seen_terms:
                continue
            self.rwi.add(th, docid, row)

    def _refresh_references(self, target_urlhash: bytes) -> None:
        """Sync a target's references_* metadata columns with the citation
        index (no-op when the target is not indexed here)."""
        cited_docid = self.metadata.docid(target_urlhash)
        if cited_docid is not None:
            total, internal, external, exthosts = \
                self.citations.reference_counts(target_urlhash)
            self.metadata.set_fields(
                cited_docid,
                references_i=total,
                references_internal_i=internal,
                references_external_i=external,
                references_exthosts_i=exthosts)

    def remove_document(self, urlhash: bytes) -> bool:
        """Blacklist/url-delete path: tombstone everywhere."""
        with self._lock:
            docid = self.metadata.delete(urlhash)
            if docid is None:
                return False
            self.rwi.delete_doc(docid)
            for target in self.citations.remove_citing_doc(docid):
                self._refresh_references(target)
            self.webgraph.remove_source(docid)
            return True

    # -- read path -----------------------------------------------------------

    def term_search(self, include_words: list[str] | None = None,
                    exclude_words: list[str] | None = None,
                    include_hashes: list[bytes] | None = None,
                    exclude_hashes: list[bytes] | None = None,
                    how: dict | None = None) -> PostingsList:
        """Conjunctive multi-term search with exclusion (TermSearch parity).

        Reads what the join needs: the shortest include term is fetched
        whole and DRIVES; a term whose list is at least PROBE_MIN_RATIO
        times as long is probed at the surviving docids (rwi.probe)
        instead of being fetched, merged and cached whole; lists of a
        size are fetched and merged as before. The answer is
        join_constructive's over rwi.get of every term, bit for bit: a
        term is probed only where the cheap bounds (rwi.count_bounds)
        prove it longer than the list the rows are taken from. `how`,
        if given, receives the path taken (`single` term, `probe`,
        `merge`), the posting rows read and how many lists were
        probed."""
        inc = list(include_hashes or []) + [word2hash(w) for w in (include_words or [])]
        exc = list(exclude_hashes or []) + [word2hash(w) for w in (exclude_words or [])]
        if how is None:
            how = {}
        how.update(path="single" if len(inc) == 1 else "merge", rows=0,
                   probes=0)
        if not inc:
            return PostingsList.empty()
        rwi = self.rwi

        def probe_of(th, want_feats=True):
            how["path"] = "probe"       # one probed term names the path
            how["probes"] += 1

            def probe(docids):
                found, rows = rwi.probe(th, docids, want_feats)
                how["rows"] += int(found.sum())
                return found, rows
            return probe

        containers, probes = [], []
        if len(inc) == 1:
            containers.append(rwi.get(inc[0]))
        else:
            bounds = [rwi.count_bounds(th) for th in inc]
            # all-or-nothing subset rule (TermSearch.java:56-58): a
            # conjunction missing any term yields nothing
            if any(upper == 0 for _, upper in bounds):
                return PostingsList.empty()
            drive = min(range(len(inc)), key=lambda i: bounds[i][1])
            driving = rwi.get(inc[drive])
            if len(driving) == 0:
                return PostingsList.empty()
            reach = PROBE_MIN_RATIO * len(driving)
            # containers stay in query order: among lists of one length
            # the first is the base
            for i, th in enumerate(inc):
                if i == drive:
                    containers.append(driving)
                elif bounds[i][0] >= reach:
                    probes.append(probe_of(th))
                else:
                    containers.append(rwi.get(th))
        how["rows"] += sum(len(c) for c in containers)
        if any(len(c) == 0 for c in containers):
            return PostingsList.empty()

        joined = join_constructive(containers, probes)
        for th in exc:
            if len(joined) == 0:
                break
            lower, upper = rwi.count_bounds(th)
            if upper == 0:
                continue
            if lower >= PROBE_MIN_RATIO * len(joined):
                found, _ = probe_of(th, want_feats=False)(joined.docids)
                joined = joined.select(~found)
            else:
                ex = rwi.get(th)
                how["rows"] += len(ex)
                if len(ex):
                    joined = exclude_destructive(joined, ex)
        return joined

    def get_metadata(self, docid: int) -> DocumentMetadata | None:
        return self.metadata.get(docid)

    # -- stats ---------------------------------------------------------------

    def doc_count(self) -> int:
        return len(self.metadata)

    def rwi_size(self) -> int:
        return self.rwi.total_postings()

    def close(self) -> None:
        if self.devstore is not None:
            self.devstore.close()
            self.devstore = None
        self.rwi.close()
        self.metadata.close()
        self.webgraph.close()
        self.dense.close()


def join_constructive(containers: list[PostingsList],
                      probes=()) -> PostingsList:
    """Intersect sorted postings on docid; vectorized join.

    Replaces the reference's size-adaptive hash-probe/merge join
    (ReferenceContainer.java:397-489): the materialized `containers`
    are intersected by a linear two-pointer merge (utils/native, else
    np.intersect1d), and each of `probes` — a callable(docids) ->
    (found mask, feature rows of the found), Segment.term_search's
    stand-in for a list far longer than the survivors — narrows them by
    binary search without its list being read whole. Which of the two a
    term gets is term_search's choice, from the lists' lengths. Joined
    feature rows come from the rarest term's postings (the first of the
    shortest containers; a probed term is never shorter); worddistance
    (P.F_WORDDISTANCE) is set to the span of first-appearance positions
    of the query words, matching the reference's accumulated
    position-distance semantics (WordReferenceVars.join); hitcount is
    the minimum over the terms, the flags are OR-ed.
    """
    if not containers:
        return PostingsList.empty()
    if len(containers) == 1 and not probes:
        return containers[0]
    containers = sorted(containers, key=len)
    base = containers[0]
    common = base.docids
    from ..utils.native import intersect as native_intersect
    for c in containers[1:]:
        hit = native_intersect(common, c.docids)
        if hit is not None:
            common = common[hit[0]]
        else:
            common = np.intersect1d(common, c.docids, assume_unique=True)
        if len(common) == 0:
            return PostingsList.empty()

    if common is base.docids:       # nothing merged yet: probes only
        feats = base.feats.copy()
    else:
        feats = base.feats[np.searchsorted(base.docids, common)]
    pos_min = feats[:, P.F_POSINTEXT].copy()
    pos_max = feats[:, P.F_POSINTEXT].copy()

    def fold(other):
        np.minimum(pos_min, other[:, P.F_POSINTEXT], out=pos_min)
        np.maximum(pos_max, other[:, P.F_POSINTEXT], out=pos_max)
        np.minimum(feats[:, P.F_HITCOUNT], other[:, P.F_HITCOUNT],
                   out=feats[:, P.F_HITCOUNT])
        feats[:, P.F_FLAGS] |= other[:, P.F_FLAGS]

    for c in containers[1:]:
        fold(c.feats[np.searchsorted(c.docids, common)])
    for probe in probes:
        found, other = probe(common)
        if not found.all():
            common, feats = common[found], feats[found]
            pos_min, pos_max = pos_min[found], pos_max[found]
            if len(common) == 0:
                return PostingsList.empty()
        fold(other)
    feats[:, P.F_WORDDISTANCE] = pos_max - pos_min
    return PostingsList(common.astype(np.int32), feats)


def exclude_destructive(joined: PostingsList, excluded: PostingsList) -> PostingsList:
    """Drop joined postings whose docid appears in `excluded`
    (ReferenceContainer.excludeDestructive:491 semantics)."""
    mask = ~np.isin(joined.docids, excluded.docids, assume_unique=True)
    return joined.select(mask)


def _urlstub(url: str) -> str:
    """URL without its protocol (the reference's *_urlstub_sxt shape)."""
    return url.split("://", 1)[-1]


def _schema_breadth_fields(doc: Document, host: str) -> dict:
    """The document→schema conversion beyond the core fields — the
    capability analog of CollectionConfiguration.yacy2solr (reference:
    search/schema/CollectionConfiguration.java: link array partitioning,
    heading zone texts, robots/canonical flags, dates-in-content,
    signatures, url/host decomposition)."""
    from urllib.parse import parse_qsl

    from ..document.datedetection import (dates_as_iso, dates_in_content)
    from ..document.signature import (_h63, exact_signature,
                                      fuzzy_profile_text)
    from ..utils.hashes import (_split, _split_host, host_dnc, hosthash,
                                normalform)
    from .metadata import join_multi, join_multi_positional
    fuzzy_profile = fuzzy_profile_text(doc.text)

    # link arrays, partitioned by host (inbound = same host); protocol
    # arrays stay positionally aligned with their stub arrays
    inb_stubs, outb_stubs, inb_texts, outb_texts = [], [], [], []
    inb_protos, outb_protos = [], []
    inb_nofollow = outb_nofollow = 0
    for a in doc.anchors:
        target_host = _host_of(a.url)
        nofollow = "nofollow" in (getattr(a, "rel", "") or "").lower()
        text = (getattr(a, "text", "") or "").strip()
        proto = a.url.split("://", 1)[0] if "://" in a.url else "http"
        if target_host == host:
            inb_stubs.append(_urlstub(a.url))
            inb_protos.append(proto)
            if text:
                inb_texts.append(text)
            inb_nofollow += nofollow
        else:
            outb_stubs.append(_urlstub(a.url))
            outb_protos.append(proto)
            if text:
                outb_texts.append(text)
            outb_nofollow += nofollow

    # heading zones
    headings = doc.headings or {}
    h_fields = {}
    htags = 0
    for level in range(1, 7):
        texts = headings.get(level, [])
        h_fields[f"h{level}_txt"] = join_multi(texts)
        h_fields[f"h{level}_i"] = len(texts)
        if texts:
            htags |= 1 << (level - 1)

    # dates mentioned in the content
    dates = dates_in_content(doc.text)

    # url decomposition
    scheme, _h, _port, path, query = _split(doc.url)
    path_parts = [p for p in path.split("/") if p]
    if path.endswith("/") or not path_parts:
        file_name, path_dirs = "", path_parts
    else:
        file_name, path_dirs = path_parts[-1], path_parts[:-1]
    subdom, organization = _split_host(host)
    dnc, orgdnc = host_dnc(host)
    qsl = parse_qsl(query, keep_blank_values=True)

    canonical_equal = 0
    if doc.canonical:
        # compare against the URL the page was FETCHED under (the parser
        # rewrites doc.url to the canonical, so doc.url would always match)
        fetched = getattr(doc, "fetched_url", doc.url)
        try:
            canonical_equal = int(
                normalform(doc.canonical) == normalform(fetched))
        except Exception:
            canonical_equal = 0

    return dict(
        content_type=doc.mime_type,
        charset_s=doc.charset,
        canonical_s=doc.canonical,
        publisher_t=doc.publisher,
        metagenerator_t=doc.generator,
        inboundlinks_urlstub_sxt=join_multi(inb_stubs),
        outboundlinks_urlstub_sxt=join_multi(outb_stubs),
        inboundlinks_anchortext_txt=join_multi(inb_texts),
        outboundlinks_anchortext_txt=join_multi(outb_texts),
        inboundlinkscount_i=len(inb_stubs),
        outboundlinkscount_i=len(outb_stubs),
        inboundlinksnofollowcount_i=inb_nofollow,
        outboundlinksnofollowcount_i=outb_nofollow,
        linksnofollowcount_i=inb_nofollow + outb_nofollow,
        # urlstubs may dedup-filter, but alt + protocol arrays must stay
        # POSITIONALLY aligned with the stub array (image serving pairs
        # them by index; the reference keeps images_protocol_sxt parallel
        # for the same reason)
        images_urlstub_sxt=join_multi_positional(
            _urlstub(im.url) for im in doc.images),
        images_alt_sxt=join_multi_positional(
            im.alt for im in doc.images),
        images_protocol_sxt=join_multi_positional(
            im.url.split("://", 1)[0] if "://" in im.url else "http"
            for im in doc.images),
        images_withalt_i=sum(1 for im in doc.images if im.alt),
        icons_urlstub_sxt=join_multi(
            [_urlstub(doc.favicon)] if doc.favicon else []),
        audiolinkscount_i=len(doc.audio_links),
        videolinkscount_i=len(doc.video_links),
        applinkscount_i=len(doc.app_links),
        robots_i=doc.robots_flags,
        htags_i=htags,
        dates_in_content_dts=join_multi(dates_as_iso(dates)),
        dates_in_content_count_i=len(dates),
        title_count_i=1 if doc.title else 0,
        title_words_val=len(doc.title.split()),
        description_count_i=1 if doc.description else 0,
        description_words_val=len(doc.description.split()),
        url_protocol_s=scheme,
        url_file_name_s=file_name,
        url_paths_sxt=join_multi(path_dirs),
        url_paths_count_i=len(path_dirs),
        url_parameter_i=len(qsl),
        url_chars_i=len(doc.url),
        host_organization_s=organization,
        host_subdomain_s=subdom,
        canonical_equal_sku_b=canonical_equal,
        exact_signature_l=exact_signature(doc.text),
        # signature = hash of the profile text: compute the (full-text
        # tokenize + count) profile ONCE, hash it here
        fuzzy_signature_l=_h63(fuzzy_profile),
        fuzzy_signature_text_t=fuzzy_profile,
        # optimistic until postprocess_uniqueness() recomputes them
        # (index/postprocess.py) — a fresh doc is unique until proven not
        title_unique_b=1, description_unique_b=1,
        exact_signature_unique_b=1, fuzzy_signature_unique_b=1,
        # -- schema long tail (VERDICT r2 missing #6) ----------------------
        inboundlinks_protocol_sxt=join_multi_positional(inb_protos),
        outboundlinks_protocol_sxt=join_multi_positional(outb_protos),
        host_id_s=hosthash(url2hash(doc.url)).decode("ascii", "replace"),
        host_dnc_s=dnc,
        host_organizationdnc_s=orgdnc,
        md5_s=_md5_hex(doc.text),
        title_exact_signature_l=exact_signature(doc.title),
        description_exact_signature_l=exact_signature(doc.description),
        title_chars_val=len(doc.title),
        description_chars_val=len(doc.description),
        # optimistic until postprocess_uniqueness recomputes
        http_unique_b=1, www_unique_b=1,
        # postprocessing bookkeeping: the doc awaits a citation/uniqueness
        # pass (the reference tags process_sxt and clears it when done)
        process_sxt="postprocessing_in",
        images_text_t=" ".join(im.alt for im in doc.images if im.alt),
        images_height_val=join_multi_positional(
            str(getattr(im, "height", 0) or 0) for im in doc.images),
        images_width_val=join_multi_positional(
            str(getattr(im, "width", 0) or 0) for im in doc.images),
        images_pixel_val=join_multi_positional(
            str((getattr(im, "height", 0) or 0)
                * (getattr(im, "width", 0) or 0)) for im in doc.images),
        li_txt=join_multi(doc.tag_texts.get("li", [])),
        licount_i=len(doc.tag_texts.get("li", [])),
        dt_txt=join_multi(doc.tag_texts.get("dt", [])),
        dtcount_i=len(doc.tag_texts.get("dt", [])),
        dd_txt=join_multi(doc.tag_texts.get("dd", [])),
        ddcount_i=len(doc.tag_texts.get("dd", [])),
        article_txt=join_multi(doc.tag_texts.get("article", [])),
        articlecount_i=len(doc.tag_texts.get("article", [])),
        # emphasis zones: unique texts + positional occurrence counts
        # (CollectionSchema bold_txt/bold_val pairing)
        **_emph_fields(doc.tag_texts),
        boldcount_i=len(doc.tag_texts.get("bold", [])),
        italiccount_i=len(doc.tag_texts.get("italic", [])),
        underlinecount_i=len(doc.tag_texts.get("underline", [])),
        css_url_sxt=join_multi(doc.css),
        css_tag_sxt=join_multi(getattr(doc, "css_tags", [])),
        csscount_i=len(doc.css),
        scripts_sxt=join_multi(doc.scripts),
        scriptscount_i=doc.script_count,
        frames_sxt=join_multi(doc.frames),
        framesscount_i=len(doc.frames),
        iframes_sxt=join_multi(doc.iframes),
        iframesscount_i=len(doc.iframes),
        refresh_s=doc.refresh,
        flash_b=int(doc.flash),
        hreflang_cc_sxt=join_multi_positional(
            cc for cc, _u in doc.hreflangs),
        hreflang_url_sxt=join_multi_positional(
            u for _cc, u in doc.hreflangs),
        navigation_type_sxt=join_multi_positional(
            t for t, _u in doc.navigation),
        navigation_url_sxt=join_multi_positional(
            u for _t, u in doc.navigation),
        opengraph_title_t=doc.opengraph.get("title", ""),
        opengraph_type_s=doc.opengraph.get("type", ""),
        opengraph_url_s=doc.opengraph.get("url", ""),
        opengraph_image_s=doc.opengraph.get("image", ""),
        publisher_url_s=doc.publisher_url,
        url_file_name_tokens_t=" ".join(
            t for t in re.split(r"[^0-9a-zA-Z]+", file_name) if t),
        url_parameter_key_sxt=join_multi_positional(
            k for k, _v in qsl),
        url_parameter_value_sxt=join_multi_positional(
            v for _k, v in qsl),
        # page-technology evaluation (document/evaluation.py)
        **_evaluation_fields(getattr(doc, "evaluation", None)),
        **h_fields,
    )


def _emph_fields(tag_texts: dict) -> dict:
    """bold/italic/underline: unique texts (first-seen order) + their
    positional occurrence counts (bold_txt + bold_val etc.)."""
    from .metadata import join_multi, join_multi_positional
    out: dict = {}
    for tag in ("bold", "italic", "underline"):
        counts: dict[str, int] = {}
        for t in tag_texts.get(tag, []):
            counts[t] = counts.get(t, 0) + 1
        out[f"{tag}_txt"] = join_multi(counts)
        out[f"{tag}_val"] = join_multi_positional(
            str(c) for t, c in counts.items() if t)
    return out


def _evaluation_fields(ev) -> dict:
    """ext_<category>_txt / _val pairs from the page evaluation."""
    if not ev:
        return {}
    from .metadata import join_multi_positional
    out = {}
    for cat, (names, counts) in ev.items():
        out[f"ext_{cat}_txt"] = join_multi_positional(names)
        out[f"ext_{cat}_val"] = join_multi_positional(
            str(c) for c in counts)
    return out


def _md5_hex(text: str) -> str:
    import hashlib
    return hashlib.md5(text.encode("utf-8", "replace")).hexdigest()


def _host_of(url: str) -> str:
    from ..utils.hashes import safe_host
    return safe_host(url)


def _ext_of(url: str) -> str:
    from ..utils.hashes import url_file_ext
    return url_file_ext(url)
