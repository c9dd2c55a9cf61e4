"""Disk-paged frozen runs — postings served from mmap, not host RAM.

Capability equivalent of the reference's on-disk container array, which
streams term containers from BLOB heap files instead of materializing the
whole index in heap (reference: source/net/yacy/kelondro/blob/HeapReader.java:60
index-then-seek reads; kelondro/rwi/ReferenceContainerArray.java:45). The
round-1 store loaded every frozen ``.npz`` run fully into host RAM at
startup, capping the index at host-memory size; a ``PagedRun`` instead
keeps only the per-term offset index resident and maps the flat postings
arrays with ``np.memmap`` — the OS pages postings in on access, and a
shared byte-budget LRU (`TermCache`) keeps hot terms materialized.

File format (one run = two files, written atomically via os.replace):

    run-XXXXXX.dat   int32 little-endian: docids[total] then feats[total, NF]
    run-XXXXXX.tix   text: "PR2 <total> <dead_seq>" header, then one line
                     per term: "<termhash> <start> <count> <crc8hex>"
                     (rows into .dat, sorted by termhash for deterministic
                     files; crc32 over the term's docid+feat row bytes),
                     then a "#CRC <crc8hex>" footer over every preceding
                     byte.  PR1 files (no checksums) stay readable.

Read-side integrity (ISSUE 10): `open` scrubs the .tix (footer crc,
parseable lines) and the .dat size against the header — truncation or
garbage raises a typed `integrity.CorruptRunError` instead of an
unhandled struct/mmap crash; a span materializing off the mmap verifies
its per-term crc lazily (VERIFY_ON_READ), so cold-tier page corruption
is detected at read and the owning RWIIndex QUARANTINES the run (term
answered from surviving generations/RAM, never a query crash).

Postings of one term are contiguous rows ``[start, start+count)`` in both
sections, docid-sorted — which is also exactly the span shape the device
arena packs from (index/devstore.py), so packing a run onto the TPU reads
each term once, straight off the map.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict

import numpy as np

from ..utils import faultinject
from . import integrity
from .integrity import CorruptRunError
from .postings import NF, PostingsList, probe_rows

_MAGIC = "PR2"
_LEGACY_MAGICS = ("PR1",)   # round-2 format: no per-term checksums


class TermCache:
    """Shared LRU of materialized PostingsLists under a byte budget.

    One cache serves every PagedRun of an index (keys are (run_path, term))
    so the budget bounds total resident postings regardless of run count.
    """

    def __init__(self, budget_bytes: int = 64 << 20):
        self.budget_bytes = budget_bytes
        self._bytes = 0
        self._map: OrderedDict[tuple, PostingsList] = OrderedDict()
        self._lock = threading.Lock()
        # observability (ISSUE 8 satellite): the cold tier's paging
        # behavior was invisible — a paging storm (mass evictions, a
        # collapsed hit ratio) could only be inferred from latency.
        # Exact under the cache lock; surfaced in devstore.counters()
        # and /metrics (yacy_term_cache_total) so traces and the health
        # rules can attribute cold-tier cost.
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.puts = 0

    @staticmethod
    def _cost(p: PostingsList) -> int:
        return p.docids.nbytes + p.feats.nbytes

    def get(self, key: tuple) -> PostingsList | None:
        with self._lock:
            p = self._map.get(key)
            if p is not None:
                self._map.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return p

    def peek(self, key: tuple) -> PostingsList | None:
        """The resident copy if there is one (a hit, recency refreshed);
        absence counts no miss: the caller reads the map instead of
        materializing (the probe path)."""
        with self._lock:
            p = self._map.get(key)
            if p is not None:
                self._map.move_to_end(key)
                self.hits += 1
            return p

    def put(self, key: tuple, p: PostingsList) -> None:
        cost = self._cost(p)
        if cost > self.budget_bytes:
            return  # larger than the whole budget: serve uncached
        with self._lock:
            self.puts += 1
            old = self._map.pop(key, None)
            if old is not None:
                self._bytes -= self._cost(old)
            self._map[key] = p
            self._bytes += cost
            while self._bytes > self.budget_bytes and self._map:
                _, ev = self._map.popitem(last=False)
                self._bytes -= self._cost(ev)
                self.evictions += 1

    def invalidate(self, key: tuple) -> None:
        with self._lock:
            p = self._map.pop(key, None)
            if p is not None:
                self._bytes -= self._cost(p)

    def invalidate_run(self, run_path: str) -> None:
        with self._lock:
            dead = [k for k in self._map if k[0] == run_path]
            for k in dead:
                self._bytes -= self._cost(self._map.pop(k))

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes


class PagedRun:
    """Immutable disk run: per-term offset index + mmap'd flat arrays."""

    def __init__(self, path: str, index: dict[bytes, tuple[int, int]],
                 total: int, cache: TermCache | None = None,
                 dead_seq: int = -1,
                 crcs: dict[bytes, int] | None = None):
        self.path = path
        self._index = index                  # termhash -> (start, count)
        self._total = total
        self._cache = cache
        # per-term span checksums (crc32 over docid+feat row bytes);
        # empty for legacy PR1 files — no claim, no verification
        self._crcs = crcs or {}
        # spans whose bytes matched their crc since this run was opened:
        # a run is immutable, so the probe path checks a span once and
        # then reads single rows off the map (the set dies with the run,
        # a term's entry with drop_term)
        self._verified: set[bytes] = set()
        # both memmaps published through ONE attribute: readers run
        # lock-free (rwi.get materializes spans outside the index lock),
        # so the pair must appear atomically — publishing docids and
        # feats as two attributes lets a concurrent reader observe
        # (docids, None) mid-init
        self._mm: tuple[np.ndarray, np.ndarray] | None = None
        self.n_postings = sum(c for _, c in index.values())
        # tombstone count at creation: this run's rows exclude every
        # tombstone journaled before it was written (flush purges the RAM
        # buffer; merge folds). Consumed by the device store's pruning
        # eligibility; -1 = unknown (legacy file without the header field).
        self.dead_seq = dead_seq

    # -- construction --------------------------------------------------------

    @staticmethod
    def write(path: str, terms: dict[bytes, PostingsList],
              cache: TermCache | None = None,
              dead_seq: int = -1) -> "PagedRun":
        """Persist a term->postings dict as one paged run (atomic)."""
        order = sorted(terms.keys())
        total = sum(len(terms[th]) for th in order)
        index: dict[bytes, tuple[int, int]] = {}
        crcs: dict[bytes, int] = {}
        tmp_dat, tmp_tix = path + ".tmp", _tix_path(path) + ".tmp"
        faultinject.io_error(path)
        with open(tmp_dat, "wb") as f:
            start = 0
            for th in order:
                index[th] = (start, len(terms[th]))
                darr = np.ascontiguousarray(terms[th].docids, dtype="<i4")
                f.write(darr)
                # span checksum: docid row bytes then feat row bytes —
                # exactly what get() re-reads off the mmap
                farr = np.ascontiguousarray(terms[th].feats, dtype="<i4")
                crcs[th] = integrity.crc32(
                    farr.reshape(-1).view(np.uint8),
                    integrity.crc32(darr.view(np.uint8)))
                start += len(terms[th])
            for th in order:
                f.write(np.ascontiguousarray(terms[th].feats, dtype="<i4"))
            f.flush()
            os.fsync(f.fileno())
        body = [f"{_MAGIC} {total} {dead_seq}"]
        for th in order:
            s, c = index[th]
            body.append(f"{th.decode('ascii')} {s} {c} {crcs[th]:08x}")
        text = "\n".join(body) + "\n"
        text += f"#CRC {integrity.crc32(text.encode('ascii')):08x}\n"
        with open(tmp_tix, "w", encoding="ascii") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        # data file lands before the index that references it; the dir
        # fsync makes both renames durable (colstore.fsync_dir)
        os.replace(tmp_dat, path)
        # chaos barrier: .dat visible under its final name, .tix still
        # .tmp — the restart must treat the run as absent (the manifest
        # never referenced it) instead of crashing on the missing .tix
        faultinject.crashpoint("pagedrun.write.dat_renamed")
        os.replace(tmp_tix, _tix_path(path))
        from .colstore import fsync_dir
        fsync_dir(os.path.dirname(path) or ".")
        return PagedRun(path, index, total, cache, dead_seq, crcs)

    @staticmethod
    def open(path: str, cache: TermCache | None = None) -> "PagedRun":
        """Open + scrub: footer crc over the .tix, parseable span lines,
        and a .dat sized to the header's row count.  Truncation or
        garbage raises a typed CorruptRunError (counted kind=run,
        action=error) — callers quarantine; nothing struct/mmap-crashes
        a query later."""
        index: dict[bytes, tuple[int, int]] = {}
        crcs: dict[bytes, int] = {}
        try:
            with open(_tix_path(path), "r", encoding="ascii") as f:
                raw = f.read()
            lines = raw.splitlines()
            if not lines:
                raise CorruptRunError(f"empty run index {path}")
            header = lines[0].split()
            if not header or header[0] not in (_MAGIC,) + _LEGACY_MAGICS:
                raise CorruptRunError(
                    f"bad run header in {path}: {header[:3]}")
            total = int(header[1])
            dead_seq = int(header[2]) if len(header) > 2 else -1
            span_lines = lines[1:]
            if span_lines and span_lines[-1].startswith("#CRC "):
                footer = span_lines.pop()
                if integrity.VERIFY_ON_READ:
                    want = int(footer.split()[1], 16)
                    upto = raw.rindex("#CRC ")
                    if integrity.crc32(raw[:upto].encode("ascii")) \
                            != want:
                        raise CorruptRunError(
                            f"run index checksum mismatch in {path}")
                    integrity.note_verified()
            for line in span_lines:
                fields = line.split()
                th, s, c = fields[0], fields[1], fields[2]
                index[th.encode("ascii")] = (int(s), int(c))
                if len(fields) > 3:
                    crcs[th.encode("ascii")] = int(fields[3], 16)
            want_bytes = total * 4 + total * NF * 4
            have = os.path.getsize(path)
            if have < want_bytes:
                raise CorruptRunError(
                    f"run data {path} truncated: {have} bytes < "
                    f"{want_bytes} expected for {total} rows")
            for s, c in index.values():
                if s < 0 or c < 0 or s + c > total:
                    raise CorruptRunError(
                        f"run index {path}: span ({s},{c}) outside "
                        f"{total} rows")
        except CorruptRunError:
            integrity.note_corruption("run", "error")
            raise
        except (OSError, ValueError, IndexError, UnicodeDecodeError) as e:
            integrity.note_corruption("run", "error")
            raise CorruptRunError(f"corrupt run {path}: {e!r}") from e
        return PagedRun(path, index, total, cache, dead_seq, crcs)

    def _maps(self) -> tuple[np.ndarray, np.ndarray]:
        maps = self._mm
        if maps is None:
            docids = np.memmap(self.path, dtype="<i4", mode="r",
                               shape=(self._total,))
            feats = np.memmap(self.path, dtype="<i4", mode="r",
                              offset=self._total * 4,
                              shape=(self._total, NF))
            maps = self._mm = (docids, feats)
        return maps

    # -- run interface (shared with rwi.FrozenRun) ---------------------------

    def get(self, termhash: bytes) -> PostingsList | None:
        span = self._index.get(termhash)
        if span is None:
            return None
        key = (self.path, termhash)
        if self._cache is not None:
            hit = self._cache.get(key)
            if hit is not None:
                return hit
        start, count = span
        docids, feats = self._maps()
        p = PostingsList(np.array(docids[start:start + count]),
                         np.array(feats[start:start + count]))
        # lazy verify-on-read (ISSUE 10): the span's bytes just paged in
        # off the cold tier — verify them ONCE per materialization (a
        # TermCache hit re-serves verified rows with zero recompute).
        # Mismatch raises typed; the owning RWIIndex quarantines the run
        # and answers the term from surviving generations/RAM.
        self._verify_span(termhash, p.docids, p.feats)
        if self._cache is not None:
            self._cache.put(key, p)
        return p

    def _verify_span(self, termhash: bytes, docids: np.ndarray,
                     feats: np.ndarray) -> None:
        """Hold a span's bytes (a copy, or the map's slices: the crc
        streams over the buffer) to the crc its .tix line claims."""
        want = self._crcs.get(termhash)
        if want is None or not integrity.VERIFY_ON_READ:
            return      # legacy file or verification off: no claim made
        if len(docids) and integrity.crc_arrays(docids, feats) != want:
            integrity.note_corruption("run", "error")
            raise CorruptRunError(
                f"span checksum mismatch for term "
                f"{termhash.decode('ascii', 'replace')} in "
                f"{self.path}")
        integrity.note_verified()
        self._verified.add(termhash)

    def probe(self, termhash: bytes, docids: np.ndarray,
              want_feats: bool = True):
        """postings.probe_rows of `docids` against this run's span of the
        term, or None if the run does not hold the term. Reads the
        TermCache's copy where one is resident, else the map — after the
        WHOLE span has passed its crc once since the run was opened: no
        row or docid of an unverified span is served, as through get()."""
        span = self._index.get(termhash)
        if span is None:
            return None
        p = None
        if self._cache is not None:
            p = self._cache.peek((self.path, termhash))
        if p is not None:
            return probe_rows(p.docids, p.feats, docids, want_feats)
        start, count = span
        all_docids, all_feats = self._maps()
        span_docids = all_docids[start:start + count]
        span_feats = all_feats[start:start + count]
        if termhash not in self._verified:
            self._verify_span(termhash, span_docids, span_feats)
        return probe_rows(span_docids, span_feats, docids, want_feats)

    def span(self, termhash: bytes) -> tuple[int, int] | None:
        """(start, count) rows of a term in the flat arrays (arena packing)."""
        return self._index.get(termhash)

    def all_spans(self) -> dict[bytes, tuple[int, int]]:
        """Live term -> (start, count) in file-row coordinates. Rows of
        dropped terms remain in the file (and in flat_chunks) but are
        unreferenced — same dead-space-until-merge contract as the file."""
        return dict(self._index)

    def flat_chunks(self, chunk_rows: int):
        """Stream the whole run as (docids, feats) numpy chunks in file
        order (device-arena packing reads the map once, sequentially)."""
        docids, feats = self._maps()
        for lo in range(0, self._total, chunk_rows):
            hi = min(self._total, lo + chunk_rows)
            yield np.array(docids[lo:hi]), np.array(feats[lo:hi])

    def docids_of(self, termhash: bytes) -> np.ndarray | None:
        """A term's sorted docids straight off the map (join path — avoids
        materializing the feature rows)."""
        span = self._index.get(termhash)
        if span is None:
            return None
        start, count = span
        return self._maps()[0][start:start + count]

    def has(self, termhash: bytes) -> bool:
        return termhash in self._index

    def term_hashes(self):
        return self._index.keys()

    def drop_term(self, termhash: bytes) -> int:
        """Remove a term from the run's view (delete-on-select handoff);
        returns the dropped posting count. The .dat rows stay on disk until
        the next merge rewrites the run — same semantics as the round-1
        in-RAM pop, which also only reclaimed space at merge."""
        span = self._index.pop(termhash, None)
        if span is None:
            return 0
        if self._cache is not None:
            self._cache.invalidate((self.path, termhash))
        self._verified.discard(termhash)
        self.n_postings -= span[1]
        return span[1]

    def close(self) -> None:
        # do NOT null the memmaps: rwi.get snapshots the run list and
        # materializes spans OUTSIDE the index lock, so a reader may
        # still be inside get() when merge retirement closes this run —
        # yanking the maps hands that reader (docids, None).  The pages
        # stay valid even after the victim file is unlinked (live mmap);
        # the last snapshot reference dying is what frees them.
        if self._cache is not None:
            self._cache.invalidate_run(self.path)


def _tix_path(dat_path: str) -> str:
    return dat_path[:-4] + ".tix" if dat_path.endswith(".dat") else dat_path + ".tix"
