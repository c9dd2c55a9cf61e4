"""The reverse word index (RWI) — LSM store of term -> postings.

Capability equivalent of the reference's IndexCell machinery (reference:
source/net/yacy/kelondro/rwi/IndexCell.java:65-283 — RAM cache + on-disk
container array + background flush/merge; ReferenceContainerCache /
ReferenceContainerArray). The shape survives because it is also the TPU
checkpoint story (SURVEY.md §5): a mutable RAM buffer absorbs writes, is
frozen into immutable sorted runs (which are what uploads to the device),
and runs are merged in the background.

Differences from the reference, by design:
- postings are dense numpy SoA blocks (index/postings.py), not byte rows;
- a frozen run persists as a disk-paged flat file pair (.dat/.tix,
  index/pagedrun.py) served through mmap with a byte-budget term LRU, so
  resident memory is bounded regardless of index size (round-1 .npz runs
  are still readable and are rewritten paged at the next merge);
- deletes are docid tombstones applied at read and folded in at merge,
  replacing the reference's in-place row removal — immutable runs cannot be
  mutated, and the device arrays built from them must not be either.

Thread model: writers append to the RAM buffer under a lock; `flush()`
freezes the buffer synchronously (callers may run it on a background
BusyThread, matching IndexCell.FlushThread); readers merge RAM + runs.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from . import integrity
from .colstore import journal_append
from .integrity import CorruptRunError
from .pagedrun import PagedRun, TermCache
from .postings import (NF, PostingsList, merge, probe_rows, remove_docids,
                       sort_dedupe)
from ..ingest import slo as ingest_slo
from ..utils import faultinject, profiling
from ..utils.eventtracker import EClass, update as track

log = logging.getLogger("yacy.rwi")

# flush threshold, postings count — reference default `wordCacheMaxCount`
# (defaults/yacy.init:793)
DEFAULT_MAX_RAM_POSTINGS = 50_000

# bounded-buffer hard cap = factor × the flush threshold (ISSUE 13
# satellite): past it writers BLOCK (counted) instead of growing the
# RAM buffer unboundedly between needs_flush() checks
DEFAULT_BACKPRESSURE_FACTOR = 2.0

# resident-postings budget for the shared paged-run term cache
DEFAULT_TERM_CACHE_BYTES = 256 << 20


def _b64key(termhash: bytes) -> str:
    return termhash.decode("ascii")


class FrozenRun:
    """Immutable sorted run held in RAM: term -> PostingsList.

    Two roles: (a) the only run form for RAM-only indexes (no data_dir);
    (b) the transient form a fresh flush/merge serves from while its
    PagedRun file is being written outside the lock (then swapped out).
    Shares the run interface with pagedrun.PagedRun: get/probe/has/
    term_hashes/drop_term/span/close.
    """

    def __init__(self, terms: dict[bytes, PostingsList], path: str | None = None,
                 dead_seq: int = -1):
        self.terms = terms
        self.path = path
        self.n_postings = sum(len(p) for p in terms.values())
        # tombstone count at creation (see PagedRun.dead_seq)
        self.dead_seq = dead_seq

    def get(self, termhash: bytes) -> PostingsList | None:
        return self.terms.get(termhash)

    def probe(self, termhash: bytes, docids: np.ndarray,
              want_feats: bool = True):
        p = self.terms.get(termhash)
        if p is None:
            return None
        return probe_rows(p.docids, p.feats, docids, want_feats)

    def has(self, termhash: bytes) -> bool:
        return termhash in self.terms

    def term_hashes(self):
        return self.terms.keys()

    def drop_term(self, termhash: bytes) -> int:
        p = self.terms.pop(termhash, None)
        if p is None:
            return 0
        self.n_postings -= len(p)
        return len(p)

    def span(self, termhash: bytes):
        return None  # not flat-file backed

    def all_spans(self) -> dict[bytes, tuple[int, int]]:
        """Flat-layout spans in the same (sorted-by-termhash) order that
        flat_chunks streams — the RAM twin of PagedRun.all_spans."""
        spans: dict[bytes, tuple[int, int]] = {}
        start = 0
        for th in sorted(self.terms):
            n = len(self.terms[th])
            spans[th] = (start, n)
            start += n
        return spans

    def flat_chunks(self, chunk_rows: int):
        for th in sorted(self.terms):
            p = self.terms[th]
            for lo in range(0, len(p), chunk_rows):
                yield p.docids[lo:lo + chunk_rows], p.feats[lo:lo + chunk_rows]

    def docids_of(self, termhash: bytes) -> np.ndarray | None:
        p = self.terms.get(termhash)
        return None if p is None else p.docids

    def close(self) -> None:
        pass

    def save(self, path: str) -> None:
        """Legacy .npz writer (round-1 format; kept for migration tests)."""
        arrays: dict[str, np.ndarray] = {}
        for th, p in self.terms.items():
            k = _b64key(th)
            arrays["d_" + k] = p.docids
            arrays["f_" + k] = p.feats
        tmp = path + ".tmp.npz"  # .npz suffix stops numpy renaming it
        np.savez_compressed(tmp, **arrays)
        os.replace(tmp, path)
        self.path = path

    @staticmethod
    def load(path: str) -> "FrozenRun":
        terms: dict[bytes, PostingsList] = {}
        with np.load(path) as z:
            for name in z.files:
                if not name.startswith("d_"):
                    continue
                k = name[2:]
                terms[k.encode("ascii")] = PostingsList(z[name], z["f_" + k])
        return FrozenRun(terms, path)


class RWIIndex:
    """RAM buffer + frozen runs, with tombstones and background-mergeable runs."""

    def __init__(self, data_dir: str | None = None,
                 max_ram_postings: int = DEFAULT_MAX_RAM_POSTINGS,
                 term_cache_bytes: int = DEFAULT_TERM_CACHE_BYTES):
        self.data_dir = data_dir
        self.max_ram_postings = max_ram_postings
        self.term_cache = TermCache(term_cache_bytes)
        # optional run-lifecycle listener (index/devstore.py packs runs onto
        # the device through these hooks): on_run_added / on_run_swapped /
        # on_run_removed / on_doc_deleted / on_term_dropped
        self.listener = None
        self._ram: dict[bytes, list[tuple[int, np.ndarray]]] = {}
        self._ram_count = 0
        self._runs: list = []  # FrozenRun | PagedRun, oldest first
        self._tombstones: set[int] = set()
        self._dead_arr: np.ndarray | None = None  # cached sorted tombstones
        self._lock = profiling.ObservedRLock("rwi")
        # bounded-buffer backpressure (ISSUE 13 satellite): hard cap =
        # backpressure_factor × max_ram_postings; wait_capacity blocks
        # (counted) past it, _flush_lock makes the flush single-flight
        # (concurrent writers skip or wait instead of stacking flushes)
        self.backpressure_factor = DEFAULT_BACKPRESSURE_FACTOR
        self._flush_lock = threading.Lock()
        self._capacity = threading.Condition(self._lock)
        self._run_seq = 0
        self._dels = None  # deletion journal: "D <docid>" / "T <termhash> <seq>"
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            # manifest records chronological run order (merge renumbers runs,
            # so filename sort order is not history order)
            mp = os.path.join(data_dir, "runs.txt")
            if os.path.exists(mp):
                with open(mp, "r", encoding="ascii") as f:
                    names = [ln.strip() for ln in f if ln.strip()]
            else:
                names = sorted(fn for fn in os.listdir(data_dir)
                               if fn.startswith("run-")
                               and fn[-4:] in (".npz", ".dat"))
            for fn in names:
                p = os.path.join(data_dir, fn)
                if os.path.exists(p):
                    # a corrupt/truncated run QUARANTINES at open (ISSUE
                    # 10): the node comes up serving the surviving
                    # generations instead of refusing to start — the
                    # files stay on disk for forensics/repair
                    try:
                        if fn.endswith(".npz"):   # round-1: full load
                            self._runs.append(FrozenRun.load(p))
                        else:          # paged: index only, mmap data
                            self._runs.append(
                                PagedRun.open(p, self.term_cache))
                    except CorruptRunError as e:
                        integrity.note_corruption("run", "quarantined")
                        log.error("quarantined corrupt run %s: %s",
                                  fn, e)
                    except Exception as e:   # legacy npz zip damage
                        integrity.note_corruption("run", "error")
                        integrity.note_corruption("run", "quarantined")
                        log.error("quarantined unreadable run %s: %r",
                                  fn, e)
                    self._run_seq = max(self._run_seq, int(fn[4:-4]) + 1)
            dp = os.path.join(data_dir, "deletions.log")
            if os.path.exists(dp):
                self._replay_deletions(dp)
            self._dels = open(dp, "a", encoding="ascii")

    def _write_manifest(self) -> None:
        if not self.data_dir:
            return
        mp = os.path.join(self.data_dir, "runs.txt")
        tmp = mp + ".tmp"
        # snapshot the run list under the (reentrant) lock; the write
        # itself needs only the frozen name list
        with self._lock:
            names = [os.path.basename(r.path) for r in self._runs
                     if r.path]
        with open(tmp, "w", encoding="ascii") as f:
            for name in names:
                f.write(name + "\n")
            f.flush()
            os.fsync(f.fileno())
        # chaos barrier: manifest .tmp durable but not renamed — restart
        # must serve the OLD manifest's run set, bit-identically
        faultinject.crashpoint("rwi.manifest.mid_write")
        os.replace(tmp, mp)
        from .colstore import fsync_dir
        fsync_dir(self.data_dir)

    # lint: unlocked-ok(construction-time: only the __init__ open path
    # calls this, before the index is shared with any other thread)
    def _replay_deletions(self, path: str) -> None:
        def run_seq_of(run) -> int:
            return int(os.path.basename(run.path)[4:-4]) if run.path else -1

        # shared scaffold (integrity.journal_lines): torn-tail repair
        # before the append-mode reopen, crc verification, and the
        # final-line-torn vs mid-file-corruption classification (a lost
        # delete re-surfaces rows; it cannot desync docids the way a
        # lost metadata put would)
        for payload, is_last in integrity.journal_lines(path, "rwi"):
            fields = payload.strip().split(" ")
            if not fields or not fields[0]:
                continue
            if fields[0] == "D":
                try:
                    self._tombstones.add(int(fields[1]))
                except (ValueError, IndexError):
                    if is_last:
                        integrity.note_torn_tail("rwi")
                    else:
                        integrity.note_corruption("journal", "error")
            elif fields[0] == "T":
                try:
                    th = fields[1].encode("ascii")
                    # horizon: only runs frozen before the removal are
                    # affected — the term may have been re-added since
                    horizon = int(fields[2]) if len(fields) > 2 \
                        else 1 << 30
                except (ValueError, IndexError,
                        UnicodeEncodeError):
                    # damaged legacy (crc-less) record: classified like
                    # the D branch, never a refused startup
                    if is_last:
                        integrity.note_torn_tail("rwi")
                    else:
                        integrity.note_corruption("journal", "error")
                    continue
                for run in self._runs:
                    if run_seq_of(run) >= horizon:
                        continue
                    run.drop_term(th)

    def _journal_deletion(self, line: str) -> None:
        if self._dels:
            # shared append+fsync helper (ISSUE 10 satellite): a
            # returned delete is on the platter, crc-prefixed
            journal_append(self._dels, line)

    def _quarantine_run(self, run, err) -> None:
        """Pull a corrupt run from serving (ISSUE 10 tentpole a): the
        term that tripped the checksum — and every other term of the
        run — is answered from the surviving generations + RAM from now
        on; a query NEVER crashes on disk corruption.  The files stay
        on disk (and in the manifest) for forensics/repair — a restart
        re-opens them and re-quarantines on the next bad read.  close()
        invalidates the run's TermCache entries; the listener hook
        drops its arena spans and bumps the epoch, so no cached or
        device answer built on the corrupt bytes survives."""
        with self._lock:
            if run not in self._runs:
                return          # raced: another reader already pulled it
            self._runs = [r for r in self._runs if r is not run]
            integrity.note_corruption("run", "quarantined")
        log.error("quarantined corrupt run %s: %s",
                  os.path.basename(run.path) if run.path else "<ram>",
                  err)
        run.close()             # drops the run's TermCache entries
        if self.listener is not None:
            self.listener.on_run_removed(run)
        track(EClass.INDEX, "run_quarantine", 1)

    # -- write path ----------------------------------------------------------

    def add(self, termhash: bytes, docid: int, feats: np.ndarray) -> None:
        """Append one posting to the RAM buffer (urlhash row -> docid row)."""
        assert feats.shape == (NF,)
        with self._lock:
            self._ram.setdefault(termhash, []).append((docid, feats))
            self._ram_count += 1

    def add_many(self, termhash: bytes, postings: PostingsList) -> None:
        """Bulk append (index transfer receive path)."""
        with self._lock:
            bucket = self._ram.setdefault(termhash, [])
            for i in range(len(postings)):
                bucket.append((int(postings.docids[i]), postings.feats[i]))
            self._ram_count += len(postings)

    def ingest_run(self, terms: dict[bytes, PostingsList]):
        """Bulk-ingest a prebuilt term->postings mapping as one frozen run,
        bypassing the per-posting RAM buffer — the fast path for surrogate
        imports (WARC/dump ingestion) and index-transfer batches, where the
        postings already arrive in columnar form (reference analog: the
        surrogate importers feeding storeDocument in bulk)."""
        with self._lock:
            clean = {th: sort_dedupe(p.docids, p.feats)
                     for th, p in terms.items() if len(p)}
            if not clean:
                return None
            run = FrozenRun(clean, dead_seq=len(self._tombstones))
            path = None
            if self.data_dir:
                path = os.path.join(self.data_dir,
                                    f"run-{self._run_seq:06d}.dat")
            self._run_seq += 1
            self._runs.append(run)
            snapshot = dict(clean)
        out = run
        if self.listener is not None:
            self.listener.on_run_added(run)
        if path:
            paged = PagedRun.write(path, snapshot, self.term_cache,
                                   dead_seq=run.dead_seq)
            out = self._swap_run(run, paged)
        track(EClass.WORDCACHE, "ingest", run.n_postings)
        return out

    def needs_flush(self) -> bool:
        return self._ram_count >= self.max_ram_postings

    def hard_max_ram_postings(self) -> int:
        """The bounded buffer's blocking cap (ISSUE 13 satellite)."""
        return int(self.max_ram_postings * self.backpressure_factor)

    def wait_capacity(self, timeout_s: float = 30.0) -> float:
        """Bounded-buffer backpressure: block the calling writer while
        the RAM buffer sits at/over the hard cap.  The first writer to
        arrive becomes the flusher (single-flight via _flush_lock);
        the rest wait on the capacity condition the flush notifies.
        Every blocked entry is COUNTED and its wall observed into the
        ``ingest.backpressure`` histogram — the SLO sees backpressure
        instead of reading a stalled write path as "no traffic".
        Returns the blocked milliseconds (0.0 on the fast path)."""
        hard = self.hard_max_ram_postings()
        if self._ram_count < hard:
            return 0.0
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        while self._ram_count >= hard:
            if self._flush_lock.acquire(blocking=False):
                try:
                    if self._ram_count >= hard:
                        self.flush()
                finally:
                    self._flush_lock.release()
                break
            with self._capacity:
                if self._ram_count < hard:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    # never wedge a writer forever on a stuck flusher:
                    # the overflow is bounded by what fit before the cap
                    log.warning("backpressure wait timed out at %d "
                                "buffered postings", self._ram_count)
                    break
                self._capacity.wait(min(remaining, 0.5))
        blocked_ms = (time.monotonic() - t0) * 1000.0
        ingest_slo.TRACKER.note_backpressure(blocked_ms)
        return blocked_ms

    def maybe_flush(self):
        """Single-flight flush trigger (the write path's call): at most
        one writer freezes the buffer; concurrent writers return
        immediately instead of stacking duplicate flushes behind the
        segment facade (the pre-ISSUE-13 needs_flush()/flush() pair
        outside the segment lock let every writer start one)."""
        if not self.needs_flush():
            return None
        if not self._flush_lock.acquire(blocking=False):
            return None          # a flush is already in flight
        try:
            if not self.needs_flush():
                return None
            return self.flush()
        finally:
            self._flush_lock.release()

    def flush(self):
        """Freeze the RAM buffer into an immutable run (and persist it).

        The disk write happens OUTSIDE the lock: queries and writers
        proceed against the already-appended in-RAM run while the paged
        file is being written (the reference's FlushThread dumps in the
        background for the same reason, IndexCell.java:115-160); the RAM
        form is then swapped for the mmap-backed PagedRun, releasing the
        postings from host memory."""
        with self._lock:
            terms: dict[bytes, PostingsList] = {}
            for th, rows in self._ram.items():
                if not rows:  # bucket emptied by delete_doc
                    continue
                d = np.fromiter((r[0] for r in rows), dtype=np.int32, count=len(rows))
                f = np.stack([r[1] for r in rows]).astype(np.int32)
                terms[th] = sort_dedupe(d, f)
            n = self._ram_count
            self._ram = {}
            self._ram_count = 0
            # crawl-to-searchable stamps (ISSUE 13a): claim the entry
            # stamps whose docs this flush freezes, and wake writers
            # blocked on the bounded buffer — the buffer just drained
            stamps = ingest_slo.TRACKER.flush_begin(self)
            self._capacity.notify_all()
            if not terms:  # only emptied buckets: nothing to persist
                # every covered doc was deleted before the freeze: the
                # claimed stamps can never reach the flushed tier —
                # counted drops, never a silent discard
                ingest_slo.TRACKER.discard(stamps)
                return None
            run = FrozenRun(terms, dead_seq=len(self._tombstones))
            # snapshot for the outside-lock write: a concurrent remove_term
            # may pop from the live run.terms dict mid-write
            snapshot = dict(terms)
            path = None
            if self.data_dir:
                path = os.path.join(self.data_dir, f"run-{self._run_seq:06d}.dat")
            self._run_seq += 1
            self._runs.append(run)
        out = run
        # attach the stamps BEFORE the device listener packs the run:
        # the pack completion observes the ingest.device tier from them
        ingest_slo.TRACKER.run_pending(run, stamps)
        if self.listener is not None:
            self.listener.on_run_added(run)
        if path:
            paged = PagedRun.write(path, snapshot, self.term_cache,
                                   dead_seq=run.dead_seq)
            out = self._swap_run(run, paged)
        # the flush covering these docs has returned (durable with a
        # data dir): the ingest.flushed tier observation
        ingest_slo.TRACKER.flush_done(stamps)
        track(EClass.WORDCACHE, "flush", n)
        return out

    def _swap_run(self, ram_run: FrozenRun, paged: PagedRun):
        """Replace a just-persisted in-RAM run with its PagedRun, carrying
        over any term drops that landed while the file was being written."""
        with self._lock:
            live = set(ram_run.terms.keys())
            for th in [t for t in paged.term_hashes() if t not in live]:
                paged.drop_term(th)
            try:
                i = self._runs.index(ram_run)
            except ValueError:
                # merged away while writing: the file pair is orphaned (it
                # never reached the manifest) — remove it, or a future
                # listdir-fallback open would resurrect folded-in deletions
                paged.close()
                for p in (paged.path, paged.path[:-4] + ".tix"):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                return ram_run
            self._runs[i] = paged
            # chaos barrier: run file pair durable, manifest not yet
            # rewritten to reference it — restart serves the pre-flush
            # state (the orphan pair is invisible; acked docs were only
            # acked AFTER a completed flush)
            faultinject.crashpoint("rwi.flush.before_manifest")
            self._write_manifest()
            if self.listener is not None:
                self.listener.on_run_swapped(ram_run, paged)
            return paged

    def merge_runs(self, max_runs: int = 8) -> bool:
        """Merge the smallest runs into one when there are more than max_runs.

        Returns True if a merge happened (BusyThread contract). Tombstones
        are folded in during the merge: merged runs are physically clean.
        """
        with self._lock:
            if len(self._runs) <= max_runs:
                return False
            # victims must be a chronological prefix: runs are ordered
            # oldest-first and later runs win docid collisions, so merging
            # an arbitrary size-based subset would let stale rows resurface
            victims = self._runs[: len(self._runs) - max_runs + 1]
            all_terms: set[bytes] = set()
            for r in victims:
                all_terms.update(r.term_hashes())
            dead = self._dead_sorted()
            # transient RAM spike proportional to the victims' size — a
            # merge is a rewrite; steady-state residency stays paged
            merged: dict[bytes, PostingsList] = {}
            corrupt = None
            for th in all_terms:
                parts = []
                for r in victims:
                    try:
                        p = r.get(th)
                    except CorruptRunError as e:
                        corrupt = (r, e)
                        break
                    if p is not None:
                        parts.append(p)
                if corrupt is not None:
                    break
                m = remove_docids(merge(parts), dead)
                if len(m):
                    merged[th] = m
            if corrupt is not None:
                # a victim failed its span checksum mid-merge: abort
                # this merge (no state was swapped yet), quarantine the
                # corrupt run, let the next merge pass fold survivors
                self._quarantine_run(*corrupt)
                return False
            new_run = FrozenRun(merged, dead_seq=len(self._tombstones))
            snapshot = dict(merged)  # outside-lock write vs remove_term race
            save_path = None
            if self.data_dir:
                # fresh sequence number: keeps it past every journaled T-line
                # horizon (its term removals are physically folded in);
                # chronological position is preserved by the manifest instead
                save_path = os.path.join(self.data_dir,
                                         f"run-{self._run_seq:06d}.dat")
            self._run_seq += 1
            victim_paths = [r.path for r in victims if r.path]
            # merged run replaces the victims at the FRONT (oldest position)
            self._runs = [new_run] + [r for r in self._runs if r not in victims]
        # listener first (pack the merged run, retire the victims' extents)
        # and only then the paged swap: on_run_swapped re-keys the packed
        # extents from the FrozenRun to its PagedRun, so the registration
        # must exist before the swap or the merged run is never packed
        if self.listener is not None:
            self.listener.on_run_added(new_run)
            for r in victims:
                self.listener.on_run_removed(r)
        # paged write outside the lock, then swap the RAM form out
        if save_path:
            paged = PagedRun.write(save_path, snapshot, self.term_cache,
                                   dead_seq=new_run.dead_seq)
            self._swap_run(new_run, paged)
        else:
            with self._lock:
                self._write_manifest()
        for r in victims:
            r.close()
        # chaos barrier: merged run live in the manifest, victims not
        # yet unlinked — restart serves the merged run; the stale files
        # are unreferenced disk garbage, not resurrected state
        faultinject.crashpoint("rwi.merge.before_unlink")
        for p in victim_paths:
            for path in (p, p[:-4] + ".tix" if p.endswith(".dat") else None):
                if path:
                    try:
                        os.remove(path)
                    except OSError:
                        pass
        track(EClass.INDEX, "merge", len(victims))
        return True

    def delete_doc(self, docid: int) -> None:
        """Tombstone a document everywhere (blacklist/url removal path)."""
        with self._lock:
            self._tombstones.add(docid)
            self._dead_arr = None  # invalidate the sorted-array cache
            for rows in self._ram.values():
                kept = [r for r in rows if r[0] != docid]
                self._ram_count -= len(rows) - len(kept)
                rows[:] = kept
            self._journal_deletion(f"D {docid}")
        if self.listener is not None:
            self.listener.on_doc_deleted(docid)

    def remove_term(self, termhash: bytes) -> PostingsList:
        """Remove and return a term's postings (DHT delete-on-select handoff,
        reference: peers/Dispatcher.java:296 selectContainersEnqueueToBuffer).

        Materializes paged postings under the lock: the read-then-drop must
        be atomic versus other removers, and a concurrent merge may unlink
        the backing file the moment the term leaves the run's index. This
        path is a rare batch operation (DHT shard handoff), not the query
        hot path — see get() for the lock-free read."""
        with self._lock:
            parts: list[PostingsList] = []
            rows = self._ram.pop(termhash, None)
            if rows:
                self._ram_count -= len(rows)
                d = np.fromiter((r[0] for r in rows), dtype=np.int32, count=len(rows))
                f = np.stack([r[1] for r in rows]).astype(np.int32)
                parts.append(sort_dedupe(d, f))
            for run in list(self._runs):
                try:
                    p = run.get(termhash)
                except CorruptRunError as e:
                    # the handoff loses this run's share of the term
                    # (counted); the run leaves serving entirely
                    self._quarantine_run(run, e)
                    continue
                if p is not None:
                    run.drop_term(termhash)
                    if self.listener is not None:
                        self.listener.on_term_dropped(run, termhash)
                    parts.append(p)
            self._journal_deletion(f"T {termhash.decode('ascii')} {self._run_seq}")
            return self._apply_tombstones(merge(parts))

    # -- read path -----------------------------------------------------------

    def _ram_postings(self, termhash: bytes) -> PostingsList | None:
        with self._lock:     # reentrant: get() already holds it
            rows = list(self._ram.get(termhash) or ())
        if not rows:
            return None
        d = np.fromiter((r[0] for r in rows), dtype=np.int32, count=len(rows))
        f = np.stack([r[1] for r in rows]).astype(np.int32)
        return sort_dedupe(d, f)

    def _dead_sorted(self) -> np.ndarray:
        """Sorted tombstone array, cached (rebuilt only after delete_doc)."""
        if self._dead_arr is None:
            self._dead_arr = np.fromiter(sorted(self._tombstones),
                                         dtype=np.int32,
                                         count=len(self._tombstones))
        return self._dead_arr

    def _apply_tombstones(self, p: PostingsList) -> PostingsList:
        if not self._tombstones or len(p) == 0:
            return p
        return remove_docids(p, self._dead_sorted())

    def get(self, termhash: bytes) -> PostingsList:
        """A term's full postings: RAM + all runs merged, tombstones applied.

        Later-written postings win on docid collision (RAM beats runs).
        Paged-run materialization (mmap page-ins) happens OUTSIDE the lock:
        runs are immutable, so only the run-list snapshot and the RAM
        buffer need the lock — a cold-term disk read must not stall
        writers (the round-1 store held the lock across reads because they
        were pure dict lookups)."""
        with self._lock:
            runs = list(self._runs)
            ram = self._ram_postings(termhash)
            dead = self._dead_sorted() if self._tombstones else None
        parts: list[PostingsList] = []
        for run in runs:
            try:
                p = run.get(termhash)
            except CorruptRunError as e:
                # NEVER a query crash (ISSUE 10): quarantine the run,
                # serve the term from the surviving generations + RAM
                self._quarantine_run(run, e)
                continue
            if p is not None:
                parts.append(p)
        if ram is not None:
            parts.append(ram)  # last -> wins collisions
        out = merge(parts)
        if dead is not None and len(out):
            out = remove_docids(out, dead)
        return out

    def probe(self, termhash: bytes, docids: np.ndarray,
              want_feats: bool = True):
        """The rows get(termhash) holds AT the sorted int32 `docids`,
        without materializing the term: (found mask over `docids`,
        feature rows aligned to docids[found], or None when
        `want_feats` is false and the membership alone is wanted).

        Walks the generations a get() would merge, newest first, and
        the first generation that holds a docid answers for it (RAM
        beats runs, a later run an earlier one: merge()'s last-wins);
        tombstoned docids are in no answer. A paged run is read through
        PagedRun.probe, which verifies a span before its first row is
        served; a mismatch quarantines the run exactly as in get(), and
        the surviving generations answer."""
        docids = np.asarray(docids, dtype=np.int32)
        with self._lock:
            runs = list(self._runs)
            ram = self._ram_postings(termhash)
            dead = self._dead_sorted() if self._tombstones else None
        generations = runs[::-1]
        if ram is not None:
            generations.insert(0, FrozenRun({termhash: ram}))
        holders = [run for run in generations if run.has(termhash)]

        def ask(run, at):
            try:
                return run.probe(termhash, at, want_feats)
            except CorruptRunError as e:
                self._quarantine_run(run, e)
                return None

        if dead is None and len(holders) == 1:
            # the usual case, one generation and no tombstone: its answer
            # is the answer, with no bookkeeping between the array calls
            # (each of which hands the interpreter lock to another thread)
            got = ask(holders.pop(), docids)
            if got is not None:
                return got
        found = np.zeros(len(docids), dtype=bool)
        feats = np.empty((len(docids), NF), np.int32) if want_feats else None
        todo = np.arange(len(docids))     # positions no generation holds yet
        if dead is not None:
            todo = todo[~probe_rows(dead, None, docids, False)[0]]
        for run in holders:
            if len(todo) == 0:
                break
            got = ask(run, docids[todo])
            if got is None:
                continue
            hit, rows = got
            found[todo[hit]] = True
            if want_feats:
                feats[todo[hit]] = rows
            todo = todo[~hit]
        return found, (feats[found] if want_feats else None)

    def count(self, termhash: bytes) -> int:
        """Posting count (the queryRWICount RPC answer); tombstones applied."""
        return len(self.get(termhash))

    def count_bounds(self, termhash: bytes) -> tuple[int, int]:
        """Cheap (lower, upper) bounds on len(get(termhash)): per-run span
        extents + RAM buffer length, NO postings materialization and no
        tombstone filtering. Upper: every generation's rows (a docid in
        two generations counts twice). Lower: the merged list holds at
        least its largest run's rows, less at most one row a tombstone
        (no finer: ingest_run does not hold its rows to the tombstones
        its dead_seq claims folded in)."""
        with self._lock:
            upper = largest = 0
            ram = self._ram.get(termhash)
            if ram is not None:
                upper += len(ram)
            for run in list(self._runs):
                sp = run.span(termhash)
                if sp is not None:
                    n = sp[1]
                elif run.has(termhash):
                    try:
                        p = run.get(termhash)
                    except CorruptRunError as e:
                        self._quarantine_run(run, e)
                        continue
                    n = len(p) if p is not None else 0
                else:
                    continue
                upper += n
                largest = max(largest, n)
            return max(0, largest - len(self._tombstones)), upper

    def count_upper(self, termhash: bytes) -> int:
        """count_bounds' upper bound. Gate decisions (device vs host
        path) only need the magnitude."""
        return self.count_bounds(termhash)[1]

    def has_term(self, termhash: bytes) -> bool:
        with self._lock:
            if termhash in self._ram:
                return True
            return any(r.has(termhash) for r in self._runs)

    def term_hashes(self) -> set[bytes]:
        with self._lock:
            out = set(self._ram.keys())
            for r in self._runs:
                out.update(r.term_hashes())
            return out

    def terms_in_ring_segment(self, start_pos: int, limit_pos: int) -> list[bytes]:
        """Term hashes whose ring position lies in [start, limit) on the closed
        ring — the DHT transfer selection primitive."""
        from ..parallel.distribution import horizontal_dht_position
        out = []
        for th in self.term_hashes():
            pos = horizontal_dht_position(th)
            if start_pos <= limit_pos:
                if start_pos <= pos < limit_pos:
                    out.append(th)
            else:  # wrapped segment
                if pos >= start_pos or pos < limit_pos:
                    out.append(th)
        return out

    # -- stats / lifecycle ---------------------------------------------------

    @property
    def ram_postings_count(self) -> int:
        return self._ram_count

    def total_postings(self) -> int:
        with self._lock:
            return self._ram_count + sum(r.n_postings for r in self._runs)

    def run_count(self) -> int:
        with self._lock:
            return len(self._runs)

    def close(self) -> None:
        self.flush()
        # drop any stamp state keyed by this instance's id: the tracker
        # is process-global, and a later RWIIndex allocated at the
        # freed address must not inherit a dead store's pending stamps
        ingest_slo.TRACKER.forget(self)
        if self._dels:
            self._dels.close()
            self._dels = None
        with self._lock:
            for r in self._runs:
                r.close()
