"""Read-side integrity — checksum footers, corruption accounting,
torn-tail accounting (ISSUE 10 tentpole a).

Five rounds of durability work made every store WRITE carefully (tmp +
fsync + rename + dir-fsync, journal generations, manifest ordering) and
then trusted every READ blindly: a flipped bit in a paged run's mmap, a
truncated ``.tix``, or a torn segment blob would surface as an
unhandled struct/mmap crash inside a query — the exact opposite of the
degrade-gracefully contract the north star needs.  This module is the
shared substrate:

- **crc32 footers** (zlib — already in-tree, no new deps) on every
  durable artifact: per-term-span checksums in ``PagedRun`` ``.tix``
  files (verified lazily when a span materializes off the mmap),
  per-column checksums in colstore segment headers (verified once per
  reader, on first touch), and crc-prefixed journal lines
  (``<crc8hex> <payload>``) on the metadata/webgraph/rwi journals.
- **verify switch**: :data:`VERIFY_ON_READ` turns read-side
  verification off (tests/test_integrity.py, tests/test_dense.py: a
  corrupt-crc file then still loads).  Writers ALWAYS emit checksums;
  only read-side verification toggles.
- **corruption counters**: every detection increments
  ``yacy_storage_corruption_total{kind,action}`` via
  :func:`note_corruption`; quarantine actions (a corrupt run pulled
  from serving, the term answered from surviving generations) are the
  graceful path, ``error`` actions raised a typed exception to the
  caller.  The ``storage_corruption`` health rule goes critical on any
  new event, which dumps a flight-recorder incident on the edge.
- **torn-tail counters**: a journal replay that drops a torn final
  line (the expected kill−9 artifact) counts it per store
  (``yacy_journal_torn_tail_total{store}``) instead of logging only —
  the chaos harness and fleet digests can now SEE partial-write
  recoveries (ISSUE 10 satellite).

Typed errors: :class:`CorruptRunError` / :class:`CorruptSegmentError` /
:class:`CorruptJournalError` all extend :class:`CorruptionError`, so
callers can catch the storage class without fishing for struct/json/
mmap internals.
"""

from __future__ import annotations

import os
import threading
import zlib

# the read-side verification switch; checksums are always WRITTEN —
# only verification toggles
VERIFY_ON_READ = True


def set_verify_on_read(on: bool) -> None:
    global VERIFY_ON_READ
    VERIFY_ON_READ = bool(on)


def verify_on_read() -> bool:
    return VERIFY_ON_READ


class CorruptionError(Exception):
    """Base of every checksum/format corruption the storage layer
    detects — callers catch THIS, not struct/json/mmap internals."""


class CorruptRunError(CorruptionError):
    """A paged run (.dat/.tix pair) failed open-scrub or a span's
    read-time checksum — the run is quarantine material."""


class CorruptSegmentError(CorruptionError):
    """A colstore segment failed open-scrub or a column checksum."""


class CorruptDenseError(CorruptionError):
    """The dense vector snapshot (vectors.npy) failed its crc32 footer
    or does not parse — quarantine material (dense serving degrades to
    sparse-only boosts; embeddings are re-encodable from text_t, so
    nothing irrecoverable is lost)."""


class CorruptJournalError(CorruptionError, ValueError):
    """A journal record failed its line checksum / decode mid-file (a
    torn FINAL line is recovered and counted, never raised).  Also a
    ValueError: the metadata replay raised ValueError on mid-file
    damage before this type existed, and its callers/tests catch
    that."""


def crc32(data: bytes, prev: int = 0) -> int:
    return zlib.crc32(data, prev) & 0xFFFFFFFF


def crc_arrays(*arrays) -> int:
    """One crc over the raw bytes of several numpy arrays, in order —
    the per-term-span / per-column checksum."""
    c = 0
    for a in arrays:
        c = zlib.crc32(memoryview(a).cast("B"), c)
    return c & 0xFFFFFFFF


# -- journal line checksums --------------------------------------------------
# format: "<crc8hex> <payload>" where crc is over the payload bytes.
# Legacy lines (no prefix) parse as before — old journals stay readable.

def crc_line(payload: str) -> str:
    return f"{crc32(payload.encode('utf-8')):08x} {payload}"


def check_line(line: str) -> tuple[str, bool]:
    """(payload, ok).  A line without a crc prefix is legacy: returned
    verbatim with ok=True (no claim made).  A prefixed line returns its
    payload with ok = crc match (when VERIFY_ON_READ; else True)."""
    if len(line) > 9 and line[8] == " ":
        prefix = line[:8]
        try:
            want = int(prefix, 16)
        except ValueError:
            return line, True           # not a crc prefix: legacy line
        payload = line[9:]
        if VERIFY_ON_READ and crc32(payload.encode("utf-8")) != want:
            return payload, False
        return payload, True
    return line, True


# -- counters ----------------------------------------------------------------

_lock = threading.Lock()
_corruption: dict[tuple[str, str], int] = {}
_torn_tails: dict[str, int] = {}
_verified = 0

# zero-filled on /metrics so health rules and alert expressions always
# resolve (the no-dead-rules discipline)
CANONICAL_EVENTS = (
    ("run", "quarantined"),      # corrupt span/open: run pulled from serving
    ("run", "error"),            # open failed with no index to quarantine from
    ("segment", "error"),        # segment open-scrub failure (structural)
    ("segment", "served_degraded"),  # column content crc mismatch: data
    #                                  served anyway (no redundant
    #                                  generation exists), loudly counted
    ("journal", "error"),        # mid-file journal record checksum mismatch
    ("dense", "quarantined"),    # dense vector snapshot crc mismatch:
    #                              file quarantined, sparse-only serving
)
JOURNAL_STORES = ("metadata", "webgraph", "rwi", "frontier", "errors")


def note_corruption(kind: str, action: str) -> None:
    with _lock:
        _corruption[(kind, action)] = _corruption.get((kind, action), 0) + 1


def corruption_counts() -> dict:
    """(kind, action) -> count, zero-filled over CANONICAL_EVENTS."""
    with _lock:
        out = {ka: 0 for ka in CANONICAL_EVENTS}
        out.update(_corruption)
        return out


def corruption_total() -> int:
    with _lock:
        return sum(_corruption.values())


def repair_torn_tail(path: str, store: str) -> bool:
    """Truncate a journal's torn FINAL line (a file not ending in a
    newline is mid-append kill−9 debris) BEFORE replay/reopen.  Without
    this the journal is reopened in append mode and the next record is
    glued onto the partial line — corrupting an acked, fsync'd record
    on the following restart.  Backscans for the last newline (bounded
    chunks, no full read), truncates after it, counts the torn tail.
    Returns True when a repair happened."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return False
    if size == 0:
        return False
    with open(path, "rb+") as f:
        f.seek(size - 1)
        if f.read(1) == b"\n":
            return False                    # clean tail: nothing torn
        pos = size
        cut = 0
        chunk = 1 << 16
        while pos > 0:
            lo = max(0, pos - chunk)
            f.seek(lo)
            buf = f.read(pos - lo)
            nl = buf.rfind(b"\n")
            if nl >= 0:
                cut = lo + nl + 1
                break
            pos = lo
        f.truncate(cut)
        f.flush()
        os.fsync(f.fileno())
    note_torn_tail(store)
    return True


def journal_lines(path: str, store: str):
    """THE shared journal replay scaffold: torn-tail repair, then a
    STREAMED read (one-line lookahead — a long-crawl host journal can
    be large and the old per-store loops never doubled startup RSS)
    splitting records on ``\\n`` ONLY (file iteration never splits on
    U+2028/U+2029/U+0085, which ``ensure_ascii=False`` payloads can
    legitimately contain), decoded with ``errors="replace"`` (a
    bit-flipped byte must become a crc-failing line, not an uncaught
    ``UnicodeDecodeError`` that refuses startup), crc verification per
    line, and the shared damage classification: a damaged FINAL line is
    the expected kill−9 artifact (torn tail, recovered + counted),
    damage earlier is real journal corruption (counted; the
    storage_corruption rule sees it).  Yields ``(payload, is_last)``
    for every intact line."""
    repair_torn_tail(path, store)

    def classify(line: str, is_last: bool):
        if not line.strip():
            return
        payload, ok = check_line(line)
        if not ok:
            if is_last:
                note_torn_tail(store)
            else:
                note_corruption("journal", "error")
            return
        yield payload, is_last

    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            prev: str | None = None
            for raw in f:
                if prev is not None:
                    yield from classify(prev, False)
                prev = raw.rstrip("\n")
            if prev is not None:
                yield from classify(prev, True)
    except OSError:
        return


def journal_records(path: str, store: str):
    """`journal_lines` + JSON decoding, classifying an undecodable
    payload exactly like a crc failure (torn tail if final, corruption
    otherwise).  Yields dict records."""
    import json
    for payload, is_last in journal_lines(path, store):
        try:
            yield json.loads(payload)
        except json.JSONDecodeError:
            if is_last:
                note_torn_tail(store)
            else:
                note_corruption("journal", "error")


def note_torn_tail(store: str) -> None:
    """A journal replay dropped a torn tail line (the expected kill−9
    artifact — recovered, visible, counted)."""
    with _lock:
        _torn_tails[store] = _torn_tails.get(store, 0) + 1


def torn_tail_counts() -> dict:
    with _lock:
        out = {s: 0 for s in JOURNAL_STORES}
        out.update(_torn_tails)
        return out


def note_verified(n: int = 1) -> None:
    """A checksum verification actually ran (the --integrity-overhead
    gate asserts the ON windows were not vacuous)."""
    global _verified
    with _lock:
        _verified += n


def verified_total() -> int:
    with _lock:
        return _verified


def reset_counters() -> None:
    """Test isolation only — production counters are monotonic."""
    global _verified
    with _lock:
        _corruption.clear()
        _torn_tails.clear()
        _verified = 0
