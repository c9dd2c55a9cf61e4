"""Immutable columnar segment files — the metadata store's disk format.

The paging engine under ``MetadataStore`` and ``WebgraphStore`` (VERDICT
r2 missing #2): the same shape ``pagedrun.py`` gave postings, applied to
document/edge columns. One ``.seg`` file holds a frozen range of rows as
raw column blobs addressed by a JSON header; every column opens as an
``np.memmap`` (numeric / fixed-width) or as an (offsets, blob) pair
(variable-width text), so reading a row touches only the pages that row
lives on — RSS stays bounded by the OS page cache, not by index size.

This replaces the grow-forever JSONL journal as the store of record
(reference analogy: the metadata store is Solr/Lucene, on disk by
construction — source/net/yacy/search/index/Fulltext.java:90-230,
kelondro/blob/HeapReader.java:60 for the header-then-payload file
shape). The journal survives only as the TAIL: rows newer than the last
snapshot, replayed at open in O(tail).

File layout (all little-endian):

    8 bytes   magic  b"YTCS0001"
    8 bytes   uint64 header length H
    H bytes   JSON header:
                n            row count
                arrays       name -> {dtype, shape, off}
                texts        name -> {ioff, blob_off, blob_len}
                meta         caller-owned JSON blob (facet tables, ...)
    payload   raw column data (8-byte aligned blobs)

Text columns store UTF-8 blobs with a uint64 offsets array [n+1]; row i
decodes blob[offsets[i]:offsets[i+1]].
"""

from __future__ import annotations

import json
import os

import numpy as np

from ..utils import faultinject
from . import integrity

MAGIC = b"YTCS0001"
_ALIGN = 8


def _pad(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def fsync_dir(path: str) -> None:
    """fsync a DIRECTORY so a rename/create inside it is durable — an
    os.replace alone orders nothing on power loss; the store-everything
    contract (reference IndexCell.java:115) needs the direntry on disk.
    Best-effort: platforms without directory fds (Windows) skip it."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def purge_stale_journals(data_dir: str, prefix: str, keep: str) -> None:
    """Delete `<prefix>.jsonl` / `<prefix>.NNNNNN.jsonl` journal
    generations the manifest no longer references (shared by the
    metadata and webgraph stores — the generation-name pattern must
    never diverge between them)."""
    import re
    pat = re.compile(rf"^{re.escape(prefix)}(\.\d{{6}})?\.jsonl$")
    try:
        for name in os.listdir(data_dir):
            if pat.match(name) and name != keep:
                try:
                    os.remove(os.path.join(data_dir, name))
                except OSError:
                    pass
    except OSError:
        pass


def write_durable(path: str, data: bytes | str,
                  encoding: str | None = None) -> None:
    """tmp + fsync + rename + dir-fsync in one place: the crash-ordering
    idiom every manifest/state file in the index uses. The tmp name is
    process-unique — two processes snapshotting the same store must
    last-writer-win, not crash each other's rename."""
    tmp = f"{path}.tmp{os.getpid()}"
    mode = "wb" if encoding is None else "w"
    faultinject.io_error(path)
    torn = faultinject.torn_write_bytes(path)
    with open(tmp, mode, encoding=encoding) as f:
        if torn is not None:
            # chaos harness: the on-disk artifact of a crash mid-write —
            # a truncated .tmp that never reaches the rename below.
            # Truncation is in BYTES on the raw fd (a str slice would
            # always land on a character boundary, cleaner than a real
            # kill−9 tear through a multi-byte sequence)
            raw = (data.encode(encoding or "utf-8")
                   if isinstance(data, str) else data)
            f.flush()
            os.write(f.fileno(), raw[:max(0, torn)])
            f.flush()
            raise faultinject.InjectedFault(
                f"injected io.torn_write on {path}")
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(path) or ".")


def journal_append(f, payload: str, sync: bool = True,
                   checksum: bool = True) -> None:
    """THE shared journal-append path (ISSUE 10 satellite): crc-prefixed
    line + flush + fsync on one code path, instead of the bare
    ``write(); flush()`` several stores grew independently — an append
    that returns is on the platter, so the crash-ordering guarantees
    the manifests state actually hold on every journal.  `checksum`
    prefixes the line with its crc32 (``integrity.crc_line``); replays
    strip it with ``integrity.check_line`` and still read legacy
    prefix-free lines."""
    name = getattr(f, "name", "")
    line = (integrity.crc_line(payload) if checksum else payload) + "\n"
    faultinject.io_error(name)
    torn = faultinject.torn_write_bytes(name)
    if torn is not None:
        # the torn-tail artifact: a partial line at EOF, then "crash".
        # BYTE-accurate (raw fd write): a real tear can land mid-way
        # through a multi-byte character, and the recovery path must
        # face exactly that
        f.flush()
        os.write(f.fileno(), line.encode("utf-8")[:max(0, torn)])
        f.flush()
        raise faultinject.InjectedFault(
            f"injected io.torn_write on {name}")
    f.write(line)
    f.flush()
    if sync:
        os.fsync(f.fileno())


def journal_append_many(f, payloads, sync: bool = True,
                        checksum: bool = True) -> None:
    """Batch form of :func:`journal_append`: one flush+fsync for a
    whole batch of records (the webgraph writes one journal line per
    edge — per-line fsync would turn an add_document_edges batch into
    dozens of disk barriers for one durability point)."""
    name = getattr(f, "name", "")
    faultinject.io_error(name)
    for payload in payloads:
        f.write((integrity.crc_line(payload) if checksum else payload)
                + "\n")
    f.flush()
    if sync:
        os.fsync(f.fileno())


def write_segment(path: str, n: int,
                  arrays: dict[str, np.ndarray],
                  texts: dict[str, list[str]],
                  meta: dict | None = None) -> None:
    """Write a frozen segment atomically (tmp + rename). A text column
    is a list of str, or (offsets, blob) as SegmentReader.text_raw gives
    it."""
    header: dict = {"n": int(n), "arrays": {}, "texts": {},
                    "meta": meta or {}}
    blobs: list[bytes] = []
    off = 0

    def add_blob(b: bytes) -> int:
        nonlocal off
        start = off
        blobs.append(b)
        pad = _pad(len(b)) - len(b)
        if pad:
            blobs.append(b"\0" * pad)
        off += _pad(len(b))
        return start

    for name, arr in arrays.items():
        arr = np.ascontiguousarray(arr)
        raw = arr.tobytes()
        header["arrays"][name] = {
            "dtype": arr.dtype.str, "shape": list(arr.shape),
            "off": add_blob(raw), "crc": integrity.crc32(raw)}
    for name, col in texts.items():
        if isinstance(col, tuple):          # (offsets, blob) as encoded
            offsets, blob = col
            offsets = np.ascontiguousarray(offsets, np.uint64)
            if len(offsets) != n + 1 or int(offsets[-1]) != len(blob):
                raise ValueError(f"text column {name}: offsets do not "
                                 f"cover {n} rows of the blob")
        else:
            if len(col) != n:
                raise ValueError(
                    f"text column {name}: {len(col)} rows != {n}")
            parts = [(s or "").encode("utf-8") for s in col]
            offsets = np.zeros(n + 1, np.uint64)
            np.cumsum(np.fromiter(map(len, parts), np.uint64, n),
                      out=offsets[1:])
            blob = b"".join(parts)
        oraw = offsets.tobytes()
        header["texts"][name] = {
            "ioff": add_blob(oraw),
            "blob_off": add_blob(blob), "blob_len": len(blob),
            "crc": integrity.crc32(blob, integrity.crc32(oraw))}

    hbytes = json.dumps(header).encode("utf-8")
    tmp = path + ".tmp"
    faultinject.io_error(path)
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(np.uint64(len(hbytes)).tobytes())
        f.write(hbytes)
        # chaos barrier: payload only partially written — the .tmp never
        # reaches the rename, so the store's visible state is unchanged
        faultinject.crashpoint("colstore.segment.mid_write")
        base = f.tell()
        pad = _pad(base) - base
        if pad:
            f.write(b"\0" * pad)
        for b in blobs:
            f.write(b)
        # durability before visibility: rename must never publish a
        # segment whose pages are still only in the page cache (power
        # loss would leave a zero-length or torn file behind the name)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(path) or ".")


class SegmentReader:
    """mmap view of one segment file; columns open lazily and cache."""

    def __init__(self, path: str):
        self.path = path
        # open scrub (ISSUE 10): magic + parseable header + every blob
        # extent inside the file — a truncated/garbage segment becomes a
        # typed CorruptSegmentError at open, never a struct/mmap crash
        # inside a later query
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                if f.read(8) != MAGIC:
                    raise integrity.CorruptSegmentError(
                        f"not a segment file: {path}")
                hlen = int(np.frombuffer(f.read(8), np.uint64)[0])
                self.header = json.loads(f.read(hlen).decode("utf-8"))
                self._payload = _pad(f.tell())
            for name, spec in self.header["arrays"].items():
                nbytes = int(np.prod(spec["shape"]) or 1) * \
                    np.dtype(spec["dtype"]).itemsize
                if self._payload + spec["off"] + nbytes > size:
                    raise integrity.CorruptSegmentError(
                        f"{path}: array {name} extends past EOF")
            for name, spec in self.header["texts"].items():
                if self._payload + spec["blob_off"] \
                        + spec["blob_len"] > size:
                    raise integrity.CorruptSegmentError(
                        f"{path}: text {name} extends past EOF")
        except integrity.CorruptSegmentError:
            integrity.note_corruption("segment", "error")
            raise
        except (OSError, ValueError, KeyError, OverflowError,
                MemoryError, json.JSONDecodeError) as e:
            integrity.note_corruption("segment", "error")
            raise integrity.CorruptSegmentError(
                f"corrupt segment {path}: {e!r}") from e
        self.n: int = self.header["n"]
        self.meta: dict = self.header.get("meta", {})
        self._arrays: dict[str, np.memmap] = {}
        self._texts: dict[str, tuple] = {}
        self._views: dict[str, memoryview] = {}
        self._text_views: dict[str, tuple] = {}

    def array(self, name: str) -> np.ndarray:
        got = self._arrays.get(name)
        if got is None:
            spec = self.header["arrays"][name]
            got = np.memmap(self.path, mode="r",
                            dtype=np.dtype(spec["dtype"]),
                            shape=tuple(spec["shape"]),
                            offset=self._payload + spec["off"])
            # lazy verify-on-read: ONE pass when the column first pages
            # in for this reader, not per access (columns are immutable;
            # a reopened reader re-verifies).  A content mismatch SERVES
            # DEGRADED (counted + logged) instead of raising: segments
            # have no redundant generation to quarantine to, the open
            # scrub already proved the extents structurally safe to
            # read, and raising here would turn every query touching
            # the column into a permanent 500 — the opposite of the
            # degrade-gracefully contract.  The storage_corruption
            # rule's critical edge still dumps the incident.
            if integrity.VERIFY_ON_READ and "crc" in spec:
                if integrity.crc_arrays(np.ascontiguousarray(got)) \
                        != spec["crc"]:
                    integrity.note_corruption("segment",
                                              "served_degraded")
                    import logging
                    logging.getLogger("yacy.colstore").error(
                        "%s: column %s checksum mismatch — serving "
                        "degraded", self.path, name)
                else:
                    integrity.note_verified()
            self._arrays[name] = got
        return got

    def has_array(self, name: str) -> bool:
        return name in self.header["arrays"]

    def has_text(self, name: str) -> bool:
        return name in self.header["texts"]

    def _text_maps(self, name: str):
        got = self._texts.get(name)
        if got is None:
            spec = self.header["texts"][name]
            offsets = np.memmap(self.path, mode="r", dtype=np.uint64,
                                shape=(self.n + 1,),
                                offset=self._payload + spec["ioff"])
            blob = (np.empty(0, np.uint8) if spec["blob_len"] == 0
                    else np.memmap(self.path, mode="r", dtype=np.uint8,
                                   shape=(spec["blob_len"],),
                                   offset=self._payload + spec["blob_off"]))
            if integrity.VERIFY_ON_READ and "crc" in spec:
                got_crc = integrity.crc_arrays(
                    np.ascontiguousarray(offsets),
                    np.ascontiguousarray(blob))
                if got_crc != spec["crc"]:
                    # served degraded, never a query crash (see array())
                    integrity.note_corruption("segment",
                                              "served_degraded")
                    import logging
                    logging.getLogger("yacy.colstore").error(
                        "%s: text column %s checksum mismatch — "
                        "serving degraded", self.path, name)
                else:
                    integrity.note_verified()
            got = (offsets, blob)
            self._texts[name] = got
        return got

    # The served path reads VALUES through plain buffer views of the
    # mappings, never through the np.memmap objects: every array-valued
    # `memmap[...]` (a slice as much as an index array) builds a memmap
    # whose __array_finalize__ calls np.may_share_memory, and that lets
    # go of the interpreter lock - under concurrent requests one
    # hand-off, up to a switch interval of waiting, per value read
    # (PERF.md, PR 28). A memoryview element is a Python number and a
    # memoryview slice never leaves the lock.

    def view(self, name: str) -> memoryview:
        """A numeric column as a buffer (`view[i]` is a Python int or
        float); a fixed-width bytes column (the S12 url hashes) as its
        raw bytes, row i at `view[i * width:(i + 1) * width]`."""
        got = self._views.get(name)
        if got is None:
            arr = self.array(name)
            got = memoryview(arr)
            if arr.dtype.kind == "S":
                got = got.cast("B")
            self._views[name] = got
        return got

    def text_views(self, name: str) -> tuple[memoryview, memoryview]:
        """(offsets, blob) of a text column as buffers: row i is
        `str(blob[offsets[i]:offsets[i + 1]], "utf-8", "replace")`."""
        got = self._text_views.get(name)
        if got is None:
            offsets, blob = self._text_maps(name)
            got = (memoryview(offsets), memoryview(blob))
            self._text_views[name] = got
        return got

    def text(self, name: str, i: int) -> str:
        offsets, blob = self.text_views(name)
        lo, hi = offsets[i], offsets[i + 1]
        if lo == hi:
            return ""
        return str(blob[lo:hi], "utf-8", "replace")

    def text_raw(self, name: str) -> tuple[np.ndarray, bytes]:
        """A whole text column as encoded: (uint64 offsets [n + 1], the
        blob), what write_segment takes back (compaction path)."""
        if not self.has_text(name):
            return np.zeros(self.n + 1, np.uint64), b""
        offsets, blob = self._text_maps(name)
        offs = np.array(offsets, np.uint64)
        return offs, bytes(blob[:int(offs[-1])])

    def text_column(self, name: str) -> list[str]:
        """Materialize a whole text column (compaction path)."""
        offsets, blob = self._text_maps(name)
        raw = bytes(blob[: int(offsets[-1])])
        offs = np.asarray(offsets)
        return [raw[int(offs[i]):int(offs[i + 1])].decode("utf-8", "replace")
                for i in range(self.n)]

    def close(self) -> None:
        self._views.clear()
        self._text_views.clear()
        self._arrays.clear()
        self._texts.clear()
