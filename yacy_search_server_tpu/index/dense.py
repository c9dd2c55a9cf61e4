"""Dense vector store — per-segment doc embeddings aligned to docids.

The M7 hybrid-rerank companion of the metadata store: one growable
``[capacity, dim]`` float16 block (the device-transfer unit for the
rerank matmul), filled at ``store_document`` time by the segment's
encoder.  Persistence is one .npy snapshot rewritten on flush/close —
embeddings are derivable data (re-encodable from text_t), so a crash
loses nothing irrecoverable.
"""

from __future__ import annotations

import os
import struct
import threading

import numpy as np

from ..ops.dense import DIM, ENCODER_VERSION
from ..utils import profiling
from . import integrity

# crc footer on the vectors.npy snapshot (M84 discipline, ISSUE 11
# satellite): magic + little-endian u32 crc32 over the npy payload,
# appended AFTER the array (np.load reads exactly the header-declared
# bytes, so footer-free legacy files and footered files both load)
_FOOTER_MAGIC = b"YDV1"
_FOOTER_LEN = len(_FOOTER_MAGIC) + 4


class DenseVectorStore:
    # device-residency cap for the forward index: beyond it the rerank
    # path falls back to the host gather (a 1 GiB f16 block is ~2M docs
    # at dim 256 — past that the block belongs in the tiered-residency
    # work of ROADMAP item 4, not in one monolithic upload).  The
    # class attribute is the default; the serving knob is
    # index.dense.deviceBudgetBytes (instance device_budget_bytes).
    DEVICE_BUDGET_BYTES = 1 << 30
    # dirty-row bookkeeping cap for the device-block patch path (see
    # device_block): a set bigger than this costs more than the full
    # re-upload it would save
    _DIRTY_CAP = 1 << 16

    def __init__(self, data_dir: str | None = None, dim: int = DIM,
                 device_budget_bytes: int | None = None):
        self.dim = dim
        self.data_dir = data_dir
        self.device_budget_bytes = (self.DEVICE_BUDGET_BYTES
                                    if device_budget_bytes is None
                                    else int(device_budget_bytes))
        self._vecs = np.zeros((256, dim), dtype=np.float16)
        self._n = 0
        self._lock = threading.Lock()
        self._dirty = 0
        self.stale_encoder = False
        # vector-content version: bumps on EVERY write (put / re-encode)
        # — the hybrid top-k cache keys on it (plus ENCODER_VERSION), so
        # a cached hybrid answer can never survive a vector or encoder
        # change (the arena epoch only covers postings mutations)
        self.version = 0
        # device-resident forward index (the M7 rerank's doc-vector
        # block, resident like the postings arena): uploaded lazily,
        # re-uploaded when the content version moves; rows pad to a
        # pow2 bucket so compile shapes stay bounded
        self._fwd = None
        self._fwd_version = -1
        self._fwd_device = None
        # serializes uploads among device_block callers WITHOUT holding
        # the write lock across the device transfer: indexers keep
        # putting vectors while a (possibly seconds-long) re-upload is
        # in flight
        self._fwd_lock = profiling.ObservedLock("dense_fwd")
        # rows written since the last device upload: device_block
        # scatters ONLY these into the resident block (indexing cadence
        # must not re-ship the whole index per query wave); None =
        # overflowed past _DIRTY_CAP, full re-upload on next access
        self._fwd_dirty: set | None = set()
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            p = self._path()
            if os.path.isfile(p):
                loaded = self._load_verified(p)
                if loaded is not None and loaded.shape[1] == dim:
                    self._vecs = loaded.copy()
                    self._n = loaded.shape[0]
                # vectors hashed by an older encoder cannot be compared
                # with current query vectors; migration re-encodes
                self.stale_encoder = (self._n > 0 and
                                      self._load_version()
                                      != ENCODER_VERSION)

    def _path(self) -> str:
        return os.path.join(self.data_dir, "vectors.npy")

    def _load_verified(self, p: str) -> np.ndarray | None:
        """Load the vector snapshot under the M84 read-side integrity
        discipline: a ``YDV1`` crc32 footer (written by _save_locked)
        is verified over the npy payload; a mismatch — or a snapshot
        torn/garbled beyond np.load — QUARANTINES the file (renamed
        ``.corrupt``) and returns None, so dense serving degrades to
        sparse-only boosts (zero vectors) instead of crashing the open.
        Footer-free legacy files load as before (no claim made).
        Counted in yacy_storage_corruption_total{kind="dense"}; the
        typed error (integrity.CorruptDenseError) is raised and caught
        here so callers that want the error surface can use
        _read_checked directly."""
        try:
            return self._read_checked(p)
        except (integrity.CorruptDenseError, OSError):
            integrity.note_corruption("dense", "quarantined")
            try:
                os.replace(p, p + ".corrupt")
            except OSError:
                pass
            return None

    @staticmethod
    def _read_checked(p: str) -> np.ndarray:
        """np.load + footer crc verification (streamed — no staging
        copy of the up-to-1-GiB snapshot); raises
        integrity.CorruptDenseError on a checksum mismatch or an
        unreadable snapshot."""
        try:
            arr = np.load(p, allow_pickle=False)
        except Exception as e:
            raise integrity.CorruptDenseError(
                f"dense snapshot does not parse as npy: {e!r}") from e
        try:
            with open(p, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size < _FOOTER_LEN:
                    return arr                       # legacy: no claim
                f.seek(size - _FOOTER_LEN)
                tail = f.read(_FOOTER_LEN)
                if tail[:len(_FOOTER_MAGIC)] != _FOOTER_MAGIC:
                    return arr                       # legacy: no claim
                if not integrity.verify_on_read():
                    return arr
                (want,) = struct.unpack("<I", tail[-4:])
                f.seek(0)
                crc = 0
                left = size - _FOOTER_LEN
                while left > 0:
                    chunk = f.read(min(1 << 22, left))
                    if not chunk:
                        break
                    left -= len(chunk)
                    crc = integrity.crc32(chunk, crc)
        except OSError as e:
            raise integrity.CorruptDenseError(
                f"dense snapshot unreadable: {e!r}") from e
        if crc != want:
            raise integrity.CorruptDenseError(
                f"dense snapshot crc mismatch: stored {want:#x}, "
                f"computed {crc:#x}")
        return arr

    def _version_path(self) -> str:
        return os.path.join(self.data_dir, "ENCODER_VERSION")

    def _load_version(self) -> int:
        try:
            with open(self._version_path(), encoding="ascii") as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return 1    # pre-versioning stores used the v1 FNV hash

    def put(self, docid: int, vec: np.ndarray) -> None:
        with self._lock:
            while docid >= self._vecs.shape[0]:
                self._vecs = np.vstack(
                    [self._vecs, np.zeros_like(self._vecs)])
            self._vecs[docid] = vec.astype(np.float16)
            self._n = max(self._n, docid + 1)
            self.version += 1
            self._dirty += 1
            if self._fwd_dirty is not None:
                self._fwd_dirty.add(docid)
                if len(self._fwd_dirty) > self._DIRTY_CAP:
                    # past the cap a full re-upload is cheaper than the
                    # bookkeeping; None = "patch set overflowed"
                    self._fwd_dirty = None
            if self.data_dir and self._dirty >= 512:
                self._save_locked()

    def get_block(self, docids: np.ndarray) -> np.ndarray:
        """[len(docids), dim] float16 gather (device-transfer unit).

        Docids without a stored vector gather zeros (zero boost), the
        same contract the device forward index gives pad rows — a
        postings row whose dense.put hasn't landed yet (or never will)
        must rank by its sparse score, not crash the hybrid query."""
        with self._lock:
            ids = np.asarray(docids, dtype=np.int64)
            out = np.zeros((len(ids), self.dim), np.float16)
            ok = (ids >= 0) & (ids < self._n)
            out[ok] = self._vecs[ids[ok]]
            return out

    def _rows_locked(self) -> int:
        # pow2 row bucket (>=256) — the ONE derivation shared by the
        # prewarm shape key and the uploaded block (divergence would
        # warm shapes device_block never dispatches)
        return 1 << max(8, (max(self._n, 1) - 1).bit_length())

    def device_rows(self) -> int:
        """The forward index's padded device row bucket (a compile-shape
        key for the devstore prewarm)."""
        with self._lock:
            return self._rows_locked()

    def device_block(self, device):
        """The device-resident forward index: ([rows, dim] float16 on
        `device`, content version) — or None when the block exceeds
        DEVICE_BUDGET_BYTES (callers fall back to the host gather).

        Block-resident like the postings arena: one upload serves every
        subsequent rerank dispatch, so the per-query host-side
        ``get_block`` gather + upload round trip disappears from the
        serving path. Stale on any vector write (the version moved);
        a stale block is PATCHED on device — only the rows written
        since the last upload cross the wire (a steady indexer must not
        cost one full-index transfer per query wave) — falling back to
        a wholesale re-upload when the row bucket grew, the dirty set
        overflowed, or more than a quarter of the block changed. Rows
        pad to a pow2 bucket (>=256) so a growing index mints a bounded
        set of compile shapes; docids past the bucket simply have no
        vector yet and the kernel scores them with zero boost."""
        import jax
        # lint: blocking-ok(serializing uploads is _fwd_lock's sole
        # purpose; the write lock is released for the transfer, so
        # indexers keep putting vectors while an upload is in flight)
        with self._fwd_lock:
            with self._lock:
                rows = self._rows_locked()
                if rows * self.dim * 2 > self.device_budget_bytes:
                    # release the last in-budget block: it can never be
                    # served again, and up to 1 GiB of pinned device
                    # memory would otherwise shadow the postings arena
                    # for the rest of the process
                    self._fwd = None
                    self._fwd_device = None
                    self._fwd_version = -1
                    return None
                if (self._fwd is not None
                        and self._fwd_version == self.version
                        and self._fwd_device is device
                        and self._fwd.shape[0] == rows):
                    return self._fwd, self._fwd_version
                # snapshot under the write lock, then release it for
                # the transfer: a put() racing the upload lands AFTER
                # `ver`, so the cached block is immediately stale and
                # the next call patches it in — but the indexer never
                # blocked on the transfer
                ver = self.version
                base, dirty = self._fwd, self._fwd_dirty
                patch = (base is not None and dirty is not None
                         and self._fwd_device is device
                         and base.shape[0] == rows
                         and 0 < len(dirty) <= rows // 4)
                if patch:
                    idx = np.fromiter(dirty, np.int64, len(dirty))
                    sub = self._vecs[idx]
                else:
                    buf = np.zeros((rows, self.dim), np.float16)
                    buf[:self._n] = self._vecs[:self._n]
                self._fwd_dirty = set()
            try:
                if patch:
                    # scatter only the dirty rows into the resident
                    # block; the index count pads to a pow2 bucket
                    # (bounded compile shapes) — pad lanes repeat idx[0]
                    # with its own row, so duplicate indices carry
                    # identical values
                    nb = 1 << max(4, (len(idx) - 1).bit_length())
                    pidx = np.full(nb, idx[0], np.int32)
                    pidx[:len(idx)] = idx
                    psub = np.repeat(sub[:1], nb, axis=0)
                    psub[:len(idx)] = sub
                    fwd = base.at[jax.device_put(pidx, device)].set(
                        jax.device_put(psub, device))
                else:
                    fwd = jax.device_put(buf, device)
            except BaseException:
                # a failed transfer must not LOSE the snapshotted dirty
                # rows: _fwd/_fwd_version are unchanged, so a later
                # patch would scatter only post-failure writes onto the
                # old base and serve these rows stale-as-fresh
                with self._lock:
                    if dirty is None or self._fwd_dirty is None:
                        self._fwd_dirty = None
                    else:
                        self._fwd_dirty |= dirty
                raise
            with self._lock:
                self._fwd = fwd
                self._fwd_version = ver
                self._fwd_device = device
            return fwd, ver

    def __len__(self) -> int:
        with self._lock:
            return self._n

    def _save_locked(self) -> None:
        tmp = self._path() + ".tmp"
        with open(tmp, "wb+") as f:
            np.save(f, self._vecs[:max(self._n, 1)])
            # crc32 footer over the npy payload, streamed back off the
            # just-written file (a BytesIO staging copy would double
            # peak RAM at the 1 GiB budget); verified at open
            # (_load_verified). Writers always emit the footer, only
            # read-side verification toggles (the M84 discipline).
            f.flush()
            f.seek(0)
            crc = 0
            while True:
                chunk = f.read(1 << 22)
                if not chunk:
                    break
                crc = integrity.crc32(chunk, crc)
            f.seek(0, os.SEEK_END)
            f.write(_FOOTER_MAGIC)
            f.write(struct.pack("<I", crc))
        os.replace(tmp, self._path())
        # while the store is stale (migration in flight) the version
        # marker must NOT advance: a crash mid-re-encode would otherwise
        # mask the remaining v1 vectors as migrated forever
        if not self.stale_encoder:
            with open(self._version_path(), "w", encoding="ascii") as f:
                f.write(str(ENCODER_VERSION))
        self._dirty = 0

    def mark_encoder_current(self) -> None:
        """Called by the migration AFTER every vector was re-encoded:
        clears staleness and stamps the encoder version."""
        with self._lock:
            self.stale_encoder = False
            self._save_locked()

    def flush(self) -> None:
        if self.data_dir:
            with self._lock:
                self._save_locked()

    def close(self) -> None:
        self.flush()
