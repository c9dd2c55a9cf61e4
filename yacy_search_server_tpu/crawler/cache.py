"""HTCache — the shared page cache (compressed content + response headers).

Capability equivalent of the reference's HTCache (reference:
source/net/yacy/crawler/data/Cache.java:59-130: gzip-compressed content in
an ArrayStack BLOB plus response headers in a MapHeap). Here: content is
gzip-compressed into sharded files keyed by url-hash, headers are a json
sidecar, and a bounded in-RAM ARC-ish buffer fronts the disk store. A
pure-RAM mode (data_dir=None) backs tests and proxy-only setups.

The cache knows what it holds: the keys of the entries on disk live in
a set, built by one walk of ``data_dir`` at construction and kept by
``store`` / ``delete`` / ``clear``, so a MISS is answered from memory
with no system call (a result page asks ten times per request, mostly
for URLs never cached). Files and set change together under the entry's
guard, one of a few striped locks: ``store`` and ``delete`` of ONE entry
exclude each other, those of different entries mostly run side by side.
The directory belongs to this one instance while it lives; a file
removed behind its back reads as a miss.
"""

from __future__ import annotations

import gzip
import json
import os
import threading
import time
from contextlib import ExitStack
from base64 import urlsafe_b64encode

from ..utils.hashes import url2hash

RAM_BUFFER_MAX = 256
DISK_GUARDS = 16


def _keys(urlhash: bytes) -> tuple[str, str]:
    k = urlsafe_b64encode(urlhash).decode("ascii").rstrip("=")
    return k[:2], k


class HTCache:
    def __init__(self, data_dir: str | None = None,
                 max_content_bytes: int = 10 * 1024 * 1024):
        self.data_dir = data_dir
        self.max_content_bytes = max_content_bytes
        self._ram: dict[bytes, tuple[bytes, dict, float]] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # keys of the entries on disk (None: pure RAM). Written under
        # the key's guard together with the files, so the two agree; read
        # without it (one set lookup)
        self._disk: set[str] | None = None
        self._guards = tuple(threading.Lock() for _ in range(DISK_GUARDS))
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            self._disk = self._walk()

    def _walk(self) -> set[str]:
        keys: set[str] = set()
        with os.scandir(self.data_dir) as shards:
            for shard in shards:
                if shard.is_dir():
                    keys.update(n[:-3] for n in os.listdir(shard.path)
                                if n.endswith(".gz"))
        return keys

    # -- store ---------------------------------------------------------------

    def store(self, url: str, content: bytes, headers: dict | None = None) -> bool:
        if len(content) > self.max_content_bytes:
            return False
        h = url2hash(url)
        headers = dict(headers or {})
        headers["x-cache-date"] = time.time()
        headers["x-cache-url"] = url
        with self._lock:
            self._ram[h] = (content, headers, time.time())
            while len(self._ram) > RAM_BUFFER_MAX:
                self._ram.pop(next(iter(self._ram)))
        if self.data_dir:
            shard, key = _keys(h)
            d = os.path.join(self.data_dir, shard)
            packed = gzip.compress(content)
            with self._guard(key):
                os.makedirs(d, exist_ok=True)
                with open(os.path.join(d, key + ".gz"), "wb") as f:
                    f.write(packed)
                # the content is there, whatever becomes of its headers
                # (`get` reads an entry without them)
                self._disk.add(key)
                with open(os.path.join(d, key + ".json"), "w",
                          encoding="utf-8") as f:
                    json.dump(headers, f)
        return True

    def _guard(self, key: str) -> threading.Lock:
        return self._guards[hash(key) % DISK_GUARDS]

    # -- load ----------------------------------------------------------------

    def _paths(self, urlhash: bytes) -> tuple[str, str] | None:
        """(content, headers) files of an entry the disk set holds."""
        if self._disk is None:
            return None
        shard, key = _keys(urlhash)
        if key not in self._disk:
            return None
        d = os.path.join(self.data_dir, shard)
        return os.path.join(d, key + ".gz"), os.path.join(d, key + ".json")

    # readers take `_lock` at most ONCE a call, for the counter alone:
    # two acquisitions back to back are what a lock convoy lives on (the
    # second finds the lock handed to a waiter that still waits for the
    # interpreter; ten look-ups a page from four threads: PERF.md PR 31)

    # lint: unlocked-ok(one dict membership test, atomic under the
    # interpreter lock; writers hold _lock among themselves)
    def has(self, url: str) -> bool:
        h = url2hash(url)
        return h in self._ram or (
            self._disk is not None and _keys(h)[1] in self._disk)

    # lint: unlocked-ok(one dict read, atomic under the interpreter
    # lock; writers hold _lock among themselves)
    def get(self, url: str) -> tuple[bytes, dict] | None:
        h = url2hash(url)
        hit = self._ram.get(h)
        if hit is not None:
            with self._lock:
                self.hits += 1
            return hit[0], hit[1]
        p = self._paths(h)
        if p:
            try:
                with open(p[0], "rb") as f:
                    content = gzip.decompress(f.read())
                headers = {}
                if os.path.exists(p[1]):
                    with open(p[1], encoding="utf-8") as f:
                        headers = json.load(f)
                with self._lock:
                    self.hits += 1
                return content, headers
            except (OSError, EOFError, json.JSONDecodeError):
                pass                    # gone, or being written: a miss
        with self._lock:
            self.misses += 1
        return None

    def age_s(self, url: str) -> float | None:
        got = self.get(url)
        if got is None:
            return None
        ts = got[1].get("x-cache-date")
        return (time.time() - ts) if ts else None

    def clear(self) -> int:
        """Delete every cached response (bin/clearcache.sh /
        ConfigHTCache_p clear); returns files removed."""
        removed = 0
        if self.data_dir and os.path.isdir(self.data_dir):
            with ExitStack() as every:       # no store or delete meanwhile
                for g in self._guards:
                    every.enter_context(g)
                for root, _dirs, names in os.walk(self.data_dir):
                    for n in names:
                        try:
                            os.remove(os.path.join(root, n))
                        except OSError:
                            continue         # still held
                        removed += 1
                        if n.endswith(".gz"):
                            self._disk.discard(n[:-3])
        with self._lock:
            self._ram.clear()
        return removed

    def delete(self, url: str) -> None:
        h = url2hash(url)
        with self._lock:
            self._ram.pop(h, None)
        if self._disk is None:
            return
        shard, key = _keys(h)
        with self._guard(key):
            # the files go whether or not the set knew them (a stray
            # headers file, an entry half written before a crash)
            self._disk.discard(key)
            for ext in (".gz", ".json"):
                try:
                    os.remove(os.path.join(self.data_dir, shard, key + ext))
                except FileNotFoundError:
                    pass
