"""AccessTracker — per-query log + host access accounting.

Capability equivalent of the reference's search access tracking (reference:
source/net/yacy/search/query/AccessTracker.java:50-172 — a bounded
in-memory list of executed queries with timing/result counts, dumped to a
log file for statistics, plus host-level access counts used for abuse
control on the public search surface; host access also in
server/serverAccessTracker.java).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

MAX_FINISHED = 500          # bounded history (reference minSize/maxSize trim)
DUMP_BATCH = 50             # entries buffered before a dump append


@dataclass
class QueryLogEntry:
    query: str
    timestamp: float
    query_count: int        # include-word count
    result_count: int
    time_ms: float
    offset: int = 0
    client: str = ""

    def dump_line(self) -> str:
        # one line per query: unixtime, client, words, results, millis, query
        return (f"{int(self.timestamp)} {self.client or '-'} "
                f"{self.query_count} {self.result_count} "
                f"{self.time_ms:.1f} {self.query}")


class AccessTracker:
    """Bounded query history with optional file dump + per-host counters.

    Two states with a lock each: the front door's window
    (`track_access`, every request's first step) never waits for a
    query being logged (`add`).  The dump file has a third lock of its
    own and is never opened under either of them: file I/O lets go of
    the interpreter, and whoever then runs into a lock held across it
    blocks, and needs the interpreter back from whoever runs (ISSUE 38).
    """

    def __init__(self, dump_path: str | None = None):
        self.dump_path = dump_path
        self._finished: deque[QueryLogEntry] = deque(maxlen=MAX_FINISHED)
        self._undumped: list[str] = []
        self._host_access: dict[str, deque[float]] = {}
        self._access_calls = 0
        self._log_lock = threading.Lock()
        self._host_lock = threading.Lock()
        self._file_lock = threading.Lock()
        if dump_path:
            os.makedirs(os.path.dirname(dump_path), exist_ok=True)

    # -- query log -----------------------------------------------------------

    def add(self, entry: QueryLogEntry) -> None:
        line = entry.dump_line() if self.dump_path else None
        with self._log_lock:
            self._finished.append(entry)
            if line is None:
                return
            self._undumped.append(line)
            full = len(self._undumped) >= DUMP_BATCH
        if full:
            self._write(wait=False)

    def latest(self, n: int = 50) -> list[QueryLogEntry]:
        with self._log_lock:
            return list(self._finished)[-n:][::-1]

    def size(self) -> int:
        with self._log_lock:
            return len(self._finished)

    def _write(self, wait: bool) -> None:
        """Append the buffered lines to the file.  They are taken out
        under the file's lock, so batches land in arrival order; an
        `add` that finds a writer at work (`wait` false) leaves its
        lines to it and does not wait for the file.

        What makes that safe is the order of the writer's last two
        steps: it looks at the buffer again AFTER it has let go of the
        file's lock.  A batch that filled during the write either found
        the lock free again (and writes itself) or filled before that
        look (and the writer goes round for it): a full batch is never
        left with nobody to write it."""
        while self._file_lock.acquire(blocking=wait):
            try:
                with self._log_lock:
                    lines, self._undumped = self._undumped, []
                path = self.dump_path
                if lines and path:
                    try:
                        with open(path, "a", encoding="utf-8") as f:
                            f.write("\n".join(lines) + "\n")
                    except OSError:
                        pass
            finally:
                self._file_lock.release()
            # a batch that filled meanwhile found the file's lock taken
            with self._log_lock:
                if len(self._undumped) < DUMP_BATCH:
                    return

    def dump(self) -> None:
        self._write(wait=True)

    # -- host access (abuse control surface) ---------------------------------

    def track_access(self, client_host: str, window_s: float = 600.0) -> int:
        """Record one access from `client_host`; returns accesses within the
        window (callers throttle above a threshold)."""
        now = time.time()
        with self._host_lock:
            # maxlen bounds a flooding client's memory; the window prune
            # below keeps the COUNT honest for throttling decisions
            times = self._host_access.setdefault(
                client_host, deque(maxlen=20_000))
            times.append(now)
            cutoff = now - window_s
            while times and times[0] < cutoff:
                times.popleft()
            # bound the dict itself: one-off client IPs must not accumulate
            # keys forever on a public node
            self._access_calls += 1
            if self._access_calls % 256 == 0:
                self._prune_hosts_locked(cutoff)
            return len(times)

    def _prune_hosts_locked(self, cutoff: float) -> None:
        dead = []
        for host, times in self._host_access.items():
            while times and times[0] < cutoff:
                times.popleft()
            if not times:
                dead.append(host)
        for host in dead:
            del self._host_access[host]

    def retry_after_s(self, client_host: str, limit: int,
                      window_s: float = 600.0) -> float:
        """Seconds until a retry from this host would PASS the windowed
        limit — the honest Retry-After for a WINDOW denial (ISSUE 9,
        replacing the hard-coded 600).  The retry appends itself before
        the `hits > limit` check, so `over + 1` oldest entries must age
        out, not `over` — an off-by-one here 429s the very client that
        honored the header exactly."""
        now = time.time()
        with self._host_lock:
            times = self._host_access.get(client_host)
            if not times:
                return 0.0
            over = len(times) - limit
            if over <= 0:
                return 0.0
            i = min(over, len(times) - 1)
            # +1 ms past the boundary: the window prune is STRICT
            # (`times[0] < cutoff`), so at the exact expiry instant the
            # entry still counts — the advertised wait must land
            # strictly after it
            return max(0.0, times[i] + window_s - now + 0.001)

    def access_hosts(self, window_s: float = 600.0) -> list[tuple[str, int]]:
        with self._host_lock:
            self._prune_hosts_locked(time.time() - window_s)
            return sorted(((h, len(t)) for h, t in self._host_access.items()),
                          key=lambda x: -x[1])
