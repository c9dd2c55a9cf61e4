"""Multi-process serving — N HTTP workers around one device-arena owner.

VERDICT r2 weak #5: a single Python process caps the served path at a
few hundred q/s of host work (parse, drain, render) long before the
kernel saturates — the GIL is the ceiling, not the device. The
reference serves from a Jetty thread pool (reference:
source/net/yacy/http/Jetty9HttpServerImpl.java:112 — real OS threads);
the CPython equivalent is PROCESSES:

- the **owner** process holds the full Switchboard: crawling, indexing,
  the RWI RAM buffer, and the device arena. It exposes
  ``rank_term``/``rank_join`` on a unix socket via ``RankServiceServer``
  (one dispatcher thread per worker connection — the device dispatch
  releases the GIL during the kernel round trip, so concurrent worker
  requests batch in the arena's _QueryBatcher exactly like same-process
  threads).
- **workers** run the HTTP surface + query host work. Each worker opens
  the SAME data dir read-only — the M48 segmented stores are mmap'd
  files, so N workers share one page cache, not N copies — and mounts a
  ``RankServiceClient`` as its serving store: every eligible query's
  device ranking rides the socket to the owner's arena.
- workers bind the same port with SO_REUSEPORT: the kernel load-balances
  connections across worker processes, no proxy needed.

Transport: ``multiprocessing.connection`` (length-prefixed pickle over
AF_UNIX, authkey-authenticated) — numpy arrays round-trip natively and
the hop costs ~50-100 µs, noise against a device dispatch.

Workers see the index as of their start (plus whatever the owner
flushed); after heavy re-indexing the operator bounces workers (the
same restart contract as any mmap-snapshot reader).
"""

from __future__ import annotations

import os
import secrets
import threading
import time
from multiprocessing.connection import Client, Listener

# spawn_worker mutates process-global os.environ around start(): one at
# a time, or concurrent spawns could leave the parent pinned to cpu
_SPAWN_LOCK = threading.Lock()

# the owner dispatches ONLY these store methods — conn.recv() is pickle
# underneath, so the dispatch surface must be a closed set, never getattr
# over attacker-chosen names.  serving_state is the degradation-ladder
# propagation channel (ISSUE 9): workers ask the owner's actuator rung
# so the whole process group degrades together
_METHODS = frozenset({"rank_term", "rank_join", "count_upper",
                      "serving_state"})


def _key_path(socket_path: str) -> str:
    return socket_path + ".key"


def _load_authkey(socket_path: str) -> bytes:
    with open(_key_path(socket_path), "rb") as fh:
        return fh.read()


class RankServiceServer:
    """Expose the owner Switchboard's serving store on a unix socket.

    The wire format (multiprocessing.connection) is pickle, so transport
    auth is the security boundary: a RANDOM per-instance authkey is
    generated at startup and persisted mode-0600 next to the socket for
    workers to read (a hardcoded key would hand any local user an HMAC
    pass and, with it, arbitrary unpickling in the owner process —
    ADVICE r3). The socket itself is also chmod 0600."""

    def __init__(self, store, socket_path: str, state_fn=None):
        self.store = store
        # owner-side serving state for workers (ISSUE 9): usually
        # sb.actuators.serving_state — the ladder rung + Retry-After the
        # whole process group serves under.  None answers level 0.
        self.state_fn = state_fn
        self.socket_path = socket_path
        if os.path.exists(socket_path):
            os.unlink(socket_path)
        self.authkey = secrets.token_bytes(32)
        kp = _key_path(socket_path)
        # O_EXCL on a freshly-unlinked path: a stale key file (whose mode
        # O_CREAT would keep) or a planted symlink must never receive the
        # new secret
        if os.path.lexists(kp):
            os.unlink(kp)
        flags = os.O_WRONLY | os.O_CREAT | os.O_EXCL
        flags |= getattr(os, "O_NOFOLLOW", 0)
        fd = os.open(kp, flags, 0o600)
        try:
            os.write(fd, self.authkey)
        finally:
            os.close(fd)
        self.listener = Listener(socket_path, family="AF_UNIX",
                                 authkey=self.authkey)
        os.chmod(socket_path, 0o600)
        self._stop = False
        self._threads: list[threading.Thread] = []
        self._accept = threading.Thread(target=self._accept_loop,
                                        name="rank-accept", daemon=True)
        self._accept.start()

    def _accept_loop(self) -> None:
        from multiprocessing import AuthenticationError
        while not self._stop:
            try:
                conn = self.listener.accept()
            except AuthenticationError:
                continue    # a rejected client must not kill the acceptor
            except (OSError, EOFError):
                # a client dying MID-HANDSHAKE raises EOF/ECONNRESET out
                # of accept() too — only a real shutdown ends the loop
                if self._stop:
                    return
                time.sleep(0.05)   # broken listener must not spin hot
                continue
            t = threading.Thread(target=self._serve, args=(conn,),
                                 name="rank-conn", daemon=True)
            t.start()
            # reap finished connection threads: one HTTP connection per
            # worker thread means a long-lived owner sees many
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _serve(self, conn) -> None:
        """One worker connection: sequential request/response (workers
        multiplex with a connection per HTTP thread)."""
        store = self.store
        while not self._stop:
            try:
                method, args, kwargs = conn.recv()
            except (EOFError, OSError):
                return
            try:
                if method not in _METHODS:
                    raise ValueError(f"method not allowed: {method!r}")
                if method == "serving_state":
                    out = self.state_fn() if self.state_fn is not None \
                        else {"level": 0, "retry_after_s": 0.0}
                elif method == "count_upper":
                    out = store.rwi.count_upper(*args)
                else:
                    out = getattr(store, method)(*args, **kwargs)
                conn.send(("ok", out))
            except Exception as e:   # worker falls back to its host path
                try:
                    conn.send(("err", repr(e)))
                except (OSError, EOFError):
                    return

    def close(self) -> None:
        self._stop = True
        try:
            self.listener.close()
        except OSError:
            pass
        for path in (self.socket_path, _key_path(self.socket_path)):
            if os.path.exists(path):
                try:
                    os.unlink(path)
                except OSError:
                    pass


class RankServiceClient:
    """Duck-types the serving store inside a worker process.

    SearchEvent._device_local calls rank_term/rank_join and reads the
    fallback counters; every call forwards over the socket to the
    owner's arena. Connections are per-thread (the server serves each
    sequentially)."""

    small_rank_n: int | None = None

    def __init__(self, socket_path: str):
        self.socket_path = socket_path
        self._local = threading.local()
        self.queries_served = 0
        self.fallbacks = 0
        self.join_served = 0
        self.join_fallbacks = 0
        # probe once so a missing owner fails at construction, not on
        # the first query
        self._conn()

    def _conn(self):
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = Client(self.socket_path, family="AF_UNIX",
                          authkey=_load_authkey(self.socket_path))
            self._local.conn = conn
        return conn

    def _call(self, method: str, *args, **kwargs):
        try:
            conn = self._conn()
            conn.send((method, args, kwargs))
            status, out = conn.recv()
        except (OSError, EOFError):
            self._local.conn = None
            return None          # owner gone: host path serves
        if status != "ok":
            return None
        return out

    # -- serving-store surface ----------------------------------------------

    def rank_term(self, *args, **kwargs):
        out = self._call("rank_term", *args, **kwargs)
        if out is None:
            self.fallbacks += 1
        else:
            self.queries_served += 1
        return out

    def rank_join(self, *args, **kwargs):
        out = self._call("rank_join", *args, **kwargs)
        if out is None:
            self.join_fallbacks += 1
        else:
            self.join_served += 1
            self.queries_served += 1
        return out

    def count_upper(self, termhash: bytes) -> int:
        out = self._call("count_upper", termhash)
        return out if out is not None else 0

    def serving_state(self) -> dict:
        """The OWNER's degradation-ladder state (ISSUE 9): workers fold
        this into their own effective level so the whole process group
        sheds/degrades together.  TTL-cached — the actuator asks at
        most ~1/s and a socket hop per search would be pure tax."""
        now = time.monotonic()
        cached = getattr(self._local, "state_cache", None)
        if cached is not None and now - cached[0] < 1.0:
            return cached[1]
        out = self._call("serving_state")
        state = out if isinstance(out, dict) else {"level": 0}
        self._local.state_cache = (now, state)
        return state

    def enable_batching(self, **_kw) -> None:
        """Owner-side batching already coalesces concurrent workers."""

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass


def make_worker_switchboard(data_dir: str, socket_path: str,
                            small_rank_n: int | None = None):
    """A read-only worker Switchboard over the owner's data dir, serving
    device ranking through the rank service."""
    from ..switchboard import Switchboard
    from ..utils.config import Config
    cfg = Config()
    cfg.set("index.device.serving", "false")    # no local arena
    sb = Switchboard(data_dir=data_dir, config=cfg)
    # READ-ONLY contract: the data dir belongs to the OWNER. Detach every
    # journal/dump sink so nothing in the worker — including store
    # close() paths, which snapshot and TRUNCATE journals — can write
    # into the owner's live files.
    meta = sb.index.metadata
    if meta._journal is not None:
        meta._journal.close()
        meta._journal = None          # close() skips snapshot without it
    wg = sb.index.webgraph
    if wg._journal is not None:
        wg._journal.close()
        wg._journal = None
    sb.index.dense.data_dir = None    # flush() becomes a no-op
    sb.access_tracker.dump_path = None
    client = RankServiceClient(socket_path)
    client.small_rank_n = small_rank_n
    sb.index.devstore = client
    return sb


def spawn_worker(ctx, data_dir: str, socket_path: str, port: int, **kw):
    """Start a worker Process with JAX pinned to CPU in its environment.

    The override must happen in the PARENT around start(): under the
    spawn method the child re-imports the main module (and with it jax)
    during bootstrap, before any code inside run_worker executes — a
    chip belongs to ONE process (the owner), and a child that inherits
    the accelerator platform fails or hangs reaching for it."""
    with _SPAWN_LOCK:
        old = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            p = ctx.Process(target=run_worker,
                            args=(data_dir, socket_path, port),
                            kwargs=kw, daemon=True)
            p.start()
        finally:
            if old is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = old
    return p


def run_worker(data_dir: str, socket_path: str, port: int,
               host: str = "127.0.0.1", ready=None, stop=None,
               small_rank_n: int | None = None) -> None:
    """Worker process main: read-only Switchboard + HTTP on a shared
    SO_REUSEPORT port. `ready`/`stop` are optional multiprocessing
    Events for supervised startup/shutdown."""
    # workers never touch the accelerator (device ranking rides the
    # socket to the owner): pin jax to CPU BEFORE anything imports it —
    # the owner process holds the chip, and a second process reaching
    # for it fails or hangs
    os.environ["JAX_PLATFORMS"] = "cpu"
    from . import YaCyHttpServer
    sb = make_worker_switchboard(data_dir, socket_path,
                                 small_rank_n=small_rank_n)
    srv = YaCyHttpServer(sb, port=port, host=host, reuse_port=True).start()
    if ready is not None:
        ready.set()
    try:
        if stop is not None:
            stop.wait()
        else:                      # standalone: serve until killed
            threading.Event().wait()
    finally:
        srv.close()
        # NO sb.close(): beyond the detached journals, subsystem close
        # paths (frontier, web structure, dense) rewrite files from this
        # worker's possibly-stale view of the owner's live data dir. The
        # process exits here — mmaps and sockets die with it.
        if sb.index.devstore is not None:
            sb.index.devstore.close()
