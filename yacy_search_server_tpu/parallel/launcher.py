"""Mesh fleet launcher + supervision harness (ISSUE 12).

One command brings a multi-process SPMD mesh up from nothing::

    python -m yacy_search_server_tpu.parallel.launcher --procs 3

The launcher finds free ports, spawns one child interpreter per mesh
process (``python -m yacy_search_server_tpu.parallel.distributed`` with
the ``YACY_MESH_*`` env contract — XLA flags land in the environment
BEFORE the child's jax initializes, which is the only reliable way to
size the per-process CPU device pool), waits for every member's HTTP
face to answer, and supervises:

* **watchdog/reaper** — children run in their own process group; ANY
  failure path (exception during bring-up, test error, supervisor
  exit) kills the whole group with TERM→KILL escalation, and an atexit
  hook backstops even that.  Children additionally watch their parent
  pid and exit on reparenting, so an orphaned fleet cannot outlive a
  SIGKILLed supervisor.
* **liveness** — `poll()` reaps exited children and reports who died;
  `kill_member()` is the chaos-harness surface for the survival tests.

The fleet object is also the client: `search()` POSTs to the
coordinator's ``/yacy/meshsearch.html`` wire servlet (the same JSON
wire every peer RPC uses), `info()`/`fault()` hit the members directly.

This is a CPU harness for the multi-process PROTOCOL: every child is
pinned to ``JAX_PLATFORMS=cpu`` with virtual devices and cannot hold a
chip (a chip belongs to one process). On a TPU host the several-chip
serving path is the single-process ``MeshSegmentStore`` that
``python -m yacy_search_server_tpu.yacy -start`` builds by itself when
it finds more than one device (``index.device.mesh=auto``).
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from . import distributed as D

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _free_ports(n: int) -> list[int]:
    """Bind-then-release n distinct ephemeral ports (the standard
    small-race pattern; children bind immediately after spawn)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _post(url: str, payload: dict, timeout_s: float = 30.0,
          headers: dict | None = None) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return json.loads(r.read().decode("utf-8"))


def _post_ex(url: str, payload: dict, timeout_s: float = 30.0,
             headers: dict | None = None) -> tuple[int, dict]:
    """Status-capturing POST: an admission 429 (or any HTTP error) is a
    RESULT the game-day workload records, not an exception — the
    availability gate is 'degraded + counted, never 500'."""
    try:
        return 200, _post(url, payload, timeout_s, headers)
    except urllib.error.HTTPError as e:
        try:
            body = e.read().decode("utf-8", "replace")
        except OSError:
            body = ""
        return e.code, {"error": body[:200]}


class MeshFleet:
    """Supervisor + client for one multi-process mesh."""

    def __init__(self, procs: int = 2, local_devices: int = 2,
                 ndocs: int = 512, seed: int = 3, n_term: int = 1,
                 run_dir: str | None = None, testing: bool = True,
                 bringup_timeout_s: float = 120.0,
                 config: dict | None = None):
        assert procs >= 2, "a multi-process mesh needs >= 2 processes"
        self.procs = procs
        self.local_devices = local_devices
        self.children: list[subprocess.Popen] = []
        self.run_dir = run_dir
        self._closed = False
        coord_port, *self.http_ports = _free_ports(procs + 1)
        self.logs: list[str] = []
        env_common = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{local_devices}",
            "PYTHONPATH": _REPO_ROOT + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            D.ENV_COORDINATOR: f"127.0.0.1:{coord_port}",
            D.ENV_NPROCS: str(procs),
            D.ENV_LOCAL_DEVICES: str(local_devices),
            D.ENV_HTTP_PORTS: ",".join(str(p) for p in self.http_ports),
            D.ENV_NDOCS: str(ndocs),
            D.ENV_SEED: str(seed),
            D.ENV_NTERM: str(n_term),
        }
        if testing:
            env_common[D.ENV_TESTING] = "1"
        if config:
            # construction-time knobs for every member's Switchboard
            # (incident cooldown, admission burst, conviction windows —
            # things the engines read once; see Config.__init__)
            env_common["YACY_CONFIG_OVERRIDES"] = ",".join(
                f"{k}={v}" for k, v in sorted(config.items()))
        atexit.register(self.close)
        try:
            for i in range(procs):
                env = dict(env_common)
                env[D.ENV_PROC_ID] = str(i)
                if run_dir:
                    mdir = os.path.join(run_dir, f"member{i}")
                    # fresh slate: a reused run dir would load last
                    # run's persisted index UNDER the deterministic
                    # corpus ingest — duplicate postings, divergent
                    # rankings (the SPMD corpus contract is per-run)
                    import shutil
                    shutil.rmtree(os.path.join(mdir, "DATA"),
                                  ignore_errors=True)
                    os.makedirs(mdir, exist_ok=True)
                    env[D.ENV_DATA_DIR] = os.path.join(mdir, "DATA")
                    logf = open(os.path.join(mdir, "member.log"), "wb")
                    self.logs.append(logf.name)
                else:
                    logf = subprocess.DEVNULL
                try:
                    self.children.append(subprocess.Popen(
                        [sys.executable, "-m",
                         "yacy_search_server_tpu.parallel.distributed"],
                        env=env, cwd=_REPO_ROOT,
                        stdout=logf, stderr=subprocess.STDOUT,
                        start_new_session=True))
                finally:
                    # Popen dup'd the fd into the child; the parent's
                    # handle would otherwise leak one fd per member per
                    # fleet in a long-lived supervisor
                    if logf is not subprocess.DEVNULL:
                        logf.close()
            self._wait_ready(bringup_timeout_s)
        except Exception:
            self.close()
            raise

    # -- supervision ---------------------------------------------------------

    def _wait_ready(self, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        fps = {}
        for i, port in enumerate(self.http_ports):
            while True:
                dead = self.poll()
                if dead:
                    raise RuntimeError(
                        f"mesh member(s) {dead} died during bring-up "
                        f"(logs: {self.logs})")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"member {i} not ready in {timeout_s}s "
                        f"(logs: {self.logs})")
                try:
                    info = self.info(i, timeout_s=5.0)
                    if info.get("ready"):
                        fps[i] = info.get("fp")
                        break
                except Exception:
                    time.sleep(0.3)
        # the partition-math determinism assertion (ISSUE 12 satellite):
        # every process must place every (term, doc) cell identically
        if len(set(fps.values())) != 1:
            raise RuntimeError(
                f"partition fingerprints diverge across processes: {fps}")
        self.fingerprint = fps[0]

    def poll(self) -> list[int]:
        """Reap exited children; returns the ids of the dead."""
        return [i for i, c in enumerate(self.children)
                if c.poll() is not None]

    def kill_member(self, i: int, sig=signal.SIGKILL) -> None:
        """Chaos surface: hard-kill one mesh process mid-soak."""
        try:
            os.kill(self.children[i].pid, sig)
        except ProcessLookupError:
            pass

    def close(self) -> None:
        """The any-failure-path reaper: TERM the whole process group of
        every child, escalate to KILL, and wait() each so no zombie —
        and no orphaned grandchild — survives the supervisor."""
        if self._closed:
            return
        self._closed = True
        for c in self.children:
            if c.poll() is None:
                try:
                    os.killpg(os.getpgid(c.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + 5.0
        for c in self.children:
            while c.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if c.poll() is None:
                try:
                    os.killpg(os.getpgid(c.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
            try:
                c.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                pass

    def __enter__(self) -> "MeshFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- client --------------------------------------------------------------

    def _url(self, i: int, endpoint: str) -> str:
        return f"http://127.0.0.1:{self.http_ports[i]}/yacy/" \
               f"{endpoint}.html"

    def search(self, word: str, k: int = 10,
               timeout_s: float = 90.0) -> dict:
        """One query through the coordinator's wire entry: scatter →
        cross-process collective (or committed host fallback) → fused
        ranking."""
        return _post(self._url(0, "meshsearch"),
                     {"word": word, "k": k}, timeout_s=timeout_s)

    def search_ex(self, word: str, k: int = 10,
                  timeout_s: float = 90.0,
                  client: str | None = None) -> tuple[int, dict]:
        """Status-capturing search with an optional per-client identity
        (X-Forwarded-For from loopback — the game-day workload realism
        layer, so token buckets/admission key on the synthetic client
        instead of the universally-exempt 127.0.0.1)."""
        hdrs = {"X-Forwarded-For": client} if client else None
        return _post_ex(self._url(0, "meshsearch"), {"word": word,
                        "k": k}, timeout_s=timeout_s, headers=hdrs)

    def get(self, i: int, page: str, timeout_s: float = 30.0,
            client: str | None = None) -> tuple[int, float]:
        """One regular-servlet GET against member `i` (status,
        wall_ms): the game-day driver for the servlet.serving SLO wall
        — the mesh wire entry bypasses the regular dispatch where that
        failpoint lives."""
        url = f"http://127.0.0.1:{self.http_ports[i]}/{page}"
        req = urllib.request.Request(
            url, headers={"X-Forwarded-For": client} if client else {})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as r:
                r.read()
                code = r.status
        except urllib.error.HTTPError as e:
            e.read()
            code = e.code
        return code, (time.perf_counter() - t0) * 1000.0

    def info(self, i: int, timeout_s: float = 30.0,
             tick_health: bool = False,
             prime_tail_gate: bool = False) -> dict:
        """Member introspection; `tick_health=True` additionally drives
        one health-engine evaluation on the member (the tail-forensics
        harness's incident driver — mesh members run no busy threads);
        `prime_tail_gate=True` drops every histogram family's windowed
        samples so compile-era warmup walls cannot hold the tail
        classifier's cached-p95 exemplar gate (or the SLO burn
        windows) above the live workload (the game-day
        warmup/measurement boundary)."""
        payload: dict = {}
        if tick_health:
            payload["tick_health"] = 1
        if prime_tail_gate:
            payload["prime_tail_gate"] = 1
        return _post(self._url(i, "meshinfo"), payload,
                     timeout_s=timeout_s)

    def fault(self, i: int, point: str, value,
              clear: bool = False) -> dict:
        return _post(self._url(i, "meshfault"),
                     {"point": point, "value": value, "clear": clear})

    def fault_list(self, i: int, n: int = 0) -> dict:
        """Member `i`'s faultinject registry + armed snapshot + the
        timestamped arm/clear/expire schedule (ISSUE 19: the verdict
        engine's one source of truth)."""
        return _post(self._url(i, "meshfault"), {"list": 1, "n": n})


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="bring up a multi-process SPMD mesh (ISSUE 12)")
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=2)
    ap.add_argument("--ndocs", type=int, default=512)
    ap.add_argument("--n-term", type=int, default=1)
    ap.add_argument("--run-dir", default=None)
    ap.add_argument("--query", default="meshterm",
                    help="smoke query served after bring-up")
    ap.add_argument("--serve", action="store_true",
                    help="keep the fleet up until Ctrl-C")
    args = ap.parse_args(argv)
    with MeshFleet(procs=args.procs, local_devices=args.local_devices,
                   ndocs=args.ndocs, n_term=args.n_term,
                   run_dir=args.run_dir) as fleet:
        print(f"mesh up: {args.procs} processes x "
              f"{args.local_devices} devices, fp={fleet.fingerprint}")
        for i in range(args.procs):
            info = fleet.info(i)
            print(f"  member {i}: pid={info['pid']} "
                  f"http={fleet.http_ports[i]}")
        rep = fleet.search(args.query)
        print(f"query '{args.query}': mode={rep['mode']} "
              f"top={rep['docids'][:5]} pids={sorted(rep['pids'].values())}")
        if args.serve:
            print("serving; Ctrl-C to stop")
            try:
                while not fleet.poll():
                    time.sleep(1.0)
            except KeyboardInterrupt:
                pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
