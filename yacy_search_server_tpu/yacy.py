"""Entry point + lifecycle: startup, lock file, CLI verbs, shutdown.

Capability equivalent of the reference's launcher (reference:
source/net/yacy/yacy.java — main:699, startup:149-408 creating the DATA
dir, the `yacy.running` lock file with PID:197-207, the Switchboard:210,
migration:285, the HTTP server:298-301, a JVM shutdown hook:380 and
sb.waitForShutdown:393; CLI verbs -start/-shutdown/-version:503-509,
where -shutdown POSTs to the running instance's Steering servlet).

Usage:
    python -m yacy_search_server_tpu.yacy [-start] [--data DIR] [--port N]
    python -m yacy_search_server_tpu.yacy -shutdown [--port N]
    python -m yacy_search_server_tpu.yacy -version
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

VERSION = "0.4.0"
REVISION = 0        # build counter within a version (release comparison)

DEFAULT_PORT = 8090


# -- lock file (yacy.running semantics) ---------------------------------------

def acquire_lock(data_dir: str) -> str:
    """Create DATA/yacy.running with our PID; detect unclean shutdown
    (yacy.java:197-207 write, :672 stale-lock detection)."""
    os.makedirs(data_dir, exist_ok=True)
    lock = os.path.join(data_dir, "yacy.running")
    if os.path.exists(lock):
        try:
            old_pid = int(open(lock, encoding="ascii").read().strip() or 0)
        except (OSError, ValueError):
            old_pid = 0
        if old_pid and _pid_alive(old_pid):
            raise RuntimeError(
                f"another instance (pid {old_pid}) holds {lock}")
        print(f"warning: stale lock {lock} (unclean shutdown?), removing",
              file=sys.stderr)
        os.remove(lock)
    with open(lock, "w", encoding="ascii") as f:
        f.write(str(os.getpid()))
    return lock


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def release_lock(lock: str) -> None:
    try:
        os.remove(lock)
    except OSError:
        pass


# -- startup ------------------------------------------------------------------

def startup(data_dir: str, port: int = DEFAULT_PORT, host: str = "127.0.0.1",
            peer_name: str | None = None, p2p: bool = True):
    """Build the full node: config, migration, switchboard/peer stack,
    HTTP server, busy threads. Returns (node_or_sb, http_server, lock)."""
    from .migration import migrate
    from .utils.config import Config

    lock = acquire_lock(data_dir)
    # async bounded logging first: everything after this logs through
    # the single-writer queue (ConcurrentLog shape, yacy.java:176-188)
    from .utils.logging import setup as setup_logging
    setup_logging(data_dir)
    settings = os.path.join(data_dir, "SETTINGS", "yacy.conf")
    config = Config(settings_path=settings)
    migrate(config, VERSION)

    port = config.get_int("port", port)
    peer_name = peer_name or config.get("peerName", f"peer-{os.getpid()}")

    def _upnp_map(sb_like) -> None:
        # best-effort router port mapping on startup (reference:
        # UPnP.addPortMappings on startup/port change, utils/upnp/
        # UPnP.java) — real SSDP/SOAP, config-gated, never fatal
        if not config.get_bool("upnp.enabled", False):
            return
        try:
            from .peers.operation import UPnP
            from .peers.upnp import SSDPDriver
            upnp = UPnP(driver=SSDPDriver())
            if upnp.add_port_mapping(port):
                sb_like.upnp = upnp
        except Exception:
            import logging
            logging.getLogger("yacy.upnp").debug(
                "UPnP port mapping unavailable", exc_info=True)

    try:
        if p2p:
            from .peers.node import P2PNode
            from .peers.transport import HttpTransport
            node = P2PNode(peer_name, HttpTransport(), data_dir=data_dir,
                           port=port, config=config)
            http = node.serve_http(host=host, port=port)
            node.deploy_threads()
            _upnp_map(node.sb)
            return node, http, lock
        from .server.httpd import YaCyHttpServer
        from .switchboard import Switchboard
        sb = Switchboard(data_dir=data_dir, config=config)
        http = YaCyHttpServer(sb, port=port, host=host).start()
        sb.deploy_threads()
        _upnp_map(sb)
        return sb, http, lock
    except BaseException:
        # a start that failed (device serving could not come up, port
        # taken) holds no lock: the next attempt is not a "stale" one
        release_lock(lock)
        raise


def wait_for_shutdown(sb) -> None:
    """Block until the shutdown event fires (signal or Steering servlet);
    the reference's sb.waitForShutdown."""
    ev = sb.shutdown_event

    def _sig(signum, frame):
        ev.set()

    for s in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(s, _sig)
        except ValueError:
            pass    # not the main thread (tests)
    while not ev.is_set():
        ev.wait(1.0)


# -- CLI verbs ----------------------------------------------------------------

def shutdown_running(port: int = DEFAULT_PORT,
                     host: str = "127.0.0.1") -> bool:
    """Ask a running instance to stop (yacy.java:503-509 POSTs to the
    Steering servlet)."""
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/Steering_p.json?shutdown=1",
                timeout=10) as r:
            return r.status == 200
    except OSError:
        return False


def peel_verb(argv: list[str]) -> tuple[str, list[str]]:
    """The reference's verbs are dash-prefixed (-start/-gui/-shutdown/
    -version), which argparse would read as options — peel first."""
    if argv and argv[0].lstrip("-") in ("start", "gui", "shutdown",
                                        "version"):
        return "-" + argv[0].lstrip("-"), argv[1:]
    return "-start", argv


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    verb, argv = peel_verb(argv)
    ap = argparse.ArgumentParser(prog="yacy-tpu", add_help=True)
    ap.add_argument("--data", default="DATA")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=DEFAULT_PORT)
    ap.add_argument("--name", default=None, help="peer name")
    ap.add_argument("--no-p2p", action="store_true")
    args = ap.parse_args(argv)
    args.verb = verb

    if args.verb == "-version":
        print(VERSION)
        return 0
    if args.verb == "-shutdown":
        ok = shutdown_running(args.port, args.host)
        print("shutdown requested" if ok else "no running instance found")
        return 0 if ok else 1

    node, http, lock = startup(args.data, port=args.port, host=args.host,
                               peer_name=args.name, p2p=not args.no_p2p)
    sb = getattr(node, "sb", node)
    print(f"serving on {http.base_url} (data: {args.data})")
    try:
        if args.verb == "-gui":
            # reference -gui: tray + browser popup beside the server
            # (gui/Tray.java); headless boxes degrade to the popup only
            from .gui import run_gui
            seed = getattr(node, "seed", None)
            run_gui(http.base_url, sb.shutdown_event,
                    peer_name=getattr(seed, "name", ""))
        wait_for_shutdown(sb)
    finally:
        print("shutting down ...")
        upnp = getattr(sb, "upnp", None)
        if upnp is not None:          # release router mappings (UPnP.java)
            try:
                upnp.delete_port_mappings()
            except Exception:
                import logging
                logging.getLogger("yacy.upnp").debug(
                    "UPnP unmap failed at shutdown", exc_info=True)
        node.close()
        http.close()
        release_lock(lock)
        from .utils.logging import shutdown as logging_shutdown
        logging_shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
