"""Servlet registry — the htroot dispatch table.

The reference compiles `htroot/<Name>.java` classes and invokes their
static `respond(RequestHeader, serverObjects, serverSwitch)` by reflection
(reference: source/net/yacy/http/servlets/YaCyDefaultServlet.java:658,
765-785). Here servlets are plain functions with the same signature,
registered by name; `/<Name>.<ext>` dispatches to the function and then
fills the `<Name>.<ext>` template.
"""

from __future__ import annotations

import threading
from typing import Callable

from ..objects import ServerObjects

Servlet = Callable[[dict, ServerObjects, object], ServerObjects]

_REGISTRY: dict[str, Servlet] = {}


def servlet(name: str):
    def deco(fn: Servlet) -> Servlet:
        _REGISTRY[name] = fn
        return fn
    return deco


def lookup(name: str) -> Servlet | None:
    _ensure_loaded()
    return _REGISTRY.get(name)


def names() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_loaded = False
_load_lock = threading.RLock()


def _ensure_loaded() -> None:
    """Import every servlet module once. `_loaded` flips only AFTER the
    imports, under a lock: the first requests after a start arrive
    concurrently, and a request that saw the flag before the registry
    filled was answered with the raw template file (200, static)."""
    global _loaded
    if _loaded:
        return
    with _load_lock:
        if _loaded:
            return
        from . import (yacysearch, status, admin, api,  # noqa: F401
                       boards, breadth, federate, gameday, graphics,
                       health, ingest, operator, proxy, monitoring, tail)
        _loaded = True
