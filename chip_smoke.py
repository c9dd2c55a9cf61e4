#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the search path still starts
and serves on the chip.

One process, no arguments needed::

    python chip_smoke.py                  # on a TPU: the contract run
    python chip_smoke.py --cpu-rehearsal  # same legs, ~20k docs, on the CPU

It starts a node the way an operator does (``yacy.startup`` — the
function behind ``-start``), loads a corpus a YaCy peer really holds,
asks every query family over real HTTP from client threads, restarts the
same data dir with ``index.device.serving=false`` and holds the device
answers to the host path's. Exit code 0 only if every check held; the
last line of standard output is then one JSON object naming the device.

Without a TPU it exits non-zero before touching the package. The
rehearsal labels every line ``rehearsal`` and never prints the pass
line: it is how the control flow is debugged before chip time is spent.

Sizes may be cut with --docs/--terms/--postings-per-term/--pipeline-docs
(to fit a time limit); every cut is printed.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.parse
import urllib.request

# -- the full-size run ------------------------------------------------------
# >= 10M documents; postings resident until the chip itself reports >= 4 GiB
# in use. A posting costs 56 B of HBM on a v5e (42 B logical; the int16
# (rows, 17) buffer pads 17 -> 24 columns, PERF.md) plus 8 B of join index:
# 64M postings grow the arena to 2^26 rows = 2^26 * 64 B = 4 GiB, before
# bitmaps and executables. That is as far as one chip goes: the next
# doubling would hold 7.5 GB twice over during the copy-on-write append, and
# the arena refuses it (DeviceArena.fits) instead of running out of memory.
# 128 terms x 500k postings, not fewer and longer lists: at 32 x 2M a wave
# of concurrent conjunctions holds the chip longer than the batcher's 2 s
# watchdog allows (1-3 batch_timeouts per window, measured in PR 21 —
# PERF.md, ROADMAP S2/S7), and this script asserts there are none.
FULL = {"docs": 10_000_000, "terms": 128, "postings_per_term": 500_000,
        "pipeline_docs": 8_192, "hosts": 4_096}
REHEARSAL = {"docs": 20_000, "terms": 8, "postings_per_term": 8_000,
             "pipeline_docs": 4_200, "hosts": 16}
MIN_DEVICE_BYTES = 4 << 30          # what memory_stats() must report in use
BUDGET_BYTES = 12 << 30             # index.device.budgetBytes for the run
CLIENT_THREADS = 8
PIPE_TERM = "smokeshared"
DEADLINE_S = 1150                   # hard stop inside the 1200 s contract

# the rerank leg's tolerance, with its reason: both sides contract
# bf16-rounded vectors with f32 accumulation (ops/dense.py), but in a
# different order (batched einsum over a gathered forward index vs one
# dot over an uploaded block). f32 sums of dim=256 products differ by up
# to dim * 2^-24 relative, and the boost is cosine * alpha *
# DENSE_BOOST_SCALE (255 << 15): 256 * 2^-24 * 8.4e6 = 128 score units.
RERANK_TOL = 128


class Smoke:
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.failures: list[str] = []
        self.summary: dict = {}

    def say(self, msg: str) -> None:
        print(("rehearsal " if self.rehearsal else "") + msg, flush=True)

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
        self.say(("ok    " if ok else "FAIL  ") + what)
        return ok


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    for k in ("docs", "terms", "postings-per-term", "pipeline-docs"):
        ap.add_argument(f"--{k}", type=int, default=None)
    return ap.parse_args(argv)


# -- HTTP client side -------------------------------------------------------

def _get(base: str, path: str, timeout: float = 120.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.status, dict(r.headers), r.read()


def _search(base: str, query: str, count: int = 10, hybrid: bool = False):
    """One /yacysearch.json request -> (status, degraded header,
    [(link, ranking)])."""
    q = urllib.parse.urlencode({"query": query, "nocache": "true",
                                "maximumRecords": count,
                                **({"hybrid": "true"} if hybrid else {})})
    status, headers, body = _get(base, "/yacysearch.json?" + q)
    try:
        items = json.loads(body)["channels"][0]["items"]
    except ValueError:
        raise RuntimeError(f"malformed JSON for {query!r}: "
                           f"{body[:600]!r}") from None
    return (status, headers.get("X-YaCy-Degraded"),
            [(it["link"], int(it["ranking"])) for it in items])


def _serving_metrics(base: str) -> dict:
    """yacy_device_serving_total{counter=...} off /metrics."""
    _s, _h, body = _get(base, "/metrics")
    out = {}
    for line in body.decode("utf-8").splitlines():
        if line.startswith('yacy_device_serving_total{counter="'):
            name = line.split('"')[1]
            out[name] = int(float(line.rsplit(" ", 1)[1]))
    return out


def _legs(t: int, nt: int) -> list[tuple[str, str, int, bool]]:
    """Thread t's queries: (leg, query, count, hybrid). Distinct terms
    per thread — repeats of one query would be served by the caches.
    Each thread starts at another leg: mixed traffic, not eight
    conjunctions in the same instant."""
    a, b, c = (f"benchterm{(t + i) % nt}" for i in range(3))
    legs = [
        ("term", a, 10, False),                        # pruned kernel
        ("and", f"{a} {b}", 10, False),                # join kernels
        ("and_not", f"{a} {b} -{c}", 10, False),
        ("site", f"site:h{t + 1}.example {a}", 10, False),  # scan + bitmap
        # rerank kernel on the pipeline-indexed term; a per-thread page
        # size (inside the one prewarmed top-k bucket, k <= 128) keys
        # the requests apart in the hybrid top-k cache
        ("hybrid", PIPE_TERM, 10 + t % 7, True),
    ]
    return legs[t % len(legs):] + legs[:t % len(legs)]


def _in_threads(fns, timeout_s: float, what: str) -> None:
    """Run the callables side by side; a failure in any of them (or one
    that outlives `timeout_s`) is raised here, on the caller's thread —
    no phase's failure is swallowed."""
    errors: list = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:
                errors.append(e)
        return run

    ts = [threading.Thread(target=guarded(fn)) for fn in fns]
    for th in ts:
        th.start()
    for th in ts:
        th.join(timeout_s)
    if errors:
        raise RuntimeError(f"{what} failed") from errors[0]
    if any(th.is_alive() for th in ts):
        raise RuntimeError(f"{what} did not finish in {timeout_s:.0f} s")


def _ask_all(base: str, nt: int) -> dict:
    """Every leg from CLIENT_THREADS client threads over real HTTP.
    Returns {(thread, leg): (status, degraded, rows)}."""
    out: dict = {}

    def client(t: int) -> None:
        for leg, query, count, hybrid in _legs(t, nt):
            out[(t, leg)] = _search(base, query, count, hybrid)

    _in_threads([lambda t=t: client(t) for t in range(CLIENT_THREADS)],
                600, "a client thread")
    return out


def _warm_round(sb, nt: int, width: int) -> None:
    """The first `width` client threads' queries through
    Switchboard.search, below the
    servlet: a cold kernel compile lasts seconds, and the same queries
    over HTTP would land those walls in the servlet.serving histogram,
    burn the serving SLO and send the degradation ladder down before
    the node has taken its first real request (PERF.md, open
    questions)."""
    def warm(t: int) -> None:
        for _leg, query, count, hybrid in _legs(t, nt):
            sb.search(query, count=count, hybrid=hybrid,
                      use_cache=False).results(offset=0, count=count)

    _in_threads([lambda t=t: warm(t) for t in range(width)],
                900, "a warm-up thread")


# -- corpus -----------------------------------------------------------------

def _load_pipeline_docs(sb, n: int, say) -> float:
    """>= SMALL_RANK_N documents through the PRODUCT write path
    (parse_source -> Segment.store_document) sharing one term, so that
    term clears the host gate, carries dense vectors, and its RAM flush
    packs onto the device under a live arena. These go in FIRST: the
    dense forward index is aligned to docids, and the rerank kernel only
    serves while that index fits its device budget."""
    from yacy_search_server_tpu.document.parser.registry import parse_source
    t0 = time.perf_counter()
    words = ("alpha", "bravo", "charlie", "delta", "echo", "foxtrot",
             "golf", "hotel", "india", "juliet", "kilo", "lima")
    for i in range(n):
        body = " ".join(words[(i * 7 + j) % len(words)]
                        for j in range(3 + i % 5))
        html = (f"<html><head><title>{PIPE_TERM} page {i}</title></head>"
                f"<body><p>{PIPE_TERM} {body} item{i % 97}</p></body>"
                f"</html>").encode("utf-8")
        doc = parse_source(f"http://p{i % 64}.example/page{i}.html",
                           "text/html", html)[0]
        sb.index.store_document(doc)
    sb.index.rwi.flush()
    wall = time.perf_counter() - t0
    say(f"write path: {n} docs in {wall:.1f}s ({n / wall:.0f} docs/s)")
    return wall


def _load_bulk(sb, cfg: dict, seed: int, say) -> float:
    """metadata.bulk_load + snapshot(), rwi.ingest_run per term — the
    only bulk path the package has (parallel/distributed.py uses the
    same). Uniform random features; data is made from --seed. The two
    stores are independent, so the metadata rows (Python-bound) and the
    postings (NumPy-bound) load side by side, as a node that imports
    while it crawls would."""
    import numpy as np
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.utils.hashes import word2hash
    t0 = time.perf_counter()
    n, hosts = cfg["docs"], cfg["hosts"]
    base = sb.index.metadata.capacity()     # docids continue from here
    walls = {}

    def load_metadata() -> None:
        step = 1_000_000        # bounded Python-object heap per chunk
        for lo in range(0, n, step):
            hi = min(n, lo + step)
            first = sb.index.metadata.bulk_load(
                [f"{i:07d}{i % hosts:05d}".encode("ascii")
                 for i in range(lo, hi)],
                sku=[f"http://h{i % hosts}.example/d{i}.html"
                     for i in range(lo, hi)],
                title=[f"doc {i}" for i in range(lo, hi)],
                host_s=[f"h{i % hosts}.example" for i in range(lo, hi)],
                size_i=[1000] * (hi - lo), wordcount_i=[100] * (hi - lo))
            if first != base + lo:
                raise RuntimeError(f"docid {first} != {base + lo}")
            sb.index.metadata.snapshot()
        walls["metadata"] = time.perf_counter() - t0

    def load_postings() -> None:
        rng = np.random.default_rng(seed)
        per = cfg["postings_per_term"]
        for t in range(cfg["terms"]):
            docids = base + np.sort(rng.choice(n, per, replace=False)
                                    ).astype(np.int32)
            feats = rng.integers(0, 1000, (per, P.NF)).astype(np.int32)
            feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, per)
            feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, per)
            feats[:, P.F_LANGUAGE] = P.pack_language("en")
            sb.index.rwi.ingest_run(
                {word2hash(f"benchterm{t}"): PostingsList(docids, feats)})
        walls["postings"] = time.perf_counter() - t0

    _in_threads([load_metadata, load_postings], DEADLINE_S, "the bulk load")
    wall = time.perf_counter() - t0
    say(f"bulk load: {n} docs (docids from {base}) in "
        f"{walls['metadata']:.1f}s || {cfg['terms']} terms x "
        f"{cfg['postings_per_term']} postings in "
        f"{walls['postings']:.1f}s; {wall:.1f}s together")
    return wall


# -- node lifecycle ---------------------------------------------------------

def _start(data_dir: str, device_serving: bool):
    """A node as `python -m yacy_search_server_tpu.yacy -start` builds
    it: SETTINGS/yacy.conf, then yacy.startup (P2P stack included)."""
    from yacy_search_server_tpu import yacy
    os.makedirs(os.path.join(data_dir, "SETTINGS"), exist_ok=True)
    with open(os.path.join(data_dir, "SETTINGS", "yacy.conf"), "w",
              encoding="utf-8") as f:
        f.write(f"index.device.budgetBytes={BUDGET_BYTES}\n")
        if not device_serving:
            # the reference answers at host speed, slower than the
            # serving SLO; its degradation ladder would shed stages
            # (and then requests) and stop being the plain reference
            f.write("index.device.serving=false\n"
                    "actuator.enabled=false\n")
    return yacy.startup(data_dir, port=0)


def _stop(node, http, lock) -> None:
    """The normal close path (yacy.main's finally block)."""
    from yacy_search_server_tpu import yacy
    node.close()
    http.close()
    yacy.release_lock(lock)


# -- comparison against the host path ---------------------------------------

def _first_diff(dev: list, host: list):
    for i, (d, h) in enumerate(zip(dev, host)):
        if d != h:
            return i, d, h
    if len(dev) != len(host):
        return min(len(dev), len(host)), None, None
    return None


def _rerank_agrees(dev: list, host: list):
    """Same links, rankings within RERANK_TOL; a link only one side
    lists must sit within the tolerance of the other side's last row
    (two candidates swapped across the page boundary)."""
    dm, hm = dict(dev), dict(host)
    for link in set(dm) | set(hm):
        if link in dm and link in hm:
            if abs(dm[link] - hm[link]) > RERANK_TOL:
                return f"{link}: {dm[link]} vs {hm[link]}"
        else:
            have, other = (dm, host) if link in dm else (hm, dev)
            if not other or abs(have[link] - other[-1][1]) > RERANK_TOL:
                return f"{link} on one side only ({have[link]})"
    return None


# -- the run ----------------------------------------------------------------

def run(args) -> int:
    rehearsal = args.cpu_rehearsal
    sm = Smoke(rehearsal)
    say, check = sm.say, sm.check
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    # 1. the backend decides before anything of the package is imported
    import jax
    devs = jax.devices()
    backend = jax.default_backend()
    say(f"jax {jax.__version__} backend={backend} devices={len(devs)} "
        f"kinds={[d.device_kind for d in devs]}")
    if not rehearsal and backend != "tpu":
        print(f"chip_smoke: no TPU — JAX found backend {backend!r} "
              f"({len(devs)} device(s)); refusing to run on it. Use "
              f"--cpu-rehearsal to debug the control flow on the CPU.",
              file=sys.stderr)
        return 2
    try:
        from yacy_search_server_tpu.ops import roofline
    except ImportError as e:
        print(f"chip_smoke: the yacy_search_server_tpu package is not "
              f"beside this script: {e}", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    if kind.lower() not in roofline.PEAKS:
        print(f"chip_smoke: device_kind {kind!r} is not a key of "
              f"ops/roofline.PEAKS {sorted(roofline.PEAKS)}",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs)}

    cfg = dict(REHEARSAL if rehearsal else FULL)
    for k in ("docs", "terms", "postings_per_term", "pipeline_docs"):
        v = getattr(args, k)
        if v is not None and v != cfg[k]:
            say(f"CUT {k}: {cfg[k]} -> {v}")
            cfg[k] = v
    full_size = not rehearsal and all(cfg[k] >= FULL[k] for k in FULL)

    # the .so is a build product git never ships: rebuild it from source
    from yacy_search_server_tpu.utils import compilecache, native
    if os.path.exists(native._SO_PATH):
        os.remove(native._SO_PATH)
    check(native.available(), "native/libyacytpu.so rebuilt from "
                              "native/yacytpu.cpp and loaded")

    cache_dir = compilecache.ensure()
    cache0 = compilecache.entry_count(cache_dir)
    say(f"compile cache: {cache_dir} ({cache0} entries before)")

    compiles = {"n": 0, "from_cache": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_kw: compiles.__setitem__(
            "n", compiles["n"]
            + (ev == "/jax/core/compile/backend_compile_duration")))
    jax.monitoring.register_event_listener(
        lambda ev, **_kw: compiles.__setitem__(
            "from_cache", compiles["from_cache"]
            + (ev == "/jax/compilation_cache/cache_hits")))

    data_dir = tempfile.mkdtemp(prefix="yacy-chip-smoke-")
    say(f"data dir {data_dir} "
        f"({shutil.disk_usage(data_dir).free >> 30} GiB free)")
    try:
        # 2. start the node the way an operator does
        t_start = time.perf_counter()
        node, http, lock = _start(data_dir, device_serving=True)
        try:
            sb = node.sb
            ds = sb.index.devstore
            check(ds is not None, "device store attached by the normal "
                                  "start (yacy.conf honoured)")
            store = type(ds).__name__
            arena_budget = getattr(getattr(ds, "arena", ds), "budget_bytes")
            check(arena_budget == BUDGET_BYTES,
                  f"index.device.budgetBytes from yacy.conf reached the "
                  f"store ({arena_budget})")
            say(f"store: {store}, {len(devs)} device(s)")
            if rehearsal and hasattr(ds, "_maybe_prewarm"):
                # the CPU backend skips the background prewarm by
                # default; the rehearsal exists to run that thread
                ds._prewarm_on = True
                ds._maybe_prewarm()

            # 3. corpus
            _load_pipeline_docs(sb, cfg["pipeline_docs"], say)
            _load_bulk(sb, cfg, args.seed, say)
            nt = cfg["terms"]
            from yacy_search_server_tpu.utils.hashes import word2hash
            resident = sum(
                ds.spans_for(word2hash(w)) is not None
                for w in [PIPE_TERM] + [f"benchterm{t}" for t in range(nt)])
            check(resident == nt + 1,
                  f"all {nt + 1} query terms device-resident ({resident})")

            for dv in devs:
                st = dv.memory_stats() or {}
                say(f"device {dv.id} after load: bytes_in_use "
                    f"{st.get('bytes_in_use')} peak "
                    f"{st.get('peak_bytes_in_use')}")

            # 4. prewarm, then unasserted rounds of the same queries at
            # 1, 2, 4, 8 and 8 threads (a deployment warms before it takes
            # traffic: the first join and the first facet bitmap re-key
            # compile families, each batch-size bucket is its own
            # compile, and the mesh store has no prewarm at all). The
            # top-k caches are emptied after each round: the asserted
            # window must reach the kernels, not the cached answers.
            t_pw = time.perf_counter()
            pw_ok = ds.prewarm_wait(900.0) \
                if hasattr(ds, "prewarm_wait") else True
            prewarm_wall = time.perf_counter() - t_pw
            check(pw_ok, "prewarm covered the current arena shapes")
            for width in (1, 2, 4, CLIENT_THREADS, CLIENT_THREADS):
                _warm_round(sb, nt, width)
                ds._topk_cache.clear()
            if hasattr(ds, "prewarm_wait"):
                check(ds.prewarm_wait(900.0) and ds.join_prewarm_wait(),
                      "prewarm re-covered the shapes the warm-up re-keyed")
            warm_wall = time.perf_counter() - t_pw
            c_warm = ds.counters()
            say(f"prewarm: waited {prewarm_wall:.1f}s after load "
                f"({time.perf_counter() - t_start:.1f}s since start), "
                f"{c_warm.get('prewarm_shapes', 'n/a')} shapes warmed, "
                f"{c_warm.get('prewarm_failures', 0)} failed; warm-up "
                f"rounds + re-warm {warm_wall - prewarm_wall:.1f}s; "
                f"{compiles['n']} compilations so far "
                f"({compiles['from_cache']} from the persistent cache)")
            check(c_warm.get("prewarm_failures", 0) == 0,
                  "prewarm_failures == 0")

            # 5. the asserted window
            sb.search_cache.clear()
            c0, m0, n0 = ds.counters(), _serving_metrics(http.base_url), \
                compiles["n"]
            t_q = time.perf_counter()
            dev_answers = _ask_all(http.base_url, nt)
            q_wall = time.perf_counter() - t_q
            c1, m1 = ds.counters(), _serving_metrics(http.base_url)
            d = {k: c1[k] - c0[k] for k in c1
                 if isinstance(c1[k], int) and k in c0}
            say(f"queries: {len(dev_answers)} over HTTP from "
                f"{CLIENT_THREADS} threads in {q_wall:.2f}s; "
                f"{compiles['n'] - n0} compilations during the window")
            check(all(v[0] == 200 for v in dev_answers.values()),
                  "every response is 200")
            check(all(v[1] is None for v in dev_answers.values()),
                  "no response carries X-YaCy-Degraded")
            check(all(len(v[2]) >= 10 for v in dev_answers.values()),
                  "every response lists >= 10 results")

            # 7. which legs the device served (a store that lacks a
            # family says so instead of passing it silently)
            on_device = {"term", "and", "and_not"}
            for leg, attr in (("site", "supports_filter_bitmap"),
                              ("hybrid", "rerank_boost")):
                if getattr(ds, attr, None):
                    on_device.add(leg)
                else:
                    say(f"not on device: {leg} ({store} has no "
                        f"{attr}; the host path answered)")
            eligible = CLIENT_THREADS * len(on_device)
            check(d["queries_served"] >= eligible,
                  f"queries_served +{d['queries_served']} >= {eligible} "
                  f"device-eligible queries")
            zero = ["fallbacks", "batch_timeouts", "device_lost",
                    "transfer_failures", "transfer_retries"]
            if "join_served" in c1:      # both stores count their joins
                check(d["join_served"] > 0,
                      f"join_served +{d['join_served']}")
                zero.append("join_fallbacks")
            if "stream_scans" in c1:     # DeviceSegmentStore families
                check(d["stream_scans"] > 0,
                      f"stream_scans +{d['stream_scans']}")
                check(d["rerank_queries"] > 0,
                      f"rerank_queries +{d['rerank_queries']}")
                zero += ["rerank_fallbacks", "prewarm_failures"]
            for k in zero:
                check(d[k] == 0, f"{k} == 0 (+{d[k]})")
            check(c1["device_losses"] == 0, "no device loss declared "
                                            "since the start")
            for k in ("queries_served", "fallbacks", "join_served",
                      "join_fallbacks", "rerank_queries",
                      "rerank_fallbacks", "prewarm_failures"):
                if k in c1:
                    check(m1.get(k, 0) - m0.get(k, 0) == d[k],
                          f"/metrics {k} delta == counters() delta")

            # 8. what the chip itself holds
            phys = []
            for dv in devs:
                st = dv.memory_stats() or {}
                phys.append(st.get("bytes_in_use", 0))
                say(f"device {dv.id}: bytes_in_use "
                    f"{st.get('bytes_in_use')} peak "
                    f"{st.get('peak_bytes_in_use')} limit "
                    f"{st.get('bytes_limit')}")
            live = sum(a.nbytes for a in jax.live_arrays())
            say(f"live jax arrays: {live} logical B in all (the rest of "
                f"bytes_in_use is layout padding and loaded executables)")
            rows = ds.live_rows()
            logical = rows * 42
            row_bytes = ds.arena.device_row_bytes if hasattr(ds, "arena") \
                else ds.device_row_bytes
            say(f"arena: {rows} postings resident; logical "
                f"{logical} B (42 B/posting); measured "
                f"{row_bytes:.1f} device B/row"
                + (f"; arena.bytes_used() {ds.arena.bytes_used()}"
                   if hasattr(ds, "arena") else "")
                + f"; chip bytes_in_use/logical = "
                  f"{sum(phys) / max(logical, 1):.2f}")
            if not rehearsal:
                if full_size:
                    check(sum(phys) >= MIN_DEVICE_BYTES,
                          f"chips report {sum(phys)} B in use "
                          f">= {MIN_DEVICE_BYTES}")
                if len(devs) > 1:
                    share = rows * row_bytes / len(devs)
                    check(all(p >= 0.5 * share for p in phys),
                          "every chip holds arena bytes")
            sm.summary.update(
                store=store, docs=cfg["docs"] + cfg["pipeline_docs"],
                postings_resident=rows, device_row_bytes=row_bytes,
                device_bytes_in_use=phys, prewarm_wait_s=prewarm_wall,
                warmup_s=warm_wall - prewarm_wall,
                prewarm_shapes=c_warm.get("prewarm_shapes"),
                compilations=compiles["n"],
                compilations_in_window=compiles["n"] - n0,
                on_device=sorted(on_device), query_window_s=q_wall,
                counters={k: d[k] for k in sorted(d) if d[k]})
        finally:
            _stop(node, http, lock)

        # 6. the plain reference: same data dir, host path
        node, http, lock = _start(data_dir, device_serving=False)
        try:
            check(node.sb.index.devstore is None,
                  "reference node runs without a device store")
            t_h = time.perf_counter()
            host_answers = _ask_all(http.base_url, nt)
            say(f"host path: {len(host_answers)} queries in "
                f"{time.perf_counter() - t_h:.1f}s")
        finally:
            _stop(node, http, lock)
        for leg in ("term", "and", "and_not", "site", "hybrid"):
            bad = None
            for t in range(CLIENT_THREADS):
                dev, host = dev_answers[(t, leg)][2], \
                    host_answers[(t, leg)][2]
                if leg == "hybrid":
                    why = _rerank_agrees(dev, host)
                    if why:
                        bad = f"thread {t}: {why}"
                elif dev != host:
                    bad = f"thread {t}: first differing row " \
                          f"{_first_diff(dev, host)}"
                if bad:
                    break
            check(bad is None,
                  f"{leg}: device answers "
                  + ("agree with the host path within "
                     f"{RERANK_TOL} score units" if leg == "hybrid"
                     else "identical to the host path")
                  + (f" — {bad}" if bad else ""))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    # 9. the cache, and the verdict
    cache1 = compilecache.entry_count(cache_dir)
    say(f"compile cache: {cache_dir} ({cache0} entries before, "
        f"{cache1} after)")
    sm.summary.update(cache_dir=cache_dir, cache_entries_before=cache0,
                      cache_entries_after=cache1, full_size=full_size,
                      failures=sm.failures, device=device)
    say("summary " + json.dumps(sm.summary, sort_keys=True))
    if sm.failures:
        say(f"FAILED: {len(sm.failures)} check(s)")
        return 1
    if rehearsal:
        say("all checks held on the CPU — this proves the control flow, "
            "not the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    args = _parse_args(argv)
    # never outlive the contract: dump every thread and exit non-zero
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
