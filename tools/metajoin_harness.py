"""What a new search event's metadata work costs under threads (ISSUE 28).

Not a test and not a benchmark cell: a ten-second measurement of HOST
work, run by hand (here, or on the chip's host through the chip tool).
It loads a MetadataStore the way ``benchmarks/run.py load_corpus`` does
(bulk_load + snapshot() per 500k rows) and times, from 1 / 4 / 8 threads,
the reads one new event makes for 80 ranked candidates of which 26 become
entries. ``--tree`` names the checkout to import the package from, so the
parent and the change are measured on one machine:

    python tools/metajoin_harness.py --tree /path/to/parent --out p.json
    python tools/metajoin_harness.py --out c.json

Every piece is timed alone as well (the bisection), next to the bare
NumPy calls under suspicion. Nothing here touches JAX or the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import resource
import statistics
import sys
import tempfile
import threading
import time

ENTRY_FIELDS = ("sku", "title", "host_s", "url_file_ext_s", "language_s",
                "size_i", "wordcount_i", "last_modified_days_i",
                "references_i")
CANDIDATES, ENTRIES = 80, 26


def build_store(metadata, rows: int, step: int, path: str):
    store = metadata.MetadataStore(path)
    hosts = 4096
    for lo in range(0, rows, step):
        r = range(lo, min(rows, lo + step))
        store.bulk_load(
            [f"{i:07d}{i % hosts:05d}".encode("ascii") for i in r],
            sku=[f"http://h{i % hosts}.example/wiki/doc{i}.html" for i in r],
            title=[f"doc {i}" for i in r],
            host_s=[f"h{i % hosts}.example" for i in r],
            size_i=[1000] * len(r), wordcount_i=[100] * len(r))
        store.snapshot()
    return store


def pieces(store, navigator, np):
    """name -> callable(candidates); what exists in the tree is timed."""
    navs = navigator.make_navigators()
    nav_fields = [n.field for n in navs.values()]
    out = {}

    def alive(cand):
        return [d for d in cand
                if not store.is_deleted(d) and d < store.capacity()]

    if hasattr(store, "rows_at"):
        def event(cand):
            rows = store.rows_at(cand, nav_fields,
                                 head_fields=ENTRY_FIELDS, head=ENTRIES)
            navigator.accumulate_batch(
                navigator.make_navigators(), rows.cols, rows.alive)
            cols = [rows.cols[f] for f in ENTRY_FIELDS]
            return [(rows.urlhashes[i], [c[i] for c in cols])
                    for i in range(ENTRIES) if rows.alive[i]]

        out["event"] = event
        out["rows_at 80 x host_s"] = \
            lambda cand: store.rows_at(cand, ("host_s",))
        return out

    def drain(cand):
        made = []
        for d in cand[:ENTRIES]:
            m = store.row(d)
            made.append(([m.get(f) for f in ENTRY_FIELDS],
                         store.urlhash_of(d)))
        return made

    def facets(cand):
        navigator.accumulate_batch(navigator.make_navigators(), store,
                                   cand)

    out["event"] = lambda cand: (facets(alive(cand)), drain(cand))
    out["per-row drain"] = drain
    out["navigator passes"] = facets
    out["text_values 80 x host_s"] = \
        lambda cand: store.text_values(cand, "host_s")
    out["alive"] = alive

    # the suspects, bare: one segment's offsets column of host_s
    seg = store._segs[0]
    offsets, blob = seg._text_maps("host_s")
    plain = np.asarray(offsets).view(np.ndarray)
    hashes = seg.array("urlhashes")
    mv = memoryview(offsets)
    n = seg.n

    def local(cand):
        return [d % n for d in cand]

    out["memmap[array] x 2"] = lambda cand: (
        lambda r: (offsets[r], offsets[r + 1]))(np.asarray(local(cand)))
    out["ndarray[array] x 2"] = lambda cand: (
        lambda r: (plain[r], plain[r + 1]))(np.asarray(local(cand)))
    out["bytes(memmap[a:b]) x 80"] = lambda cand: [
        bytes(blob[i:i + 12]) for i in local(cand)]
    out["np.may_share_memory x 80"] = lambda cand: [
        np.may_share_memory(plain, hashes) for _ in cand]
    out["memmap scalar x 160"] = lambda cand: [
        (int(offsets[i]), int(offsets[i + 1])) for i in local(cand)]
    out["memoryview scalar x 160"] = lambda cand: [
        (mv[i], mv[i + 1]) for i in local(cand)]
    out["np.asarray(list) x 10"] = lambda cand: [
        np.asarray(local(cand)) for _ in range(10)]
    out["bytes(S12 memmap scalar) x 52"] = lambda cand: [
        bytes(hashes[i]) for i in local(cand)[:ENTRIES] * 2]

    def imports(cand):
        for _ in range(CANDIDATES * 5):
            import bisect  # noqa: F401  (the suspect IS the statement)

    out["function-local import x 400"] = imports
    return out


def measure(fn, cands, threads: int, calls: int) -> dict:
    walls: list[float] = []
    cpus: list[float] = []
    gate = threading.Barrier(threads + 1)

    def work(seed):
        rnd = random.Random(seed)
        mine_w, mine_c = [], []
        gate.wait()
        for _ in range(calls):
            cand = cands[rnd.randrange(len(cands))]
            c0, t0 = time.thread_time(), time.perf_counter()
            fn(cand)
            mine_w.append(time.perf_counter() - t0)
            mine_c.append(time.thread_time() - c0)
        walls.extend(mine_w)
        cpus.extend(mine_c)

    ts = [threading.Thread(target=work, args=(s,)) for s in range(threads)]
    for t in ts:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    sw0 = resource.getrusage(resource.RUSAGE_SELF).ru_nvcsw
    for t in ts:
        t.join(600)
    if any(t.is_alive() for t in ts):
        raise RuntimeError("a harness thread did not finish")
    wall = time.perf_counter() - t0
    switches = resource.getrusage(resource.RUSAGE_SELF).ru_nvcsw - sw0
    walls.sort()
    # a thread that lets go of the interpreter lock and has to wait for
    # it is one voluntary context switch: the count a call says how
    # often the piece hands the lock away
    return {"threads": threads,
            "calls_per_s": round(threads * calls / wall, 1),
            "switches_per_call": round(switches / (threads * calls), 2),
            "p50_ms": round(statistics.median(walls) * 1e3, 3),
            "p95_ms": round(walls[int(len(walls) * 0.95)] * 1e3, 3),
            "cpu_ms_per_call": round(statistics.fmean(cpus) * 1e3, 3)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--step", type=int, default=500_000)
    ap.add_argument("--threads", default="1,4,8")
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    import numpy as np
    from yacy_search_server_tpu.index import metadata
    from yacy_search_server_tpu.search import navigator
    rnd = random.Random(28)
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        store = build_store(metadata, args.rows, args.step, tmp)
        load_s = time.perf_counter() - t0
        cands = [[rnd.randrange(args.rows) for _ in range(CANDIDATES)]
                 for _ in range(512)]
        result = {"tree": args.tree, "rows": args.rows,
                  "segments": len(store._segs),
                  "load_s": round(load_s, 1),
                  "switch_interval_s": sys.getswitchinterval(),
                  "cpus": os.cpu_count(), "pieces": {}}
        for name, fn in pieces(store, navigator, np).items():
            for cand in cands[:32]:
                fn(cand)         # columns open (and verify) before timing
            result["pieces"][name] = [
                measure(fn, cands, int(t), args.calls)
                for t in args.threads.split(",")]
            print(name, json.dumps(result["pieces"][name]), flush=True)
        store.close()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
