"""What the host gate's ranking costs under threads (ISSUE 34).

Not a test and not a benchmark cell: a measurement of HOST work, run by
hand (here, or on the chip's host through the chip tool), in the form of
``tools/metajoin_harness.py`` and with its ``measure``. A request under the
host gate ranks its candidates with one ``CardinalRanker(profile).rank(cand,
None, k=100)`` (``search.normalizing``); this times that call from 1 / 4 / 8
threads over blocks of 54, 430 and 2,048 rows (an And HighLow's survivors,
a short and a full Term Low list). ``--tree`` names the checkout to import
the package from, so the parent and the change are measured on one machine:

    python tools/hostrank_harness.py --tree /path/to/parent --out p.json
    python tools/hostrank_harness.py --out c.json

Where the tree has the fused native scorer, the choices it leaves are
timed beside the call as shipped: the library bound through ``CDLL`` (the
interpreter lock let go for the call) or ``PyDLL`` (held), the top k taken
inside the call or by NumPy's stable argsort outside it, and the NumPy twin
alone. ``--busy`` runs every measurement again beside that many threads
of plain interpreter work, which is what a serving node's other request
threads are to this one: they take the lock whenever it is let go and keep
it for a switch interval. Nothing here starts a node or touches the chip.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import sys
import threading
import time

from metajoin_harness import measure

K = 100
BLOCKS = 32


def blocks(np, P, n: int):
    """Candidate lists of n rows with the value ranges a crawl's postings
    have (word counts, positions, a 30-bit flag field, two languages)."""
    rng = np.random.default_rng(34 + n)
    out = []
    for _ in range(BLOCKS):
        f = rng.integers(0, 3000, (n, P.NF)).astype(np.int32)
        f[:, P.F_FLAGS] = rng.integers(0, 1 << 30, n)
        f[:, P.F_LANGUAGE] = rng.choice(
            [P.pack_language("en"), P.pack_language("de")], n)
        out.append(P.PostingsList(
            np.sort(rng.choice(2_500_000, n, replace=False)).astype(
                np.int32), f))
    return out


def pieces(np, P, R, native):
    """name -> (callable(plist), the library handle it runs under)."""
    prof = R.RankingProfile()
    out = {"rank": (lambda pl: R.CardinalRanker(prof).rank(pl, None, k=K),
                    None)}
    if not hasattr(native, "cardinal_topk") or native.load() is None:
        return out
    lang = P.pack_language("en")
    consts = R._native_consts(prof)

    def inside(pl):
        s, order = native.cardinal_topk(pl.feats, consts, lang, K)
        return s[order], pl.docids[order]

    def outside(pl):
        s, _ = native.cardinal_topk(pl.feats, consts, lang, 0)
        order = np.argsort(-s, kind="stable")[:K]
        return s[order], pl.docids[order]

    def twin(pl):
        s = R.cardinal_scores_host(pl.feats, prof, "en")
        order = np.argsort(-s, kind="stable")[:K]
        return s[order], pl.docids[order]

    let_go = ctypes.CDLL(native._SO_PATH)
    held = ctypes.PyDLL(native._SO_PATH)
    for lib in (let_go, held):
        native._bind_scorer(lib)
    for how, lib in (("CDLL", let_go), ("PyDLL", held)):
        out[f"{how}, top-k inside"] = (inside, lib)
        out[f"{how}, top-k outside"] = (outside, lib)
    out["numpy twin"] = (twin, None)
    return out


def beside(busy: int, run):
    """run() while `busy` threads spin in the interpreter."""
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x = (x * 31 + 7) % 1000003

    ts = [threading.Thread(target=spin, daemon=True) for _ in range(busy)]
    for t in ts:
        t.start()
    try:
        return run()
    finally:
        stop.set()
        for t in ts:
            t.join(10)


def calls_for(fn, cand, most: int) -> int:
    """Calls a thread makes: `most`, fewer where three calls now say that
    would take a thread more than ~3 s (the NumPy twin beside busy
    threads waits a switch interval at every array call)."""
    t0 = time.perf_counter()
    for _ in range(3):
        fn(cand)
    each = (time.perf_counter() - t0) / 3
    return max(20, min(most, int(3.0 / max(each, 1e-6))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--sizes", default="54,430,2048")
    ap.add_argument("--threads", default="1,4,8")
    ap.add_argument("--busy", default="0,2")
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    import numpy as np
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.ops import ranking as R
    from yacy_search_server_tpu.utils import native
    native.load()
    shipped = getattr(native, "LIB_HELD", None)
    result = {"tree": args.tree,
              "native": "libyacytpu" if native.available() else "numpy",
              "switch_interval_s": sys.getswitchinterval(),
              "cpus": os.cpu_count(), "k": K, "pieces": {}}
    for n in (int(s) for s in args.sizes.split(",")):
        cands = blocks(np, P, n)
        for name, (fn, lib) in pieces(np, P, R, native).items():
            native.LIB_HELD = lib if lib is not None else shipped
            for cand in cands:
                fn(cand)
            for busy in (int(b) for b in args.busy.split(",")):
                key = f"{name}, n={n}" + (f", {busy} busy" if busy else "")
                result["pieces"][key] = beside(busy, lambda: [
                    measure(fn, cands, int(t), calls_for(fn, cands[0],
                                                         args.calls))
                    for t in args.threads.split(",")])
                print(key, json.dumps(result["pieces"][key]), flush=True)
        native.LIB_HELD = shipped
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
