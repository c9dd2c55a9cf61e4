#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One new process per run: builds the deployment's corpus from --seed,
starts a node as `python -m yacy_search_server_tpu.yacy -start` does,
warms this cell's shapes, lets closed-loop HTTP clients in child
processes drive GET /yacysearch.json for --seconds, compares what they
read with the plain reference, and prints one JSON object as the last
line of standard output. Refuses any backend but `tpu` unless
--cpu-rehearsal is given (a rehearsal's line says `cpu` and is never a
device number). See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import faulthandler      # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import queue             # noqa: E402
import shutil            # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import corpus, costs, generators, reference  # noqa: E402
from benchmarks import trace_reduce                          # noqa: E402

HARD_STOP_S = 1150          # never outlive a first (compiling) run's limit
GRACE_S = 60.0              # a request sent in the window is waited for
START_DELAY_S = 3.0         # clients are spawned this long before t0
TRACE_LEAD_S = 1.5          # the profiler is started this long before t0
TRACE_SLICES = 3            # traced slices, spread evenly over the window:
TRACE_SLICE_S = 2.0         # its start (caches cold) and its steady part
WARM_THREADS = 8
REHEARSAL_SCALE = 64        # --cpu-rehearsal cuts every list by this
SHIPPED_HOST_GATE_ROWS = 4096   # ops/ranking.SMALL_RANK_N, unless the store
                                # carries a small_rank_n of its own


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run a 1/64 corpus on the CPU to debug the "
                         "control flow; never a device number")
    ap.add_argument("--control", default=None,
                    help="also put the named controls (comma-separated, of "
                         + ", ".join([*reference.CONTROLS, *reference.PROBES])
                         + "; or "
                         "`all`) in the program's place and print each "
                         "one's numbers and verdict; not part of a "
                         "benchmark run")
    return ap.parse_args(argv)


def controls(arg) -> list:
    known = {**reference.CONTROLS, **reference.PROBES}
    names = list(known) if arg == "all" else \
        [n for n in (arg or "").split(",") if n]
    for n in names:
        if n not in known:
            raise SystemExit(f"run.py: no control {n!r}")
    return names


def _load_json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def _in_threads(fns, timeout_s: float, what: str) -> None:
    """Run side by side; the first failure is raised on the caller."""
    errors: list = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:     # re-raised below
                errors.append(e)
        return run

    ts = [threading.Thread(target=guarded(fn), daemon=True) for fn in fns]
    for th in ts:
        th.start()
    for th in ts:
        th.join(timeout_s)
    if errors:
        raise RuntimeError(f"{what} failed") from errors[0]
    if any(th.is_alive() for th in ts):
        raise RuntimeError(f"{what} did not finish in {timeout_s:.0f} s")


# -- the deployment ---------------------------------------------------------

def scaled(cfg: dict, scale: int) -> dict:
    """The rehearsal's corpus: every count of rows cut by `scale`, the
    structure (tiers, windows inside windows, list counts) kept."""
    if scale == 1:
        return cfg
    cfg = json.loads(json.dumps(cfg))
    c = cfg["corpus"]
    c["docs"] //= scale
    for spec in c["tiers"].values():
        spec["length"] = max(8, spec["length"] // scale)
        spec["window"] //= scale
    c["tiers"]["low"]["lists"] = max(64, c["tiers"]["low"]["lists"] // 8)
    return cfg


def start_node(data_dir: str, conf_lines):
    """A node as `python -m yacy_search_server_tpu.yacy -start` builds it
    (copied from chip_smoke._start): SETTINGS/yacy.conf, yacy.startup."""
    from yacy_search_server_tpu import yacy
    os.makedirs(os.path.join(data_dir, "SETTINGS"), exist_ok=True)
    with open(os.path.join(data_dir, "SETTINGS", "yacy.conf"), "w",
              encoding="utf-8") as f:
        f.writelines(line + "\n" for line in conf_lines)
    return yacy.startup(data_dir, port=0)


def stop_node(node, http, lock) -> None:
    from yacy_search_server_tpu import yacy
    node.close()
    http.close()
    yacy.release_lock(lock)


def load_corpus(sb, lay, seed: int) -> dict:
    """metadata.bulk_load + snapshot() beside rwi.ingest_run (the bulk
    path of chip_smoke._load_bulk). Largest lists first, so the arena
    reaches its final capacity early; all Low lists in one run."""
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.utils.hashes import word2hash
    t0 = time.monotonic()
    n, hosts = lay.docs, lay.hosts
    base = sb.index.metadata.capacity()
    if base != 0:
        raise RuntimeError(f"fresh node already holds {base} documents")
    walls = {}
    made: queue.Queue = queue.Queue(maxsize=64)

    def load_metadata() -> None:
        step = 500_000
        for lo in range(0, n, step):
            r = range(lo, min(n, lo + step))
            first = sb.index.metadata.bulk_load(
                [f"{i:07d}{i % hosts:05d}".encode("ascii") for i in r],
                sku=[corpus.url_of(i, hosts) for i in r],
                title=[f"doc {i}" for i in r],
                host_s=[f"h{i % hosts}.example" for i in r],
                size_i=[1000] * len(r), wordcount_i=[100] * len(r))
            if first != lo:
                raise RuntimeError(f"docid {first} != {lo}")
            sb.index.metadata.snapshot()
        walls["metadata_s"] = time.monotonic() - t0

    def make_lists() -> None:
        # NumPy draws the lists while the node ingests the ones before
        try:
            for term in lay.terms:
                made.put((term, corpus.term_list(lay, term, seed)))
        finally:
            made.put(None)

    def load_postings() -> None:
        low = {}
        while (item := made.get()) is not None:
            term, (docids, feats) = item
            plist = PostingsList(docids, feats)
            if term.tier == "low":
                low[word2hash(term.name)] = plist
            else:
                sb.index.rwi.ingest_run({word2hash(term.name): plist})
        if low:
            sb.index.rwi.ingest_run(low)
        walls["postings_s"] = time.monotonic() - t0

    _in_threads([load_metadata, make_lists, load_postings], HARD_STOP_S,
                "the bulk load")
    walls["load_s"] = time.monotonic() - t0
    return walls


def warm_up(sb, ds, queries) -> None:
    """Every shape this cell's stream can reach, through
    Switchboard.search below the servlet (a compile wall over HTTP would
    burn the node's serving SLO before its first timed request)."""
    if hasattr(ds, "prewarm_wait") and not ds.prewarm_wait(900.0):
        raise RuntimeError("prewarm did not cover the arena shapes")
    chunks = [queries[i::WARM_THREADS] for i in range(WARM_THREADS)]

    def warm(part):
        for q in part:
            sb.search(q, count=10, use_cache=False).results(offset=0,
                                                            count=10)

    # the first sight of a join family compiles its batch buckets in the
    # background: one thread first, then side by side
    warm(queries[:min(len(queries), 48)])
    if hasattr(ds, "join_prewarm_wait"):
        ds.join_prewarm_wait()
    _in_threads([lambda p=p: warm(p) for p in chunks], 900.0, "warm-up")
    if hasattr(ds, "prewarm_wait"):
        if not (ds.prewarm_wait(900.0) and ds.join_prewarm_wait()):
            raise RuntimeError("prewarm did not re-cover the shapes")


# -- the window -------------------------------------------------------------

def int_counters(ds) -> dict:
    return {k: v for k, v in ds.counters().items()
            if isinstance(v, int) and not isinstance(v, bool)}


def servlet_counts() -> list:
    from yacy_search_server_tpu.utils import histogram
    h = histogram.get("servlet.serving")
    return list(h.snapshot()["counts"]) if h is not None else []


def drive(http, wl: dict, stream, seconds: float, out_dir: str, trace: bool,
          ds) -> dict:
    """Spawn the client processes, hold the window, collect their rows."""
    import urllib.parse
    u = urllib.parse.urlparse(http.base_url)
    n_cl, n_proc = int(wl["clients"]), int(wl["client_processes"])
    per_client = [[[i, stream[i]] for i in range(c, len(stream), n_cl)]
                  for c in range(n_cl)]
    t0 = time.monotonic() + START_DELAY_S
    procs, outs = [], []
    for p in range(n_proc):
        job = {"host": u.hostname, "port": u.port,
               "path": "/yacysearch.json",
               "queries": per_client[p::n_proc], "t0": t0,
               "seconds": seconds, "grace_s": GRACE_S,
               "out": os.path.join(out_dir, f"rows{p}.json")}
        path = os.path.join(out_dir, f"job{p}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(job, f)
        outs.append(job["out"])
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), path]))
    snap = {"slices": []}

    def sleep_until(t):
        time.sleep(max(0.0, t - time.monotonic()))

    def traced_slice(k, length):
        """Profile [start, start + length) of the window; the first
        slice's profiler is up before the clients' first request, and
        that idle lead-in is left out of the slice."""
        import jax
        start = t0 + k * seconds / TRACE_SLICES
        log_dir = os.path.join(out_dir, f"trace{k}")
        shutil.rmtree(log_dir, ignore_errors=True)
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        po.host_tracer_level = 1
        sleep_until(start - (TRACE_LEAD_S if k == 0 else 0.0))
        jax.profiler.start_trace(log_dir, profiler_options=po)
        if k == 0:
            sleep_until(t0)
            snap["c0"], snap["h0"] = int_counters(ds), servlet_counts()
        t_on, c_on = time.monotonic(), (snap["c0"] if k == 0
                                        else int_counters(ds))
        sleep_until(max(start, t_on) + length)
        t_off, c_off = time.monotonic(), int_counters(ds)
        jax.profiler.stop_trace()
        snap["slices"].append({"dir": log_dir, "t": (t_on - t0, t_off - t0),
                               "c0": c_on, "c1": c_off,
                               "stop_s": time.monotonic() - t_off})

    try:
        if trace:
            length = min(TRACE_SLICE_S, 0.5 * seconds / TRACE_SLICES)
            for k in range(TRACE_SLICES):
                traced_slice(k, length)
        else:
            sleep_until(t0)
            snap["c0"], snap["h0"] = int_counters(ds), servlet_counts()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        snap["c1"], snap["h1"] = int_counters(ds), servlet_counts()
        for pr in procs:
            pr.wait(timeout=seconds + GRACE_S + 120)
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
                pr.wait()
    rows, dry = [], 0
    for pr, path in zip(procs, outs):
        if pr.returncode != 0:
            raise RuntimeError(f"a client process exited {pr.returncode}")
        got = _load_json(path)
        rows += got["rows"]
        dry += got["dry"]
    rows.sort(key=lambda r: r[1])
    snap.update(rows=rows, dry=dry, t0=t0)
    return snap


def quantile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of a sorted list."""
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * q))]


def end_to_end(snap: dict, seconds: float, setup_s: float) -> dict:
    rows, t0 = snap["rows"], snap["t0"]
    ok = [r for r in rows if r[3] == 200 and not r[4] and r[6] is None]
    in_window = sum(1 for r in ok if r[2] <= t0 + seconds)
    lat = sorted((r[2] - r[1]) * 1000.0 for r in rows)
    return {"qps": in_window / seconds,
            "p50_ms": quantile(lat, 0.50) if lat else float("nan"),
            "p95_ms": quantile(lat, 0.95) if lat else float("nan"),
            "setup_s": setup_s, "_ok": ok, "_samples": len(lat)}


# -- per-layer metrics ------------------------------------------------------

def layer_context(snap, wl, lay, stream, gate_rows, device_kind,
                  traced) -> dict:
    by_name = lay.by_name()
    rows = snap["rows"]

    def lengths(qi):
        return [by_name[w].length for w in stream[qi].split()]

    spans = [sl["t"] for sl in snap["slices"]]
    in_trace = [r for r in rows if any(
        lo <= r[1] - snap["t0"] <= hi for lo, hi in spans)] if traced else []
    trace_counters = {}
    for sl in snap["slices"]:
        for k, v in sl["c1"].items():
            trace_counters[k] = trace_counters.get(k, 0) + v \
                - sl["c0"].get(k, 0)
    return {
        "workload": wl["name"],
        "attempted": len(rows),
        "queries": [stream[r[0]] for r in rows],
        "lengths": lengths,
        "rows": rows,
        "trace_rows": in_trace,
        "counters": {k: snap["c1"][k] - snap["c0"].get(k, 0)
                     for k in snap["c1"]},
        "trace_counters": trace_counters if traced else {},
        "servlet_counts": [b - a for a, b in zip(
            snap["h0"] or [0] * len(snap["h1"]), snap["h1"])],
        "servlet_bounds_ms": _servlet_bounds(),
        "trace": traced or None,
        "trace_window_s": sum(hi - lo for lo, hi in spans) or None,
        "peak": costs.peak(device_kind) if traced else None,
        "host_gate_rows": gate_rows,
    }


def _servlet_bounds():
    from yacy_search_server_tpu.utils import histogram
    return list(histogram.BUCKET_BOUNDS_MS)


def layer_metrics(bench: dict, cell: str, ctx: dict) -> dict:
    """Each per-layer metric of this cell through its own reader,
    benchmarks/layer_metrics/<name>.py: read(ctx) -> number or None."""
    e2e = {m["name"] for m in bench["end_to_end"]}
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        if m["moves"] not in e2e:
            raise ValueError(f"{m['name']} moves an unknown metric")
        mod = importlib.import_module(
            "benchmarks.layer_metrics." + m["name"].replace(".", "_")
            .replace("-", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run ----------------------------------------------------------------

def run(args, fault=None) -> int:
    """`fault`: a callable(node) a test passes to break the timed path
    underneath a rehearsal; never set by the command line."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    wl_path = os.path.join(HERE, "workloads", args.workload + ".json")
    if not os.path.exists(wl_path):
        say(f"run.py: no workload file {wl_path}")
        return 2
    wl = _load_json(wl_path)
    chips = cells.get(args.workload, {}).get("chips", 1)
    rehearsal = args.cpu_rehearsal
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    say(f"jax {jax.__version__} platform={platform} devices={len(devs)} "
        f"kind={devs[0].device_kind}")
    if not rehearsal and (platform != "tpu" or len(devs) < chips):
        say(f"run.py: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{platform} device(s). Refusing to measure (--cpu-rehearsal "
            f"debugs the control flow on the CPU).")
        return 2
    try:
        from yacy_search_server_tpu.utils import compilecache
    except ImportError as e:
        say(f"run.py: the program is not beside the benchmark: {e}")
        return 2
    if not rehearsal:
        costs.peak(devs[0].device_kind)        # unknown kind: an error

    # the compile cache: where the program places it (the environment's
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); every
    # program is kept, however quickly it compiled
    cache_dir = compilecache.ensure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = {"n": 0, "hits": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_kw: compiles.__setitem__(
            "n", compiles["n"]
            + (ev == "/jax/core/compile/backend_compile_duration")))
    jax.monitoring.register_event_listener(
        lambda ev, **_kw: compiles.__setitem__(
            "hits", compiles["hits"]
            + (ev == "/jax/compilation_cache/cache_hits")))

    cfg = scaled(corpus.load_config(wl["config"]),
                 REHEARSAL_SCALE if rehearsal else 1)
    lay = corpus.layout(cfg, args.seed)
    gen = generators.load(wl["generator"])
    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="yacy-bench-")
    say(f"cell {args.workload}: {lay.docs} documents, {lay.postings} "
        f"postings in {len(lay.terms)} lists; cache {cache_dir}; "
        f"data {data_dir}")
    split = {}
    try:
        t = time.monotonic()
        node, http, lock = start_node(data_dir, cfg.get("yacy_conf", []))
        split["start_s"] = time.monotonic() - t
        try:
            sb = node.sb
            ds = sb.index.devstore
            if ds is None:
                raise RuntimeError("no device store attached")
            if rehearsal and hasattr(ds, "_maybe_prewarm"):
                ds._prewarm_on = True      # the CPU backend skips it
                ds._maybe_prewarm()
                ds.small_rank_n = SHIPPED_HOST_GATE_ROWS // REHEARSAL_SCALE
            split.update(load_corpus(sb, lay, args.seed))
            t = time.monotonic()
            warm_up(sb, ds, gen.warm(lay, wl["params"], args.seed))
            split["warm_s"] = time.monotonic() - t
            stream = gen.generate(
                lay, wl["params"], args.seed,
                int(wl["stream_per_second"] * args.seconds))
            # the window starts with the caches cold and the node's
            # latency windows empty, the same in every run
            sb.search_cache.clear()
            ds._topk_cache.clear()
            from yacy_search_server_tpu.utils import histogram
            histogram.reset_windows()
            if fault is not None:
                fault(node)
            n0 = compiles["n"]
            jax.config.update("jax_log_compiles", True)   # none expected
            setup_s = time.monotonic() + START_DELAY_S - T_START
            snap = drive(http, wl, stream, args.seconds, out_dir,
                         bool(args.trace), ds)
            jax.config.update("jax_log_compiles", False)
            compiled_in_window = compiles["n"] - n0
            peak_bytes = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)
            arena_rows = ds.live_rows()
            gate = getattr(ds, "small_rank_n", None)
            gate_rows = SHIPPED_HOST_GATE_ROWS if gate is None else int(gate)
        finally:
            t = time.monotonic()
            stop_node(node, http, lock)
            split["stop_s"] = time.monotonic() - t
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    e2e = end_to_end(snap, args.seconds, setup_s)
    rows, ok = snap["rows"], e2e.pop("_ok")
    samples = e2e.pop("_samples")
    failed = len(rows) - len(ok)
    say(f"set-up {setup_s:.1f}s: " + ", ".join(
        f"{k} {v:.1f}" for k, v in split.items())
        + f"; compilations {compiles['n']} ({compiles['hits']} from the "
          f"cache), {compiled_in_window} inside the window")
    say(f"window {args.seconds:.0f}s: {len(rows)} requests, {failed} "
        f"failed, {snap['dry']} clients ran dry; latency over {samples} "
        f"samples; {arena_rows} postings resident")
    for r in [r for r in rows if r not in ok][:5]:
        say(f"  failed: status {r[3]} degraded {r[4]} error {r[6]} "
            f"query {stream[r[0]]!r}")

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if args.trace:
        parts = []
        for sl in snap["slices"]:
            path = trace_reduce.find_xplane(sl["dir"])
            if path is not None:
                parts.append(trace_reduce.reduce(path))
            shutil.rmtree(sl["dir"], ignore_errors=True)
        traced = trace_reduce.combine(parts) if parts else None
        if traced is not None:
            window_s = sum(sl["t"][1] - sl["t"][0] for sl in snap["slices"])
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = window_s
            breakdown = {"device_ops": traced["device_ops"],
                         "idle_gaps": traced["idle_gaps"]}
            say(f"trace: {len(parts)} slices at " + ", ".join(
                f"{sl['t'][0]:.1f}-{sl['t'][1]:.1f}s" for sl in
                snap["slices"]) + f" of the window; busy "
                f"{traced['busy_s']:.3f}s of {window_s:.3f}s; first to last "
                f"device operation " + ", ".join(
                    f"{p['span_s']:.2f}" for p in parts) + " s; stopping "
                f"the profiler took " + ", ".join(
                    f"{sl['stop_s']:.1f}" for sl in snap["slices"]) + " s")
        ctx = layer_context(snap, wl, lay, stream, gate_rows,
                            devs[0].device_kind,
                            traced if traced and traced["busy_s"] > 0
                            else None)
        metrics = layer_metrics(bench, args.workload, ctx)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if "workloads" not in m
                   or args.workload in m["workloads"]}

    # the comparison, once the window has closed, the peak has been read
    # and the node is gone: a sample of the answered requests drawn from
    # the seed, every one held to the plain reference
    import numpy as np
    t = time.monotonic()
    rng = np.random.default_rng([int(args.seed), 271828])
    n_cmp = min(len(ok), int(wl["compare_sample"]))
    pick = sorted(rng.choice(len(ok), n_cmp, replace=False).tolist()) \
        if n_cmp else []
    answers = [(stream[ok[i][0]], ok[i][5]) for i in pick]
    ref = reference.Reference(lay, args.seed)
    verdict = reference.compare(ref, answers, lay.hosts)
    numbers = dict(verdict["numbers"])
    numbers["stale_served"] = (snap["c1"].get("rank_cache_stale_served", 0)
                               - snap["c0"].get("rank_cache_stale_served", 0))
    correct = reference.decide(numbers, len(answers))
    say(f"compared {len(answers)} answers in {time.monotonic() - t:.1f}s")
    for ex in verdict["examples"]:
        say("  differs: " + json.dumps(ex))
    for name in controls(args.control):
        # the control in the program's place: its answers to the same
        # queries through the same comparison and the same limits
        ctl = reference.Reference(lay, args.seed, control=name)
        got = reference.compare(ref, ctl.served([q for q, _ in answers]),
                                lay.hosts)["numbers"]
        got["stale_served"] = 0
        kind = "control" if name in reference.CONTROLS else "probe"
        say(f"{kind} {name}: correct "
            f"{str(reference.decide(got, len(answers))).lower()} "
            + json.dumps(got))
    compared = {k: {"value": numbers[k], "limit": reference.LIMITS[k]}
                for k in reference.LIMITS}
    compared["answers_compared"] = {"value": len(answers), "limit": ">=1"}
    say("compared " + json.dumps(compared))
    line = {"correct": correct, "attempted": len(rows), "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    args = _parse_args(argv)
    controls(args.control)
    faulthandler.dump_traceback_later(HARD_STOP_S, exit=True)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
