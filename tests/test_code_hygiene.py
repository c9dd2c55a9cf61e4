"""Repo-wide code-hygiene assertions.

Round 18 (ISSUE 14): the scanners that used to live here as private
regex/AST walks — silent broad excepts, jit-kernel cost-model/oracle
coverage, bounded in-flight queues, wall-measuring servlet spans — are
now registered checkers on the yacylint engine
(yacy_search_server_tpu/utils/lint), which parses every file ONCE and
runs the whole pipeline, with one exemption grammar
(`# lint: <token>(reason)`) and one shrink-only baseline.  The test
names below survive as thin wrappers over the engine so tier-1 history
stays comparable; the non-lintable hygiene gates (runtime /metrics
resolution, committed-artifact completeness, faultpoint liveness)
remain as before.
"""
import pathlib
import re

from yacy_search_server_tpu.utils.lint import engine as lint_engine

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "yacy_search_server_tpu"


def _lint(only: set[str]):
    """One engine run (baseline applied) restricted to `only`."""
    res = lint_engine.run(root=REPO, only=only)
    return lint_engine.apply_baseline(
        res, lint_engine.load_baseline(lint_engine.baseline_path(REPO)))


def _assert_clean(res, hint: str):
    assert not res.findings, (
        hint + ":\n  " + "\n  ".join(f.render() for f in res.findings))


def test_no_silent_broad_excepts():
    """A bare ``except Exception: pass`` hides index-hygiene and serving
    failures the operator needs to see (VERDICT r4 weak #6); now the
    lint engine's broad-except checker."""
    res = _lint({"broad-except"})
    _assert_clean(res, "silent `except Exception: pass` — log the "
                       "failure or narrow the exception type")
    assert res.stats["broad-except"]["broad_handlers"] > 50, \
        "broad-except census collapsed (checker rot?)"


# -- silicon accounting coverage (ISSUE 1, engine-run since ISSUE 14) --------

def test_every_device_kernel_has_a_cost_model():
    """Every named device kernel (jit- or pallas-compiled) in ops/,
    ingest/ and index/devstore.py must carry a cost-model entry in
    ops/roofline.KERNELS — or a reasoned costmodel-ok lint exemption on
    its def.  A kernel without either is invisible to the roofline
    layer."""
    res = _lint({"kernel-cost-model"})
    _assert_clean(res, "device kernels without a roofline cost model")
    stats = res.stats["kernel-cost-model"]
    assert stats["kernels_seen"] >= 25, \
        "kernel census collapsed (scanner rot?)"
    assert stats["registry_kernels"] >= 25


# -- pipelined dispatch hygiene (ISSUE 3) ------------------------------------

def test_completer_and_inflight_queues_are_bounded():
    """Every queue in the package must be bounded (or carry a reasoned
    unbounded-ok exemption): an unbounded queue of issued-but-unfetched
    device buffers is unbounded in-flight device memory.  The engine's
    unbounded-queue checker generalizes the old devstore/meshstore
    in-flight scan to the whole tree."""
    res = _lint({"unbounded-queue"})
    _assert_clean(res, "queues without a maxsize bound")
    stats = res.stats["unbounded-queue"]
    # the scanner must still SEE both batchers' in-flight queues — a
    # rename that dodges the census fails here instead of passing
    assert stats["inflight_bounded"] >= 2, \
        "in-flight completion queues not found (renamed? checker rot?)"
    assert stats["queue_sites"] >= 6


PACKED_KERNELS = (
    "score_topk16_packed",
    "_rank_spans_packed_kernel",
    "_rank_pruned_batch1_packed_kernel",
    "_rank_scan_batch_packed_kernel",
    "_rank_join_batch_packed_kernel",
    "_rank_join_bm_batch_packed_kernel",
    "_rerank_fwd_batch_packed_kernel",
)


def test_packed_kernel_variants_have_registered_cost_models():
    """Serving kernels must be registered BY NAME (an exemption is not
    acceptable) — checked statically off ops/roofline.py, the same
    single-parse view the engine uses."""
    repo = lint_engine.discover(REPO)
    kernels = repo.dict_literal_keys(
        "yacy_search_server_tpu/ops/roofline.py", "KERNELS")
    missing = [k for k in PACKED_KERNELS if k not in kernels]
    assert not missing, (
        "packed-output kernel variants without a roofline cost model "
        "(register in ops/roofline.KERNELS; an exemption is not "
        "acceptable for serving kernels):\n  " + "\n  ".join(missing))


# -- compressed residency / dense-first hygiene (ISSUES 8 + 11) --------------

def test_bp_kernels_have_cost_models_and_numpy_oracles():
    """Every ``*_bp_kernel`` must carry BOTH a by-name cost model and a
    NumPy oracle in ops/packed.BP_ORACLES (the parity anchor the
    bit-identity contract rests on) — the engine's kernel-oracle
    checker."""
    res = _lint({"kernel-oracle"})
    _assert_clean(res, "serving-kernel oracle/registration violations")
    assert res.stats["kernel-oracle"]["bp_kernels"], \
        "no *_bp kernels found (renamed? checker rot?)"


def test_ann_kernels_have_cost_models_and_numpy_oracles():
    """Every ``_ann_*`` kernel needs its ANN_ORACLES entry (host
    fallback + parity anchor) and by-name registration; dead oracle
    entries flag too — same kernel-oracle checker, asserted through the
    ann census."""
    res = _lint({"kernel-oracle"})
    _assert_clean(res, "ann kernel oracle/registration violations")
    assert res.stats["kernel-oracle"]["ann_kernels"], \
        "no _ann_* kernels found (renamed? checker rot?)"


def test_ann_metric_series_resolve(tmp_path):
    """No dead series (ISSUE 11 satellite): every yacy_ann_* series the
    ANN counters pin — and the vector-side yacy_device_hbm_bytes tiers
    — must resolve on a rendered /metrics exposition of a plain store
    (zero-filled without an index), so fleet digest fields, dashboards
    and future health rules can reference them on every node."""
    from yacy_search_server_tpu.index.devstore import ANN_ZERO_COUNTERS
    from yacy_search_server_tpu.server.servlets.monitoring import \
        prometheus_text
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.fleet import digest_series

    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        text = prometheus_text(sb, include_buckets=False)
    finally:
        sb.close()
    for key in ANN_ZERO_COUNTERS:
        if key in ("ann_vectors", "ann_clusters",
                   "ann_centroid_version") or key.endswith("_bytes"):
            continue    # gauges (hbm tiers / version), not counters
        assert f'counter="{key[4:]}"' in text, \
            f"yacy_ann_total{{counter={key[4:]}}} missing from /metrics"
    assert "yacy_ann_centroid_version" in text
    assert "yacy_ann_resident_vectors" in text
    for tier in ("dense", "ann_hot", "ann_warm", "ann_cold"):
        assert f'yacy_device_hbm_bytes{{tier="{tier}"}}' in text, \
            f"vector-side hbm tier {tier} missing from /metrics"
    # the fleet digest's tier shortcuts must point at series that exist
    series = digest_series({"tiers": {}})
    for k, v in series.items():
        if k.startswith("tiers."):
            name = v.split("{")[0]
            assert name in text, f"fleet digest series {v} unresolved"


# -- streaming-ingest hygiene (ISSUE 13) -------------------------------------

INGEST_KERNELS = ("_pack_block_batch_kernel",)


def test_ingest_kernels_have_registered_cost_models():
    """The write path's device kernels are held to the same silicon
    accounting as the serving kernels: registered BY NAME (the device
    index build is a throughput claim)."""
    from yacy_search_server_tpu.utils.lint import named_kernels
    repo = lint_engine.discover(REPO)
    ctx = repo.get("yacy_search_server_tpu/ingest/devbuild.py")
    found = [name for name, _fn in named_kernels(ctx)]
    assert set(INGEST_KERNELS) <= set(found), \
        "ingest kernels renamed? update INGEST_KERNELS"
    kernels = repo.dict_literal_keys(
        "yacy_search_server_tpu/ops/roofline.py", "KERNELS")
    for k in INGEST_KERNELS:
        assert k in kernels, (
            f"{k} must be REGISTERED by name (an exemption is not "
            f"acceptable for the device index build)")


def test_ingest_package_stays_jax_free_outside_devbuild():
    """slo/scheduler (and the package root) must not import jax: the
    chaos harness imports the RWI write path — and with it ingest.slo —
    in dozens of short-lived subprocesses."""
    for rel in ("__init__.py", "slo.py", "scheduler.py"):
        src = (PKG / "ingest" / rel).read_text(encoding="utf-8")
        assert not re.search(r"^\s*(import jax|from jax)", src,
                             re.MULTILINE), \
            f"ingest/{rel} imports jax (breaks the jax-free contract)"


# -- no dead faultpoints (ISSUE 10 satellite) --------------------------------
# Every faultpoint name registered in utils/faultinject.py must have (a)
# a REACHABLE injection site in package source and (b) at least one test
# exercising it — mirroring the no-dead-rules / no-dead-actuators gates.
# A registered name no site reaches (or no test arms) is a hole in the
# chaos harness's coverage claim.

def _all_source(root: pathlib.Path) -> str:
    return "\n".join(p.read_text(encoding="utf-8")
                     for p in sorted(root.rglob("*.py")))


def test_no_dead_faultpoints():
    from yacy_search_server_tpu.utils import faultinject as FI

    pkg_src = _all_source(PKG)
    tests_dir = pathlib.Path(__file__).resolve().parent
    test_src = _all_source(tests_dir)

    # (a) every registered crashpoint has its named barrier in product
    # code, and the kill−9 harness iterates the FULL registry (so a new
    # crashpoint is automatically killed-at and verified)
    for name in FI.CRASHPOINTS:
        assert f'crashpoint("{name}")' in pkg_src, (
            f"crashpoint {name!r} registered but no "
            f"faultinject.crashpoint() site reaches it")
    assert "faultinject.CRASHPOINTS" in test_src, (
        "the chaos harness must parametrize over the crashpoint "
        "registry")

    # (b) every other faultpoint: a live injection site + a test
    sites = {
        "servlet.serving": 'faultinject.sleep("servlet.serving")',
        "batcher.dispatch": 'faultinject.sleep("batcher.dispatch")',
        "mesh.step": 'faultinject.sleep("mesh.step")',
        "peer.blackhole": "faultinject.blackholed(",
        "io.torn_write": "faultinject.torn_write_bytes(",
        "io.error": "faultinject.io_error(",
        "device.transfer_fail":
            'faultinject.take("device.transfer_fail")',
        "proc.crashpoint": "faultinject.crashpoint(",
    }
    assert set(sites) == set(FI.REGISTERED_FAULTPOINTS), (
        "faultpoint registry drifted from the hygiene gate's site map — "
        "update both together")
    for name, site in sites.items():
        assert site in pkg_src, (
            f"faultpoint {name!r} has no injection site in package "
            f"source")
        assert name in test_src, (
            f"faultpoint {name!r} is not exercised by any test")


# -- tracing coverage (ISSUE 2, engine-run since ISSUE 14) -------------------

def test_wall_measuring_servlets_open_spans():
    """Every @servlet handler that measures a wall or touches the
    roofline PROFILER must open a trace span — or carry a reasoned
    trace-ok lint exemption on its def (the old TRACING_EXEMPT dict is
    gone; exemptions audit with one grep now)."""
    res = _lint({"servlet-trace"})
    _assert_clean(res, "servlet handlers that measure a wall without "
                       "opening a tracing span")
    assert res.stats["servlet-trace"]["servlet_handlers"] > 80, \
        "servlet census collapsed (checker rot?)"


# -- tail forensics (ISSUE 15) ------------------------------------------------

def test_no_dead_tail_causes():
    """Every cause label the tail-attribution engine can emit must have
    (a) an emitting branch in the classifier source and (b) a dedicated
    non-vacuity test (`test_cause_<label>` in tests/test_tailattr.py)
    driving the REAL code path via the faultinject registry — a label
    nothing can produce, or nothing proves producible, is a dead
    diagnosis an operator would wait on forever."""
    from yacy_search_server_tpu.utils import tailattr

    src = pathlib.Path(tailattr.__file__).read_text(encoding="utf-8")
    tests_src = (pathlib.Path(__file__).resolve().parent
                 / "test_tailattr.py").read_text(encoding="utf-8")
    for cause in tailattr.CAUSES:
        # >= 2 quoted occurrences: ONE is the CAUSES canon literal
        # itself, so at least one EMITTING site must exist elsewhere in
        # the module (deleting a classifier branch fails here — a
        # single-occurrence check would be vacuous against the canon)
        assert src.count(f'"{cause}"') >= 2, (
            f"cause {cause!r} is in the canon but the classifier "
            f"source never emits it (no second quoted occurrence)")
        assert f"def test_cause_{cause}" in tests_src, (
            f"cause {cause!r} has no exercising test_cause_{cause} in "
            f"tests/test_tailattr.py — every emitted label needs a "
            f"non-vacuity test")


def test_tail_reach_gate():
    """Servlet-observed histogram families stay classifier-reachable
    (engine checker; see utils/lint/checkers.check_tail_reach)."""
    res = _lint({"tail-reach"})
    _assert_clean(res, "servlet walls observing families the tail "
                       "classifier cannot reach")
    assert res.stats["tail-reach"]["servlet_observed_families"] >= 2, \
        "servlet observe census collapsed (checker rot?)"
