"""Node health engine (ISSUE 4): live rule states with evidence, the
SLO burn-rate windows, the flight recorder's rate-limited incident dump
(synthetic worker_stall -> critical -> recovery), exemplar round-trip
from /metrics back to the trace ring, the Performance_Health_p servlet,
and the no-dead-rules / every-histogram-exported hygiene gates."""

import json

import pytest

from yacy_search_server_tpu.server.objects import ServerObjects
from yacy_search_server_tpu.switchboard import Switchboard
from yacy_search_server_tpu.utils import histogram as hg
from yacy_search_server_tpu.utils import tracing
from yacy_search_server_tpu.utils.health import parse_exposition


@pytest.fixture(autouse=True)
def _fresh_observability(monkeypatch):
    # the corruption counters are the process's: what another test file
    # of this worker counted would fire storage_corruption on a first tick
    from yacy_search_server_tpu.index import integrity
    monkeypatch.setattr(integrity, "_corruption", {})
    hg.reset()
    hg.set_enabled(True)
    tracing.set_enabled(True)
    tracing.clear()
    yield
    hg.reset()
    hg.set_enabled(True)
    tracing.set_enabled(True)
    tracing.clear()


@pytest.fixture
def sb(tmp_path):
    board = Switchboard(data_dir=str(tmp_path / "DATA"))
    yield board
    board.close()


def _metrics_text(board) -> str:
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    return prometheus_text(board)


# -- rule engine basics ------------------------------------------------------

def test_tick_evaluates_every_rule_with_evidence(sb):
    state = sb.health.tick()
    assert state in ("ok", "warn", "critical")
    rows = sb.health.rule_table()
    assert len(rows) >= 7
    names = {name for name, _d, _s in rows}
    assert {"slo_serving_p95", "rank_cache_collapse", "stale_rate_spike",
            "batcher_backlog", "worker_stall", "log_drops",
            "crawler_frontier_starvation"} <= names
    for name, _desc, st in rows:
        assert st.state in ("ok", "warn", "critical"), name
        assert st.cause, f"rule {name} gave no cause"
        assert isinstance(st.evidence, dict)
    # a quiet freshly-built node is healthy
    assert sb.health.states["worker_stall"].state == "ok"


def test_slo_burn_rate_rule_fires_and_recovers(sb):
    h = hg.histogram("servlet.serving")
    # sustained load far over the 250ms objective at well over the qps
    # floor: both burn windows saturate -> critical
    for _ in range(200):
        h.record(900.0)
    sb.health.tick()
    st = sb.health.states["slo_serving_p95"]
    assert st.state == "critical", st
    assert "burn" in st.cause
    assert st.evidence["fast_burn"] >= 6
    # recovery: the slow load rotates out of every window
    for _ in range(hg.WINDOWS):
        h.rotate()
    for _ in range(60):
        h.record(5.0)
    sb.health.tick()
    assert sb.health.states["slo_serving_p95"].state == "ok"


def test_tick_rotates_idle_families_so_verdicts_expire(sb):
    """A critical SLO verdict must not stick after traffic STOPS: the
    tick drives window rotation even for families receiving no records
    (recording-side rotation is lazy and an idle family never
    records)."""
    h = hg.histogram("servlet.serving")
    for _ in range(200):
        h.record(900.0)
    sb.health.tick()
    assert sb.health.states["slo_serving_p95"].state == "critical"
    # idle from here on: no records arrive; expire the rotation
    # deadlines so each tick advances the ring one slot
    for _ in range(hg.WINDOWS):
        for hh in hg.all_histograms():
            hh._next_rot = 0.0
        sb.health.tick()
    assert sb.health.states["slo_serving_p95"].state == "ok"
    assert h.windowed_count() == 0


def test_slo_rule_ignores_traffic_below_qps_floor(sb):
    h = hg.histogram("servlet.serving")
    for _ in range(5):            # 5 requests / 30s window << 1 qps
        h.record(5000.0)
    sb.health.tick()
    st = sb.health.states["slo_serving_p95"]
    assert st.state == "ok"
    assert "floor" in st.cause


# -- hygiene gates (ISSUE 4 satellite) ---------------------------------------

def test_every_rule_references_only_live_metric_series(sb):
    """No silent dead rules: every series a rule reads must exist on the
    /metrics exposition of a real node — fail the build otherwise."""
    missing = sb.health.undefined_series()
    assert not missing, (
        "health rules referencing series absent from /metrics:\n  "
        + "\n  ".join(missing))
    for rule in sb.health.rules:
        assert rule.series, f"rule {rule.name} declares no series"


def test_every_registered_histogram_appears_in_the_exposition(sb):
    # list first, then render: a family registered in between (the
    # sampler's first tick) is in a later exposition, not in this one
    listed = hg.all_histograms()
    text = _metrics_text(sb)
    samples = parse_exposition(text)
    for h in listed:
        fam = hg.prom_name(h.name)
        assert f"{fam}_count" in samples, fam
        assert f"{fam}_sum" in samples, fam
        assert any(k.startswith(f"{fam}_bucket{{") for k in samples), fam
        assert f"# TYPE {fam} histogram" in text, fam


def test_acceptance_histogram_families_exported(sb):
    """The ISSUE 4 acceptance list: servlet serving, batcher dispatch,
    kernel fetch, mesh collective and crawler fetch must expose
    Prometheus histogram series."""
    text = _metrics_text(sb)
    for fam in ("yacy_servlet_serving_ms", "yacy_devstore_batch_ms",
                "yacy_kernel_fetch_ms", "yacy_mesh_collective_ms",
                "yacy_crawler_fetch_ms"):
        assert f"# TYPE {fam} histogram" in text, fam
        assert f"{fam}_count" in parse_exposition(text), fam


# -- flight recorder ---------------------------------------------------------

def _inject_stall(board, n: int = 1) -> None:
    ds = board.index.devstore
    if ds is None or getattr(ds, "_batcher", None) is None:
        pytest.skip("no device batcher on this host")
    ds._batcher.timeout_worker_stall += n


def test_flight_recorder_dumps_exactly_one_rate_limited_incident(
        sb, tmp_path):
    # an exemplar-bearing slow trace so the incident can link to it
    with tracing.trace("servlet.yacysearch") as r:
        slow_tid = r.ctx[0]
        tracing.emit("search.slowstage", 4000.0)
    sb.health.tick()                      # healthy baseline snapshot
    assert sb.health.states["worker_stall"].state == "ok"

    _inject_stall(sb)
    assert sb.health.tick() == "critical"
    st = sb.health.states["worker_stall"]
    assert st.state == "critical"
    assert "wedged" in st.cause
    assert st.evidence["new_in_window"] >= 1
    assert sb.health.incident_count == 1

    # a second stall while still critical is NOT a new edge; a
    # recover+re-fire inside the cooldown is an edge but rate-limited —
    # either way: exactly one incident file
    _inject_stall(sb)
    sb.health.tick()
    assert sb.health.incident_count == 1
    incident_dir = tmp_path / "DATA" / "HEALTH"
    files = sorted(incident_dir.glob("incident-*.jsonl"))
    assert len(files) == 1, files

    rows = [json.loads(ln) for ln in
            files[0].read_text().splitlines() if ln]
    kinds = {r_["kind"] for r_ in rows}
    assert {"incident", "snapshot", "exemplar"} <= kinds
    head = rows[0]
    assert head["kind"] == "incident"
    assert "worker_stall" in head["entered_critical"]
    firing = {r_["name"]: r_ for r_ in head["rules"]}
    assert firing["worker_stall"]["state"] == "critical"
    assert firing["worker_stall"]["evidence"]["new_in_window"] >= 1
    snaps = [r_ for r_ in rows if r_["kind"] == "snapshot"]
    assert len(snaps) >= 2           # baseline + critical tick
    assert any('yacy_batch_timeouts_total{cause="worker_stall"}'
               in s["series"] for s in snaps)
    exemplar_tids = {r_["trace_id"] for r_ in rows
                     if r_["kind"] == "exemplar"}
    assert slow_tid in exemplar_tids

    # recovery: no new stalls for stallRecoveryTicks ticks -> ok
    for _ in range(sb.config.get_int("health.stallRecoveryTicks", 3) + 1):
        sb.health.tick()
    assert sb.health.states["worker_stall"].state == "ok"
    assert sb.health.overall() in ("ok", "warn")
    assert sb.health.incident_count == 1


# -- exemplar round trip (ISSUE 4 satellite) ---------------------------------

def test_slow_request_exemplar_resolves_from_metrics_to_trace_ring(sb):
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_metrics)
    with tracing.trace("servlet.yacysearch") as r:
        tid = r.ctx[0]
        tracing.emit("search.slowstage", 3500.0)
    # the trace id is retrievable from the negotiated OpenMetrics form
    # of /metrics (exemplars are an OpenMetrics feature)...
    om = respond_metrics({"accept": "application/openmetrics-text"},
                         ServerObjects({}), sb)
    assert om.raw_ctype.startswith("application/openmetrics-text")
    assert om.raw_body.endswith("# EOF\n")
    ex_lines = [ln for ln in om.raw_body.splitlines()
                if f'trace_id="{tid}"' in ln]
    assert ex_lines, "slow request's trace id missing from /metrics"
    assert any("yacy_search_slowstage_ms_bucket" in ln
               for ln in ex_lines)
    # ...while the classic 0.0.4 form stays exemplar-free (a classic
    # expfmt parser rejects anything after the sample value)
    classic = respond_metrics({"accept": ""}, ServerObjects({}), sb)
    assert classic.raw_ctype.startswith("text/plain; version=0.0.4")
    assert "trace_id=" not in classic.raw_body
    # ...and resolves in the trace ring / Performance_Trace_p
    rec = tracing.get_trace(tid)
    assert rec is not None
    assert any(s.name == "search.slowstage" for s in rec.spans)
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_trace)
    prop = respond_trace({"ext": "json"},
                         ServerObjects({"trace": tid}), sb)
    assert prop.get_int("spans", 0) >= 1


# -- Performance_Health_p servlet --------------------------------------------

def test_health_servlet_rule_table_and_incident_download(sb):
    from yacy_search_server_tpu.server.servlets.health import (
        respond_health)
    # force an evaluation from the page itself (operator affordance)
    prop = respond_health({"ext": "json"},
                          ServerObjects({"tick": "1"}), sb)
    assert prop.get("overall") in ("ok", "warn", "critical")
    n = prop.get_int("rules", 0)
    assert n >= 7
    names = {prop.get(f"rules_{i}_name") for i in range(n)}
    assert "worker_stall" in names
    for i in range(n):
        assert prop.get(f"rules_{i}_state") in ("ok", "warn", "critical")
        assert prop.get(f"rules_{i}_cause")

    # histogram rows with sparklines once a family has data
    hg.observe("servlet.serving", 12.0)
    prop = respond_health({"ext": "json"}, ServerObjects({}), sb)
    hn = prop.get_int("histograms", 0)
    assert hn >= 1
    hnames = {prop.get(f"histograms_{i}_name") for i in range(hn)}
    assert "servlet.serving" in hnames
    i = [i for i in range(hn)
         if prop.get(f"histograms_{i}_name") == "servlet.serving"][0]
    assert prop.get_int(f"histograms_{i}_window_count", 0) >= 1
    assert prop.get(f"histograms_{i}_spark")

    # induce an incident, then list + download it through the servlet
    _inject_stall(sb)
    sb.health.tick()
    prop = respond_health({"ext": "json"}, ServerObjects({}), sb)
    assert prop.get("overall") == "critical"
    assert prop.get_int("incidents", 0) == 1
    name = prop.get("incidents_0_name")
    dl = respond_health({"ext": "jsonl"},
                        ServerObjects({"format": "incident",
                                       "name": name}), sb)
    assert dl.raw_body and '"kind": "incident"' in dl.raw_body
    # unknown names never read the filesystem
    miss = respond_health({"ext": "jsonl"},
                          ServerObjects({"format": "incident",
                                         "name": "../etc/passwd"}), sb)
    assert miss.raw_body == "{}"


def test_health_busy_thread_deployed(sb):
    sb.deploy_threads()
    t = sb.threads.get("15_health")
    assert t is not None and t.is_alive()
    # /metrics carries the health gauges for the alerting path
    samples = parse_exposition(_metrics_text(sb))
    assert "yacy_health_status" in samples
    assert 'yacy_health_rule{rule="worker_stall"}' in samples
