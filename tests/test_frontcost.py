"""What a cached answer costs the front (ISSUE 38): the page's properties
escaped and filled by C-level string operations, the event's id taken
once, and the access tracker's locks off the request's way.

The per-character `escape_json` that served until PR 36 lives on here as
the oracle, as the template interpreter does in `test_pagefinish.py`: the
table-driven one has to return the same string for every `str`.
"""

import json
import os
import random
import re
import subprocess
import sys
import threading
import urllib.request

import pytest

from yacy_search_server_tpu.search import accesstracker as accesstracker_mod
from yacy_search_server_tpu.search.accesstracker import (DUMP_BATCH,
                                                         MAX_FINISHED,
                                                         AccessTracker,
                                                         QueryLogEntry)
from yacy_search_server_tpu.search.query import QueryParams
from yacy_search_server_tpu.server import ServerObjects
from yacy_search_server_tpu.server.objects import escape_json
from yacy_search_server_tpu.server.servlets import yacysearch

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the oracle: escape_json as it was (PR 36, server/objects.py) -------------

def escape_json_loop(s) -> str:
    out = []
    for ch in str(s):
        if ch == '"':
            out.append('\\"')
        elif ch == "\\":
            out.append("\\\\")
        elif ch == "\n":
            out.append("\\n")
        elif ch == "\r":
            out.append("\\r")
        elif ch == "\t":
            out.append("\\t")
        elif ord(ch) < 0x20:
            out.append("\\u%04x" % ord(ch))
        else:
            out.append(ch)
    return "".join(out)


def test_escape_json_equals_the_loop_on_every_code_point():
    for cp in range(0x110000):
        ch = chr(cp)
        assert escape_json(ch) == escape_json_loop(ch), hex(cp)
    # and in company: a code point's escape does not depend on its
    # neighbours, whatever width the string is stored in
    for lo, hi in ((0, 0x80), (0, 0x100), (0, 0x10000), (0, 0x110000)):
        whole = "".join(map(chr, range(lo, hi)))
        assert escape_json(whole) == escape_json_loop(whole), hex(hi)


_CLASSES = {
    "quotes_and_backslashes": '"\\\\""\\"/\'',
    "controls": "".join(map(chr, range(0x20))) + "\x7f\x80\x9f",
    "lone_surrogates": "\ud800\udbff\udc00\udfff",
    "astral": "\U00010000\U0001f600\U0010ffff\U000e0001",
    "latin1_and_bmp": "\xe9\xa0\xff\u0100\u4e2d\u2028\u2029\ufeff\uffff",
    "plain": "abc XYZ 0189 -_.~/?&=:;<>",
}
_CLASSES["mixed"] = "".join(_CLASSES.values())


@pytest.mark.parametrize("name", sorted(_CLASSES))
def test_escape_json_equals_the_loop_on_random_strings(name):
    rnd = random.Random(f"38-{name}")
    alphabet = _CLASSES[name]
    for _ in range(400):
        s = "".join(rnd.choice(alphabet)
                    for _ in range(rnd.randrange(0, 48)))
        got = escape_json(s)
        assert got == escape_json_loop(s), repr(s)
        if name != "lone_surrogates":
            assert json.loads(f'"{got}"') == s


@pytest.mark.parametrize("value", (None, 0, -17, 3.5, True, b"by\"tes\n",
                                   ("a", '"'), ""),
                         ids=repr)
def test_escape_json_of_what_is_not_a_string(value):
    assert escape_json(value) == escape_json_loop(value)
    p = ServerObjects()
    p.put_json("k", value)
    assert p.get("k") == escape_json_loop(value)


def test_put_strings_takes_the_map_as_it_is():
    p = ServerObjects({"a": 1})
    p.put_strings({"b": "x\"y", "a": "2"})
    assert p.as_dict() == {"a": "2", "b": "x\"y"}


# -- a page of awkward characters through the real server ---------------------

# what a title, a host's path and a description can hold: every character
# JSON, HTML and XML treat specially, controls, astral and BMP characters
AWKWARD_TITLES = (
    'He said "numbat" \\ and left',
    "tab\there <b>&amp;</b> 'single' & more",
    "astral \U0001f600 \U00010000 and bmp \u4e2d\u6587 \xe9",
    "controls \x01\x02\x1f mid\x0bword \x7f",
    "slash / back\\\\slash \\\" quote",
    "]]> <![CDATA[ </script> <!-- -->",
)


def _awkward_node(tmp_path):
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.server import YaCyHttpServer
    from yacy_search_server_tpu.switchboard import Switchboard
    sb = Switchboard(data_dir=str(tmp_path / "DATA"),
                     transport=lambda u, h: (404, {}, b""))
    for i in range(12):
        title = AWKWARD_TITLES[i % len(AWKWARD_TITLES)]
        sb.index.store_document(Document(
            url=(f"http://h{i}.test/p{i}.html?a=1&b=\"q\"&c=<x>"
                 f"#frag'{i}"),
            title=f"{title} {i}", author=f"A\"u\\thor <{i % 2}>",
            text=f"numbat termite mound {i}. " * 4,
            mime_type="text/html", language="en",
            publish_date_days=19_000 + 400 * (i % 3)))
    return sb, YaCyHttpServer(sb, port=0).start()


_VOLATILE = re.compile(
    rb'("searchTime": "|"traceID": ")[0-9a-f]*|\(?\b[0-9]+ ms\b')


def _get(srv, path: str) -> bytes:
    with urllib.request.urlopen(srv.base_url + path, timeout=30) as r:
        assert r.status == 200
        # a searchtime and a traceID differ a request on any tree
        return _VOLATILE.sub(rb"\1", r.read())


@pytest.fixture(scope="module")
def awkward(tmp_path_factory):
    sb, srv = _awkward_node(tmp_path_factory.mktemp("awkward"))
    try:
        yield sb, srv
    finally:
        srv.close()
        sb.close()


@pytest.mark.parametrize("ext", ("json", "html", "rss"))
@pytest.mark.parametrize("query", (
    "numbat", 'numbat "termite mound"', "numbat site:h3.test",
    "nothing_matches_this", ""))
def test_served_pages_equal_the_converting_path(awkward, ext, query,
                                                monkeypatch):
    """The body of every surface, byte for byte, against the same page
    filled the way it was: the oracle's escape, and every property
    through `put`'s conversion one at a time."""
    _sb, srv = awkward
    path = (f"/yacysearch.{ext}?query={urllib.request.quote(query)}"
            "&maximumRecords=10&hybrid=true")
    got = _get(srv, path)
    monkeypatch.setattr(yacysearch, "escape_json", escape_json_loop)

    def one_at_a_time(self, ready):
        for k, v in ready.items():
            assert type(k) is str and type(v) is str, (k, v)
            self.put(k, v)

    monkeypatch.setattr(ServerObjects, "put_strings", one_at_a_time)
    assert _get(srv, path) == got
    if query == "numbat":
        assert b"numbat" in got.lower() and b"h3.test" in got


def test_json_page_gives_back_the_raw_strings(awkward):
    sb, srv = awkward
    page = json.loads(_get(srv, "/yacysearch.json?query=numbat"
                                "&maximumRecords=12"))["channels"][0]
    event = sb.search("numbat", count=12)
    want = event.results(offset=0, count=12)
    assert len(page["items"]) == len(want) == 12
    for item, r in zip(page["items"], want):
        assert item["title"] == r.title and item["link"] == r.url
        assert item["host"] == r.host
    titles = " ".join(i["title"] for i in page["items"])
    for needle in ('"numbat" \\', "\U0001f600", "\x01\x02\x1f", "]]>",
                   "\t"):
        assert needle in titles, repr(needle)
    names = {n["facetname"]: [e["name"] for e in n["elements"]]
             for n in page["navigation"]}
    assert "hosts" in names and len(names["hosts"]) == 10


# -- query_id: one value an event --------------------------------------------

def test_query_id_is_one_value_before_and_after_the_page(awkward):
    sb, _srv = awkward
    event = sb.search("termite mound", count=10)
    before = event.query.query_id()
    event.results(offset=0, count=10)
    assert event.query.query_id() == before == event.event_id
    assert sb.search_cache.event_by_id(before) is event
    # a second request of the same string finds the same event by the
    # id of its own QueryParams
    assert sb.search("termite mound", count=10) is event
    assert QueryParams.parse("termite mound").query_id() \
        == QueryParams.parse("termite mound").query_id()


@pytest.mark.parametrize("use_cache", (True, False))
def test_the_pages_event_id_is_the_one_the_event_was_built_under(
        awkward, use_cache):
    """`eventID` is read off the event, not worked out again: the id
    the cache keeps it under, and one for an event no cache keeps."""
    sb, srv = awkward
    page = json.loads(_get(
        srv, "/yacysearch.json?query=numbat+mound&maximumRecords=10"
        + ("" if use_cache else "&nocache=true")))
    event = sb.search("numbat mound", count=10, use_cache=use_cache)
    assert event.event_id == event.query.query_id()
    assert page["channels"][0]["eventID"] == event.event_id
    assert (sb.search_cache.event_by_id(event.event_id) is event) \
        == use_cache


@pytest.mark.parametrize("field, value", (
    ("degrade_level", 1), ("degrade_level", 3), ("contentdom", 2),
    ("hybrid", True), ("lang", "de"), ("url_filter", len)))
def test_query_id_tells_apart_what_the_cache_must(field, value):
    plain = QueryParams.parse("numbat mound")
    other = QueryParams.parse("numbat mound")
    setattr(other, field, value)
    assert other.query_id() != plain.query_id()
    assert other.query_id() == other.query_id()
    # an id taken BEFORE the field was set is not handed out after it
    late = QueryParams.parse("numbat mound")
    assert late.query_id() == plain.query_id()
    setattr(late, field, value)
    assert late.query_id() == other.query_id()


# -- the access tracker under threads -----------------------------------------

def _entry(thread: int, i: int) -> QueryLogEntry:
    return QueryLogEntry(query=f"t{thread} q{i}", timestamp=1000.0 + i,
                         query_count=2, result_count=i, time_ms=1.5,
                         client=f"10.0.0.{thread}")


def _lines(path) -> list[str]:
    with open(path, encoding="utf-8") as f:
        return f.read().splitlines()


@pytest.mark.parametrize("threads, each", ((8, 2000), (3, 7), (1, 50)))
def test_tracker_counts_and_logs_exactly_under_threads(tmp_path, threads,
                                                       each):
    path = str(tmp_path / "LOG" / "queries.log")
    tr = AccessTracker(path)
    gate = threading.Barrier(threads)
    counts: list[list[int]] = [[] for _ in range(threads)]

    def work(t):
        gate.wait()
        for i in range(each):
            counts[t].append(tr.track_access("192.0.2.1"))
            tr.add(_entry(t, i))

    ts = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    sent = threads * each
    # the window: every access counted once, no count handed out twice
    assert sorted(c for cs in counts for c in cs) == list(range(1, sent + 1))
    assert tr.access_hosts() == [("192.0.2.1", sent)]
    assert tr.size() == min(MAX_FINISHED, sent)
    assert len(tr.latest(50)) == min(50, sent)
    # less than a batch waits for dump(); nothing after it
    written = len(_lines(path)) if os.path.exists(path) else 0
    assert written + len(tr._undumped) == sent
    assert len(tr._undumped) < DUMP_BATCH
    tr.dump()
    lines = _lines(path)
    assert len(lines) == sent and len(set(lines)) == sent
    for t in range(threads):
        mine = [ln for ln in lines if f" t{t} q" in ln]
        assert mine == [_entry(t, i).dump_line() for i in range(each)]
    assert tr._undumped == []


def test_tracker_lets_requests_by_while_a_batch_is_written(tmp_path,
                                                           monkeypatch):
    """No file is opened under a lock that `track_access` or `add`
    takes: with the open stuck, both still return from other threads."""
    path = str(tmp_path / "LOG" / "queries.log")
    tr = AccessTracker(path)
    stuck, release = threading.Event(), threading.Event()

    def slow_open(*a, **kw):
        stuck.set()
        assert release.wait(30)
        return open(*a, **kw)

    monkeypatch.setattr(accesstracker_mod, "open", slow_open, raising=False)
    writer = threading.Thread(target=lambda: [
        tr.add(_entry(0, i)) for i in range(DUMP_BATCH)])
    writer.start()
    assert stuck.wait(30)            # the 50th add is inside open()
    passed = []

    def others(t):
        for i in range(DUMP_BATCH + 5):      # a second batch fills
            passed.append(tr.track_access("192.0.2.7"))
            tr.add(_entry(t, i))

    ts = [threading.Thread(target=others, args=(t,)) for t in (1, 2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    try:
        assert not any(t.is_alive() for t in ts), \
            "track_access / add waited for the file"
        assert writer.is_alive() and len(passed) == 2 * (DUMP_BATCH + 5)
        assert tr.size() == 3 * DUMP_BATCH + 10
        assert tr.retry_after_s("192.0.2.7", limit=10) > 0
    finally:
        release.set()
        writer.join(30)
    tr.dump()
    lines = _lines(path)
    # arrival order: the stuck batch first, then each thread's own order
    assert lines[:DUMP_BATCH] == [_entry(0, i).dump_line()
                                  for i in range(DUMP_BATCH)]
    assert len(lines) == len(set(lines)) == 3 * DUMP_BATCH + 10
    for t in (1, 2):
        assert [ln for ln in lines if f" t{t} q" in ln] == [
            _entry(t, i).dump_line() for i in range(DUMP_BATCH + 5)]


def test_a_batch_that_fills_during_a_write_is_written_by_the_writer(
        tmp_path, monkeypatch):
    """The 50th `add` of the NEXT batch arrives while the writer is
    stuck in the file: it does not wait, and the writer's look at the
    buffer after it let go of the file's lock writes that batch too,
    in order, with no `dump()` asked."""
    path = str(tmp_path / "LOG" / "queries.log")
    tr = AccessTracker(path)
    stuck, release = threading.Event(), threading.Event()
    opened = []

    def slow_open(*a, **kw):
        opened.append(a[0])
        if len(opened) == 1:
            stuck.set()
            assert release.wait(30)
        return open(*a, **kw)

    monkeypatch.setattr(accesstracker_mod, "open", slow_open, raising=False)
    writer = threading.Thread(target=lambda: [
        tr.add(_entry(0, i)) for i in range(DUMP_BATCH)])
    writer.start()
    assert stuck.wait(30)
    filler = threading.Thread(target=lambda: [
        tr.add(_entry(1, i)) for i in range(DUMP_BATCH + 3)])
    filler.start()
    filler.join(10)
    try:
        assert not filler.is_alive(), "the 50th add waited for the file"
        assert len(tr._undumped) == DUMP_BATCH + 3 and len(opened) == 1
    finally:
        release.set()
        writer.join(30)
    assert not writer.is_alive() and len(opened) == 2
    assert _lines(path) == (
        [_entry(0, i).dump_line() for i in range(DUMP_BATCH)]
        + [_entry(1, i).dump_line() for i in range(DUMP_BATCH + 3)])
    assert tr._undumped == []


def test_tracker_without_a_file_buffers_nothing():
    tr = AccessTracker()
    for i in range(3 * DUMP_BATCH):
        tr.add(_entry(0, i))
    tr.dump()
    assert tr._undumped == [] and tr.size() == 3 * DUMP_BATCH
    assert tr.latest(1)[0].query == f"t0 q{3 * DUMP_BATCH - 1}"


def test_switchboard_close_leaves_no_query_unwritten(tmp_path):
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.switchboard import Switchboard
    sb = Switchboard(data_dir=str(tmp_path / "DATA"),
                     transport=lambda u, h: (404, {}, b""))
    n = 2 * DUMP_BATCH + 20
    try:
        sb.index.store_document(Document(
            url="http://h.test/p.html", title="Numbat",
            text="numbat termite mound. " * 4))
        for i in range(n):
            sb.search(f"numbat q{i}", client="192.0.2.9")
        path = os.path.join(str(tmp_path / "DATA"), "LOG", "queries.log")
        assert len(_lines(path)) == 2 * DUMP_BATCH
    finally:
        sb.close()
    lines = _lines(path)
    assert [ln.rsplit(" ", 1)[1] for ln in lines] == [
        f"q{i}" for i in range(n)]
    assert all(" 192.0.2.9 2 " in ln for ln in lines)


def test_loopback_over_the_window_is_counted_and_asks_no_wait(awkward,
                                                              monkeypatch):
    """Every request is counted before any branch; the wait of a denial
    is worked out only for a client that can be denied."""
    sb, srv = awkward
    monkeypatch.setattr(sb.config, "get_int", lambda k, d=0: (
        2 if k == "httpd.maxAccessPerHost.600s" else d))
    asked = []
    real = sb.access_tracker.retry_after_s
    monkeypatch.setattr(sb.access_tracker, "retry_after_s",
                        lambda *a, **kw: asked.append(a) or real(*a, **kw))
    before = dict(sb.access_tracker.access_hosts()).get("127.0.0.1", 0)
    for _ in range(4):
        _get(srv, "/yacysearch.json?query=numbat")
    assert dict(sb.access_tracker.access_hosts())["127.0.0.1"] == before + 4
    assert asked == []
    # a client behind the local front IS denied, with the window's wait
    req = urllib.request.Request(
        srv.base_url + "/yacysearch.json?query=numbat",
        headers={"X-Forwarded-For": "198.51.100.4"})
    codes = []
    for _ in range(4):
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                codes.append((r.status, None))
        except urllib.error.HTTPError as e:
            codes.append((e.code, e.headers.get("Retry-After")))
    assert [c for c, _ in codes] == [200, 200, 429, 429]
    assert all(int(ra) >= 1 for c, ra in codes if c == 429)
    assert [a[0] for a in asked] == ["198.51.100.4"] * 2


# -- trace ids: still the system's entropy, read 512 ids at a time ------------

def test_trace_ids_are_cut_from_the_systems_entropy(monkeypatch):
    """`do_tracefetch` hands a trace's spans to whoever names its id:
    an id is 64 bits of `secrets`, never a seeded generator's."""
    from yacy_search_server_tpu.utils import tracing
    reads = []
    real = tracing.secrets.token_hex

    def token_hex(n):
        reads.append(n)
        return real(n)

    monkeypatch.setattr(tracing.secrets, "token_hex", token_hex)
    monkeypatch.setattr(tracing, "_id_pool", [])
    ids = [tracing.new_trace_id() for _ in range(1024)]
    assert reads == [4096, 4096]               # 512 ids a read
    assert len(set(ids)) == 1024 and tracing._id_pool == []
    assert all(re.fullmatch("[0-9a-f]{16}", i) for i in ids)
    assert all(tracing.valid_trace_id(i) for i in ids)


def test_trace_ids_of_many_threads_are_handed_out_once():
    from yacy_search_server_tpu.utils import tracing
    gate = threading.Barrier(8)
    got: list[list[str]] = [[] for _ in range(8)]

    def work(t):
        gate.wait()
        got[t] = [tracing.new_trace_id() for _ in range(3000)]

    ts = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    ids = [i for g in got for i in g]
    assert len(ids) == len(set(ids)) == 8 * 3000


_FORK = """
import os
from yacy_search_server_tpu.utils import tracing
tracing.new_trace_id()
waiting = set(tracing._id_pool)
r, w = os.pipe()
pid = os.fork()
if pid == 0:
    try:
        os.write(w, " ".join(
            tracing.new_trace_id() for _ in range(600)).encode())
    finally:
        os._exit(0)
os.close(w)
with os.fdopen(r) as f:
    childs = set(f.read().split())
os.waitpid(pid, 0)
assert len(waiting) == 511 and len(childs) == 600
assert not waiting & childs
assert waiting == set(tracing._id_pool)
print("ok")
"""


@pytest.mark.skipif(not hasattr(os, "fork"), reason="needs fork")
def test_a_forked_child_hands_out_none_of_its_parents_trace_ids():
    # in a process of its own: this one has JAX's threads, and a fork
    # beside them is a deadlock waiting to happen
    out = subprocess.run([sys.executable, "-c", _FORK], cwd=_ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "ok", out.stderr
