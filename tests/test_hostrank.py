"""The host gate's ranking in one call (ISSUE 34): the fused native scorer
behind `CardinalRanker.rank` against its oracle, `cardinal_scores_host`,
bit for bit; the fallbacks; the counters that say which one answered; the
loader's rebuild of a stale library."""

import os
import subprocess
import threading
from dataclasses import fields

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.ops import ranking as R
from yacy_search_server_tpu.utils import native

LANGS = ("en", "de")


def _profile(name: str) -> R.RankingProfile:
    prof = R.RankingProfile()
    if name != "default":
        for f in fields(prof):
            setattr(prof, f.name, 0 if name == "zeros" else 15)
        prof.authority = 5          # > 12 is the NumPy twin's (own test)
    return prof


def _block(n: int, shape: str, seed: int) -> np.ndarray:
    """int32 [n, NF] rows of the kind `shape` names."""
    rng = np.random.default_rng(seed)
    f = rng.integers(0, 3000, (n, P.NF)).astype(np.int32)
    f[:, P.F_LANGUAGE] = rng.choice(
        [P.pack_language(c) for c in LANGS + ("",)], n)
    f[:, P.F_FLAGS] = rng.integers(0, 1 << 30, n)
    if shape == "span0":
        # every second column holds one value: its span is 0
        f[:, ::2] = f[0, ::2]
    elif shape == "tfspan0":
        # one term frequency in every row (and 0 / 1 in the first case)
        f[:, P.F_HITCOUNT] = 0 if seed % 2 else 7
        f[:, P.F_WORDS_IN_TEXT] = 40
        f[:, P.F_WORDS_IN_TITLE] = 2
    elif shape == "int16":
        # past the int16 clip both ways, a zero and a negative tf divisor
        f = rng.integers(-100_000, 100_000, (n, P.NF)).astype(np.int32)
        f[0, P.F_WORDS_IN_TEXT], f[0, P.F_WORDS_IN_TITLE] = -1, 0
    elif shape == "flag31":
        f[:, P.F_FLAGS] = rng.integers(-2**31, 2**31, n)
        f[::3, P.F_FLAGS] |= np.int32(-2**31)
    return f


def _native_scores(f, prof, lang, k):
    got = native.cardinal_topk(f, R._native_consts(prof),
                               P.pack_language(lang), k)
    assert got is not None
    return got


@pytest.mark.filterwarnings("ignore::RuntimeWarning")   # the oracle's 0 / 0
@pytest.mark.parametrize("lang", LANGS)
@pytest.mark.parametrize("profile", ("default", "zeros", "fifteens"))
@pytest.mark.parametrize("shape", ("span0", "tfspan0", "int16", "flag31"))
@pytest.mark.parametrize("n", (1, 2, 54, 430, 2048, 4096))
def test_native_scores_equal_the_numpy_twin(n, shape, profile, lang):
    prof = _profile(profile)
    f = _block(n, shape, seed=n + len(shape))
    oracle = R.cardinal_scores_host(f, prof, lang)
    scores, order = _native_scores(f, prof, lang, 100)
    assert scores.dtype == np.int64 and np.array_equal(scores, oracle)
    assert np.array_equal(order, np.argsort(-oracle, kind="stable")[:100])
    # and through the ranker, which says who answered
    how = {}
    plist = P.PostingsList(np.arange(n, dtype=np.int32) * 3 + 1, f)
    s, d = R.CardinalRanker(prof, lang).rank(plist, None, k=100, how=how)
    assert how == {"ranker": "native"}
    assert np.array_equal(s, oracle[order])
    assert np.array_equal(d, plist.docids[order])


def test_equal_scores_come_out_by_ascending_index():
    f = np.tile(_block(1, "plain", 3), (600, 1))
    f[::7, P.F_HITCOUNT] += 5            # a better class, every 7th row
    scores, order = _native_scores(f, R.RankingProfile(), "en", 200)
    assert len(set(scores.tolist())) == 2
    best = np.arange(0, 600, 7)
    rest = np.setdiff1d(np.arange(600), best)
    assert order.tolist() == best.tolist() + rest[:200 - len(best)].tolist()
    # k past the block: all of it, once
    _, whole = _native_scores(f, R.RankingProfile(), "en", 5000)
    assert sorted(whole.tolist()) == list(range(600))


def test_authority_takes_the_numpy_twin_and_says_so():
    prof = R.RankingProfile()
    prof.authority = 13
    f = _block(300, "plain", 5)
    plist = P.PostingsList(np.arange(300, dtype=np.int32), f)
    hosts = [b"h%02d" % (i % 9) for i in range(300)]
    how = {}
    s, d = R.CardinalRanker(prof, "en").rank(plist, hosts, k=20, how=how)
    assert how == {"ranker": "numpy"}
    oracle = R.cardinal_scores_host(
        f, prof, "en", R.hostid_array(plist.docids, hosts))
    order = np.argsort(-oracle, kind="stable")[:20]
    assert np.array_equal(s, oracle[order]) and np.array_equal(d, order)


def test_a_shift_past_fifteen_stays_on_numpy():
    prof = R.RankingProfile()
    prof.date = 16
    assert R._native_consts(prof) is None
    how = {}
    R.CardinalRanker(prof).rank(
        P.PostingsList(np.arange(9, dtype=np.int32), _block(9, "plain", 1)),
        None, how=how)
    assert how == {"ranker": "numpy"}


def test_past_the_gate_the_device_ranks():
    n = R.SMALL_RANK_N + 1
    how = {}
    R.CardinalRanker().rank(
        P.PostingsList(np.arange(n, dtype=np.int32), _block(n, "plain", 2)),
        None, how=how)
    assert how == {"ranker": "device"}


def _fresh_loader(monkeypatch):
    """The loader as a process start finds it; the session's library is
    put back when the test ends."""
    monkeypatch.setattr(native, "_loaded", False)
    monkeypatch.setattr(native, "LIB", None)
    monkeypatch.setattr(native, "LIB_HELD", None)


def _rank(f):
    how = {}
    plist = P.PostingsList(np.arange(len(f), dtype=np.int32), f)
    s, d = R.CardinalRanker().rank(plist, None, k=50, how=how)
    return how["ranker"], s.tolist(), d.tolist()


def test_switched_off_the_ranker_answers_the_same(monkeypatch):
    f = _block(430, "plain", 11)
    with_lib = _rank(f)
    assert with_lib[0] == "native"
    _fresh_loader(monkeypatch)
    monkeypatch.setenv("YACYTPU_NATIVE", "0")
    assert _rank(f) == ("numpy",) + with_lib[1:]


def _stale_tree(tmp_path, monkeypatch, edit) -> str:
    """A native directory of its own whose built library is `edit` of
    the source and NEWER than it; the loader pointed there."""
    src = tmp_path / "yacytpu.cpp"
    so = tmp_path / "libyacytpu.so"
    with open(native._SRC_PATH, encoding="utf-8") as fh:
        text = fh.read()
    old = tmp_path / "old.cpp"
    old.write_text(edit(text), encoding="utf-8")
    subprocess.run(["g++", "-O1", "-fPIC", "-shared", "-std=c++17", "-o",
                    str(so), str(old)], check=True, timeout=120)
    src.write_text(text, encoding="utf-8")
    os.utime(src, (1, 1))
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    _fresh_loader(monkeypatch)
    return str(so)


def test_load_rebuilds_a_library_of_another_version(tmp_path, monkeypatch):
    so = _stale_tree(
        tmp_path, monkeypatch, lambda text: text.replace(
            "ytn_abi_version() { return %d; }" % native.ABI_VERSION,
            "ytn_abi_version() { return 1; }"))
    before = os.stat(so).st_ino
    lib = native.load()
    assert lib is not None and lib.ytn_abi_version() == native.ABI_VERSION
    assert os.stat(so).st_ino != before
    f = _block(54, "plain", 4)
    assert _rank(f)[0] == "native"
    assert native.sort_dedupe_order(np.array([3, 1, 3], np.int32),
                                    min_batch=1).tolist() == [1, 2]


def test_a_library_without_the_symbol_falls_back(tmp_path, monkeypatch):
    f = _block(54, "plain", 4)
    with_lib = _rank(f)

    def strip(text):
        return text.replace("ytn_cardinal_scores", "ytn_no_such_scorer")

    so = _stale_tree(tmp_path, monkeypatch, strip)
    # the source it would rebuild from lacks the scorer too: no cure
    with open(native._SRC_PATH, encoding="utf-8") as fh:
        text = fh.read()
    with open(native._SRC_PATH, "w", encoding="utf-8") as fh:
        fh.write(strip(text))
    os.utime(native._SRC_PATH, (1, 1))
    assert native.load() is None and os.path.exists(so)
    assert _rank(f) == ("numpy",) + with_lib[1:]


def test_eight_threads_rank_different_blocks_at_once():
    blocks = [_block(n, "plain", 20 + i) for i, n in
              enumerate((54, 430, 2048, 4096, 1, 999, 2047, 3000))]
    want = [np.argsort(-R.cardinal_scores_host(f, R.RankingProfile(), "en"),
                       kind="stable")[:100].tolist() for f in blocks]
    wrong: list[int] = []
    gate = threading.Barrier(len(blocks))

    def work(i):
        gate.wait()
        for _ in range(40):
            if _rank_order(blocks[i]) != want[i]:
                wrong.append(i)

    def _rank_order(f):
        plist = P.PostingsList(np.arange(len(f), dtype=np.int32), f)
        return R.CardinalRanker().rank(plist, None, k=100)[1].tolist()

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(len(blocks))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not wrong and not any(t.is_alive() for t in ts)


@pytest.mark.parametrize("ranker", ("native", "numpy"))
def test_a_gate_answer_counts_its_ranker(tmp_path, monkeypatch, ranker):
    """`search.normalizing` carries the attr, the stage counter of the
    ranker moves by one event and the candidates' rows, `/metrics` lists
    it; the page is the same either way."""
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils import tracing
    from yacy_search_server_tpu.utils.eventtracker import EClass, totals
    if ranker == "numpy":
        _fresh_loader(monkeypatch)
        monkeypatch.setenv("YACYTPU_NATIVE", "0")
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        for i in range(5):
            sb.index.store_document(Document(
                url=f"http://gate.test/{i}.html", title=f"Quokka {i}",
                text="quokka " * (i + 1) + "grass " * 9))

        def count(label):
            return totals().get((EClass.SEARCH, label), (0, 0, 0))[:2]

        selects: list[int] = []
        select = P.PostingsList.select
        monkeypatch.setattr(
            P.PostingsList, "select",
            lambda self, mask: (selects.append(len(mask)),
                                select(self, mask))[1])
        labels = ("NORMALIZING_NATIVE", "NORMALIZING_NUMPY")
        before = [count(label) for label in labels]
        with tracing.trace("test.gate") as root:
            page = sb.search("quokka", use_cache=False).results()
        assert [e.url for e in page][0] == "http://gate.test/4.html"
        spans = [s for s in tracing.get_trace(root.ctx[0]).spans
                 if s.name == "search.normalizing"]
        assert [s.attrs["ranker"] for s in spans] == [ranker]
        moved = [(a[0] - b[0], a[1] - b[1]) for a, b in
                 zip((count(label) for label in labels), before)]
        assert moved == ([(1, 5), (0, 0)] if ranker == "native"
                         else [(0, 0), (1, 5)])
        assert ('yacy_stage_events_total{class="search",'
                'label="NORMALIZING_%s"}' % ranker.upper()
                in prometheus_text(sb))
        # nothing constrained that query: the joined block went to the
        # ranker as it was; a modifier filters it into a copy, same page
        assert selects == []
        sited = sb.search("quokka site:gate.test", use_cache=False).results()
        assert [e.url for e in sited] == [e.url for e in page]
        assert selects == [5]
    finally:
        sb.close()

