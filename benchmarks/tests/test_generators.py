import collections
import json
import os

import pytest

from benchmarks import corpus, generators

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEAD = {"strings": 64, "and_share": 0.5, "s": 1.1}
# the mixes that no cell sends yet (PERF.md section 7)
QUESTIONS = {"lengths": {"2": 0.2, "3": 0.35, "4": 0.25, "5": 0.12,
                         "6": 0.08},
             "tier_share": {"high": 0.1, "med": 0.4, "low": 0.5},
             "block": 400}


def _cell(name):
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        wl = json.load(f)
    lay = corpus.layout(corpus.load_config(wl["config"]), 4000000007)
    return wl, lay


def _cases():
    wl, lay = _cell("wiki.tasks")
    return [("questions", QUESTIONS, lay),
            (wl["generator"], wl["params"], lay), ("head", HEAD, lay)]


@pytest.mark.parametrize("gen,params,lay", _cases(),
                         ids=["questions", "tasks", "head"])
def test_deterministic_in_the_seed(gen, params, lay):
    g = generators.load(gen)
    a = g.generate(lay, params, 2 ** 31 + 11, 500)
    assert a == g.generate(lay, params, 2 ** 31 + 11, 500)
    assert a != g.generate(lay, params, 2 ** 31 + 12, 500)
    assert g.warm(lay, params, 5) == g.warm(lay, params, 5)
    known = lay.by_name()
    assert all(w in known for q in a for w in q.split())


def test_questions_keep_their_stated_shares():
    wl, lay = {"params": QUESTIONS}, _cell("wiki.tasks")[1]
    qs = generators.load("questions").generate(lay, wl["params"], 7, 20000)
    n = collections.Counter(len(q.split()) for q in qs)
    for k, share in wl["params"]["lengths"].items():
        assert n[int(k)] / len(qs) == pytest.approx(share, abs=0.015)
    tiers = collections.Counter(
        lay.by_name()[w].tier for q in qs for w in q.split())
    total = sum(tiers.values())
    for t, share in wl["params"]["tier_share"].items():
        # drawing without repeats inside a question thins the small pools
        assert tiers[t] / total == pytest.approx(share, abs=0.04)
    by = lay.by_name()
    # distinct where the space allows: only word sets without a Low term
    # (some hundreds of strings) may repeat
    sets = collections.Counter(frozenset(q.split()) for q in qs)
    assert len(sets) > 0.97 * len(qs)
    assert all(by[w].tier != "low" for s, n in sets.items() if n > 1
               for w in s)
    # every block of 400 holds the same classes, whatever the seed
    other = generators.load("questions").generate(lay, wl["params"], 8, 800)

    def shape(q):
        return tuple(sorted(by[w].tier for w in q.split()))
    for lo in (0, 400):
        assert (collections.Counter(map(shape, qs[lo:lo + 400]))
                == collections.Counter(map(shape, other[lo:lo + 400])))
    for q in qs[:2000]:                   # one topic per question
        ts = [by[w] for w in q.split()]
        assert len({t.med_window for t in ts if t.tier != "high"}) <= 1
        assert len({t.sub_window for t in ts if t.tier == "low"}) <= 1


def test_tasks_keep_one_sixth_each_and_one_order_per_pair():
    wl, lay = _cell("wiki.tasks")
    qs = generators.load("tasks").generate(lay, wl["params"], 7, 24000)
    by = lay.by_name()
    cats = collections.Counter(
        tuple(by[w].tier for w in q.split()) for q in qs)
    assert len(cats) == 6
    for c, n in cats.items():
        assert n / len(qs) == pytest.approx(1 / 6, abs=0.012), c
    sets = collections.defaultdict(set)
    for q in qs:
        sets[frozenset(q.split())].add(q)
    assert all(len(v) == 1 for v in sets.values())
    for lo in range(0, 600, 60):          # the same in every block
        assert collections.Counter(
            tuple(by[w].tier for w in q.split())
            for q in qs[lo:lo + 60]) == {c: 10 for c in cats}


def test_head_is_a_fixed_set_under_a_zipfian_popularity():
    _wl, lay = _cell("wiki.tasks")
    g = generators.load("head")
    strings = g.warm(lay, HEAD, 9)
    assert len(strings) == 64 and len(set(strings)) == 64
    assert sum(" " in s for s in strings) == 32
    qs = g.generate(lay, HEAD, 9, 20000)
    assert set(qs) <= set(strings)
    top = collections.Counter(qs).most_common(1)[0][1] / len(qs)
    assert 0.15 < top < 0.30          # 1/H(64, 1.1) = 0.22


def test_corpus_lists_depend_only_on_seed_and_term():
    _wl, lay = _cell("wiki.tasks")
    t = lay.tier("low")[5]
    d1, f1 = corpus.term_list(lay, t, 2 ** 31 + 5)
    d2, f2 = corpus.term_list(lay, t, 2 ** 31 + 5)
    assert (d1 == d2).all() and (f1 == f2).all()
    assert len(set(d1.tolist())) == t.length
    assert d1.min() >= t.start and d1.max() < t.start + t.window
    assert corpus.doc_of(corpus.url_of(123456, lay.hosts)) == 123456
    assert lay.postings == 15_728_640
