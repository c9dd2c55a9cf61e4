"""The comparison that decides `correct`, and its control, at a size a
test run can hold."""

import json
import os

import pytest

from benchmarks import corpus, costs, generators, reference, run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small(cell):
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        wl = json.load(f)
    cfg = run.scaled(corpus.load_config(wl["config"]), 16)
    lay = corpus.layout(cfg, 2 ** 31 + 77)
    qs = generators.load(wl["generator"]).generate(
        lay, wl["params"], 2 ** 31 + 77, 300)
    return lay, qs


ZERO = {"wrong_answers": 0, "max_rank_gap": 0, "max_miss_gap": 0,
        "tie_order_answers": 0}


def _verdict(ref, served, lay):
    got = reference.compare(ref, served, lay.hosts)
    numbers = dict(got["numbers"], stale_served=0)
    return numbers, reference.decide(numbers, got["compared"])


def test_the_reference_agrees_with_itself():
    lay, qs = _small("wiki.tasks")
    ref = reference.Reference(lay, 2 ** 31 + 77)
    numbers, correct = _verdict(ref, ref.served(qs), lay)
    assert correct and numbers == dict(ZERO, stale_served=0)
    assert not reference.decide(numbers, 0)          # nothing compared
    assert not reference.decide(dict(numbers, stale_served=1), 300)


@pytest.mark.parametrize("control,number", [
    ("tf_bfloat16", "max_rank_gap"), ("drop_tail", "max_miss_gap"),
    ("tf_max_255", "max_rank_gap")])
def test_a_control_in_the_programs_place_is_not_correct(control, number):
    """Through the same comparison and the same limits that decide a
    run's `correct` (reference.decide)."""
    lay, qs = _small("wiki.tasks")
    ref = reference.Reference(lay, 2 ** 31 + 77)
    ctl = reference.Reference(lay, 2 ** 31 + 77, control=control)
    numbers, correct = _verdict(ref, ctl.served(qs), lay)
    assert not correct
    assert numbers["wrong_answers"] > 0 and numbers[number] > 0
    if control == "tf_max_255":
        assert numbers["max_rank_gap"] == 256       # one unit, shifted 8


def test_equal_rankings_in_another_order_are_not_correct():
    """The control tie_desc, on a page made to hold a tie (a sample this
    small seldom has one of its own)."""
    lay, qs = _small("wiki.tasks")
    ref = reference.Reference(lay, 2 ** 31 + 77)
    q = next(q for q in qs if len(ref.answer(q)) == reference.PAGE)
    docids, scores = ref.scored(q)
    want = ref.answer(q)
    import numpy as np
    scores[np.searchsorted(docids, want[1][0])] = want[0][1]
    ref._last = (q, (docids, scores))
    want = ref.answer(q)
    assert want[0][1] == want[1][1] and want[0][0] < want[1][0]
    swapped = [want[1], want[0]] + want[2:]
    served = [(q, [(corpus.url_of(d, lay.hosts), r) for d, r in swapped])]
    numbers, correct = _verdict(ref, served, lay)
    assert not correct
    assert numbers == dict(ZERO, wrong_answers=1, tie_order_answers=1,
                           stale_served=0)
    assert reference.page(docids, scores, lay.hosts,
                          ties_descending=True)[:2] == swapped[:2]


def test_an_altered_ranking_or_a_foreign_link_is_wrong():
    lay, qs = _small("wiki.tasks")
    ref = reference.Reference(lay, 2 ** 31 + 77)
    served = ref.served(qs[:20])
    q, items = next((q, it) for q, it in served if it)
    bumped = [(q, [(items[0][0], items[0][1] + 1)] + items[1:])]
    got = reference.compare(ref, bumped, lay.hosts)["numbers"]
    assert got["wrong_answers"] == 1 and got["max_rank_gap"] == 1
    foreign = [(q, [("http://h1.example/d999999999.html", 5)] + items[1:])]
    got = reference.compare(ref, foreign, lay.hosts)["numbers"]
    assert got["wrong_answers"] == 1 and got["max_rank_gap"] >= 2 ** 31
    short = [(q, items[:-1])]
    assert reference.compare(ref, short, lay.hosts)["numbers"][
        "max_miss_gap"] >= 2 ** 31


def test_join_takes_the_first_shortest_list_as_its_base():
    lay, _ = _small("wiki.tasks")
    hi = lay.tier("high")
    ref = reference.Reference(lay, 2 ** 31 + 77)
    a = ref.answer(f"{hi[0].name} {hi[1].name}")
    b = ref.answer(f"{hi[1].name} {hi[0].name}")
    assert {d for d, _ in a} != {d for d, _ in b} or a != b


def test_a_roofline_share_over_100_raises_and_an_unknown_chip_is_an_error():
    assert costs.share_pct(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError):
        costs.share_pct(1.01, 1.0, "x")
    assert costs.peak("TPU v5 lite")["bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            costs.peak(kind)


def test_a_share_under_0_or_over_100_raises_and_is_never_clipped():
    from benchmarks.layer_metrics import (_shared, device_idle_pct,
                                          topk_cache_hit_pct)
    assert _shared.share_of(1, 4, "x") == 25.0
    assert _shared.share_of(0, 0, "x") is None
    for part in (-1, 5):
        with pytest.raises(ValueError):
            _shared.share_of(part, 4, "x")
    with pytest.raises(ValueError):        # a request counted twice
        topk_cache_hit_pct.read({"counters": {"rank_cache_hits": 11},
                                 "attempted": 10})
    with pytest.raises(ValueError):        # busier than the window is long
        device_idle_pct.read({"trace": {"busy_s": 2.0},
                              "trace_window_s": 1.5})


@pytest.mark.parametrize("seed", [2 ** 31 + 77, 1073984206, 7])
def test_no_seed_draws_a_page_an_exact_comparison_cannot_settle(seed):
    """The recipe's title documents: the page of every query over High
    and Med lists holds title documents only, no two equal rankings (nor
    at the page's edge), and no document whose term frequency is the
    candidates' largest or smallest (where a quotient sits exactly on a
    whole number). A Low list holds none, and no list holds a title
    document as an ordinary row."""
    import itertools
    import numpy as np
    cfg = run.scaled(corpus.load_config("lucene-wikimedium10m-default"), 16)
    lay = corpus.layout(cfg, seed)
    stars = set(np.concatenate(lay.star_docs).tolist())
    lengths = np.concatenate(lay.star_domlength)
    assert len(stars) == len(lengths) == len(set(lengths.tolist())) == 192
    hi, med, low = lay.tier("high"), lay.tier("med"), lay.tier("low")
    pairs = list(itertools.combinations(hi, 2)) \
        + [(a, b) for a in hi for b in med]
    rng = np.random.default_rng(seed)
    queries = [(t,) for t in hi + med] + [
        pairs[i] for i in rng.choice(len(pairs), 120, replace=False)]
    ref = reference.Reference(lay, seed)
    for terms in queries:
        docids, feats = reference.join([ref._list(t.name) for t in terms])
        scores = reference.cardinal(feats)
        top = np.lexsort((docids, -scores))[:reference.PAGE + 2]
        assert (np.diff(scores[top]) < 0).all(), terms
        got = reference.page(docids, scores, lay.hosts)
        assert len(got) == reference.PAGE
        assert {d for d, _ in got} <= stars, terms
        f = feats.astype(np.float32)
        tf = f[:, corpus.F_HITCOUNT] / (
            f[:, corpus.F_WORDS_IN_TEXT] + f[:, corpus.F_WORDS_IN_TITLE] + 1)
        edge = docids[(tf == tf.max()) | (tf == tf.min())]
        assert not set(edge.tolist()) & stars, terms
        on = np.isin(docids, list(stars))
        assert ((feats[:, corpus.F_FLAGS] & corpus.FLAG_IN_TITLE) != 0
                ).tolist() == on.tolist()
    for t in low[:40]:
        docids, feats = corpus.term_list(lay, t, seed)
        assert not set(docids.tolist()) & stars
        assert not (feats[:, corpus.F_FLAGS] & corpus.FLAG_IN_TITLE).any()
