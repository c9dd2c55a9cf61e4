"""Questions of three words and more, by class: luceneutil's nightly
tasks And3Terms and And2Terms2StopWords and their like.

params: {"classes": {"<name>": {"slots": [high, med, low], "weight": w}},
"block": 60}. A class is how many of a question's words are High, Med
and Low lists (a stop word, a regular term, a rare term: the
configuration says what each tier stands for). The stream is made of
blocks of `block` questions; every block holds each class in its
expected count (largest remainders), in an order drawn from the seed, so
that every seed sends the same set of sizes in another order.

For one question a topic (Med window, Low sub-window) is drawn first,
then each slot draws one list of its tier that covers the topic, without
repeating a term: the words of one question intersect in a page or more.
A word set is always written in ONE order, by tier (low, med, high:
rarest first) and then by list number, as generators/tasks.py writes a
pair and generators/questions.py does not: the node takes a
conjunction's features from the FIRST of its shortest lists in word
order while its event cache keys on the unordered set, so two orders of
one set are two right answers and one cache entry. Repeats fall as they
fall.
"""

import numpy as np

from . import apportion
from .questions import TIERS, _PoolCache

BLOCK = 60
WARM_STREAM = 96
_RAREST_FIRST = {"low": 0, "med": 1, "high": 2}


def block_of(params):
    """`block` classes (their slot counts), each in its expected count."""
    names = sorted(params["classes"])
    counts = apportion([float(params["classes"][c]["weight"])
                        for c in names], int(params.get("block", BLOCK)))
    return [tuple(int(s) for s in params["classes"][c]["slots"])
            for c, n in zip(names, counts) for _ in range(n)]


def written(terms) -> str:
    """The one string of a word set."""
    return " ".join(t.name for t in sorted(
        terms, key=lambda t: (_RAREST_FIRST[t.tier], t.index)))


def _check(pools, block):
    room = pools.room()
    for cls in set(block):
        if len(cls) != len(TIERS) or sum(cls) < 2 \
                or any(n > have for n, have in zip(cls, room)):
            raise ValueError(f"class {cls}: a topic has {room} lists to "
                             f"draw (high, med, low) without repeating")


def _draw(pools, cls, rng):
    mw = int(rng.integers(pools.n_med_windows))
    sw = int(rng.integers(pools.n_sub_windows))
    terms = []
    for tier, n in zip(TIERS, cls):
        pool = pools.covering(tier, mw, sw)
        terms += [pool[int(i)] for i in rng.choice(len(pool), n,
                                                   replace=False)]
    return written(terms)


def generate(lay, params, seed, n):
    pools = _PoolCache(lay)
    block = block_of(params)
    _check(pools, block)
    rng = np.random.default_rng([int(seed), 31337])
    out = []
    while len(out) < n:
        out += [_draw(pools, block[int(i)], rng)
                for i in rng.permutation(len(block))]
    return out[:n]


def warm(lay, params, seed):
    """One question of every class from every Med window, and then 96 of
    the stream under another seed: every join shape (partners, rare
    bucket) the stream reaches, often enough for the warm-up's threads
    to form every wave bucket."""
    pools = _PoolCache(lay)
    block = block_of(params)
    _check(pools, block)
    out = []
    for mw in range(lay.n_med_windows):
        for cls in sorted(set(block)):
            out.append(written(
                t for tier, n in zip(TIERS, cls)
                for t in pools.covering(tier, mw, 0)[:n]))
    rng = np.random.default_rng([int(seed), 424243])
    out += [_draw(pools, block[int(i) % len(block)], rng)
            for i in rng.permutation(WARM_STREAM)]
    return out
