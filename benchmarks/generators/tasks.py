"""luceneutil's nightly task mix restricted to conjunctive semantics.

params: {"categories": {"TermHigh": 1, "TermMed": 1, "TermLow": 1,
"AndHighHigh": 1, "AndHighMed": 1, "AndHighLow": 1}, "block": 60}
(weights). A category is a tuple of tiers. The stream is made of blocks
of `block` tasks; every block holds each category in its expected count
(largest remainders), in an order drawn from the seed, so that every
seed sends the same set of sizes in another order. Each slot draws one
list of its tier with replacement over the whole corpus (High covers
every window, so a pair with a High list always has answers); a pair of
one tier is written in list order, so that one word set is one string.
Repeats fall as they fall.
"""

import re

import numpy as np

from . import apportion

BLOCK = 60


def _tiers(category: str):
    parts = re.findall(r"High|Med|Low", category)
    if not parts:
        raise ValueError(f"category {category!r} names no tier")
    return [p.lower() for p in parts]


def _draw(pools, category, rng):
    tiers = _tiers(category)
    picked = []
    for t in tiers:
        pool = pools[t]
        while True:
            term = pool[int(rng.integers(len(pool)))]
            if term not in picked:
                break
        picked.append(term)
    # two lists of one tier are equally long, and the node takes a
    # conjunction's features from the FIRST of its shortest lists while
    # its event cache keys on the unordered word set: one order per set
    picked.sort(key=lambda t: (tiers.index(t.tier), t.index))
    return " ".join(t.name for t in picked)


def block_of(params):
    """`block` categories, each in its expected count."""
    cats = sorted(params["categories"])
    counts = apportion([float(params["categories"][c]) for c in cats],
                       int(params.get("block", BLOCK)))
    return [c for c, n in zip(cats, counts) for _ in range(n)]


def generate(lay, params, seed, n):
    pools = {t: lay.tier(t) for t in ("high", "med", "low")}
    block = block_of(params)
    rng = np.random.default_rng([int(seed), 31337])
    out = []
    while len(out) < n:
        out += [_draw(pools, block[int(i)], rng)
                for i in rng.permutation(len(block))]
    return out[:n]


def warm(lay, params, seed):
    pools = {t: lay.tier(t) for t in ("high", "med", "low")}
    block = block_of(params)
    out = []
    for c in sorted(set(block)):
        out.append(" ".join(pools[t][i].name
                            for i, t in enumerate(_tiers(c))))
    rng = np.random.default_rng([int(seed), 424243])
    out += [_draw(pools, block[int(i) % len(block)], rng)
            for i in rng.permutation(96)]
    return out
