"""Natural-language questions: every query a conjunction of 2-6 terms.

params: {"lengths": {"2": .20, ...}, "tier_share": {"high": .1, "med": .4,
"low": .5}, "block": 400}. A question's class is its length and how many
of its term slots are High, Med and Low (each slot draws its tier by
`tier_share`). The stream is made of blocks of `block` questions; every
block holds each class in its expected count (largest remainders), in an
order drawn from the seed. So every seed sends the same set of sizes, in
another order, with other topics and terms: a run's work does not move
with its seed.

For one question a topic (Med window, Low sub-window) is drawn first,
then each slot draws one list of its tier that covers the topic, without
repeating a term; the words are written in a drawn order. Word sets are
made distinct within the run where the space allows (the node's event
cache keys on the unordered set): a repeat is redrawn up to 8 times (the
High/Med-only pairs have a space of some hundreds of strings and may
repeat, as short head questions do).
"""

import math

import numpy as np

from . import apportion

TIERS = ("high", "med", "low")
BLOCK = 400


def classes(room, params):
    """[((high, med, low) slot counts, probability)] over the feasible
    classes: a tier cannot fill more slots than `room` says it has lists
    that cover a topic."""
    share = [float(params["tier_share"][t]) for t in TIERS]
    out = []
    total = sum(float(v) for v in params["lengths"].values())
    for k, p_len in sorted((int(k), float(v) / total)
                           for k, v in params["lengths"].items()):
        for h in range(k + 1):
            for m in range(k + 1 - h):
                lo = k - h - m
                if h > room[0] or m > room[1] or lo > room[2]:
                    continue
                p = (math.factorial(k) / (math.factorial(h)
                                          * math.factorial(m)
                                          * math.factorial(lo))
                     * share[0] ** h * share[1] ** m * share[2] ** lo)
                if p > 0:
                    out.append(((h, m, lo), p_len * p))
    norm = sum(p for _c, p in out)
    return [(c, p / norm) for c, p in out]


def block_of(cls, size):
    """`size` classes, each in its expected count."""
    counts = apportion([p for _c, p in cls], size)
    return [c for (c, _p), n in zip(cls, counts) for _ in range(n)]


def _draw(pools_of, cls, rng):
    mw = int(rng.integers(pools_of.n_med_windows))
    sw = int(rng.integers(pools_of.n_sub_windows))
    words = []
    for tier, n in zip(TIERS, cls):
        pool = pools_of.covering(tier, mw, sw)
        for i in rng.choice(len(pool), n, replace=False):
            words.append(pool[int(i)].name)
    return " ".join(words[int(i)] for i in rng.permutation(len(words)))


def generate(lay, params, seed, n):
    rng = np.random.default_rng([int(seed), 31337])
    cache = _PoolCache(lay)
    size = int(params.get("block", BLOCK))
    block = block_of(classes(cache.room(), params), size)
    out, seen = [], set()
    while len(out) < n:
        for i in rng.permutation(size):
            for _try in range(8):
                q = _draw(cache, block[int(i)], rng)
                if frozenset(q.split()) not in seen:
                    break
            seen.add(frozenset(q.split()))
            out.append(q)
    return out[:n]


def warm(lay, params, seed):
    """One question of every class of the block from each Med window
    (every join shape the stream can reach), then a block of the stream
    under another seed."""
    cache = _PoolCache(lay)
    block = block_of(classes(cache.room(), params),
                     int(params.get("block", BLOCK)))
    out = []
    for mw in range(lay.n_med_windows):
        for cls in sorted(set(block)):
            if cls[2] and mw:
                continue        # a Low term: the host gate, no new shape
            words = []
            for tier, n in zip(TIERS, cls):
                words += [t.name for t in cache.covering(tier, mw, 0)[:n]]
            out.append(" ".join(reversed(words)))     # rarest first
    rng = np.random.default_rng([int(seed), 424243])
    out += [_draw(cache, block[int(i)], rng)
            for i in rng.permutation(len(block))[:96]]
    return out


class _PoolCache:
    """Layout with the per-topic pools memoised (a stream asks for the
    same few pools tens of thousands of times)."""

    def __init__(self, lay):
        self._lay = lay
        self._memo = {}
        self.n_med_windows = lay.n_med_windows
        self.n_sub_windows = lay.n_sub_windows

    def covering(self, tier, mw, sw):
        key = (tier, mw if tier != "high" else -1, sw if tier == "low" else -1)
        if key not in self._memo:
            self._memo[key] = self._lay.covering(tier, mw, sw)
        return self._memo[key]

    def room(self):
        """Per tier, the fewest lists that cover any one topic."""
        return [min(len(self.covering(t, mw, sw))
                    for mw in range(self.n_med_windows)
                    for sw in range(self.n_sub_windows)) for t in TIERS]
