"""The head of a query log: a fixed set of query strings under a zipfian
popularity, so that after the first pass the caches answer nearly all.

params: {"strings": 64, "and_share": 0.5, "s": 1.1}. The strings are
drawn once from the seed (Term queries over the three tiers in turn, And
pairs of a High list with a list of each tier in turn); every request
then samples one by benchmarks/generators/zipf.ZipfSampler.
"""

import numpy as np

from .zipf import ZipfSampler

TIERS = ("high", "med", "low")


def _strings(lay, params, seed):
    rng = np.random.default_rng([int(seed), 31337])
    pools = {t: lay.tier(t) for t in TIERS}
    n = int(params["strings"])
    n_and = int(round(n * float(params["and_share"])))
    out, i = [], -1
    while len(out) < n:
        i += 1
        pool = pools[TIERS[i % 3]]
        w = pool[int(rng.integers(len(pool)))].name
        if len(out) < n_and:
            hi = pools["high"]
            h = hi[int(rng.integers(len(hi)))].name
            while h == w:
                h = hi[int(rng.integers(len(hi)))].name
            # one order per word set (see generators/tasks.py)
            w = " ".join(sorted((h, w), key=lambda x: (x[1] != "h", x)))
        if w in out:
            continue
        out.append(w)
    order = rng.permutation(len(out))
    return [out[int(i)] for i in order]


def generate(lay, params, seed, n):
    z = ZipfSampler(_strings(lay, params, seed), s=float(params["s"]),
                    seed=int(seed) % (2 ** 31))
    return [z.sample() for _ in range(n)]


def warm(lay, params, seed):
    return _strings(lay, params, seed)
