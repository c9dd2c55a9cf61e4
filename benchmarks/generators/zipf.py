"""Seeded zipfian sampler over a fixed item list (copied from the
program's utils/gameday.ZipfSampler so that the yardstick does not move
with the program): the weight of the rank-i item is 1/(i+1)^s."""

import bisect
import random


class ZipfSampler:
    def __init__(self, items, s: float = 1.1, seed: int = 7):
        if not items:
            raise ValueError("zipf needs at least one item")
        self.items = list(items)
        self.s = float(s)
        self._rng = random.Random(seed)
        weights = [1.0 / (i + 1) ** self.s for i in range(len(self.items))]
        total = sum(weights)
        self._cdf, acc = [], 0.0
        for w in weights:
            acc += w / total
            self._cdf.append(acc)
        self._cdf[-1] = 1.0

    def sample(self):
        return self.items[bisect.bisect_left(self._cdf, self._rng.random())]
