"""Query-stream generators, one module each, found by name.

A generator module has two functions over a corpus layout
(benchmarks/corpus.py) and the `params` of a workload file:

    generate(layout, params, seed, n) -> list of n query strings
    warm(layout, params, seed)        -> query strings that together
                                         touch every kernel shape the
                                         stream can reach

A new traffic mix that an existing generator can express is a new
workload file and nothing else.
"""

import importlib


def apportion(weights, size: int) -> list:
    """How many of `size` places each weight gets: its expected count,
    the places left over to the largest remainders (ties by position, so
    the counts are the same for every seed)."""
    total = float(sum(weights))
    want = [w * size / total for w in weights]
    counts = [int(w) for w in want]
    rest = sorted(range(len(want)), key=lambda i: (-(want[i] - counts[i]), i))
    for i in rest[:size - sum(counts)]:
        counts[i] += 1
    return counts


def load(name: str):
    return importlib.import_module(f"benchmarks.generators.{name}")
