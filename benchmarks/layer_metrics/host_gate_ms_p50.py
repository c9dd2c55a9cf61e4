"""Median latency, client clock, of the requests that the shipped host
gate answered in NumPy (shortest list <= 4,096 rows)."""

from ._shared import latency_ms


def read(ctx):
    return latency_ms(ctx, 0.50, on_device=False)
