"""99th percentile, client clock, of the requests that the shipped host
gate answered: the node's tail is made here, not on the device."""

from ._shared import latency_ms


def read(ctx):
    return latency_ms(ctx, 0.99, on_device=False)
