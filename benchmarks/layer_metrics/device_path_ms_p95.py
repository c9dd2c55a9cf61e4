"""95th percentile, client clock, of the requests that passed the host
gate (caches, batcher and kernels answered them)."""

from ._shared import latency_ms


def read(ctx):
    return latency_ms(ctx, 0.95, on_device=True)
