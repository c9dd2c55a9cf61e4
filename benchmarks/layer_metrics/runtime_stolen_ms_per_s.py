"""Milliseconds per second that the runtime's own housekeeping held the
interpreter: the collector (`runtime.gc`), the stack sampler's ticks
(`runtime.sampler_tick`) and the health thread's evaluations
(`runtime.health_tick`), windowed sums over the seconds their windows
cover. Those run from the window's start to this reading: past the
close too, unlike the request families (`_spans`)."""

from ._spans import spent_ms_per_s


def read(ctx):
    return spent_ms_per_s(("runtime.gc", "runtime.sampler_tick",
                           "runtime.health_tick"))
