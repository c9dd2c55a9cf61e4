"""Batcher timeouts in the window, the three cause buckets together."""


def read(ctx):
    c = ctx["counters"]
    if "batch_timeouts" not in c:
        return None
    causes = [k for k in c if k.startswith("batch_timeout_")]
    return sum(c[k] for k in causes) if causes else c["batch_timeouts"]
