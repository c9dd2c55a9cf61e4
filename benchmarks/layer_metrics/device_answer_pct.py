"""Share of the window's requests whose answer the device store gave
(`queries_served`: kernels and its top-k cache); the rest took the
shipped host gate (shortest list <= 4,096 rows), the event cache or a
fallback."""


from ._shared import share_of


def read(ctx):
    return share_of(ctx["counters"].get("queries_served", 0),
                    ctx["attempted"], "device_answer_pct")
