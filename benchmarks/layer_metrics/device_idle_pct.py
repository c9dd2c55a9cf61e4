"""Share of the traced slices of the window in which no operation ran on
the device: 1 - union of device-operation intervals over their length."""


from ._shared import share_of


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_window_s"]:
        return None
    return share_of(ctx["trace_window_s"] - tr["busy_s"],
                    ctx["trace_window_s"], "device_idle_pct")
