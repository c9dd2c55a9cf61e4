"""Arithmetic the readers share."""


def device_query(ctx, qi) -> bool:
    """True where the shipped host gate lets the query reach the device:
    its shortest list is longer than the gate (4,096 rows)."""
    return min(ctx["lengths"](qi)) > ctx["host_gate_rows"]


def share_of(part, whole, what: str):
    """part / whole in percent; None where there is no whole. A share
    under 0 or over 100 is a fault of the count and raises: a clipped
    reading would hide a request counted twice."""
    if not whole:
        return None
    pct = 100.0 * part / whole
    if not 0.0 <= pct <= 100.0:
        raise ValueError(f"{what}: {part} of {whole} is no share")
    return pct


def program_seconds(ctx, needle: str) -> float:
    return sum(s for name, s in ctx["trace"]["by_program"].items()
               if needle in name)


def latency_ms(ctx, q: float, on_device=None):
    """Nearest-rank percentile, on the clients' clock, of the window's
    requests (of those the host gate kept, or let through, when
    `on_device` is False or True); None under 20 samples."""
    lat = sorted((r[2] - r[1]) * 1000.0 for r in ctx["rows"]
                 if on_device is None
                 or device_query(ctx, r[0]) == on_device)
    if len(lat) < 20:
        return None
    return lat[min(len(lat) - 1, int(len(lat) * q))]
