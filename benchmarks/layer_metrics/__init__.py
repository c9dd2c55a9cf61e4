"""Per-layer metric readers, one module each, found by the metric's name
in BENCHMARK.json (`.` and `-` become `_`).

    read(ctx) -> number, or None where there is nothing to read

`ctx` is what benchmarks/run.py gathered in a --trace 1 run: counter
deltas over the window (`counters`) and over its traced slices
(`trace_counters`), the servlet histogram's bucket counts over the
window, the reduced device trace of the slices together (`trace`, None
without a device; `trace_window_s` their length), the requests (`rows`,
`trace_rows` those sent inside a slice: [query index, sent, done, status,
degraded, items, error]) with `queries` and `lengths(query index)`, the
chip's `peak`. A reader never returns 0 for a share of a roofline, and
never clips a share: one under 0 or over 100 raises (`_shared.share_of`,
`costs.share_pct`).
"""
