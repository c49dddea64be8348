"""Per-layer metric readers, one module per metric named in BENCHMARK.json.

Each module defines `read(ctx) -> float | None`; `ctx` carries the window's
gateway ticks (`ctx.ticks`), the slot count (`ctx.slots`), the reduced
trace (`ctx.trace`), the work the traced run dispatched (`ctx.work`) and
the device's peaks (`ctx.peaks`).  A reader that finds nothing to read
returns None and the metric is left out of the result.
"""
