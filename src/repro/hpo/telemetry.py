"""Spans of the served path, and the per-tick phase times they feed.

`span(name, phases, **meta)` brackets one phase of the gateway -> pool ->
engine path.  It opens a `jax.profiler.TraceAnnotation(name, **meta)`,
a host span on the profiler's `/host:CPU` plane on the device trace's
clock, and adds the phase's `time.perf_counter()` milliseconds to
`phases[name]`, the tally of the tick it belongs to (see `StudyGateway`'s
`stats` records).  The span and the counter are one measurement.

Spans are always on.  With no profiler session running, a span costs
about half a microsecond; the served path opens a fixed number per tick,
whatever its width (only re-anchors, evictions and restores get one each).
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class span:
    """Context manager: one host span, its wall time added to `phases`."""

    __slots__ = ("_name", "_phases", "_ann", "_t0")

    def __init__(self, name: str, phases: dict | None = None, **meta):
        self._name = name
        self._phases = phases
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        ms = 1e3 * (time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)
        if self._phases is not None:
            self._phases[self._name] = self._phases.get(self._name, 0.0) + ms
