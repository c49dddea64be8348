"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the metrics read.

Device planes are named `/device:TPU:<i>`; their `XLA Ops` line holds one
event per executed HLO instruction, named by the instruction's text
(`%fused_ei_grad_pallas.8 = (f32[...]) custom-call(...)`).  A `while` or
`conditional` event spans the ops of its body, so busy time is the union of
the intervals and an op's self time leaves out the ops nested in it.  Host
spans (`jax.profiler.TraceAnnotation`) lie on the `/host:CPU` plane on the
same clock; the benchmark names its own `bench.*`.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
import warnings

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class Op:
    name: str          # instruction name as the trace prints it
    start: float       # ns
    end: float         # ns
    self_ns: float = 0.0


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]           # ns, the bench.window span
    devices: dict[str, list[Op]]          # plane name -> ops in the window
    spans: list[Span]                     # bench.* host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(_union_ns([(o.start, o.end) for o in ops])
                  for ops in self.devices.values())
        return tot * 1e-9 / len(self.devices)

    def op_seconds(self, pattern: str) -> float:
        """Self seconds of the ops whose name matches `pattern`, summed
        over the devices."""
        rx = re.compile(pattern)
        return sum(o.self_ns for ops in self.devices.values() for o in ops
                   if rx.search(o.name)) * 1e-9

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(1 for ops in self.devices.values() for o in ops
                   if rx.search(o.name))

    def top_ops(self, k: int = 10) -> list[list]:
        """The k ops with the most self time: [[name, seconds], ...]."""
        tot: dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                tot[o.name] = tot.get(o.name, 0.0) + o.self_ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The k longest idle gaps of the first device inside the window,
        each named by the host span that covers most of it:
        [["<span> @<offset s>", seconds], ...]."""
        if not self.devices:
            return []
        ops = next(iter(self.devices.values()))
        busy = _merge([(o.start, o.end) for o in ops])
        gaps, cur = [], self.window[0]
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if self.window[1] > cur:
            gaps.append((cur, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            out.append([f"{self._cover(a, b)} @{(a - self.window[0]) * 1e-9:.3f}s",
                        (b - a) * 1e-9])
        return out

    def _cover(self, a: float, b: float) -> str:
        best, best_ns = "host outside bench spans", 0.0
        for s in self.spans:
            if s.name == WINDOW_SPAN:
                continue
            ov = min(b, s.end) - max(a, s.start)
            if ov > best_ns:
                best, best_ns = s.name, ov
        return best


def _merge(iv):
    iv = sorted(iv)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union_ns(iv) -> float:
    return sum(b - a for a, b in _merge(iv))


def instr_name(event_name: str) -> str:
    """`%fused_ei_grad_pallas.8 = (...) custom-call(...)` ->
    `fused_ei_grad_pallas.8`."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _self_times(ops: list[Op]) -> None:
    """Self time = duration minus the time of the ops nested directly in it
    (a while loop's events span its body's)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def load(path) -> Trace:
    """Read one xplane file and clip the device ops to the window span."""
    import jax
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = jax.profiler.ProfileData.from_file(str(path))
        spans, window = [], None
        devices: dict[str, list[Op]] = {}
        raw: dict[str, list[Op]] = {}
        for plane in pd.planes:
            if plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            s = Span(e.name, e.start_ns, e.end_ns)
                            spans.append(s)
                            if e.name == WINDOW_SPAN:
                                window = (s.start, s.end)
            elif DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        raw[plane.name] = [
                            Op(instr_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span on {HOST_PLANE}")
    lo, hi = window
    for name, ops in raw.items():
        clipped = [Op(o.name, max(o.start, lo), min(o.end, hi))
                   for o in ops if o.end > lo and o.start < hi]
        _self_times(clipped)
        devices[name] = clipped
    return Trace(window, devices, spans)
