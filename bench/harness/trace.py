"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds, per chip, a plane ``/device:TPU:<n>`` whose
line ``XLA Modules`` has one event per program execution (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per operation (named by its HLO text, ``%<op name> = ...``). A
Pallas kernel's operation is named after the jitted function that calls
it (``%paged_micro_attention.5 = ... custom-call(...)``). Host spans
that the harness opens with ``TraceAnnotation`` (``bench.*``) are on the
host plane's thread lines, on the same clock.

Busy time is the union of the program executions' intervals; an idle
gap is a stretch of the traced window with no program running, named
after the innermost ``bench.*`` span open at its middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_NAME = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
MODULE_NAME = re.compile(r"^jit_(.*?)(?:\(\d+\))?$")
WINDOW_SPAN = "bench.window"
CONTAINERS = {"while", "conditional", "call"}   # ops that hold other ops


def op_base(event_name: str) -> str:
    """``%paged_micro_attention.5 = (...) custom-call(...)`` ->
    ``paged_micro_attention``; other names pass through."""
    m = OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def module_base(event_name: str) -> str:
    """``jit__decode_step_paged_jit(1347...)`` -> ``_decode_step_paged_jit``."""
    m = MODULE_NAME.match(event_name)
    return m.group(1) if m else event_name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """Intervals cut to [lo, hi]."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    """Summed length."""
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclass
class DeviceTrace:
    """One chip's program executions and operations."""
    modules: Dict[str, List[Interval]] = field(
        default_factory=lambda: defaultdict(list))
    ops: Dict[str, List[Interval]] = field(
        default_factory=lambda: defaultdict(list))


@dataclass
class Trace:
    """The reduced trace: chips, host spans and the traced window."""
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]   # (name, start_ns, end_ns)
    window: Interval

    # --- readings ------------------------------------------------------ #
    def busy(self, chip: int, within: Optional[Sequence[Interval]] = None
             ) -> List[Interval]:
        """Merged intervals in which chip ``chip`` ran a program, cut to
        the window (and to ``within`` when given)."""
        allm = [iv for ivs in self.devices[chip].modules.values()
                for iv in ivs]
        b = clip(union(allm), *self.window)
        return intersect(b, union(within)) if within is not None else b

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(total(self.busy(c)) for c in range(len(self.devices))
                   ) / max(1, len(self.devices)) / 1e9

    @property
    def window_s(self) -> float:
        """Length of the traced window in seconds."""
        return (self.window[1] - self.window[0]) / 1e9

    def module_times(self, name: str) -> List[float]:
        """Durations (s) of every execution of one program, all chips,
        that started inside the window."""
        lo, hi = self.window
        return [(e - s) / 1e9 for d in self.devices
                for s, e in d.modules.get(name, ()) if lo <= s < hi]

    def op_time(self, name: str) -> Tuple[float, int]:
        """(seconds, count) of one operation's executions in the window."""
        lo, hi = self.window
        ivs = [(s, e) for d in self.devices for s, e in d.ops.get(name, ())
               if lo <= s < hi]
        return total(ivs) / 1e9, len(ivs)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations that took most device time, as
        [name, seconds]; loops and calls, which hold other operations,
        are left out."""
        lo, hi = self.window
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, ivs in d.ops.items():
                if name in CONTAINERS:
                    continue
                tot[name] += sum(e - s for s, e in ivs if lo <= s < hi)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in best if v > 0]

    def span_at(self, t: float) -> str:
        """Innermost ``bench.*`` span open at time ``t`` (or "none")."""
        best, width = "none", float("inf")
        for name, s, e in self.spans:
            if s <= t < e and e - s < width and name != WINDOW_SPAN:
                best, width = name, e - s
        return best

    def gaps(self, chip: int = 0) -> List[Interval]:
        """Idle stretches of a chip in the window, longest first."""
        busy = self.busy(chip)
        edges = [self.window[0]] + [x for iv in busy for x in iv] + \
            [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def idle_gaps(self, n: int = 10, chip: int = 0) -> List[List]:
        """The ``n`` longest idle gaps of a chip in the window, each as
        [host span open in it, seconds]."""
        return [[self.span_at((s + e) / 2), (e - s) / 1e9]
                for s, e in self.gaps(chip)[:n]]


def _host_spans(planes) -> List[Tuple[str, float, float]]:
    spans = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce_xplane(path: str) -> Trace:
    """Read a trace file into a ``Trace``; its window is the
    ``bench.window`` span (or the span of the device events)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = []
    planes = list(pd.planes)
    for pl in planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        dev = DeviceTrace()
        for ln in pl.lines:
            if ln.name == "XLA Modules":
                for ev in ln.events:
                    dev.modules[module_base(ev.name)].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif ln.name == "XLA Ops":
                for ev in ln.events:
                    dev.ops[op_base(ev.name)].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
        devices.append(dev)
    spans = _host_spans(planes)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        window = win[0]
    else:
        allm = [iv for d in devices for ivs in d.modules.values()
                for iv in ivs]
        window = (min(s for s, _ in allm), max(e for _, e in allm))
    spans.sort(key=lambda s: s[1])
    return Trace(devices=devices, spans=spans, window=window)


def to_trace_clock(t_mono: float, anchor_mono: float, anchor_ns: float
                   ) -> float:
    """Map a ``time.monotonic()`` reading to the trace's clock, given
    one moment known on both (the window span's start)."""
    return anchor_ns + (t_mono - anchor_mono) * 1e9

