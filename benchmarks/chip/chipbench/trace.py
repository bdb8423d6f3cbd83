"""From a profiler trace to metrics: busy time, idle share, device time
inside host spans and the breakdown.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain ``Event``
records; everything after that works on the records alone, so a small
recorded fixture checks the arithmetic (``tests/bench_chip``).

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane, named by their HLO text; an op that holds others
(a ``while`` over a scan's body) spans them, so busy time is a union and an
op's own time excludes the ops inside it. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events on the host plane. Both carry
nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
LAYOUT = re.compile(r"\{[^{}]*\}")
RESULT = re.compile(r"^([a-z0-9]+\[[0-9,]*\])")
# the benchmark's own host spans, innermost first when two overlap
SPANS = ("aggregate", "upload", "fetch")
WINDOW = "window"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def op_name(text: str) -> str:
    """``%fusion.5 = f32[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.5
    f32[8,128]``: the instruction's name and its result's shape."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    m = RESULT.match(LAYOUT.sub("", rest))
    return f"{name} {m.group(1)}" if m else name


def own_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, its own ns) for the events of one line: an event's own time
    leaves out the events inside it."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    inner: Dict[int, List[Interval]] = defaultdict(list)
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= evs[stack[-1]].end_ns:
            inner[stack[-1]].append((e.start_ns, e.end_ns))
        stack.append(i)
    return [(e, e.dur_ns - measure(inner.get(i, ()))) for i, e in enumerate(evs)]


def device_ids(events: Iterable[Event]) -> List[int]:
    ids = {int(m.group(1)) for e in events
           if (m := DEVICE_PLANE.match(e.plane))}
    return sorted(ids)


def device_ops(events: Iterable[Event], device: int) -> List[Event]:
    plane = f"/device:TPU:{device}"
    return [e for e in events if e.plane == plane and e.line == OPS_LINE]


def host_spans(events: Iterable[Event], name: str) -> List[Event]:
    return [e for e in events
            if not DEVICE_PLANE.match(e.plane) and e.name == name]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in merge(intervals))


def clip(intervals: Iterable[Interval], within: Sequence[Interval]) -> List[Interval]:
    """The parts of ``intervals`` that lie inside the union of ``within``."""
    spans = merge(within)
    out = []
    for lo, hi in merge(intervals):
        for s_lo, s_hi in spans:
            a, b = max(lo, s_lo), min(hi, s_hi)
            if b > a:
                out.append((a, b))
    return out


def subtract(intervals: Iterable[Interval], minus: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` with the union of ``minus`` taken out."""
    cut = merge(minus)
    out = []
    for lo, hi in merge(intervals):
        cur = lo
        for c_lo, c_hi in cut:
            if c_hi <= cur or c_lo >= hi:
                continue
            if c_lo > cur:
                out.append((cur, c_lo))
            cur = max(cur, c_hi)
        if cur < hi:
            out.append((cur, hi))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


@dataclasses.dataclass
class Trace:
    """A reduced trace: the events, and the window the benchmark measured."""

    events: List[Event]
    window: Interval  # ns, from the benchmark's ``window`` span

    @classmethod
    def from_events(cls, events: List[Event]) -> "Trace":
        windows = host_spans(events, WINDOW)
        if not windows:
            raise ValueError("the trace holds no 'window' span")
        lo = min(e.start_ns for e in windows)
        hi = max(e.end_ns for e in windows)
        return cls(events, (lo, hi))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def devices(self) -> List[int]:
        return device_ids(self.events)

    def ops(self, device: int) -> List[Interval]:
        return clip(spans_of(device_ops(self.events, device)), [self.window])

    def busy_s(self) -> Optional[float]:
        """Busy seconds in the window, averaged over the devices traced."""
        devs = self.devices
        if not devs:
            return None
        return sum(measure(self.ops(d)) for d in devs) / len(devs) / 1e9

    def idle_share(self, device: int = 0) -> Optional[float]:
        if device not in self.devices:
            return None
        busy = measure(self.ops(device))
        return 1.0 - busy / (self.window[1] - self.window[0])

    def span_count(self, name: str) -> int:
        return len(clip(spans_of(host_spans(self.events, name)), [self.window]))

    def device_time_in(self, name: str, device: int = 0) -> Optional[float]:
        """Seconds device ``device`` was busy inside host spans ``name``."""
        if device not in self.devices:
            return None
        spans = clip(spans_of(host_spans(self.events, name)), [self.window])
        return measure(clip(self.ops(device), spans)) / 1e9

    def breakdown(self, device: int = 0, top: int = 10) -> Dict[str, list]:
        """The device ops that took most time, and the longest idle gaps on
        ``device``, each gap named by the innermost benchmark span that
        covers its middle (``host`` where none does)."""
        lo, hi = self.window
        evs = [dataclasses.replace(e, start_ns=max(e.start_ns, lo),
                                   dur_ns=min(e.end_ns, hi) - max(e.start_ns, lo))
               for e in device_ops(self.events, device)
               if e.end_ns > lo and e.start_ns < hi]
        total: Dict[str, float] = defaultdict(float)
        for e, own in own_times(evs):
            total[op_name(e.name)] += own
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        gaps = subtract([self.window], spans_of(evs))
        spans = {n: spans_of(host_spans(self.events, n)) for n in SPANS}

        def name_of(lo: float, hi: float) -> str:
            mid = (lo + hi) / 2
            for n in SPANS:
                if any(a <= mid < b for a, b in spans[n]):
                    return n
            return "host"

        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[name_of(lo, hi), (hi - lo) / 1e9] for lo, hi in gaps],
        }
