"""From a profiler trace to numbers: device busy time, idle gaps, kernel time.

``load`` reads the ``.xplane.pb`` files that ``jax.profiler.trace`` wrote
into a plain ``Trace``: each device's operation events (the ``XLA Ops`` line
of its ``/device:...`` plane) and the host spans the harness opened
(``TraceAnnotation`` names starting ``bench.``).  Every function below works
on that plain form, so it is checked on constructed traces without a chip.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, List, Tuple

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]  # device plane name -> operation events
    host: List[Event]  # the harness's own host spans
    # device plane name -> asynchronous operations, start to done
    async_ops: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)

    def window(self, span: str = HOST_PREFIX + "window") -> Tuple[float, float]:
        """(start, end) of the host span that bounds the traced steps."""
        spans = [e for e in self.host if e.name == span]
        if not spans:
            raise ValueError(f"no host span {span!r} in the trace")
        return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def load(trace_dir, device_prefix: str = "/device:TPU:") -> Trace:
    import jax

    devices: Dict[str, List[Event]] = {}
    async_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for path in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        data = jax.profiler.ProfileData.from_file(str(path))
        for plane in data.planes:
            on_device = plane.name.startswith(device_prefix)
            for line in plane.lines:
                if on_device and line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                into = (host if not on_device
                        else devices.setdefault(plane.name, []) if line.name == OPS_LINE
                        else async_ops.setdefault(plane.name, []))
                for e in line.events:
                    if not on_device and not e.name.startswith(HOST_PREFIX):
                        continue
                    into.append(Event(e.name, float(e.start_ns), float(e.duration_ns),
                                      tuple((k, v) for k, v in e.stats)))
    return Trace(devices, host, async_ops)


def merge(intervals) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(e - s for s, e in _clip(
        merge((ev.start_ns, ev.end_ns) for ev in events), lo, hi))


def idle_gaps(events: List[Event], lo: float, hi: float,
              host: List[Event] = ()) -> List[Tuple[str, float]]:
    """Gaps in [lo, hi] in which no operation ran, longest first, each named
    after the innermost harness span that was open at its middle."""
    busy = _clip(merge((ev.start_ns, ev.end_ns) for ev in events), lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_spans = [h for h in host if h.start_ns <= mid <= h.end_ns
                      and h.name != HOST_PREFIX + "window"]
        label = (min(open_spans, key=lambda h: h.dur_ns).name if open_spans
                 else "no harness span")
        out.append((label, (e - s) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def select(events: List[Event], pred: Callable[[Event], bool]) -> List[Event]:
    return [e for e in events if pred(e)]


def named(pattern: str) -> Callable[[Event], bool]:
    """Events whose name, or whose HLO text (``long_name``), matches."""
    rx = re.compile(pattern)
    return lambda e: bool(rx.search(e.name) or rx.search(str(e.stat("long_name", ""))))


def device_seconds(events: List[Event]) -> float:
    """Summed durations (not the union) of the events, in seconds."""
    return sum(e.dur_ns for e in events) * 1e-9


def innermost(events: List[Event]) -> List[Event]:
    """The events that hold no other event: a loop's or a call's operations
    are listed inside it on the same line, and would count twice."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    holds = [False] * len(order)
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= order[stack[-1]].end_ns:
            holds[stack[-1]] = True
        stack.append(i)
    return [e for e, h in zip(order, holds) if not h]


def short_name(name: str) -> str:
    """``%fusion.4 = f32[191466756]{...} fusion(...)`` -> ``%fusion.4 =
    f32[191466756]``: the operation and its result type."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    return f"{head} = {re.split(r'[{ ]', rest, maxsplit=1)[0]}"[:120]


def top_ops(events: List[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` innermost operations with the most device time, seconds."""
    total: Dict[str, float] = {}
    for e in innermost(events):
        key = short_name(e.name)
        total[key] = total.get(key, 0.0) + e.dur_ns * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
