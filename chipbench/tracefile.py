"""From a profiler trace to device busy time, program times and gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
events; ``reduce`` does the arithmetic on them, so it can be tested on a
small recorded trace.  Device planes are ``/device:TPU:<n>``: their
"XLA Modules" line holds one event per program execution (named
``jit_<function>(<id>)``), their "XLA Ops" line one per operation.  The
benchmark's own host spans (``jax.profiler.TraceAnnotation``) are on the
host plane, on the same clock.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # ns
    end: float          # ns


def load(path: str, host_spans: Iterable[str]) -> List[Event]:
    """Device modules and ops, and the named host spans, of one trace."""
    from jax.profiler import ProfileData
    host_spans = set(host_spans)
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                if host and ev.name not in host_spans:
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.end_ns)))
    return out


def layout(path: str) -> Dict[str, List[str]]:
    """Plane name -> its line names: what a trace holds, for an error
    message when ``load`` finds no device operations in it."""
    from jax.profiler import ProfileData
    return {plane.name: sorted({line.name for line in plane.lines})
            for plane in ProfileData.from_file(path).planes}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(ev: Event, lo: float, hi: float):
    a, b = max(ev.start, lo), min(ev.end, hi)
    return (a, b) if b > a else None


def program_pattern(function: str):
    """Matches the module events of ``jax.jit(<function>)``."""
    return re.compile(rf"^jit_{re.escape(function)}(?![A-Za-z0-9_])")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                                # mean over devices
    programs: Dict[str, Tuple[int, float]]       # name -> (calls, seconds)
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: Sequence[Event], lo: float, hi: float,
           programs: Dict[str, str], labels: Iterable[str],
           top: int = 10) -> Reduced:
    """Reduce the events of [lo, hi] (ns).  ``programs`` maps a metric's
    name for a program to its jitted function's name.  Busy time is the
    union of the operation intervals of each device, averaged over the
    devices; an idle gap is labelled by the host span among ``labels``
    that overlaps it most ("none" where none does).  Those spans follow
    one another and do not nest."""
    devices = sorted({e.plane for e in events if e.line == OPS})
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy_by_dev = {}
    for dev in devices:
        iv = [c for e in events if e.plane == dev and e.line == OPS
              for c in [_clip(e, lo, hi)] if c]
        busy_by_dev[dev] = _union(iv)
    busy = sum(sum(b - a for a, b in u) for u in busy_by_dev.values())
    busy_s = busy / len(devices) / 1e9

    progs = {}
    for label, fn in programs.items():
        pat = program_pattern(fn)
        evs = [e for e in events if e.line == MODULES and pat.match(e.name)
               and lo <= e.start and e.end <= hi]
        progs[label] = (len(evs), sum(e.end - e.start for e in evs) / 1e9)

    by_op: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        if e.line == OPS:
            c = _clip(e, lo, hi)
            if c:
                by_op[e.name] += (c[1] - c[0]) / 1e9 / len(devices)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    # gaps on the first device, labelled by the host span overlapping most
    labels = set(labels)
    host = sorted((e.start, e.end, e.name) for e in events
                  if not e.plane.startswith("/device:") and e.name in labels)
    gaps, t = [], lo
    for a, b in busy_by_dev[devices[0]] + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    label_total: Dict[str, List[float]] = collections.defaultdict(list)
    j = 0
    for a, b in gaps:
        while j < len(host) and host[j][1] <= a:
            j += 1
        best, over = "none", 0.0
        for s, e, name in host[j:]:
            if s >= b:
                break
            o = min(b, e) - max(a, s)
            if o > over:
                best, over = name, o
        label_total[best].append((b - a) / 1e9)
    idle = sorted(((f"{name}: {len(v)} gaps, longest {max(v):.6f} s",
                    sum(v)) for name, v in label_total.items()),
                  key=lambda kv: -kv[1])[:top]
    return Reduced((hi - lo) / 1e9, busy_s, progs, top_ops, idle)


def window(events: Sequence[Event], span: str) -> Tuple[float, float]:
    """[start, end] (ns) of the host span named ``span``."""
    found = [e for e in events if e.name == span
             and not e.plane.startswith("/device:")]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host spans named {span!r}")
    return found[0].start, found[0].end
