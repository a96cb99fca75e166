"""Engine spans and model scopes in a profiler trace.

The program writes its own host spans (``repro.serving.trace``: the
engine tick and its phases) on the profiler's host plane, beside the
harness's spans and on the clock of the device lines.  The model step's
named scopes (``repro.models.scopes``) are in the compiled program's op
metadata (``op_name="jit(step)/.../mlp/dot_general"``); a TPU trace
names each "XLA Ops" event by its HLO instruction and carries no op
name, so ``HloScopes`` reads instruction -> scope from the compiled
programs' text and ``load`` looks each op up there.  ``reduce`` puts
leaf device time down to scopes and idle time down to the innermost
span the host was in.  The arithmetic works on plain events, so it is
tested on a recorded list.

Against a program without spans or scopes every reduction still runs:
scope time then all reads ``(unscoped)`` and the engine spans are absent.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.tracefile import (MODULES, OPS, Event, _clip, _union,
                                 program_pattern)

try:
    from repro.models import scopes as _scopes
    from repro.serving.trace import (FLUSH, FLUSH_WAIT, NAMES as ENGINE_SPANS,
                                     STEP)
    SCOPES = _scopes.ALL
    # the parameter group whose weights each scope's matmuls read
    WEIGHT_SCOPES = {"ffn": _scopes.MLP, "attn": _scopes.ATTN_PROJ}
except ImportError:             # a program from before the spans
    SCOPES, ENGINE_SPANS, WEIGHT_SCOPES = (), (), {}
    STEP, FLUSH, FLUSH_WAIT = "engine.step", "engine.flush", \
        "engine.flush.wait"

UNSCOPED = "(unscoped)"
NONE = "none"


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation, with the scope of its instruction."""
    plane: str
    name: str
    start: float        # ns
    end: float          # ns
    scope: str = UNSCOPED


@dataclasses.dataclass
class Trace:
    events: List[Event]     # as ``tracefile.load`` reads them
    ops: List[Op]           # the "XLA Ops" events of ``events``, scoped
    attrs: Dict[Event, dict]  # stats of the host spans that carry any


def scope_of(text: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The deepest of ``scopes`` named as a component of an op-name path
    ("jit(step)/while/body/mlp/dot_general" -> "mlp") in ``text``."""
    found = None
    for part in text.replace('"', "/").replace(" ", "/").split("/"):
        if part in scopes:
            found = part
    return found


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = (\S+)')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# XLA's own converts of float32 weights to bfloat16 carry no op name, and
# are hoisted out of the layer scan; the weight they read names the
# scope whose matmul uses them (entry parameters are named by their
# path in the parameter tree: params["trunk"][0]["ffn"]["w_in"] ->
# %params__trunk___0___ffn____w_in__)
_WEIGHT_CONVERT = re.compile(r' convert\(%params__(\w+)')


def _weight_scope(line: str) -> Optional[str]:
    m = _WEIGHT_CONVERT.search(line)
    if m is None:
        return None
    return next((WEIGHT_SCOPES[p] for p in m.group(1).split("_")
                 if p in WEIGHT_SCOPES), None)


def _instr(text: str) -> Optional[Tuple[str, str]]:
    """(instruction name, first token of its shape) of an HLO line, or
    of a TPU op event's name, which is that line without its metadata."""
    m = _INSTR.match(text)
    return (m.group(1), m.group(2)) if m else None


class HloScopes:
    """Instruction -> scope, read from compiled programs' HLO text.
    Instruction names repeat across programs (one per decode bucket and
    prompt length); a name read with two scopes is told apart by its
    shape, and left unscoped where the shape does not part them."""

    def __init__(self, texts: Iterable[str] = ()):
        self._by_name: Dict[str, set] = collections.defaultdict(set)
        self._by_key: Dict[Tuple[str, str], set] = \
            collections.defaultdict(set)
        self._memo: Dict[str, str] = {}
        for text in texts:
            for line in text.splitlines():
                key = _instr(line)
                if key is None:
                    continue
                m = _OP_NAME.search(line)
                scope = (scope_of(m.group(1)) if m
                         else _weight_scope(line)) or UNSCOPED
                self._by_name[key[0]].add(scope)
                self._by_key[key].add(scope)

    def scope(self, op_text: str) -> str:
        got = self._memo.get(op_text)
        if got is None:
            got = self._memo[op_text] = self._lookup(op_text)
        return got

    def _lookup(self, op_text: str) -> str:
        key = _instr(op_text)
        if key is None:
            return UNSCOPED
        for found in (self._by_name.get(key[0]), self._by_key.get(key)):
            if found is not None and len(found) == 1:
                return next(iter(found))
        return UNSCOPED


def load(path: str, host_spans: Iterable[str],
         hlo: Optional[HloScopes] = None) -> Trace:
    """Device modules and ops, and the named host spans, of one trace;
    the same events as ``tracefile.load`` with each op's scope (from
    ``hlo``) and each host span's stats beside them."""
    hlo = hlo if hlo is not None else HloScopes()
    from jax.profiler import ProfileData
    host_spans = set(host_spans)
    events, ops, attrs = [], [], {}
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
                e = Event(plane.name, line.name, ev.name,
                          float(ev.start_ns), float(ev.end_ns))
                events.append(e)
                if line.name == OPS:
                    ops.append(Op(e.plane, e.name, e.start, e.end,
                                  hlo.scope(e.name)))
                elif host:
                    st = dict(ev.stats)
                    if st:
                        attrs[e] = st
    return Trace(events, ops, attrs)


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The ops that enclose no other op of their device: a ``while`` or
    other container event spans its body's ops, which are counted
    instead."""
    out = []
    for plane in sorted({o.plane for o in ops}):
        seq = sorted((o for o in ops if o.plane == plane),
                     key=lambda o: (o.start, -o.end))
        for i, o in enumerate(seq):
            nxt = seq[i + 1] if i + 1 < len(seq) else None
            if nxt is None or nxt.start >= o.end or nxt.end > o.end:
                out.append(o)
    return out


def _in_modules(ops: List[Op], modules: List[Event]) -> List[Op]:
    """The ops of one device that lie inside one of ``modules``."""
    mods = sorted((m.start, m.end) for m in modules)
    out, j = [], 0
    for o in sorted(ops, key=lambda o: o.start):
        while j < len(mods) and mods[j][1] < o.start:
            j += 1
        if j < len(mods) and mods[j][0] <= o.start and o.end <= mods[j][1]:
            out.append(o)
    return out


def _innermost(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """(start, end, name) pieces of host time, each named by the
    innermost span covering it: the latest-started of those open, the
    shorter of two that start together, and the engine's span where it
    and the harness's span around it start and end together."""
    spans = sorted(spans, key=lambda e: (e.start, -e.end))
    points = sorted({t for e in spans for t in (e.start, e.end)})
    out, stack, i = [], [], 0
    for a, b in zip(points, points[1:]):
        stack = [e for e in stack if e.end > a]
        while i < len(spans) and spans[i].start <= a:
            if spans[i].end > a:
                stack.append(spans[i])
            i += 1
        if stack:
            name = max(stack, key=lambda e: (
                e.start, -e.end, e.name.startswith("engine."))).name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def _attribute(gaps: List[Tuple[float, float]],
               pieces: List[Tuple[float, float, str]]
               ) -> Dict[str, List[float]]:
    """Seconds of each gap by the span covering it ("none" where no
    span does): name -> the seconds it got from each gap it touched."""
    by: Dict[str, List[float]] = collections.defaultdict(list)
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        got: Dict[str, float] = collections.defaultdict(float)
        covered = 0.0
        for s, e, name in pieces[j:]:
            if s >= b:
                break
            o = min(b, e) - max(a, s)
            if o > 0:
                got[name] += o
                covered += o
        if (b - a) - covered > 0:
            got[NONE] += (b - a) - covered
        for name, ns in got.items():
            by[name].append(ns / 1e9)
    return by


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    return out


def _summary(by: Dict[str, List[float]]) -> List[Tuple[str, float]]:
    return sorted(((f"{name}: {len(v)} gaps, longest {max(v):.6f} s",
                    sum(v)) for name, v in by.items()),
                  key=lambda kv: -kv[1])


@dataclasses.dataclass
class Reduced:
    window_s: float
    # program label -> scope -> leaf device seconds (mean over devices)
    device_scopes: Dict[str, Dict[str, float]]
    # program label -> calls in the window
    calls: Dict[str, int]
    # every idle gap of the first device, by the innermost host span
    idle_gaps_engine: List[Tuple[str, float]]
    # seconds in which no program was in flight on the first device,
    # and the same seconds by the innermost host span
    between_s: float
    between_by_span: Dict[str, float]
    # engine span name -> (count, seconds) inside the window
    spans: Dict[str, Tuple[int, float]]

    @property
    def between_share(self) -> float:
        return self.between_s / self.window_s

    def host_tick_s(self) -> Optional[float]:
        """Host seconds per engine tick that are not spent waiting on
        the device: (step + flush - flush.wait) over the ticks."""
        ticks = self.spans.get(STEP, (0, 0.0))[0]
        if not ticks:
            return None
        s = lambda n: self.spans.get(n, (0, 0.0))[1]     # noqa: E731
        return (s(STEP) + s(FLUSH) - s(FLUSH_WAIT)) / ticks

    def scope_s_per_call(self, label: str, scope: str) -> Optional[float]:
        calls = self.calls.get(label, 0)
        got = self.device_scopes.get(label, {})
        if not calls or scope not in got:
            return None
        return got[scope] / calls


def reduce(tr: Trace, lo: float, hi: float, programs: Dict[str, str],
           labels: Iterable[str]) -> Reduced:
    """Reduce the events of [lo, hi] (ns).  ``programs`` maps a label to
    a jitted function's name, as for ``tracefile.reduce``; ``labels``
    are the host spans (harness and engine) that may name idle time."""
    events = tr.events
    devices = sorted({o.plane for o in tr.ops})
    if not devices:
        raise ValueError("the trace holds no device operations")
    leaf = leaves([o for o in tr.ops if _clip(o, lo, hi)])

    scoped: Dict[str, Dict[str, float]] = {}
    calls: Dict[str, int] = {}
    for label, fn in programs.items():
        pat = program_pattern(fn)
        mods = [e for e in events if e.line == MODULES and pat.match(e.name)
                and lo <= e.start and e.end <= hi]
        calls[label] = len({(m.start, m.end) for m in mods
                            if m.plane == devices[0]})
        got: Dict[str, float] = collections.defaultdict(float)
        for dev in devices:
            for o in _in_modules([o for o in leaf if o.plane == dev],
                                 [m for m in mods if m.plane == dev]):
                got[o.scope] += (o.end - o.start) / 1e9 / len(devices)
        scoped[label] = dict(got)

    dev0 = devices[0]
    busy = _union([c for o in tr.ops if o.plane == dev0
                   for c in [_clip(o, lo, hi)] if c])
    inflight = _union(busy + [c for e in events
                              if e.plane == dev0 and e.line == MODULES
                              for c in [_clip(e, lo, hi)] if c])
    labels = set(labels)
    host = [e for e in events if not e.plane.startswith("/device:")
            and e.name in labels and _clip(e, lo, hi)]
    pieces = _innermost(host)
    idle = _attribute(_gaps(busy, lo, hi), pieces)
    between = _attribute(_gaps(inflight, lo, hi), pieces)

    spans: Dict[str, Tuple[int, float]] = {}
    for e in host:
        if e.name in ENGINE_SPANS or e.name.startswith("engine."):
            n, s = spans.get(e.name, (0, 0.0))
            spans[e.name] = (n + 1, s + (e.end - e.start) / 1e9)
    return Reduced(
        window_s=(hi - lo) / 1e9, device_scopes=scoped, calls=calls,
        idle_gaps_engine=_summary(idle),
        between_s=sum(sum(v) for v in between.values()),
        between_by_span={k: sum(v) for k, v in between.items()},
        spans=spans)
