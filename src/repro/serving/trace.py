"""Host spans of the serving path, on the profiler's clock.

``span(phases, name, **attrs)`` is the one mechanism: it opens a
``jax.profiler.TraceAnnotation`` (a TraceMe on the profiler's host plane,
on the same clock as the device's "XLA Ops" and "XLA Modules" lines,
with ``attrs`` as the event's stats) and adds the span's host-clock
duration to ``phases``.  With no profiler session running a TraceMe
records nothing; the per-phase counters are kept either way, so a run
that is not traced still says which phase a stall sat in.

Span names (each engine owns one ``Phases``):

    engine.step             one tick that ran (attrs: tick, decoded, admitted)
      engine.schedule       preemption, ``next_tick``, retire, restore
      engine.pages          page allocation for the decoded slots, table operand
      engine.decode         dispatch of the decode step (and its table upload)
      engine.prefill        one admitted request (attr: req_id)
    engine.flush            resolving placeholder tokens
      engine.flush.wait     the blocking ``device_get``
      engine.flush.resolve  writing the values into the sequences
    engine.submit           one request into the queue (attr: req_id)
"""
from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation

_clock = time.perf_counter

STEP = "engine.step"
SCHEDULE = "engine.schedule"
PAGES = "engine.pages"
DECODE = "engine.decode"
PREFILL = "engine.prefill"
FLUSH = "engine.flush"
FLUSH_WAIT = "engine.flush.wait"
FLUSH_RESOLVE = "engine.flush.resolve"
SUBMIT = "engine.submit"
NAMES = (STEP, SCHEDULE, PAGES, DECODE, PREFILL, FLUSH, FLUSH_WAIT,
         FLUSH_RESOLVE, SUBMIT)


class Phases:
    """Count, total seconds and longest span of each span name."""

    __slots__ = ("_by",)

    def __init__(self):
        self._by: Dict[str, list] = {}       # name -> [count, total, longest]

    def add(self, name: str, seconds: float) -> None:
        c = self._by.get(name)
        if c is None:
            c = self._by[name] = [0, 0.0, 0.0]
        c[0] += 1
        c[1] += seconds
        if seconds > c[2]:
            c[2] = seconds

    def summary(self) -> Dict[str, Dict[str, float]]:
        """name -> {"count", "total_ms", "longest_ms"}."""
        return {n: {"count": c, "total_ms": 1e3 * t, "longest_ms": 1e3 * m}
                for n, (c, t, m) in self._by.items()}


class span:
    """``with span(phases, name, **attrs):`` — see the module docstring."""

    __slots__ = ("_phases", "_name", "_trace", "_t0")

    def __init__(self, phases: Phases, name: str, **attrs):
        self._phases = phases
        self._name = name
        self._trace = TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._trace.__enter__()
        self._t0 = _clock()
        return self

    def set(self, **attrs) -> None:
        """Attach stats known only inside the span (a traced run only)."""
        if TraceAnnotation.is_enabled():
            self._trace.set_metadata(**attrs)

    def __exit__(self, *exc):
        dt = _clock() - self._t0
        self._trace.__exit__(*exc)
        self._phases.add(self._name, dt)
        return False
