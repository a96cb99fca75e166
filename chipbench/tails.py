"""What a user of the served model sees, from the benchmark's host spans.

Every time is host clock (``time.perf_counter``) in seconds.  A request
is timed from its due time, whatever the engine was doing then.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (copied from ``repro.serving.metrics``)."""
    ss = sorted(xs)
    if not ss:
        return float("nan")
    i = min(len(ss) - 1, max(0, int(math.ceil(q / 100 * len(ss))) - 1))
    return ss[i]


@dataclasses.dataclass
class Served:
    """One request as the open loop saw it."""
    rid: int
    due: float                       # host clock
    prompt_len: int
    n_out: int
    issued: Optional[float] = None   # start of the step that prefilled it
    stamps: List[float] = dataclasses.field(default_factory=list)


def due_in(served: Sequence[Served], t_open: float, t_close: float):
    return [s for s in served if t_open <= s.due < t_close]


def ttft(served: Sequence[Served], t_open: float, t_close: float
         ) -> List[float]:
    """Seconds from due time to the first token on the host, for every
    request due in the window.  One with no first token by the close
    counts with its wait so far, t_close - due: a lower bound of its
    time to first token."""
    out = []
    for s in due_in(served, t_open, t_close):
        if s.stamps and s.stamps[0] <= t_close:
            out.append(s.stamps[0] - s.due)
        else:
            out.append(t_close - s.due)
    return out


def itl(served: Sequence[Served], t_open: float, t_close: float
        ) -> List[float]:
    """Every gap between consecutive host-visible tokens of a request,
    both tokens inside the window."""
    out = []
    for s in served:
        inside = [t for t in s.stamps if t_open <= t <= t_close]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def queue_wait(served: Sequence[Served], t_open: float, t_close: float
               ) -> List[float]:
    """Due time to the start of the step that issued the prefill; a
    request not yet issued at the close counts with its wait so far."""
    return [(s.issued if s.issued is not None and s.issued <= t_close
             else t_close) - s.due
            for s in due_in(served, t_open, t_close)]


def tokens_in(served: Sequence[Served], t_open: float, t_close: float
              ) -> int:
    return sum(1 for s in served for t in s.stamps if t_open <= t <= t_close)
