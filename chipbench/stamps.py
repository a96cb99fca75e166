"""The scheduler's host-clock stamps of each request, against its due time.

``SeqState.t_submit`` (first submission) and ``t_admit`` (admission into
a slot) are ``time.perf_counter`` stamps, the clock of the harness's due
times.  A program without them reads nothing here.
"""
from __future__ import annotations

from typing import List, Optional

from chipbench import tails


def _due(win):
    """(served, seq or None) of every request due in the window."""
    return [(s, win.seqs.get(s.rid))
            for s in tails.due_in(win.served, win.t_open, win.t_close)]


def _stamped(win) -> bool:
    return any(getattr(q, "t_submit", None) is not None
               for q in win.seqs.values())


def submit_lag(win) -> Optional[List[float]]:
    """Seconds from due time to submission: how late the open loop ran.
    A request not submitted by the close counts with t_close - due."""
    if not _stamped(win):
        return None
    return [(q.t_submit if q is not None and q.t_submit is not None
             else win.t_close) - s.due for s, q in _due(win)]


def admit_wait(win) -> Optional[List[float]]:
    """Seconds from submission to admission into a slot, over requests
    due in the window and submitted; one not admitted by the close
    counts with t_close - t_submit."""
    if not _stamped(win):
        return None
    out = []
    for _, q in _due(win):
        if q is None or q.t_submit is None:
            continue
        t = q.t_admit if q.t_admit is not None and q.t_admit <= win.t_close \
            else win.t_close
        out.append(t - q.t_submit)
    return out


def submit_lag_p95_ms(ctx):
    xs = submit_lag(ctx.window)
    return 1e3 * tails.percentile(xs, 95) if xs else None


def admit_wait_p95_ms(ctx):
    xs = admit_wait(ctx.window)
    return 1e3 * tails.percentile(xs, 95) if xs else None
