"""The engine's per-span counters and the scheduler's host-clock stamps,
with no profiler running."""
import time

import jax

from repro.configs import get_config, reduced
from repro.models import init_params
from repro.serving import trace
from repro.serving.engine import ContinuousBatchingEngine
from repro.serving.scheduler import Scheduler, SeqState


def test_phases_count_every_tick_with_the_profiler_off():
    cfg = reduced(get_config("qwen2.5-3b"), d_model=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=64)
    for rid, n in enumerate([3, 5, 4]):
        eng.submit([1 + rid] * 8, n, req_id=rid)
    steps = 1                                  # the last, idle one
    while eng.step():
        steps += 1
        eng.flush()
    ph = eng.phases.summary()
    assert ph[trace.STEP]["count"] == ph[trace.SCHEDULE]["count"] == steps
    assert ph[trace.SUBMIT]["count"] == ph[trace.PREFILL]["count"] == 3
    assert ph[trace.DECODE]["count"] == ph[trace.PAGES]["count"] \
        == eng.stats["decode_ticks"]
    assert ph[trace.FLUSH]["count"] == ph[trace.FLUSH_WAIT]["count"] \
        == ph[trace.FLUSH_RESOLVE]["count"] == steps - 1
    children = sum(ph[n]["total_ms"] for n in (
        trace.SCHEDULE, trace.PAGES, trace.DECODE, trace.PREFILL))
    assert 0 < children <= ph[trace.STEP]["total_ms"]
    assert ph[trace.FLUSH_WAIT]["total_ms"] <= ph[trace.FLUSH]["total_ms"]
    for p in ph.values():
        assert 0 <= p["longest_ms"] <= p["total_ms"]


def test_stamps_keep_the_first_submission_and_mark_the_admission():
    t0 = time.perf_counter()
    sched = Scheduler(1, max_prefill_per_tick=1)
    a, b = SeqState(0, [5], 4), SeqState(1, [5], 4)
    sched.submit(a)
    sched.submit(b)
    assert t0 <= a.t_submit <= b.t_submit
    assert a.t_admit is None and b.t_admit is None
    tick = sched.next_tick()
    assert [s for _, s in tick.admit] == [a]
    assert a.t_admit >= b.t_submit and b.t_admit is None
    # b leaves un-prefilled in a handoff and is submitted elsewhere: its
    # first submission stays, as submit_tick does
    sched.drain()
    assert b in sched.handoff()
    first = b.t_submit
    other = Scheduler(1, max_prefill_per_tick=1)
    other.submit(b)
    assert b.t_submit == first
    assert [s for _, s in other.next_tick().admit] == [b]
    assert b.t_admit >= first
