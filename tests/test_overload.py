"""Overload survival: page quotas, shedding, and page-granular
preemption over the PackedKV wire.

The acceptance bar mirrors the other scheduling layers: overload
control REORDERS and REJECTS, it never changes what an admitted request
computes — greedy tokens stay bit-equal with an uninterrupted run
across preempt → park (host tier) → resume, no sequence is ever both
shed and completed, and every allocator drains back to all-free.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.models import PageTable, init_params
from repro.serving.autoscaler import (Autoscaler, AutoscalerConfig,
                                      LoadSignals, ScaleUp)
from repro.serving.cluster import LiveCluster
from repro.serving.engine import ContinuousBatchingEngine, InferenceEngine
from repro.serving.metrics import MetricsLog
from repro.serving.scheduler import (AdmissionPolicy, PageQuota, Scheduler,
                                     SeqState, SlotState,
                                     StrictPriorityPolicy, SubmitResult)
from repro.serving.workload import BATCH, INTERACTIVE, SLOClass, STANDARD

MAX_LEN = 48
PAGE_SIZE = 16
_CTX = {}


def _ctx():
    if not _CTX:
        cfg = reduced(get_config("qwen2.5-3b"), d_model=64)
        _CTX["cfg"] = cfg
        _CTX["params"] = init_params(cfg, jax.random.PRNGKey(0))
        _CTX["ref"] = InferenceEngine(cfg, _CTX["params"], max_len=MAX_LEN)
    return _CTX["cfg"], _CTX["params"], _CTX["ref"]


def _toks(seed, length):
    cfg, _, _ = _ctx()
    return list(map(int, jax.random.randint(
        jax.random.PRNGKey(seed), (length,), 0, cfg.vocab_size)))


def _reference(prompt, n_tok):
    _, _, ref = _ctx()
    toks = ref.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                        n_tok, cache_len=MAX_LEN)
    return list(map(int, toks[0]))


def _engine(**kw):
    cfg, params, _ = _ctx()
    kw.setdefault("n_slots", 2)
    kw.setdefault("page_size", PAGE_SIZE)
    return ContinuousBatchingEngine(cfg, params, max_len=MAX_LEN, **kw)


def _assert_drained(eng):
    eng.flush()
    eng.pages.check_invariants()
    assert eng.pages.n_slot_owned == 0
    assert eng.pages.n_reserved == 0
    assert eng._dedupe == {}
    if eng.pages.prefix is not None:
        eng.pages.prefix.clear(eng.pages)
    assert eng.pages.n_allocated == 0


def _drain(eng, budget=600):
    for _ in range(budget):
        if not eng.step():
            break
    eng.flush()


# ------------------------------------------------------------ page quotas
def test_page_quota_floor_and_ceiling_math():
    q = PageQuota(reserved_frac=0.25, ceiling_frac=0.6)
    assert q.floor_pages(16) == 4
    assert q.ceiling_pages(16) == 9          # int(0.6*16) = 9
    assert PageQuota().floor_pages(16) == 0
    assert PageQuota().ceiling_pages(16) == 16


def test_quota_blocked_rules():
    pol = AdmissionPolicy(quotas={"interactive": PageQuota(reserved_frac=0.25),
                                  "batch": PageQuota(ceiling_frac=0.5)})
    total = 16
    # batch over its burstable ceiling (8 pages of 16) is vetoed
    assert pol.quota_blocked("batch", 4, {"batch": 6}, total, headroom=10)
    assert not pol.quota_blocked("batch", 2, {"batch": 6}, total,
                                 headroom=10)
    # any class admitting into interactive's unfilled 4-page floor is
    # vetoed once headroom - need dips below the owed floor
    assert pol.quota_blocked("batch", 2, {}, total, headroom=5)
    assert not pol.quota_blocked("batch", 1, {}, total, headroom=5)
    # the floor's own class is never blocked by its own reservation
    assert not pol.quota_blocked("interactive", 4, {}, total, headroom=4)
    # no quotas configured: nothing is ever blocked
    assert not AdmissionPolicy().quota_blocked("batch", 99, {}, total, 0)


def test_quota_keeps_interactive_floor_free_pure_scheduler():
    """Batch flood against a 0.5 ceiling + interactive 0.25 floor: batch
    never charges past its ceiling, and a late interactive arrival
    admits immediately because its floor pages were never given away."""
    pt = PageTable(n_pages=16, page_size=4, n_slots=4, max_pages=4)
    pol = StrictPriorityPolicy(
        quotas={"interactive": PageQuota(reserved_frac=0.25),
                "batch": PageQuota(ceiling_frac=0.5)})
    sched = Scheduler(4, pages=pt, policy=pol)
    # each batch request reserves 4 pages worst-case (6 + 8 tokens)
    for rid in range(4):
        assert not sched.submit(
            SeqState(rid, [1] * 6, 8, slo=BATCH)).shed
    ceiling = pol.quotas["batch"].ceiling_pages(pt.n_pages)
    interactive_admitted = None
    for t in range(200):
        if t == 10:
            sched.submit(SeqState(99, [1] * 6, 8, slo=INTERACTIVE))
        tick = sched.next_tick()
        for slot, seq in tick.admit:
            if seq.req_id == 99 and interactive_admitted is None:
                interactive_admitted = t
            sched.on_prefilled(slot, 1)
        for slot in tick.decode:
            sched.on_decoded(slot, 1)
        assert sched._class_pages.get("batch", 0) <= ceiling, \
            "batch charged past its burstable ceiling"
        if tick.idle:
            break
    # 2 of 4 slots stay quota-limited for batch, yet interactive walks in
    assert interactive_admitted is not None and interactive_admitted <= 12
    assert len(sched.finished) == 5
    assert sched._class_pages == {} or \
        all(v == 0 for v in sched._class_pages.values())
    pt.check_invariants()


# ---------------------------------------------------------------- shedding
def test_submit_sheds_with_retry_hint():
    sched = Scheduler(1, shed_limit=2)
    assert not sched.submit(SeqState(0, [1, 2], 2, slo=BATCH)).shed
    assert not sched.submit(SeqState(1, [1, 2], 2, slo=BATCH)).shed
    r = sched.submit(SeqState(2, [1, 2], 2, slo=BATCH))
    assert r.shed and r.status == SubmitResult.SHED
    assert r.retry_after >= 1 and "shed_limit" in r.reason
    assert sched.stats["shed"] == 1
    # the backlog bound is CLASS-LOCAL: only same-or-higher-priority
    # waiters count, so an interactive submit jumps the batch backlog
    assert not sched.submit(SeqState(3, [1, 2], 2, slo=INTERACTIVE)).shed
    # ...and a shed sequence was never enqueued
    assert all(s.req_id != 2 for s in sched.queue)


def test_engine_shed_log_and_terminality():
    eng = _engine(n_slots=2, shed_limit=2,
                  policy=StrictPriorityPolicy())
    rids, prompts = [], {}
    for i in range(6):
        p = _toks(40 + i, 5)
        rid = eng.submit(p, 3, slo=BATCH, t_arrive=float(i))
        rids.append(rid)
        prompts[rid] = p
    shed = eng.take_shed()
    assert shed and eng.take_shed() == []        # drained exactly once
    shed_ids = {rid for rid, _, _ in shed}
    for rid, cls, retry in shed:
        assert cls == "batch" and retry >= 1
    _drain(eng)
    fin = eng.sched.finished
    assert not (shed_ids & set(fin)), "sequence both shed and completed"
    for rid in set(rids) - shed_ids:
        assert fin[rid].generated == _reference(prompts[rid], 3)
    _assert_drained(eng)


# -------------------------------------------------------- victim selection
def test_pick_victims_ordering_and_class_protection():
    """Lowest class first, latest deadline first among equals, never a
    same-or-higher class, and never a partial cover."""
    pt = PageTable(n_pages=12, page_size=4, n_slots=3, max_pages=4)
    sched = Scheduler(3, max_prefill_per_tick=3, pages=pt)
    sched.submit(SeqState(0, [1] * 4, 4, slo=BATCH, t_arrive=0.0))
    sched.submit(SeqState(1, [1] * 4, 4, slo=BATCH, t_arrive=5.0))
    sched.submit(SeqState(2, [1] * 4, 4, slo=STANDARD, t_arrive=0.0))
    tick = sched.next_tick()
    slot_of = {}
    for slot, seq in tick.admit:
        slot_of[seq.req_id] = slot
        sched.on_prefilled(slot, 1)          # all three now in DECODE
    assert len(slot_of) == 3
    # batch pair outranks standard; deadline 35 (req 1) loses before 30
    v = sched.pick_victims(1, INTERACTIVE)
    assert v == [slot_of[1]]
    order = sched.pick_victims(10**9, INTERACTIVE) or \
        [i for i in sorted(range(3), key=lambda i: (
            sched.slots[i].priority, -sched.slots[i].deadline, i))]
    assert order[:2] == [slot_of[1], slot_of[0]]
    # a standard requester may only evict batch work
    v = sched.pick_victims(1, STANDARD)
    assert v and all(sched.slots[i].priority < STANDARD.priority
                     for i in v)
    # batch preempts nobody; an impossible ask yields NO victims at all
    assert sched.pick_victims(1, BATCH) == []
    assert sched.pick_victims(10**6, INTERACTIVE) == []
    # need_slot forces one victim even when no pages are needed
    assert sched.pick_victims(0, INTERACTIVE, need_slot=True) \
        == [slot_of[1]]


def test_preempt_frees_slot_pages_and_quota():
    pt = PageTable(n_pages=12, page_size=4, n_slots=2, max_pages=4)
    sched = Scheduler(2, pages=pt,
                      policy=StrictPriorityPolicy(
                          quotas={"batch": PageQuota(ceiling_frac=1.0)}))
    sched.submit(SeqState(0, [1] * 4, 4, slo=BATCH))
    tick = sched.next_tick()
    (slot, seq), = tick.admit
    sched.on_prefilled(slot, 1)
    assert pt.n_reserved > 0 and sched._class_pages.get("batch", 0) > 0
    out = sched.preempt(slot)
    assert out is seq and sched.state[slot] is SlotState.FREE
    assert sched.stats["preempted"] == 1
    assert pt.n_reserved == 0 and pt.n_slot_owned == 0
    assert sched._class_pages.get("batch", 0) == 0
    pt.check_invariants()
    # a preempted sequence is NOT finished — it re-enters via resume
    assert out.req_id not in sched.finished


# ------------------------------------------- engine preempt/park/resume
def test_preempt_park_resume_bit_equal():
    """Explicit preempt_export → hold off-engine (the cluster parks to
    the host tier) → adopt back later: tokens bit-equal throughout."""
    eng = _engine(n_slots=2, policy=StrictPriorityPolicy())
    p0, p1 = _toks(1, 6), _toks(2, 6)
    r0 = eng.submit(p0, 8, slo=BATCH, t_arrive=0.0)
    r1 = eng.submit(p1, 8, slo=BATCH, t_arrive=0.1)
    for _ in range(4):
        eng.step()                     # both mid-decode
    victims = [i for i in eng.sched.live_slots()
               if eng.sched.slots[i] is not None
               and eng.sched.state[i] is SlotState.DECODE]
    assert len(victims) == 2
    triples = eng.preempt_export(victims[:1])
    parked = eng.take_preempted()      # the cluster's harvest step
    assert [t[0].req_id for t in triples] == \
        [t[0].req_id for t in parked] and len(parked) == 1
    seq, payload, pages = parked[0]
    assert pages > 0 and seq.generated and not seq.finished
    assert eng.sched.stats["preempted"] == 1
    # the survivor keeps decoding while the victim sits in the host tier
    for _ in range(6):
        eng.step()
    eng.adopt([(seq, payload)])
    _drain(eng)
    fin = eng.sched.finished
    assert fin[r0].generated == _reference(p0, 8)
    assert fin[r1].generated == _reference(p1, 8)
    _assert_drained(eng)


def test_preempt_resume_on_second_engine_bit_equal():
    """The payload is self-contained PackedKV: a victim packed on one
    engine resumes on a DIFFERENT engine with bit-equal tokens."""
    eng1 = _engine(n_slots=2, policy=StrictPriorityPolicy())
    eng2 = _engine(n_slots=2, policy=StrictPriorityPolicy())
    p = _toks(7, 6)
    rid = eng1.submit(p, 8, slo=BATCH)
    for _ in range(4):
        eng1.step()
    slot = next(i for i in eng1.sched.live_slots()
                if eng1.sched.state[i] is SlotState.DECODE)
    eng1.preempt_export([slot])
    (seq, payload, _), = eng1.take_preempted()
    n_done = len(seq.generated)
    assert 0 < n_done < 8
    eng2.adopt([(seq, payload)])
    _drain(eng2)
    assert eng2.sched.finished[rid].generated == _reference(p, 8)
    _drain(eng1)
    _assert_drained(eng1)
    _assert_drained(eng2)


@pytest.mark.parametrize("prefix", [0, 28], ids=["distinct", "shared"])
def test_standalone_engine_auto_preempts_and_self_readopts(prefix,
                                                           monkeypatch):
    """preemption=True without a cluster: an interactive arrival evicts
    a batch slot this very tick, and the victim re-enters through the
    engine's own outbox → resume queue next step — nothing is lost.
    With a shared prefix that ends mid-page, sharers skip its prefill
    and fork the partial page (copy-on-write) before writing, and the
    victim ships as deduped PackedKV: every paged program runs on the
    donated pool along the way."""
    eng = _engine(n_slots=2, preemption=True,
                  policy=StrictPriorityPolicy())
    forks = []
    fork = eng.pages.fork

    def counted(slot, index):
        old, new = fork(slot, index)
        forks.append(old != new)
        return old, new
    monkeypatch.setattr(eng.pages, "fork", counted)
    shared = _toks(19, prefix)
    prompts = {}
    for i, (slo, n_tok) in enumerate([(BATCH, 10), (BATCH, 10),
                                      (INTERACTIVE, 4)]):
        p = shared + _toks(20 + i, 6)
        rid = eng.submit(p, n_tok, slo=slo, t_arrive=float(i))
        prompts[rid] = (p, n_tok)
        if i == 1:
            for _ in range(3):
                eng.step()             # batch pair reaches DECODE
    eng.step()
    assert eng.sched.stats["preempted"] >= 1
    # interactive got the freed slot ahead of the parked victim
    live = [eng.sched.slots[i] for i in eng.sched.live_slots()
            if eng.sched.slots[i] is not None]
    assert any(s.slo is INTERACTIVE for s in live)
    _drain(eng)
    fin = eng.sched.finished
    assert set(fin) == set(prompts)
    for rid, (p, n_tok) in prompts.items():
        assert fin[rid].generated == _reference(p, n_tok), rid
    assert (eng.sched.stats["shared_tokens"] > 0) == bool(prefix)
    assert any(forks) == bool(prefix)
    _assert_drained(eng)


# ------------------------------------------------- randomized interleaving
_OPS = st.lists(st.integers(0, 9), min_size=4, max_size=24)


@settings(max_examples=6, deadline=None)
@given(ops=_OPS)
def test_random_submit_preempt_park_resume_interleavings(ops):
    """Allocator invariants hold after EVERY operation, the pool drains
    to all-free, no sequence is both shed and completed, and every
    non-shed sequence finishes bit-equal to the reference."""
    classes = (BATCH, STANDARD, INTERACTIVE)
    eng = _engine(n_slots=3, shed_limit=3,
                  policy=StrictPriorityPolicy())
    parked, prompts, shed_ids = [], {}, set()
    for k, op in enumerate(ops):
        if op <= 3:                                        # submit
            p = _toks(1000 + k, 5)
            n_tok = 2 + (k % 4)
            rid = eng.submit(p, n_tok, slo=classes[op % 3],
                             t_arrive=float(k))
            prompts[rid] = (p, n_tok)
        elif op <= 6:                                      # run a tick
            eng.step()
        elif op == 7:                                      # preempt one
            live = [i for i in eng.sched.live_slots()
                    if eng.sched.slots[i] is not None
                    and eng.sched.state[i] is SlotState.DECODE
                    and not eng.sched.slots[i].finished
                    and eng.sched.slots[i].generated]
            if live:
                eng.preempt_export([live[k % len(live)]])
                parked.extend(eng.take_preempted())        # park (host)
        elif op == 8 and parked:                           # resume one
            seq, payload, _ = parked.pop(0)
            eng.adopt([(seq, payload)])
        else:                                              # harvest sheds
            shed_ids |= {r for r, _, _ in eng.take_shed()}
        eng.pages.check_invariants()
    for seq, payload, _ in parked:                         # resume rest
        eng.adopt([(seq, payload)])
    _drain(eng)
    shed_ids |= {r for r, _, _ in eng.take_shed()}
    fin = eng.sched.finished
    assert not (shed_ids & set(fin)), "sequence both shed and completed"
    assert set(prompts) == shed_ids | set(fin), "sequence lost"
    for rid in fin:
        p, n_tok = prompts[rid]
        assert fin[rid].generated == _reference(p, n_tok), rid
    _assert_drained(eng)


# ------------------------------------------------------------ cluster wiring
def test_cluster_preempts_parks_to_host_tier_and_resumes():
    lc = LiveCluster(n_nodes=1, n_slots=2, max_len=MAX_LEN,
                     page_size=PAGE_SIZE,
                     admission=StrictPriorityPolicy(), preemption=True)
    cfg, params, _ = _ctx()
    lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0])
    prompts = {}
    for i, (slo, n_tok) in enumerate([(BATCH, 10), (BATCH, 10)]):
        p = _toks(60 + i, 6)
        prompts[lc.submit("m", p, n_tok, slo=slo)] = (p, n_tok)
    for _ in range(4):
        lc.tick()
    p = _toks(66, 6)
    prompts[lc.submit("m", p, 4, slo=INTERACTIVE)] = (p, 4)
    parked_seen = False
    for _ in range(400):
        active = lc.tick()
        if any(mm.parked.get("m") for mm in lc.nodes):
            parked_seen = True
        if not active:
            break
    kinds = [e.kind for e in lc.audit_log]
    assert "preempt" in kinds and "park" in kinds and "resume" in kinds
    assert parked_seen, "victim never visited the host-tier pen"
    assert [e for e in lc.audit_log if e.kind == "preempt"][0].req_id in \
        {e.req_id for e in lc.audit_log if e.kind == "resume"}
    ev = lc.take_preempt_events()
    assert ev and all(pages > 0 for _, _, pages in ev)
    out = lc.results("m")
    assert set(out) == set(prompts)
    for rid, (p, n_tok) in prompts.items():
        assert out[rid] == _reference(p, n_tok), rid
    for eng in lc.serving["m"].locals_.values():
        _assert_drained(eng)
    # nothing left parked anywhere
    assert all(not mm.parked.get("m") for mm in lc.nodes)


def test_park_timeout_sheds_with_audit():
    """A victim that cannot re-enter within max_park_ticks is shed with
    a park_timeout audit entry instead of waiting forever."""
    lc = LiveCluster(n_nodes=1, n_slots=2, max_len=MAX_LEN,
                     page_size=PAGE_SIZE,
                     admission=StrictPriorityPolicy(), preemption=True,
                     max_park_ticks=3)
    cfg, params, _ = _ctx()
    lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0])
    victim_p = _toks(70, 6)
    victim = lc.submit("m", victim_p, 20, slo=BATCH)
    for _ in range(4):
        lc.tick()
    # interactive flood keeps both slots + the queue saturated well past
    # the park timeout, so the parked batch victim can never re-enter
    flood = {}
    for i in range(8):
        p = _toks(71 + i, 6)
        flood[lc.submit("m", p, 8, slo=INTERACTIVE)] = p
    for _ in range(600):
        if not lc.tick():
            break
    kinds = [(e.kind, e.req_id) for e in lc.audit_log]
    assert ("preempt", victim) in kinds
    assert ("park_timeout", victim) in kinds
    shed = lc.take_shed_events()
    assert any(rid == victim for _, rid, _ in shed)
    out = lc.results("m")
    assert victim not in out, "shed sequence still completed"
    for rid, p in flood.items():
        assert out[rid] == _reference(p, 8), rid
    for eng in lc.serving["m"].locals_.values():
        _assert_drained(eng)
    assert all(not mm.parked.get("m") for mm in lc.nodes)


def test_park_timeout_reroutes_resume_queue_to_free_node():
    """A resume-queue park wedged behind long-running work re-routes to
    another replica once it times out — arbiter-ranked, bit-equal."""
    lc = LiveCluster(n_nodes=2, n_slots=2, max_len=MAX_LEN,
                     page_size=PAGE_SIZE,
                     admission=StrictPriorityPolicy(),
                     max_park_ticks=2)
    cfg, params, _ = _ctx()
    lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0, 1])
    eng0 = lc.serving["m"].locals_[0]
    # a donor engine outside the cluster produces a mid-flight victim
    donor = _engine(n_slots=1, policy=StrictPriorityPolicy())
    p = _toks(80, 6)
    rid = donor.submit(p, 8, slo=BATCH)
    for _ in range(4):
        donor.step()
    donor.preempt_export([next(i for i in donor.sched.live_slots())])
    (seq, payload, _), = donor.take_preempted()
    # wedge node 0: both slots busy with long interactive work, then
    # adopt the victim — no free slot, so it parks in the resume queue
    busy = {}
    for i in range(2):
        bp = _toks(81 + i, 6)
        brid = 1000 + i
        eng0.submit(bp, 30, req_id=brid, slo=INTERACTIVE)
        busy[brid] = (bp, 30)
    lc.tick()
    lc.tick()
    assert eng0.sched.in_flight == 2
    eng0.adopt([(seq, payload)])
    assert any(s.req_id == rid for s in eng0.sched.resume_queue)
    for _ in range(600):
        if not lc.tick():
            break
    resumes = [e for e in lc.audit_log
               if e.kind == "resume" and e.req_id == rid]
    assert resumes and "rerouted off node 0" in resumes[0].detail
    assert rid in lc.serving["m"].locals_[1].sched.finished
    out = lc.results("m")
    assert out[rid] == _reference(p, 8)
    for brid, (bp, n_tok) in busy.items():
        assert out[brid] == _reference(bp, n_tok), brid
    for eng in lc.serving["m"].locals_.values():
        _assert_drained(eng)


# ----------------------------------------------------------------- metrics
def test_metrics_overload_keys_nan_gated():
    log = MetricsLog()
    log.on_arrival(1, "m", 0.0, slo=INTERACTIVE)
    log.on_first_token(1, 0.1)
    log.on_finish(1, 0.2, 4)
    s = log.summary()
    # a run that never preempted/shed emits NONE of the overload keys
    for k in ("preemptions", "pages_reclaimed", "n_shed",
              "goodput_interactive", "shed_frac_interactive"):
        assert k not in s, k
    log.on_preempt(0.15, "m", 1, pages=3)
    s = log.summary()
    assert s["preemptions"] == 1 and s["pages_reclaimed"] == 3
    assert s["n_shed"] == 0
    assert s["goodput_interactive"] == 1.0
    assert s["shed_frac_interactive"] == 0.0


def test_metrics_shed_is_terminal_and_classed():
    log = MetricsLog()
    log.on_arrival(1, "m", 0.0, slo=BATCH)
    log.on_arrival(2, "m", 0.0, slo=BATCH)
    log.on_shed(1, 0.1, retry_after=2.0)
    log.on_shed(1, 0.2, retry_after=9.0)      # first-write-wins
    log.on_first_token(2, 0.1)
    log.on_finish(2, 0.3, 4)
    assert log.requests[1].retry_after == 2.0
    s = log.summary()
    assert s["n_shed"] == 1
    assert s["shed_frac_batch"] == 0.5
    assert s["goodput_batch"] == 0.5
    # unknown req_id tolerated (shed can race the arrival record)
    log.on_shed(999, 0.4)


# -------------------------------------------------------------- autoscaler
def test_autoscaler_shed_overload_trigger():
    asc = Autoscaler(AutoscalerConfig(shed_high=0.2))
    base = dict(model="m", queue_depth=0, slots_total=8, slots_busy=4,
                nodes_busy=1, slots_per_instance=4, n_replicas=1)
    calm = LoadSignals(recent_arrivals=10, recent_sheds=1, **base)
    n, reason = asc.desired_new_nodes(calm)
    assert n == 0 and "shed" not in reason     # 0.1 < shed_high
    hot = LoadSignals(recent_arrivals=10, recent_sheds=4, **base)
    n, reason = asc.desired_new_nodes(hot)
    assert n == 1 and "shed" in reason
    # trigger disabled by default — sheds alone never scale
    off = Autoscaler(AutoscalerConfig())
    n, reason = off.desired_new_nodes(hot)
    assert n == 0 and "shed" not in reason
