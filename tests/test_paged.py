"""Paged KV cache: allocator invariants, engine exactness, page-granular
handoff, and the recompute-vs-transfer resume policy.

The acceptance bar for the paged engine is EXACT greedy-token equality
with the striped (pooled) engine and the static reference — the paged
layout is a storage change, not a model change — including across
drain → handoff → adopt with parked (resume-queue) sequences, payload
drops (forced recomputation), and the wire-buffer roundtrip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.models import (PackedKV, PageTable, batch_axes, init_cache,
                          init_params, pages_for, payload_nbytes)
from repro.serving.cluster import LiveCluster
from repro.serving.engine import ContinuousBatchingEngine, InferenceEngine
from repro.serving.tiers import HardwareProfile

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


def _prompt(seed, length):
    cfg, _, _ = _ctx()
    return list(map(int, jax.random.randint(
        jax.random.PRNGKey(seed), (length,), 0, cfg.vocab_size)))


def _reference(prompt, n_tok):
    _, _, ref = _ctx()
    toks = ref.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]},
                        n_tok, cache_len=MAX_LEN)
    return list(map(int, toks[0]))


# ------------------------------------------------------------- allocator
@settings(max_examples=25, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 2), st.integers(0, 3),
                              st.integers(0, 40)),
                    min_size=1, max_size=50))
def test_page_table_never_leaks_or_double_frees(ops):
    """Random reserve/ensure/release interleavings: every page is owned
    by at most one slot, allocated+free always covers the pool, and a
    full release drains back to empty."""
    pt = PageTable(n_pages=8, page_size=4, n_slots=4, max_pages=4)
    for kind, slot, arg in ops:
        if kind == 0:
            pt.reserve(slot, arg % 17)
        elif kind == 1:
            want = arg % 17
            if pages_for(want, 4) <= 4:
                try:
                    pt.ensure(slot, want)
                except RuntimeError:
                    pass            # pool exhausted: admission's job
        else:
            freed = pt.release(slot)
            assert len(freed) == len(set(freed))
        pt.check_invariants()
    for s in range(4):
        pt.release(s)
    pt.check_invariants()
    assert pt.n_allocated == 0 and pt.n_reserved == 0


def test_page_table_double_free_raises():
    pt = PageTable(n_pages=4, page_size=4, n_slots=2, max_pages=2)
    pt.ensure(0, 5)
    stolen = pt._slot_pages[0][0]
    pt._slot_pages[1].append(stolen)       # corrupt: two owners
    with pytest.raises(RuntimeError, match="double free"):
        pt.release(1)


def test_page_table_admission_accounting():
    pt = PageTable(n_pages=4, page_size=4, n_slots=4, max_pages=4)
    assert pt.can_admit(16) and not pt.can_admit(17)
    pt.reserve(0, 9)                       # 3 pages worst case
    assert pt.can_admit(4) and not pt.can_admit(5)
    pt.ensure(0, 5)                        # 2 of the 3 materialize
    assert pt.n_allocated == 2 and pt.n_reserved == 3
    pt.release(0)
    assert pt.can_admit(16)


# ------------------------------------------------- batch-axes regression
def test_batch_axes_ambiguous_raises():
    """Regression: silent wrong answers on ambiguous leaves.  A pool
    built with n_slots=1 is indistinguishable from the batch-1 reference
    (slot count equals the reference's batch axis everywhere) and must
    raise, as must caches whose non-batch dims differ."""
    cfg, _, _ = _ctx()
    with pytest.raises(ValueError, match="n_slots"):
        batch_axes(init_cache(cfg, 1, 32), init_cache(cfg, 1, 32))
    with pytest.raises(ValueError, match="ambiguous"):
        batch_axes(init_cache(cfg, 4, 32), init_cache(cfg, 1, 16))


def test_batch_axes_slot_count_collision_still_detected():
    """n_slots equal to every other tempting axis size (max_len) must
    still resolve: the reference comparison disambiguates."""
    cfg, _, _ = _ctx()
    axes = batch_axes(init_cache(cfg, 32, 32), init_cache(cfg, 1, 32))
    ks = [a for a in jax.tree.leaves(axes) if a >= 0]
    assert ks and all(a == ks[0] or a >= 0 for a in ks)


# ------------------------------------------------------ engine exactness
def test_paged_engine_matches_pooled_and_static():
    """5 mixed requests through 3 slots: paged and striped engines emit
    identical greedy tokens, equal to the static reference; the paged
    pool drains back to zero allocated pages."""
    cfg, params, _ = _ctx()
    reqs = [(8, 6), (12, 3), (5, 9), (9, 4), (7, 7)]
    prompts = {i: _prompt(400 + i, L) for i, (L, _) in enumerate(reqs)}
    outs = {}
    for paged in (False, True):
        eng = ContinuousBatchingEngine(cfg, params, n_slots=3,
                                       max_len=MAX_LEN, paged=paged,
                                       page_size=PAGE_SIZE)
        for i, (_, n) in enumerate(reqs):
            eng.submit(prompts[i], n, req_id=i)
        outs[paged] = eng.run()
        if paged:
            eng.pages.check_invariants()
            assert eng.pages.n_allocated == 0
    assert outs[True] == outs[False]
    for i, (_, n) in enumerate(reqs):
        assert outs[True][i] == _reference(prompts[i], n), f"req {i}"


def test_paged_pool_undersized_throttles_but_stays_exact():
    """A pool with fewer pages than slots×max_pages admits by page
    budget: requests queue instead of corrupting each other, and every
    output still matches the reference."""
    cfg, params, _ = _ctx()
    eng = ContinuousBatchingEngine(cfg, params, n_slots=3, max_len=MAX_LEN,
                                   page_size=PAGE_SIZE, n_pages=2,
                                   max_prefill_per_tick=3)
    prompts = {i: _prompt(500 + i, 6) for i in range(3)}
    for i in range(3):
        eng.submit(prompts[i], 8, req_id=i)     # 14 tokens → 1 page each
    out = eng.run()
    assert len(out) == 3
    for i in range(3):
        assert out[i] == _reference(prompts[i], 8), f"req {i}"
    with pytest.raises(ValueError, match="pages"):
        eng.submit(_prompt(999, 40), 8)          # 48 tokens > 2-page pool


# ----------------------------------------------- page-granular handoff
def _mid_gen_engine(n_slots=4, n_reqs=4, base_seed=600, ntok=6):
    cfg, params, _ = _ctx()
    eng = ContinuousBatchingEngine(cfg, params, n_slots=n_slots,
                                   max_len=MAX_LEN, page_size=PAGE_SIZE,
                                   max_prefill_per_tick=n_slots)
    want = {}
    for i in range(n_reqs):
        p = _prompt(base_seed + i, 5 + i)
        eng.submit(p, ntok, req_id=i)
        want[i] = _reference(p, ntok)
    for _ in range(3):
        eng.step()
    eng.drain()
    return eng, want


def test_paged_handoff_park_resume_exact():
    """Drain → page-granular handoff → adopt with overflow: two of four
    live sequences park in the resume queue and enter DECODE as pages
    and slots free up; outputs equal the never-handed-off reference and
    no sequence re-runs prefill."""
    a, want = _mid_gen_engine()
    pairs = a.handoff()
    assert all(isinstance(c, PackedKV) for _, c in pairs)
    assert a.pages.n_allocated == 0        # source released every page
    cfg, params, _ = _ctx()
    b = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                                 page_size=PAGE_SIZE)
    b.adopt(pairs)
    assert b.sched.stats["adopted"] == 2
    assert len(b.sched.resume_queue) == 2
    out = b.run()
    assert {i: out[i] for i in want} == want
    assert b.sched.stats["prefills"] == 0
    assert b.sched.stats["adopted"] == 4
    b.pages.check_invariants()
    assert b.pages.n_allocated == 0


def test_paged_handoff_moves_fewer_bytes_than_pooled():
    """Equal output, fewer bytes: live pages of short sequences are a
    fraction of the whole max_len stripe the pooled gather ships."""
    a, _ = _mid_gen_engine()
    paged_bytes = sum(payload_nbytes(c) for _, c in a.handoff())
    cfg, params, _ = _ctx()
    pooled = ContinuousBatchingEngine(cfg, params, n_slots=4,
                                      max_len=MAX_LEN, paged=False,
                                      max_prefill_per_tick=4)
    for i in range(4):
        pooled.submit(_prompt(600 + i, 5 + i), 6, req_id=i)
    for _ in range(3):
        pooled.step()
    pooled.drain()
    pooled_bytes = sum(payload_nbytes(c) for _, c in pooled.handoff())
    assert 0 < paged_bytes < 0.7 * pooled_bytes


def test_paged_wire_roundtrip_and_dropped_payload_exact():
    """The contiguous wire buffer reconstructs the payload bit-exactly,
    and dropping payloads entirely (recompute path, §4.4) still yields
    reference tokens at adoption."""
    a, want = _mid_gen_engine(n_slots=2, n_reqs=2, base_seed=700)
    pairs = a.handoff()
    cfg, params, _ = _ctx()
    wired, dropped = [], []
    for s, c in pairs:
        rt = c.from_wire(*c.wire())
        for x, y in zip(jax.tree.leaves(c.kv), jax.tree.leaves(rt.kv)):
            assert (jnp.asarray(x) == jnp.asarray(y)).all()
        wired.append((s, rt))
        dropped.append((s, None))
    b = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                                 page_size=PAGE_SIZE)
    b.adopt(wired)
    out = b.run()
    assert {i: out[i] for i in want} == want
    # fresh engine, recompute-only adoption (payloads dropped)
    a2, want2 = _mid_gen_engine(n_slots=2, n_reqs=2, base_seed=700)
    c = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                                 page_size=PAGE_SIZE)
    c.adopt([(s, None) for s, _ in a2.handoff()])
    out2 = c.run()
    assert {i: out2[i] for i in want2} == want2


def test_attention_free_model_paged_handoff():
    """A pure-recurrent model (xLSTM: no KV pools, state is O(d) per
    slot) still runs the paged engine path: handoff payloads carry the
    engine's page size, and drain→adopt stays exact vs pooled."""
    cfg = reduced(get_config("xlstm-1.3b"), d_model=64, n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = list(map(int, jax.random.randint(
        jax.random.PRNGKey(2), (6,), 0, cfg.vocab_size)))
    outs = {}
    for paged in (False, True):
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                       max_len=MAX_LEN, paged=paged,
                                       page_size=PAGE_SIZE)
        eng.submit(prompt, 6, req_id=0)
        for _ in range(3):
            eng.step()
        eng.drain()
        pairs = eng.handoff()
        b = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                     max_len=MAX_LEN, paged=paged,
                                     page_size=PAGE_SIZE)
        if paged:
            assert all(c.page_size == PAGE_SIZE for _, c in pairs)
        b.adopt(pairs)
        outs[paged] = b.run()[0]
    assert outs[True] == outs[False] and len(outs[True]) == 6


def test_adopt_parks_in_order_no_small_request_bypass():
    """Once one adoption parks for lack of pages, every later pair parks
    too — the same FCFS no-bypass rule the scheduler's admission applies
    — and the parked sequences resume in handoff order, exactly."""
    cfg, params, _ = _ctx()
    a = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                                 page_size=PAGE_SIZE,
                                 max_prefill_per_tick=2)
    big_p, small_p = _prompt(750, 18), _prompt(751, 5)
    a.submit(big_p, 6, req_id=0)          # 24 tokens → 2 pages worst case
    a.submit(small_p, 5, req_id=1)        # 10 tokens → 1 page
    want = {0: _reference(big_p, 6), 1: _reference(small_p, 5)}
    for _ in range(3):
        a.step()
    a.drain()
    pairs = a.handoff()
    assert [s.req_id for s, _ in pairs] == [0, 1]

    b = ContinuousBatchingEngine(cfg, params, n_slots=3, max_len=MAX_LEN,
                                 page_size=PAGE_SIZE, n_pages=3)
    b.submit(_prompt(752, 18), 6, req_id=9)   # holds 2 of the 3 pages
    b.step()
    b.adopt(pairs)
    # big (2 pages) cannot fit beside req 9's reservation; small could,
    # but must not run ahead of it
    assert b.sched.stats["adopted"] == 0
    assert [s.req_id for s in b.sched.resume_queue] == [0, 1]
    out = b.run()
    assert {i: out[i] for i in want} == want
    assert b.sched.stats["prefills"] == 1      # only req 9
    b.pages.check_invariants()


# --------------------------------------- cluster resume-path pricing
def _cluster_scale_down(link_bw):
    cfg, params, _ = _ctx()
    lc = LiveCluster(n_nodes=2, hw=HardwareProfile(link_bw=link_bw),
                     n_slots=2, max_len=MAX_LEN, page_size=PAGE_SIZE)
    lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0, 1])
    eng = lc.serving["m"].locals_[1]
    want = {}
    for i in range(2):
        p = _prompt(800 + i, 6)
        eng.submit(p, 6, req_id=i)
        want[i] = _reference(p, 6)
    for _ in range(4):
        eng.step()
    lc.scale_down("m", [1])
    lc.drain_serving()
    return lc, want


def test_cluster_prices_transfer_vs_recompute_per_request():
    """The same drain under a fast and a crippled inter-node link takes
    opposite §4.4 resume paths — and both end in exact tokens."""
    fast, want_f = _cluster_scale_down(link_bw=1e15)
    slow, want_s = _cluster_scale_down(link_bw=10.0)
    for lc, want, expect in ((fast, want_f, "transfer"),
                             (slow, want_s, "recompute")):
        live = [d for d in lc.handoff_log if d.n_tokens > 0]
        assert live and all(d.chosen == expect for d in live), \
            (expect, [(d.chosen, d.n_tokens) for d in lc.handoff_log])
        out = lc.results("m")
        for i, toks in want.items():
            assert out[i] == toks, (expect, i)
    moved = [d for d in fast.handoff_log if d.chosen == "transfer"]
    assert all(d.payload_bytes > 0 and d.t_transfer < d.t_recompute
               for d in moved)


# ------------------------------------------------- roofline replay clock
def test_replay_roofline_decode_clock():
    """Default replay pricing uses the roofline per-token time (SimModel
    .tok_time) instead of the 2 ms constant: the reduced model's decode
    is orders of magnitude cheaper, tokens stay exact, and pinning
    tick_seconds reproduces the old constant clock."""
    from repro.serving.autoscaler import Autoscaler, AutoscalerConfig
    from repro.serving.simulator import SimModel
    from repro.serving.workload import Request
    cfg, params, _ = _ctx()
    prompts = {i: _prompt(900 + i, 5) for i in range(4)}
    trace = [Request(i, "m", 0.0005 * i, 5, 4) for i in range(4)]

    def run(tick_seconds):
        lc = LiveCluster(n_nodes=2, n_slots=2, max_len=MAX_LEN,
                         page_size=PAGE_SIZE)
        lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0])
        asc = Autoscaler(AutoscalerConfig(cooldown_up=10.0, keepalive=10.0))
        log = lc.replay(trace, autoscaler=asc, tick_seconds=tick_seconds,
                        prompt_fn=lambda r: prompts[r.req_id])
        return lc, log

    lc_roof, log_roof = run(None)
    lc_const, log_const = run(0.002)
    for log in (log_roof, log_const):
        assert log.summary()["n_finished"] == 4
    for lc in (lc_roof, lc_const):
        out = lc.results("m")
        for i in range(4):
            assert out[i] == _reference(prompts[i], 4), i
    tok = SimModel.from_config(cfg).tok_time(HardwareProfile())
    assert tok < 0.002 / 10          # the regimes are far apart
    e2e_roof = max(m.t_finish for m in log_roof.requests.values())
    e2e_const = max(m.t_finish for m in log_const.requests.values())
    assert e2e_roof < e2e_const, (e2e_roof, e2e_const)


# ------------------------------------------------ fused decode fast path
@settings(max_examples=20, deadline=None)
@given(lens=st.lists(st.integers(0, 24), min_size=3, max_size=3))
def test_fused_append_matches_host_scatter_bytes(lens):
    """The fused kernel's in-kernel KV append and the host-side
    ``.at[pg, off].set`` scatter (the XLA path) produce byte-identical
    page pools outside the trash page, for any ragged fill — including
    FREE slots (lens 0 → no pages), which land on the trash page."""
    from repro.kernels.ops import paged_decode_step

    B, KVH, H, dh, ps, MP = 3, 2, 4, 16, 8, 3
    P = B * MP + 2
    rng = np.random.default_rng(sum(lens) * 31 + 5)
    table_np = np.full((B, MP), -1, np.int32)
    free = list(rng.permutation(P - 1))
    for b, n in enumerate(lens):
        for i in range(-(-n // ps)):
            table_np[b, i] = free.pop()
    table = jnp.asarray(table_np)
    L = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, KVH, dh)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, KVH, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), jnp.float32)

    _, ko, vo = paged_decode_step(q, kn, vn, k, v, table, L,
                                  interpret=True)

    n1 = np.maximum(np.asarray(lens) - 1, 0)
    pg = table_np[np.arange(B), np.minimum(n1 // ps, MP - 1)]
    pg = np.where(pg >= 0, pg, P - 1)
    kh = k.at[pg, n1 % ps].set(kn)
    vh = v.at[pg, n1 % ps].set(vn)
    np.testing.assert_array_equal(np.asarray(ko[:P - 1]),
                                  np.asarray(kh[:P - 1]), err_msg=str(lens))
    np.testing.assert_array_equal(np.asarray(vo[:P - 1]),
                                  np.asarray(vh[:P - 1]), err_msg=str(lens))


def test_pallas_engine_exact_and_invariants_every_step():
    """attn_impl="pallas" drives the single-launch fused decode step;
    the allocator must hold its invariants after EVERY engine step and
    the greedy tokens must match the static reference exactly."""
    cfg, params, _ = _ctx()
    eng = ContinuousBatchingEngine(cfg, params, n_slots=3, max_len=MAX_LEN,
                                   page_size=PAGE_SIZE, attn_impl="pallas")
    reqs = [(8, 5), (12, 3), (5, 6), (9, 4)]
    prompts = {i: _prompt(700 + i, L) for i, (L, _) in enumerate(reqs)}
    for i, (_, n) in enumerate(reqs):
        eng.submit(prompts[i], n, req_id=i)
    steps = 0
    while eng.step():
        eng.pages.check_invariants()
        steps += 1
    eng.flush()
    out = {rid: s.generated for rid, s in eng.sched.finished.items()}
    assert steps > 0 and len(out) == len(reqs)
    for i, (_, n) in enumerate(reqs):
        assert out[i] == _reference(prompts[i], n), f"req {i}"
    assert eng.pages.n_allocated == 0
