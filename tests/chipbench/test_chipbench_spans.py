"""The program's engine spans, request stamps and model scopes, and the
reductions that read them (``chipbench/spans.py``, ``stamps.py``,
``scopecost.py``): a tiny engine traced on the CPU, and recorded event
lists where the CPU trace has no device plane."""
import dataclasses
import glob

import jax
import pytest

from chipbench_tiny import tiny_cell
from chipbench import (attribute, cells, modelspec, openloop, peaks,
                       readers, scopecost, spans, stamps, tails, tracefile,
                       traffic, weights)
from chipbench.spans import Op
from chipbench.tracefile import Event
from repro.models import scopes
from repro.serving import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
PROGRAMS = {"prefill": "prefill_scatter", "decode": "step"}
HARNESS = openloop.SPANS
LABELS = HARNESS + trace.NAMES
MS = 1e6

# the harness's span each engine span is called from, and the engine
# span each child phase sits in
PARENT = {trace.SUBMIT: "submit", trace.STEP: "step", trace.FLUSH: "flush",
          trace.SCHEDULE: trace.STEP, trace.PAGES: trace.STEP,
          trace.DECODE: trace.STEP, trace.PREFILL: trace.STEP,
          trace.FLUSH_WAIT: trace.FLUSH, trace.FLUSH_RESOLVE: trace.FLUSH}


@pytest.fixture(scope="module")
def traced_tiny(tmp_path_factory):
    """A tiny cell's window, traced on the CPU, and its untraced twin's
    engine counters."""
    cell = tiny_cell()
    spec, seed, seconds = cell.config, 2**33 + 5, 2.0
    requests = traffic.generate(cell.traffic, seed, seconds,
                                modelspec.dims(spec)["vocab"])
    params = weights.make(spec, seed)
    eng = openloop.warm(spec, params, cell.traffic, seconds, seed)
    off, _, _ = attribute.window(cell, eng, requests, seconds, False)
    tdir = str(tmp_path_factory.mktemp("trace"))
    eng = openloop.warm(spec, params, cell.traffic, seconds, seed)
    jax.profiler.start_trace(tdir)
    win = openloop.drive(eng, requests, seconds, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = glob.glob(tdir + "/**/*.xplane.pb", recursive=True)[0]
    tr = spans.load(path, LABELS + (openloop.WINDOW_SPAN,))
    return cell, win, tr, eng, off


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_every_engine_span_sits_in_its_parent(traced_tiny):
    _, win, tr, eng, _ = traced_tiny
    host = [e for e in tr.events if not e.plane.startswith("/device:")]
    by = {n: [e for e in host if e.name == n] for n in LABELS}
    for name, parent in PARENT.items():
        assert by[name], f"no {name} span in the trace"
        for e in by[name]:
            assert any(_inside(e, p) for p in by[parent]), (name, e)
    # the counters saw what the trace saw
    for name in trace.NAMES:
        assert eng.phases.summary()[name]["count"] == len(by[name])
    submitted = {tr.attrs[e]["req_id"] for e in by[trace.SUBMIT]}
    prefilled = [tr.attrs[e]["req_id"] for e in by[trace.PREFILL]]
    assert submitted == set(win.seqs)
    assert set(prefilled) <= submitted and len(set(prefilled)) == len(
        prefilled)
    ticks = [tr.attrs[e] for e in by[trace.STEP] if e in tr.attrs]
    assert ticks and all({"tick", "decoded", "admitted"} <= set(t)
                         for t in ticks)


def test_stamps_lie_between_due_and_first_token(traced_tiny):
    _, win, _, _, _ = traced_tiny
    due = tails.due_in(win.served, win.t_open, win.t_close)
    checked = 0
    for s in due:
        q = win.seqs.get(s.rid)
        if q is None or s.issued is None:
            continue
        # due <= submitted <= the step that admitted it starts <= admitted
        # <= its first token reaches the host
        assert s.due <= q.t_submit <= s.issued <= q.t_admit <= s.stamps[0]
        checked += 1
    assert checked >= 5
    assert all(x >= 0 for x in stamps.submit_lag(win))
    assert all(x >= 0 for x in stamps.admit_wait(win))


def test_the_new_readers_read_numbers_on_the_cpu(traced_tiny):
    cell, win, tr, eng, off = traced_tiny
    ctx = readers.Context(cell.config, win, 1.0, 0,
                          peaks.peak("TPU v5 lite"))
    for name in ("admit_wait_p95_ms", "submit_lag_p95_ms"):
        value = cells.metric_reader(name)(ctx)
        assert value is not None and value >= 0, name
    phases = eng.phases.summary()
    host_tick = (phases[trace.STEP]["total_ms"]
                 + phases[trace.FLUSH]["total_ms"]
                 - phases[trace.FLUSH_WAIT]["total_ms"]) \
        / phases[trace.STEP]["count"]
    assert 0 < host_tick < phases[trace.STEP]["longest_ms"] \
        + phases[trace.FLUSH]["longest_ms"]
    # the untraced window counted its spans and the collector's pauses
    assert off["engine_phases"][trace.STEP]["count"] > 0
    assert off["spans_per_tick"] > 2 and off["gc"]["count"] >= 0
    assert off["span_us_off"] > 0


def test_a_program_without_stamps_reads_nothing():
    @dataclasses.dataclass
    class OldSeq:                      # SeqState before the stamps
        req_id: int

    served = [tails.Served(0, 1.0, 16, 4)]
    win = openloop.Window(0.0, 2.0, served, [], {0: OldSeq(0)})
    ctx = readers.Context({}, win, 1.0, 0, {})
    assert stamps.submit_lag(win) is None
    assert cells.metric_reader("admit_wait_p95_ms")(ctx) is None
    assert cells.metric_reader("submit_lag_p95_ms")(ctx) is None


def test_scope_of_takes_the_deepest_scope_of_an_op_name():
    assert spans.scope_of("jit(step)/jit(main)/while/body/mlp/dot_general"
                          ) == scopes.MLP
    assert spans.scope_of("jit(step)/attn_kv/attn_proj/dot") == \
        scopes.ATTN_PROJ
    assert spans.scope_of('%fusion.3 = f32[2] fusion(), metadata={op_name='
                          '"jit(step)/lm_head/argmax"}') == scopes.LM_HEAD
    assert spans.scope_of("jit(step)/while/body/dynamic_slice") is None
    assert spans.scope_of("jit(step)/mlpx/dot") is None


def _recorded():
    """One tick: harness and engine spans, a decode step whose trunk is a
    ``while`` container over scoped leaf ops, then a flush; a prefill."""
    h = lambda n, a, b: Event(HOST, "python", n, a * MS, b * MS)  # noqa
    d = lambda line, n, a, b: Event(DEV, line, n, a * MS, b * MS)  # noqa
    host = [
        h("window", 0, 200),
        h("step", 0, 112), h(trace.STEP, 1, 111),
        h(trace.SCHEDULE, 1, 3), h(trace.PAGES, 3, 4),
        h(trace.DECODE, 4, 6), h(trace.PREFILL, 6, 110),
        h("flush", 112, 160), h(trace.FLUSH, 112, 160),
        h(trace.FLUSH_WAIT, 112, 150), h(trace.FLUSH_RESOLVE, 150, 160),
        h("sleep", 165, 200),
    ]
    modules = [d("XLA Modules", "jit_step(3)", 5, 105),
               d("XLA Modules", "jit_prefill_scatter(4)", 120, 150)]
    ops = [
        Op(DEV, "fusion.7", 5, 15, scopes.EMBED),
        Op(DEV, "while.3", 15, 95),                       # the container
        Op(DEV, "fusion.1", 15, 45, scopes.MLP),
        Op(DEV, "fusion.2", 45, 65, scopes.ATTN_KV),
        Op(DEV, "copy.50", 65, 80),                        # unscoped
        Op(DEV, "fusion.4", 80, 95, scopes.ATTN_PROJ),
        Op(DEV, "fusion.8", 95, 100, scopes.LM_HEAD),      # 100-105 bubble
        Op(DEV, "fusion.9", 120, 150, scopes.MLP),
    ]
    ops = [dataclasses.replace(o, start=o.start * MS, end=o.end * MS)
           for o in ops]
    events = host + modules + [Event(o.plane, "XLA Ops", o.name, o.start,
                                     o.end) for o in ops]
    return spans.Trace(events, ops, {})


def test_scope_seconds_count_each_leaf_op_once():
    tr = _recorded()
    r = spans.reduce(tr, 0, 200 * MS, PROGRAMS, LABELS)
    dec = r.device_scopes["decode"]
    assert dec == pytest.approx({
        scopes.EMBED: 0.010, scopes.MLP: 0.030, scopes.ATTN_KV: 0.020,
        spans.UNSCOPED: 0.015, scopes.ATTN_PROJ: 0.015,
        scopes.LM_HEAD: 0.005})
    assert r.device_scopes["prefill"] == pytest.approx({scopes.MLP: 0.030})
    # scope seconds plus (unscoped) are the step's leaf busy time: the
    # module's 100 ms less its 5 ms bubble, the while counted through
    # its body only
    base = tracefile.reduce(tr.events, 0, 200 * MS, PROGRAMS, HARNESS)
    assert sum(dec.values()) == pytest.approx(
        base.programs["decode"][1] - 0.005)
    assert r.calls == {"decode": 1, "prefill": 1}
    assert r.scope_s_per_call("decode", scopes.ATTN_KV) == \
        pytest.approx(0.020)
    assert r.scope_s_per_call("decode", "nothing") is None
    assert [o.name for o in spans.leaves(tr.ops)] == [
        "fusion.7", "fusion.1", "fusion.2", "copy.50", "fusion.4",
        "fusion.8", "fusion.9"]


def test_idle_time_goes_to_the_innermost_span():
    tr = _recorded()
    r = spans.reduce(tr, 0, 200 * MS, PROGRAMS, LABELS)
    # idle: 0-5 (step, then engine.step's schedule/pages/decode), 100-105
    # inside the step's module (engine.prefill), 105-120 (prefill, then
    # the harness between step and flush, then engine.flush.wait),
    # 150-200 (resolve, the loop, sleep)
    idle = {k.split(":")[0]: v for k, v in r.idle_gaps_engine}
    assert idle == pytest.approx({
        "step": 0.001 + 0.001, trace.STEP: 0.001, trace.SCHEDULE: 0.002,
        trace.PAGES: 0.001, trace.DECODE: 0.001 + 0.0,
        trace.PREFILL: 0.005 + 0.005, trace.FLUSH_WAIT: 0.008,
        trace.FLUSH_RESOLVE: 0.010, spans.NONE: 0.005, "sleep": 0.035})
    # between programs: all of it but the 5 ms bubble inside jit_step
    assert r.between_s == pytest.approx(0.200 - 0.100 - 0.030)
    assert r.between_share == pytest.approx(0.35)
    assert r.between_by_span[trace.PREFILL] == pytest.approx(0.005)
    # host time per tick: step + flush - flush.wait
    assert r.host_tick_s() == pytest.approx(0.110 + 0.048 - 0.038)
    assert r.spans[trace.FLUSH_WAIT] == (1, pytest.approx(0.038))


def test_existing_readers_read_the_same_with_engine_spans():
    tr = _recorded()
    without = [e for e in tr.events if not e.name.startswith("engine.")]
    lo, hi = tracefile.window(tr.events, "window")
    with_spans = tracefile.reduce(tr.events, lo, hi, PROGRAMS, HARNESS)
    assert with_spans == tracefile.reduce(without, lo, hi, PROGRAMS, HARNESS)
    cell = tiny_cell()
    served = [tails.Served(0, 0.01, 16, 4, issued=0.02, stamps=[0.03, 0.05])]
    win = openloop.Window(0.0, 0.2, served, [[40, 41], [42]], {})
    peak = peaks.peak("TPU v5 lite")
    for m in cell.per_layer:
        read = cells.metric_reader(m["name"])
        a = read(readers.Context(cell.config, win, 1.0, 0, peak, with_spans))
        b = read(readers.Context(cell.config, win, 1.0, 0, peak,
                                 tracefile.reduce(without, lo, hi, PROGRAMS,
                                                  HARNESS)))
        assert a == b, m["name"]


def test_no_device_operations_is_an_error():
    tr = _recorded()
    bare = spans.Trace([e for e in tr.events if e.plane == HOST], [], {})
    with pytest.raises(ValueError, match="no device"):
        spans.reduce(bare, 0, 200 * MS, PROGRAMS, LABELS)


def test_attention_cost_by_hand():
    spec = tiny_cell().config          # 2 layers, 4 heads of 32, 2 KV heads
    contexts = [10, 30]
    # scores and values: 4 FLOPs per head, head dim, layer and token
    dense = cells.architecture("dense_decoder")
    assert dense.attn_flops(spec, contexts) == 4 * 2 * 4 * 32 * 40
    # K and V, float32, 2 KV heads of 32 per layer: read 40, write 2
    assert dense.attn_bytes(spec, contexts) == 2 * 2 * 2 * 32 * 4 * 42
    peak = {"bf16_flops": 1e12, "hbm_bytes_s": 1e9}
    least = scopecost.attn_least_seconds(spec, contexts, peak)
    assert least == pytest.approx(2 * 2 * 2 * 32 * 4 * 42 / 1e9)
    assert scopecost.attn_roofline(spec, [contexts], peak, 2 * least) == \
        pytest.approx(50.0)


def test_every_scope_is_read_from_the_compiled_programs(traced_tiny):
    cell, _, _, eng, _ = traced_tiny
    texts = list(attribute.program_texts(cell, eng, 2.0))
    buckets = openloop.decode_buckets(cell.config, cell.traffic, 2.0)
    lengths = traffic.prompt_lengths(cell.traffic, 2.0)
    assert len(texts) == len(buckets) + len(lengths)
    hlo = spans.HloScopes(texts)
    found = {hlo.scope(line) for text in texts for line in text.splitlines()}
    assert set(scopes.ALL) | {spans.UNSCOPED} == found


def test_a_tpu_op_event_finds_its_scope_by_instruction():
    text = """
  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/while/body/closed_call/mlp/dot_general" stack_frame_id=3}
  ROOT %copy.9 = f32[4]{0} copy(%q), metadata={op_name="jit(step)/while/body/dynamic_slice"}
  %fusion.4 = f32[8]{0} fusion(%r), kind=kLoop, calls=%d, metadata={op_name="jit(step)/attn_kv/gather"}
  %convert.13 = bf16[18,2048,11008]{2,1,0} convert(%params__trunk___0___ffn____w_gate__.1), backend_config={}
  %convert.10 = bf16[18,2048,2048]{2,1,0} convert(%params__trunk___0___attn____wo__.1)
  %convert.12 = bf16[18,2048,256]{2,1,0} convert(%custom-call.24)
"""
    other = """
  %fusion.4 = f32[16]{0} fusion(%r), kind=kLoop, calls=%d, metadata={op_name="jit(step)/lm_head/argmax"}
"""
    hlo = spans.HloScopes([text, other])
    # the trace's name is the instruction with its operands' types and
    # its layout's tiling, and no metadata
    assert hlo.scope("%fusion.3 = f32[2]{0} fusion(f32[2]{0:T(128)} %p), "
                     "kind=kLoop, calls=%c") == scopes.MLP
    assert hlo.scope("%copy.9 = f32[4]{0} copy(f32[4]{0} %q)") == \
        spans.UNSCOPED
    # one name, two programs, two scopes: the shape tells them apart
    assert hlo.scope("%fusion.4 = f32[16]{0} fusion(f32[16]{0} %r)") == \
        scopes.LM_HEAD
    assert hlo.scope("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %r)") == \
        scopes.ATTN_KV
    assert hlo.scope("%fusion.4 = f32[32]{0} fusion(f32[32]{0} %r)") == \
        spans.UNSCOPED
    assert hlo.scope("%nothing.1 = f32[1]{0} copy()") == spans.UNSCOPED
    # XLA's hoisted converts of the weights carry no op name: the weight
    # they read names the scope
    assert hlo.scope("%convert.13 = bf16[18,2048,11008]{2,1,0:T(8,128)(2,1)}"
                     " convert(f32[18,2048,11008]{2,1,0:T(8,128)} "
                     "%params__trunk___0___ffn____w_gate__.1)") == scopes.MLP
    assert hlo.scope("%convert.10 = bf16[18,2048,2048]{2,1,0} convert(f32["
                     "18,2048,2048]{2,1,0} %params__trunk___0___attn____wo__"
                     ".1)") == scopes.ATTN_PROJ
    assert hlo.scope("%convert.12 = bf16[18,2048,256]{2,1,0} convert(bf16["
                     "18,2048,256]{2,1,0} %custom-call.24)") == spans.UNSCOPED


def test_the_tool_reads_the_new_metrics_from_a_reduced_trace():
    tr = _recorded()
    r = spans.reduce(tr, 0, 200 * MS, PROGRAMS, LABELS)
    spec = tiny_cell().config
    win = openloop.Window(0.0, 0.2, [], [[40, 41]], {})
    got = attribute.new_metrics(spec, win, peaks.peak("TPU v5 lite"), r)
    assert got["host_tick_ms"] == pytest.approx(120.0)
    assert got["idle_between_programs"] == pytest.approx(35.0)
    assert got["decode_attn_ms"] == pytest.approx(20.0)
    assert got["decode_mlp_ms"] == pytest.approx(30.0)
    assert got["attn_roofline"] == pytest.approx(scopecost.attn_roofline(
        spec, [[40, 41]], peaks.peak("TPU v5 lite"), 0.020))
