"""Put a cell's host time, idle gaps and step time down to the program's
own spans, stamps and named scopes.

    python chipbench/attribute.py <cell> --seed <n> --seconds 51 \
        [--python-tracer 0]

Run on the chip.  One process: set-up as ``run.measure`` does it, then
two windows of the cell's traffic on fresh engines, the first with the
profiler off and the second traced.  Each window reports the engine's
per-span counters (count, total and longest ms), Python's garbage
collections and the stamps' tails; the traced one also the per-layer
metrics that read the engine spans and the model's scopes
(``chipbench/spans.py``), beside the benchmark's own readers on the same
trace.  Before each window it times one span, entered and left, with
the profiler off and on.  The traced window profiles Python calls too,
as ``run.py --trace 1`` does; ``--python-tracer 0`` leaves them out, to
see what that tracer costs the host.  The result is one JSON line, also
written to ``chiprun_out/attribute-<cell>-<seed>-py<0|1>.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402  (sets the compile cache up)
from chipbench import (cells, modelspec, openloop, peaks,  # noqa: E402
                       readers, scopecost, spans, stamps, tails, tracefile,
                       traffic, weights)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.models import scopes  # noqa: E402
from repro.serving import trace  # noqa: E402


class GcPauses:
    """Count and longest pause of Python's garbage collector."""

    def __init__(self):
        self.count, self.longest_ms, self._t0 = 0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.longest_ms = max(self.longest_ms,
                                  1e3 * (time.perf_counter() - self._t0))
            self._t0 = None


def span_us(n: int = 100000) -> float:
    """Host microseconds to enter and leave one span carrying a stat."""
    ph = trace.Phases()
    t0 = time.perf_counter()
    for i in range(n):
        with trace.span(ph, trace.SUBMIT, req_id=i):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


def _options(python_tracer: int):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = python_tracer
    return opts


def _ms(xs, q):
    return 1e3 * tails.percentile(xs, q) if xs else None


def program_texts(cell, eng, seconds):
    """Compiled HLO text of every program the window can run: the decode
    step of each page bucket and the prefill of each prompt length.
    After ``openloop.warm`` each is a hit in JAX's in-memory cache."""
    for mp in openloop.decode_buckets(cell.config, cell.traffic, seconds):
        yield eng._step.lower(eng.params, eng.cache, eng._last_tok,
                              mp=mp).compile().as_text()
    for n in traffic.prompt_lengths(cell.traffic, seconds):
        tokens = jnp.zeros((1, n), jnp.int32)
        yield eng._prefill_scatter.lower(eng.params, eng.cache,
                                         eng._last_tok, tokens,
                                         0).compile().as_text()


def window(cell, eng, requests, seconds, traced, python_tracer=1):
    """One window on a fresh engine (``openloop.warm``).  Returns the
    window's numbers, the window, and for a traced one
    (``tracefile.reduce``, ``spans.reduce``, the loaded trace)."""
    out = {"span_us_off": span_us()}
    tdir = None
    if traced:
        tdir = tempfile.mkdtemp(prefix="chipbench-attribute-")
        jax.profiler.start_trace(tdir, profiler_options=_options(
            python_tracer))
        out["span_us_on"] = span_us(20000)
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        win = openloop.drive(eng, requests, seconds,
                             jax.profiler.TraceAnnotation if traced else None)
    finally:
        gc.callbacks.remove(pauses)
    if traced:
        jax.profiler.stop_trace()
    phases = eng.phases.summary()
    ticks = phases.get(trace.STEP, {}).get("count", 0)
    out.update(
        engine_phases=phases,
        spans_per_tick=(sum(p["count"] for p in phases.values()) / ticks
                        if ticks else None),
        gc={"count": pauses.count, "longest_ms": pauses.longest_ms},
        submit_lag_p95_ms=_ms(stamps.submit_lag(win), 95),
        admit_wait_p95_ms=_ms(stamps.admit_wait(win), 95),
        admit_wait_p50_ms=_ms(stamps.admit_wait(win), 50))
    if not traced:
        return out, win, None
    hlo = spans.HloScopes(program_texts(cell, eng, seconds))
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    labels = openloop.SPANS + trace.NAMES
    tr = spans.load(found[0], labels + (openloop.WINDOW_SPAN,), hlo)
    shutil.rmtree(tdir, ignore_errors=True)
    lo, hi = tracefile.window(tr.events, openloop.WINDOW_SPAN)
    base = tracefile.reduce(tr.events, lo, hi, run.PROGRAMS, openloop.SPANS)
    red = spans.reduce(tr, lo, hi, run.PROGRAMS, labels)
    out["spans_in_trace"] = {n: [c, 1e3 * s] for n, (c, s)
                             in sorted(red.spans.items())}
    out["breakdown"] = {"device_ops": base.top_ops,
                        "idle_gaps": base.idle_gaps,
                        "device_scopes": red.device_scopes,
                        "idle_gaps_engine": red.idle_gaps_engine[:12],
                        "between_programs": sorted(
                            red.between_by_span.items(),
                            key=lambda kv: -kv[1])[:12]}
    _, step_s = base.programs["decode"]
    scoped = sum(red.device_scopes["decode"].values())
    out["decode_scoped_over_step"] = scoped / step_s if step_s else None
    out["idle_share"] = 100.0 * base.idle_share
    out["busy_s"], out["window_s"] = base.busy_s, base.window_s
    return out, win, (base, red, tr)


def new_metrics(spec, win, peak, red) -> dict:
    """The per-layer metrics that read the engine spans and the scopes."""
    def ms(s):
        return None if s is None else 1e3 * s
    attn = red.scope_s_per_call("decode", scopes.ATTN_KV)
    return {
        "host_tick_ms": ms(red.host_tick_s()),
        "idle_between_programs": 100.0 * red.between_share,
        "decode_attn_ms": ms(attn),
        "decode_mlp_ms": ms(red.scope_s_per_call("decode", scopes.MLP)),
        "attn_roofline": (scopecost.attn_roofline(spec, win.decode_steps,
                                                  peak, attn)
                          if attn and win.decode_steps else None)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    device = run.setup_process(cell.chips)
    peak = peaks.peak(device.device_kind)
    spec = cell.config
    vocab = modelspec.dims(spec)["vocab"]
    requests = traffic.generate(cell.traffic, args.seed, args.seconds, vocab)
    params = weights.make(spec, args.seed)
    result = {"cell": cell.name, "seed": args.seed,
              "device": device.device_kind,
              "python_tracer": args.python_tracer}
    eng = openloop.warm(spec, params, cell.traffic, args.seconds, args.seed)
    setup_s = time.perf_counter() - T_START
    off, win, _ = window(cell, eng, requests, args.seconds, False)
    ctx = readers.Context(spec, win, setup_s, 0, peak)
    for m in cell.end_to_end:
        off[m["name"]] = cells.metric_reader(m["name"])(ctx)
    result["profiler_off"] = off
    del eng
    gc.collect()
    eng = openloop.warm(spec, params, cell.traffic, args.seconds, args.seed)
    on, win, (base, red, _) = window(cell, eng, requests, args.seconds,
                                     True, args.python_tracer)
    ctx = readers.Context(spec, win, setup_s, 0, peak, base)
    for m in cell.per_layer:
        on[m["name"]] = cells.metric_reader(m["name"])(ctx)
    on["new"] = new_metrics(spec, win, peak, red)
    result["profiler_on"] = on
    line = json.dumps(result)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"attribute-{cell.name}-"
                           f"{args.seed}-py{args.python_tracer}.json"),
              "w") as f:
        f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
