"""Set-up and the measured window: the engine driven by an open loop.

The window drives ``ContinuousBatchingEngine.submit`` / ``step`` /
``flush`` with the paged cache and the engine's default decode path.
Requests are submitted at their due times whatever the engine is doing;
after every ``step`` the loop calls ``flush``, as a streaming server
would, and stamps each token that reached the host then.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from chipbench import modelspec, tails
from chipbench import traffic as traffic_mod

SPANS = ("submit", "step", "flush", "sleep")
WINDOW_SPAN = "window"


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    served: List[tails.Served]
    # per engine tick that decoded: the tokens each decoded row attended
    decode_steps: List[List[int]]
    seqs: dict                     # rid -> the engine's SeqState
    pages_peak: int = 0            # most KV pool pages allocated at once
    pages: int = 0                 # pages the pool holds


def engine(spec: dict, params):
    from repro.serving import ContinuousBatchingEngine
    s = spec["serving"]
    return ContinuousBatchingEngine(
        modelspec.model_config(spec), params, n_slots=s["n_slots"],
        max_len=s["max_len"], page_size=s["page_size"])


def decode_buckets(spec: dict, traffic: dict, seconds: float) -> range:
    """Every live-page bucket ``mp`` the engine can pick for this mix:
    from a lone sequence of the shortest prompt to the longest request,
    by the engine's own rule ((pos - 1) // page + 1, at most max_pages)."""
    s = spec["serving"]
    ps = s["page_size"]
    lo = min(traffic_mod.prompt_lengths(traffic, seconds)) // ps + 1
    hi = (traffic_mod.longest(traffic, seconds) - 1) // ps + 1
    return range(max(lo, 1), min(hi, -(-s["max_len"] // ps)) + 1)


def warm(spec: dict, params, traffic: dict, seconds: float, seed: int):
    """Compile (or load) every program the window will run, then return
    a fresh engine for the window.  Prompt lengths run through a
    throwaway engine by the public path; each decode bucket runs once
    with the page table on the host and once on the device, as the
    engine hands it over on ticks that do and do not change it."""
    vocab = modelspec.dims(spec)["vocab"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    eng = engine(spec, params)
    for n in traffic_mod.prompt_lengths(traffic, seconds):
        eng.submit(rng.integers(0, vocab, n).tolist(), 2)
    eng.run()
    del eng
    gc.collect()
    eng = engine(spec, params)
    table = eng.pages.device_table()
    for mp in decode_buckets(spec, traffic, seconds):
        for pages in (np.asarray(table), table):
            jax.block_until_ready(eng._step(
                eng.params, dict(eng.cache, pages=pages), eng._last_tok,
                mp=mp))
    return eng


def drive(eng, requests: List[traffic_mod.Request], seconds: float,
          span: Optional[Callable[[str], contextlib.AbstractContextManager]]
          = None) -> Window:
    """Run the open loop for ``seconds``; returns what the host saw."""
    span = span or (lambda name: contextlib.nullcontext())
    clock = time.perf_counter
    gc.collect()
    gc.freeze()
    t_open = clock()
    t_close = t_open + seconds
    served = [tails.Served(r.rid, t_open + r.due, len(r.prompt), r.n_out)
              for r in requests]
    seqs, live, steps = {}, [], []
    nxt, pages_peak = 0, 0
    with span(WINDOW_SPAN):
        while True:
            now = clock()
            if now >= t_close:
                break
            if nxt < len(served) and served[nxt].due <= now:
                with span("submit"):
                    while nxt < len(served) and served[nxt].due <= now:
                        r = requests[nxt]
                        eng.submit(r.prompt, r.n_out, req_id=r.rid)
                        seq = eng.sched.queue[-1]
                        assert seq.req_id == r.rid
                        seqs[r.rid] = seq
                        live.append((served[nxt], seq))
                        nxt += 1
            t0 = clock()
            with span("step"):
                ran = eng.step()
            if not ran:
                due = served[nxt].due if nxt < len(served) else t_close
                with span("sleep"):
                    time.sleep(max(0.0, min(due, t_close) - clock()))
                continue
            with span("flush"):
                eng.flush()
            t1 = clock()
            pages_peak = max(pages_peak, eng.pages.n_allocated)
            dec, still = [], []
            for s, seq in live:
                n = len(seq.generated)
                if n > len(s.stamps):
                    if not s.stamps:
                        s.issued = t0
                    else:
                        dec.append(s.prompt_len + n - 1)
                    s.stamps.extend([t1] * (n - len(s.stamps)))
                if n < s.n_out:
                    still.append((s, seq))
            live = still
            if dec:
                steps.append(dec)
    gc.unfreeze()
    return Window(t_open, t_close, served, steps, seqs, pages_peak,
                  eng.n_pages)
