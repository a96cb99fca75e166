"""The arithmetic behind each metric file in ``metrics/``.

A reader takes the run's ``Context`` and returns a number, or None where
the run gave it nothing to read (no trace, no call of the program).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from chipbench import cost, modelspec, tails
from chipbench.openloop import Window
from chipbench.tracefile import Reduced


@dataclasses.dataclass
class Context:
    spec: dict
    window: Window
    setup_s: float
    window_compiles: int
    peak: dict
    reduced: Optional[Reduced] = None       # only with --trace 1


def _span(ctx):
    return ctx.window.served, ctx.window.t_open, ctx.window.t_close


def ttft_p95_ms(ctx):
    return 1e3 * tails.percentile(tails.ttft(*_span(ctx)), 95)


def ttft_p50_ms(ctx):
    return 1e3 * tails.percentile(tails.ttft(*_span(ctx)), 50)


def itl_p95_ms(ctx):
    gaps = tails.itl(*_span(ctx))
    return 1e3 * tails.percentile(gaps, 95) if gaps else None


def out_tok_s(ctx):
    w = ctx.window
    return tails.tokens_in(*_span(ctx)) / (w.t_close - w.t_open)


def setup_s(ctx):
    return ctx.setup_s


def queue_wait_p95_ms(ctx):
    return 1e3 * tails.percentile(tails.queue_wait(*_span(ctx)), 95)


def _program_ms(ctx, label):
    if ctx.reduced is None:
        return None
    calls, secs = ctx.reduced.programs[label]
    return 1e3 * secs / calls if calls else None


def prefill_ms(ctx):
    return _program_ms(ctx, "prefill")


def decode_step_ms(ctx):
    return _program_ms(ctx, "decode")


def decode_roofline(ctx):
    """Percent: the least time the steps' bytes and FLOPs need, over
    their device time (mean per step on both sides)."""
    ms, steps = decode_step_ms(ctx), ctx.window.decode_steps
    if not ms or not steps:
        return None
    least = sum(cost.least_seconds(ctx.spec, c, ctx.peak) for c in steps)
    return 100.0 * least / len(steps) / (ms / 1e3)


def decode_mfu(ctx):
    """Percent: model FLOPs per decode step over device time per step
    times the bf16 peak."""
    ms, steps = decode_step_ms(ctx), ctx.window.decode_steps
    if not ms or not steps:
        return None
    arch = modelspec.module(ctx.spec)
    flops = sum(arch.decode_flops(ctx.spec, c) for c in steps) / len(steps)
    return 100.0 * flops / (ms / 1e3) / ctx.peak["bf16_flops"]


def idle_share(ctx):
    if ctx.reduced is None:
        return None
    return 100.0 * ctx.reduced.idle_share


def window_compiles(ctx):
    return ctx.window_compiles
