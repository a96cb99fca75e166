"""The least time the ``attn_kv`` scope of a decode step needs, from
shapes alone, as ``cost.py`` takes it for the whole step.

The operations and bytes are the architecture module's (``attn_flops``,
``attn_bytes``): the scores and values over each row's context, its live
KV read once and its new K/V written once.  The copy of the pool and the
gather's materialized pages are not needed and not counted.
"""
from __future__ import annotations

from typing import Sequence

from chipbench import modelspec


def attn_least_seconds(spec: dict, contexts: Sequence[int],
                       peak: dict) -> float:
    m = modelspec.module(spec)
    return max(m.attn_flops(spec, contexts) / peak["bf16_flops"],
               m.attn_bytes(spec, contexts) / peak["hbm_bytes_s"])


def attn_roofline(spec: dict, steps: Sequence[Sequence[int]], peak: dict,
                  attn_s_per_step: float) -> float:
    """Percent: the least time of the attention's needed bytes and FLOPs
    over its device time (mean per step on both sides)."""
    least = sum(attn_least_seconds(spec, c, peak) for c in steps)
    return 100.0 * least / len(steps) / attn_s_per_step
