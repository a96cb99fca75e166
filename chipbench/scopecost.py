"""Operations and bytes that the ``attn_kv`` scope of a decode step
needs, from shapes alone, as ``cost.py`` counts them for the whole step.

Counted: the live KV of each decoded row read once and its new K/V
written once, and the scores and values over that context.  The copy of
the pool and the gather's materialized pages are not needed and not
counted.
"""
from __future__ import annotations

from typing import Sequence

from chipbench import modelspec


def attn_flops(spec: dict, contexts: Sequence[int]) -> float:
    """Scores and values of one decode step over rows whose contexts
    (tokens attended, the new one included) are ``contexts``."""
    z = modelspec.dims(spec)
    return float(4 * z["layers"] * z["h"] * z["dh"] * sum(contexts))


def attn_bytes(spec: dict, contexts: Sequence[int]) -> float:
    kv = modelspec.kv_bytes_per_token(spec)
    return float(kv * (sum(contexts) + len(contexts)))


def attn_least_seconds(spec: dict, contexts: Sequence[int],
                       peak: dict) -> float:
    return max(attn_flops(spec, contexts) / peak["bf16_flops"],
               attn_bytes(spec, contexts) / peak["hbm_bytes_s"])


def attn_roofline(spec: dict, steps: Sequence[Sequence[int]], peak: dict,
                  attn_s_per_step: float) -> float:
    """Percent: the least time of the attention's needed bytes and FLOPs
    over its device time (mean per step on both sides)."""
    least = sum(attn_least_seconds(spec, c, peak) for c in steps)
    return 100.0 * least / len(steps) / attn_s_per_step
