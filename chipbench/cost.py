"""Operations and bytes that a decode step needs, from shapes alone.

Counted is what the algorithm needs, not what the compiled program
does: weights read once at their stored dtype, the live KV of each
decoded row read once, and its new K/V written once.  The copy of the
KV pool that the step makes today, and XLA's converts of float32
weights to bfloat16, are not needed and not counted.
"""
from __future__ import annotations

from typing import Sequence

from chipbench import modelspec


def _matmul_params(spec: dict) -> int:
    z = modelspec.dims(spec)
    q, kv = z["h"] * z["dh"], z["kvh"] * z["dh"]
    return z["d"] * (2 * q + 2 * kv + 3 * z["ff"])


def decode_flops(spec: dict, contexts: Sequence[int]) -> float:
    """One decode step over rows whose contexts (tokens attended, the
    new one included) are ``contexts``: projections, MLP, attention
    scores and values, and the logits."""
    z = modelspec.dims(spec)
    per_row = 2 * (z["layers"] * _matmul_params(spec) + z["d"] * z["vocab"])
    attn = 4 * z["layers"] * z["h"] * z["dh"]
    return float(len(contexts) * per_row + attn * sum(contexts))


def decode_bytes(spec: dict, contexts: Sequence[int]) -> float:
    z = modelspec.dims(spec)
    embed_rows = len(contexts) * z["d"] * z["bytes"]
    if not z["tied"]:
        # the untied embedding is gathered row by row, never read whole
        weights = modelspec.weight_bytes(spec) - z["vocab"] * z["d"] * z["bytes"]
    else:
        weights = modelspec.weight_bytes(spec)
    kv = modelspec.kv_bytes_per_token(spec)
    return float(weights + embed_rows + kv * (sum(contexts) + len(contexts)))


def least_seconds(spec: dict, contexts: Sequence[int], peak: dict) -> float:
    return max(decode_flops(spec, contexts) / peak["bf16_flops"],
               decode_bytes(spec, contexts) / peak["hbm_bytes_s"])
