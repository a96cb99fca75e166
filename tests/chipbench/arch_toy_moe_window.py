"""A toy architecture module: routed experts under windowed and full
attention, the block a mixed-window sparse-expert model brings.

The discovery test copies it into a copy of the benchmark as
``chipbench/architectures/toy_moe_window.py``.  The configuration file
gives the layers' kinds as ``layer_types`` ("sliding_attention" or
"full_attention", one per layer, as published configs do); the program's
``layer_pattern`` is their shortest period, each kind followed by the
program's ``moe`` feed-forward, and the layers past the last whole
period are its ``rem`` layers.  A windowed layer attends, and keeps, at
most ``sliding_window`` tokens, the new one included.

A decode step over ``rows`` rows reads, in each expert layer, the router,
the shared expert and E·(1 − (1 − k/E)^rows) of its E routed experts:
how many distinct experts ``rows`` rows that each pick k of E read, on
average, when the router spreads them evenly.
"""
from __future__ import annotations

import math
from typing import Sequence

from chipbench import modelspec


def pattern(spec: dict) -> tuple:
    kinds = ["attn" if t == "sliding_attention" else "attn_full"
             for t in spec["layer_types"]]
    period = next(p for p in range(1, len(kinds) + 1)
                  if all(k == kinds[i % p] for i, k in enumerate(kinds)))
    return tuple(f"{k}:moe" for k in kinds[:period])


def model_config(spec: dict):
    from repro.configs.base import ModelConfig
    a, z = spec["architecture"], modelspec.dims(spec)
    return ModelConfig(
        arch_id=spec["name"], family="moe", n_layers=z["layers"],
        d_model=z["d"], n_heads=z["h"], n_kv_heads=z["kvh"], d_head=z["dh"],
        d_ff=0, vocab_size=z["vocab"], layer_pattern=pattern(spec),
        norm=a["norm"], act="silu", qkv_bias=bool(a["qkv_bias"]),
        out_bias=bool(a["o_bias"]), tie_embeddings=z["tied"],
        rope_theta=float(spec["rope_theta"]),
        window=int(spec["sliding_window"]),
        n_experts=spec["num_experts"], top_k=spec["num_experts_per_tok"],
        expert_d_ff=spec["moe_intermediate_size"],
        n_shared_experts=1,
        shared_expert_d_ff=spec["shared_expert_intermediate_size"],
        norm_eps=modelspec.norm_eps(spec), source=spec["source"])


def _layer(spec: dict, lead: tuple) -> dict:
    """{path under the layer: (shape, init, scale)}; ``lead`` is the
    layer axis of a stacked group, or () for a remainder layer."""
    z, a = modelspec.dims(spec), spec["architecture"]
    d, q, kv = z["d"], z["h"] * z["dh"], z["kvh"] * z["dh"]
    E, f = spec["num_experts"], spec["moe_intermediate_size"]
    sf = spec["shared_expert_intermediate_size"]
    out = {}
    for norm in ("norm1", "norm2"):
        out[f"{norm}/scale"] = (lead + (d,), "one", 0.02)
        if a["norm"] == "ln":
            out[f"{norm}/bias"] = (lead + (d,), "normal", 0.02)
    for name, (i, o) in {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                         "wo": (q, d)}.items():
        out[f"attn/{name}"] = (lead + (i, o), "normal", 1 / math.sqrt(i))
    if a["qkv_bias"]:
        for name, o in {"bq": q, "bk": kv, "bv": kv}.items():
            out[f"attn/{name}"] = (lead + (o,), "normal", 0.02)
    if a["o_bias"]:
        out["attn/bo"] = (lead + (d,), "normal", 0.02)
    out["moe/router"] = (lead + (d, E), "normal", 1 / math.sqrt(d))
    for name, (i, o) in {"w_in": (d, f), "w_gate": (d, f),
                         "w_out": (f, d)}.items():
        out[f"moe/{name}"] = (lead + (E, i, o), "normal", 1 / math.sqrt(i))
    for name, (i, o) in {"w_in": (d, sf), "w_gate": (d, sf),
                         "w_out": (sf, d)}.items():
        out[f"moe/shared/{name}"] = (lead + (i, o), "normal",
                                     1 / math.sqrt(i))
    return out


def shapes(spec: dict) -> dict:
    z = modelspec.dims(spec)
    d, period = z["d"], len(pattern(spec))
    reps, rem = divmod(z["layers"], period)
    out = {"embed": ((z["vocab"], d), "normal",
                     float(spec["initializer_range"]))}
    groups = [(f"trunk/{i}", (reps,)) for i in range(period)]
    groups += [(f"rem/{i}", ()) for i in range(rem)]
    for prefix, lead in groups:
        for path, leaf in _layer(spec, lead).items():
            out[f"{prefix}/{path}"] = leaf
    out["final_norm/scale"] = ((d,), "one", 0.02)
    if not z["tied"]:
        out["head"] = ((d, z["vocab"]), "normal", 1 / math.sqrt(d))
    return out


def _windows(spec: dict) -> list:
    """Per layer, the most tokens it attends: its window, or None."""
    pat = pattern(spec)
    return [int(spec["sliding_window"]) if pat[i % len(pat)].startswith(
        "attn:") else None for i in range(spec["num_hidden_layers"])]


def _attended(spec: dict, contexts: Sequence[int]) -> int:
    """Tokens the rows attend, summed over rows and layers."""
    return sum(c if w is None else min(c, w)
               for w in _windows(spec) for c in contexts)


def _kv_token(spec: dict) -> int:
    z = modelspec.dims(spec)
    return 2 * z["kvh"] * z["dh"] * z["bytes"]


def _weight_bytes(spec: dict) -> int:
    return modelspec.dims(spec)["bytes"] * sum(
        math.prod(shape) for shape, _, _ in shapes(spec).values())


def _experts_read(spec: dict, rows: int) -> float:
    E, k = spec["num_experts"], spec["num_experts_per_tok"]
    return E * (1 - (1 - k / E) ** rows)


def attn_flops(spec: dict, contexts: Sequence[int]) -> float:
    z = modelspec.dims(spec)
    return float(4 * z["h"] * z["dh"] * _attended(spec, contexts))


def attn_bytes(spec: dict, contexts: Sequence[int]) -> float:
    z = modelspec.dims(spec)
    written = z["layers"] * len(contexts)
    return float(_kv_token(spec) * (_attended(spec, contexts) + written))


def decode_flops(spec: dict, contexts: Sequence[int]) -> float:
    z = modelspec.dims(spec)
    d, q, kv = z["d"], z["h"] * z["dh"], z["kvh"] * z["dh"]
    E, k = spec["num_experts"], spec["num_experts_per_tok"]
    f, sf = spec["moe_intermediate_size"], spec["shared_expert_intermediate_size"]
    per_layer = d * (2 * q + 2 * kv + E + 3 * k * f + 3 * sf)
    per_row = 2 * (z["layers"] * per_layer + d * z["vocab"])
    return float(len(contexts) * per_row) + attn_flops(spec, contexts)


def decode_bytes(spec: dict, contexts: Sequence[int]) -> float:
    z = modelspec.dims(spec)
    d, E = z["d"], spec["num_experts"]
    expert = 3 * d * spec["moe_intermediate_size"] * z["bytes"]
    unread = z["layers"] * (E - _experts_read(spec, len(contexts))) * expert
    # an untied embedding is gathered row by row, never read whole
    embed = 0 if z["tied"] else z["vocab"] * d * z["bytes"]
    weights = _weight_bytes(spec) - unread - embed
    rows = len(contexts) * d * z["bytes"]
    return float(weights + rows) + attn_bytes(spec, contexts)
