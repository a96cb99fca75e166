"""The dense decoder block: Qwen2 and StableLM-2.

Every layer is the same: GQA or MHA attention over the whole context,
then a gated SiLU MLP of ``intermediate_size``.  The program runs it as
the layer pattern ``("attn:dense",)``, so the weights are one stacked
group, ``trunk/0``, and no remainder layers.

The counts of a decode step are what the algorithm needs, not what the
compiled program does: weights read once at their stored dtype, the live
KV of each decoded row read once, and its new K/V written once.  The
copy of the KV pool and XLA's converts of float32 weights to bfloat16
are not needed and not counted.  The attention's share (``attn_*``) is
the ``attn_kv`` scope's: the scores and values over the context and the
KV they read and write.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

from chipbench import modelspec


def dims(spec: dict) -> Dict[str, int]:
    """``modelspec.dims`` and the MLP's width."""
    return dict(modelspec.dims(spec), ff=spec["intermediate_size"])


def model_config(spec: dict):
    """The program's ``ModelConfig`` for this file."""
    from repro.configs.base import ModelConfig
    a, z = spec["architecture"], dims(spec)
    if a["mlp"] != "gated_silu" or spec["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: the program's dense block is a "
                         f"gated SiLU MLP, not {a['mlp']}/{spec['hidden_act']}")
    if spec["torch_dtype"] != "float32":
        raise ValueError(f"{spec['name']}: the engine serves float32 only")
    return ModelConfig(
        arch_id=spec["name"], family="dense", n_layers=z["layers"],
        d_model=z["d"], n_heads=z["h"], n_kv_heads=z["kvh"], d_head=z["dh"],
        d_ff=z["ff"], vocab_size=z["vocab"], layer_pattern=("attn:dense",),
        norm=a["norm"], act="silu", qkv_bias=bool(a["qkv_bias"]),
        out_bias=bool(a["o_bias"]), tie_embeddings=z["tied"],
        rope_theta=float(spec["rope_theta"]),
        rope_pct=float(spec.get("partial_rotary_factor", 1.0)),
        norm_eps=modelspec.norm_eps(spec), source=spec["source"])


def shapes(spec: dict) -> dict:
    """{path: (shape, init, scale)} (see ``weights``); the one stacked
    group ``trunk/0`` carries the layer axis first."""
    z, a = dims(spec), spec["architecture"]
    d, L, q, kv = z["d"], z["layers"], z["h"] * z["dh"], z["kvh"] * z["dh"]
    std = float(spec["initializer_range"])
    out = {"embed": ((z["vocab"], d), "normal", std)}

    def norm(prefix, lead=()):
        out[f"{prefix}/scale"] = (lead + (d,), "one", 0.02)
        if a["norm"] == "ln":
            out[f"{prefix}/bias"] = (lead + (d,), "normal", 0.02)

    t = "trunk/0"
    norm(f"{t}/norm1", (L,))
    norm(f"{t}/norm2", (L,))
    for name, (i, o) in {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                         "wo": (q, d)}.items():
        out[f"{t}/attn/{name}"] = ((L, i, o), "normal", 1 / math.sqrt(i))
    if a["qkv_bias"]:
        for name, o in {"bq": q, "bk": kv, "bv": kv}.items():
            out[f"{t}/attn/{name}"] = ((L, o), "normal", 0.02)
    if a["o_bias"]:
        out[f"{t}/attn/bo"] = ((L, d), "normal", 0.02)
    for name, (i, o) in {"w_in": (d, z["ff"]), "w_gate": (d, z["ff"]),
                         "w_out": (z["ff"], d)}.items():
        out[f"{t}/ffn/{name}"] = ((L, i, o), "normal", 1 / math.sqrt(i))
    norm("final_norm")
    if not z["tied"]:
        out["head"] = ((d, z["vocab"]), "normal", 1 / math.sqrt(d))
    return out


def layer_params(spec: dict) -> int:
    """Weights of one layer, biases and norms included."""
    z, a = dims(spec), spec["architecture"]
    d, q, kv = z["d"], z["h"] * z["dh"], z["kvh"] * z["dh"]
    n = d * q + 2 * d * kv + q * d + 3 * d * z["ff"]
    if a["qkv_bias"]:
        n += q + 2 * kv
    if a["o_bias"]:
        n += d
    norm = 2 * d if a["norm"] == "ln" else d
    return n + 2 * norm


def weight_bytes(spec: dict) -> int:
    """Bytes of all weights as stored."""
    z, a = dims(spec), spec["architecture"]
    norm = 2 * z["d"] if a["norm"] == "ln" else z["d"]
    head = 0 if z["tied"] else z["vocab"] * z["d"]
    n = z["layers"] * layer_params(spec) + z["vocab"] * z["d"] + head + norm
    return n * z["bytes"]


def kv_bytes_per_token(spec: dict) -> int:
    """K and V that one token adds, over every layer."""
    z = dims(spec)
    return 2 * z["layers"] * z["kvh"] * z["dh"] * z["bytes"]


def _matmul_params(spec: dict) -> int:
    z = dims(spec)
    q, kv = z["h"] * z["dh"], z["kvh"] * z["dh"]
    return z["d"] * (2 * q + 2 * kv + 3 * z["ff"])


def decode_flops(spec: dict, contexts: Sequence[int]) -> float:
    """One decode step over rows whose contexts (tokens attended, the
    new one included) are ``contexts``: projections, MLP, attention
    scores and values, and the logits."""
    z = dims(spec)
    per_row = 2 * (z["layers"] * _matmul_params(spec) + z["d"] * z["vocab"])
    attn = 4 * z["layers"] * z["h"] * z["dh"]
    return float(len(contexts) * per_row + attn * sum(contexts))


def decode_bytes(spec: dict, contexts: Sequence[int]) -> float:
    z = dims(spec)
    embed_rows = len(contexts) * z["d"] * z["bytes"]
    if not z["tied"]:
        # the untied embedding is gathered row by row, never read whole
        weights = weight_bytes(spec) - z["vocab"] * z["d"] * z["bytes"]
    else:
        weights = weight_bytes(spec)
    kv = kv_bytes_per_token(spec)
    return float(weights + embed_rows + kv * (sum(contexts) + len(contexts)))


def attn_flops(spec: dict, contexts: Sequence[int]) -> float:
    """Scores and values of one decode step over rows whose contexts
    (tokens attended, the new one included) are ``contexts``."""
    z = dims(spec)
    return float(4 * z["layers"] * z["h"] * z["dh"] * sum(contexts))


def attn_bytes(spec: dict, contexts: Sequence[int]) -> float:
    kv = kv_bytes_per_token(spec)
    return float(kv * (sum(contexts) + len(contexts)))
