"""A configuration file as the program's ``ModelConfig``, and its sizes.

The file holds the published ``config.json`` keys (with the cuts listed
under ``reduced``) and an ``architecture`` block for what the modeling
code fixes and the file has no key for.  The program runs the model that
the file states: the harness builds the ``ModelConfig`` from these
numbers, not from the program's own registry.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def dims(spec: dict) -> Dict[str, int]:
    """The sizes every other module of the benchmark works from."""
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return {
        "d": d, "h": h, "kvh": spec["num_key_value_heads"],
        "dh": spec.get("head_dim", d // h), "ff": spec["intermediate_size"],
        "vocab": spec["vocab_size"], "layers": spec["num_hidden_layers"],
        "tied": bool(spec.get("tie_word_embeddings", False)),
        "bytes": DTYPE_BYTES[spec["torch_dtype"]],
    }


def rope_dims(spec: dict) -> int:
    dh = dims(spec)["dh"]
    rd = int(dh * spec.get("partial_rotary_factor", 1.0))
    return rd - rd % 2


def norm_eps(spec: dict) -> float:
    return float(spec["rms_norm_eps"] if spec["architecture"]["norm"] == "rms"
                 else spec["layer_norm_eps"])


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
        norm_eps=norm_eps(spec), source=spec["source"])


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
    z = dims(spec)
    return 2 * z["layers"] * z["kvh"] * z["dh"] * z["bytes"]
