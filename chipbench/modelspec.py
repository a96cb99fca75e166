"""A configuration file's sizes, and the architecture module it names.

The file holds the published ``config.json`` keys (with the cuts listed
under ``reduced``) and an ``architecture`` block for what the modeling
code fixes and the file has no key for.  ``architecture.module`` names
the file under ``chipbench/architectures/`` that knows the block's
shape: the program's ``ModelConfig``, the weights' leaves and the counts
of a decode step (see ``cells.architecture``).  What every file has is
read here.  The program runs the model that the file states: the harness
builds the ``ModelConfig`` from these numbers, not from the program's
own registry.
"""
from __future__ import annotations

from typing import Dict

from chipbench import cells

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def dims(spec: dict) -> Dict[str, int]:
    """The sizes every configuration file gives."""
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return {
        "d": d, "h": h, "kvh": spec["num_key_value_heads"],
        "dh": spec.get("head_dim", d // h),
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


def module(spec: dict):
    """The architecture module the file names."""
    return cells.architecture(spec["architecture"]["module"])


def model_config(spec: dict):
    """The program's ``ModelConfig`` for this file."""
    return module(spec).model_config(spec)
