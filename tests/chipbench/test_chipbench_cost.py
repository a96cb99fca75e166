"""FLOP and byte counts against hand-worked shapes; the dense module
against the readings of the tree before it was one; the peaks table; the
refusal off the TPU."""
import hashlib
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench_tiny import tiny_spec
from chipbench import cells, cost, modelspec, peaks, weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a hand-sized model: d 8, 2 heads of 4, 1 KV head, ff 16, vocab 10, 3 layers
SPEC = {
    "name": "hand", "hidden_size": 8, "num_attention_heads": 2,
    "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
    "num_hidden_layers": 3, "tie_word_embeddings": True,
    "torch_dtype": "float32",
    "architecture": {"module": "dense_decoder", "norm": "rms",
                     "qkv_bias": True, "o_bias": False, "mlp": "gated_silu"},
}
dense = cells.architecture("dense_decoder")


def test_sizes_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16; biases 8+4+4; norms 2x8
    assert dense.layer_params(SPEC) == 64 + 32 + 32 + 64 + 384 + 16 + 16
    # 3 layers, embed 10x8 (tied), final norm 8, all float32
    assert dense.weight_bytes(SPEC) == 4 * (3 * 608 + 80 + 8)
    # K and V, 3 layers, 1 head of 4, float32
    assert dense.kv_bytes_per_token(SPEC) == 2 * 3 * 4 * 4


def test_decode_flops_by_hand():
    # per row: 2 * (3 layers * 8*(2*8 + 2*4 + 3*16) + 8*10 logits) = 3616
    # attention: 4 * 3 layers * 2 heads * 4 dims * context
    assert dense.decode_flops(SPEC, [5, 7]) == 2 * 3616 + 96 * 12


def test_decode_bytes_by_hand():
    weights = 4 * (3 * 608 + 80 + 8)
    rows = 2 * 8 * 4                           # embedding rows gathered
    kv = 96 * (5 + 7 + 2)                      # live KV read, new K/V written
    assert dense.decode_bytes(SPEC, [5, 7]) == weights + rows + kv
    untied = dict(SPEC, tie_word_embeddings=False)
    # an untied embedding is gathered, not read whole; the head is read
    assert dense.decode_bytes(untied, [5, 7]) == \
        weights + 4 * 80 + rows + kv - 4 * 80


def test_least_time_is_the_larger_bound():
    peak = {"bf16_flops": 1e3, "hbm_bytes_s": 1e9}
    assert cost.least_seconds(SPEC, [5], peak) == \
        dense.decode_flops(SPEC, [5]) / 1e3
    peak = {"bf16_flops": 1e15, "hbm_bytes_s": 1e3}
    assert cost.least_seconds(SPEC, [5], peak) == \
        dense.decode_bytes(SPEC, [5]) / 1e3


def test_published_widths_give_the_issue_sizes():
    q, s = cells.config("qwen2.5-3b"), cells.config("stablelm-1.6b")
    assert dense.kv_bytes_per_token(q) == 36_864
    assert dense.kv_bytes_per_token(s) == 196_608
    assert round(dense.weight_bytes(q) / 1e9, 1) == 6.8
    assert round(dense.weight_bytes(s) / 1e9, 2) == 4.11


# What the tree before the architecture modules (commit f011897, whose
# dense block sat in modelspec.py, weights.py, cost.py and scopecost.py)
# read for each configuration file at published widths, at CONTEXTS and
# the v5e's peaks.  Each count was printed with repr() by that tree's own
# functions (modelspec.model_config and weight_bytes, cost.decode_flops,
# decode_bytes and least_seconds, scopecost.attn_flops and attn_bytes);
# "shapes_sha" is sha256 of repr(sorted(weights.shapes(spec).items())),
# and "tiny_sha" is _weights_sha of weights.make(tiny_spec(config),
# 2**33 + 5) on the CPU.  The dense module has to read the same, bit for
# bit.
CONTEXTS = [1, 100, 1024, 1792]
PARENT = {
    "qwen2.5-3b": {
        "decode_flops": 14017576960.0, "decode_bytes": 6901923840.0,
        "least_seconds": 0.008427257435897437,
        "attn_flops": 430129152.0, "attn_bytes": 107679744.0,
        "weight_bytes": 6794211328,
        "shapes_sha": "b7022613c34b2fcd77cdd39de039b62f"
                      "205cc22df8aca55647a29d86d0cf9ccf",
        "tiny_sha": "04bb7f2564aa338739b13eb2929029fd"
                    "7adbd5164904f149b012bb8631bca9b7",
        "config": dict(d_model=2048, n_heads=16, n_kv_heads=2, d_head=128,
                       d_ff=11008, vocab_size=151936, n_layers=18,
                       norm="rms", tie_embeddings=True, rope_theta=1e6,
                       rope_pct=1.0, norm_eps=1e-6,
                       source="https://huggingface.co/Qwen/Qwen2.5-3B"),
    },
    "stablelm-1.6b": {
        "decode_flops": 6863421440.0, "decode_bytes": 3863363584.0,
        "least_seconds": 0.004717171653235653,
        "attn_flops": 286752768.0, "attn_bytes": 574291968.0,
        "weight_bytes": 4111122432,
        "shapes_sha": "999b48282761f97ddd5652840b37455a"
                      "b4249836cd415375a9b0fbfdcb6086d0",
        "tiny_sha": "e197d69947a7ee8bb84f1d7a853a3b99"
                    "a9d5e21a110982beabdaf91412d4a1d9",
        "config": dict(d_model=2048, n_heads=32, n_kv_heads=32, d_head=64,
                       d_ff=5632, vocab_size=100352, n_layers=12,
                       norm="ln", tie_embeddings=False, rope_theta=10000.0,
                       rope_pct=0.25, norm_eps=1e-5,
                       source="https://huggingface.co/stabilityai/"
                              "stablelm-2-1_6b"),
    },
}


def _weights_sha(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", list(PARENT))
def test_the_dense_module_counts_as_before(config):
    from repro.configs.base import ModelConfig
    spec, want = cells.config(config), PARENT[config]
    assert modelspec.module(spec) is dense
    assert modelspec.model_config(spec) == ModelConfig(
        arch_id=config, family="dense", layer_pattern=("attn:dense",),
        act="silu", qkv_bias=True, out_bias=False, **want["config"])
    peak = peaks.peak("TPU v5 lite")
    got = {"decode_flops": dense.decode_flops(spec, CONTEXTS),
           "decode_bytes": dense.decode_bytes(spec, CONTEXTS),
           "least_seconds": cost.least_seconds(spec, CONTEXTS, peak),
           "attn_flops": dense.attn_flops(spec, CONTEXTS),
           "attn_bytes": dense.attn_bytes(spec, CONTEXTS),
           "weight_bytes": dense.weight_bytes(spec)}
    for name, value in got.items():
        assert value == want[name], name
    table = repr(sorted(dense.shapes(spec).items()))
    assert hashlib.sha256(table.encode()).hexdigest() == want["shapes_sha"]


@pytest.mark.parametrize("config", list(PARENT))
def test_the_weights_are_the_same_bits_as_before(config):
    tree = weights.make(tiny_spec(config), 2**33 + 5)
    assert _weights_sha(tree) == PARENT[config]["tiny_sha"]


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2.5-3b.chat",
         "--seed", "1", "--seconds", "1", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_off_the_tpu_it_exits_non_zero_naming_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_with_only_the_benchmark_files_it_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
