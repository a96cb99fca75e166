"""FLOP and byte counts against hand-worked shapes; the peaks table;
the refusal off the TPU."""
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import cost, modelspec, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a hand-sized model: d 8, 2 heads of 4, 1 KV head, ff 16, vocab 10, 3 layers
SPEC = {
    "name": "hand", "hidden_size": 8, "num_attention_heads": 2,
    "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
    "num_hidden_layers": 3, "tie_word_embeddings": True,
    "torch_dtype": "float32",
    "architecture": {"norm": "rms", "qkv_bias": True, "o_bias": False,
                     "mlp": "gated_silu"},
}


def test_sizes_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16; biases 8+4+4; norms 2x8
    assert modelspec.layer_params(SPEC) == 64 + 32 + 32 + 64 + 384 + 16 + 16
    # 3 layers, embed 10x8 (tied), final norm 8, all float32
    assert modelspec.weight_bytes(SPEC) == 4 * (3 * 608 + 80 + 8)
    # K and V, 3 layers, 1 head of 4, float32
    assert modelspec.kv_bytes_per_token(SPEC) == 2 * 3 * 4 * 4


def test_decode_flops_by_hand():
    # per row: 2 * (3 layers * 8*(2*8 + 2*4 + 3*16) + 8*10 logits) = 3616
    # attention: 4 * 3 layers * 2 heads * 4 dims * context
    assert cost.decode_flops(SPEC, [5, 7]) == 2 * 3616 + 96 * 12


def test_decode_bytes_by_hand():
    weights = 4 * (3 * 608 + 80 + 8)
    rows = 2 * 8 * 4                           # embedding rows gathered
    kv = 96 * (5 + 7 + 2)                      # live KV read, new K/V written
    assert cost.decode_bytes(SPEC, [5, 7]) == weights + rows + kv
    untied = dict(SPEC, tie_word_embeddings=False)
    # an untied embedding is gathered, not read whole; the head is read
    assert cost.decode_bytes(untied, [5, 7]) == \
        weights + 4 * 80 + rows + kv - 4 * 80


def test_least_time_is_the_larger_bound():
    peak = {"bf16_flops": 1e3, "hbm_bytes_s": 1e9}
    assert cost.least_seconds(SPEC, [5], peak) == \
        cost.decode_flops(SPEC, [5]) / 1e3
    peak = {"bf16_flops": 1e15, "hbm_bytes_s": 1e3}
    assert cost.least_seconds(SPEC, [5], peak) == \
        cost.decode_bytes(SPEC, [5]) / 1e3


def test_published_widths_give_the_issue_sizes():
    from chipbench import cells
    q, s = cells.config("qwen2.5-3b"), cells.config("stablelm-1.6b")
    assert modelspec.kv_bytes_per_token(q) == 36_864
    assert modelspec.kv_bytes_per_token(s) == 196_608
    assert round(modelspec.weight_bytes(q) / 1e9, 1) == 6.8
    assert round(modelspec.weight_bytes(s) / 1e9, 2) == 4.11


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
