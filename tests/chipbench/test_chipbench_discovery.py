"""The harness finds a cell's parts by name: a new cell, configuration,
traffic mix, per-layer metric and model block are new files and entries
only."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny  # (also puts the checkout on the path)
from chipbench import cells, modelspec, readers

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "chipbench"), dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))


def _files(root) -> dict:
    return {str(p): p.read_bytes() for p in root.rglob("*") if p.is_file()}


def _unchanged_but_the_entries(root, before: dict):
    """No file that was there changed, other than the list of entries."""
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, p


def test_a_new_cell_config_mix_and_metric_are_found_by_name(tmp_path):
    _copy_benchmark(tmp_path)
    before = _files(tmp_path)
    cfg = cells.config("qwen2.5-3b", str(tmp_path))
    cfg = dict(cfg, name="qwen2.5-3b-deep", num_hidden_layers=20)
    (tmp_path / "chipbench/configs/qwen2.5-3b-deep.json").write_text(
        json.dumps(cfg))
    mix = dict(cells.traffic("qwen2.5-3b.chat", str(tmp_path)))
    mix["arrivals"] = {"kind": "poisson", "rate_rps": 1.5}
    (tmp_path / "chipbench/traffic/deep.chat.json").write_text(
        json.dumps(mix))
    (tmp_path / "chipbench/metrics/steps_per_s.py").write_text(
        '"""steps_per_s: engine ticks per second of the window."""\n'
        "def read(ctx):\n"
        "    w = ctx.window\n"
        "    return len(w.decode_steps) / (w.t_close - w.t_open)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen2.5-3b-deep", "source": cfg["source"],
                             "file": "chipbench/configs/qwen2.5-3b-deep.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "deep.chat", "config": "qwen2.5-3b-deep",
                               "traffic": "deep.chat", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine + KV pages",
                               "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.cell("deep.chat", str(tmp_path))
    assert cell.config["num_hidden_layers"] == 20
    assert cell.traffic["arrivals"]["rate_rps"] == 1.5
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    found = cells.readers(cell, str(tmp_path))
    assert "steps_per_s" in found
    win = type("W", (), {"decode_steps": [[1], [2], [3]], "t_open": 0.0,
                         "t_close": 2.0})
    ctx = readers.Context(cell.config, win, 1.0, 0, {})
    assert found["steps_per_s"](ctx) == 1.5
    assert modelspec.model_config(cell.config).n_layers == 20
    _unchanged_but_the_entries(tmp_path, before)


# a CPU-sized block of a mixed-window sparse-expert model: three layers
# of windowed, full, windowed attention (a period of 2 and one remainder
# layer), each with 4 routed experts (2 a token) and a shared one
TOY = {
    "name": "toy-moe-window", "source": "https://example.org/toy-moe-window",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 512, "num_hidden_layers": 3,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "sliding_window": 16, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "initializer_range": 0.02, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "architecture": {"module": "toy_moe_window", "norm": "rms",
                     "qkv_bias": False, "o_bias": False},
    "reduced": {},
    "serving": {"n_slots": 2, "max_len": 64, "page_size": 16},
}


def test_a_new_architecture_is_new_files_only(tmp_path):
    """A block the dense module does not know (routed experts, windowed
    and full attention mixed, a pattern of period 2) comes in as an
    architecture module, a configuration, a traffic file and entries;
    the copy's own harness then builds the program's ModelConfig, makes
    the program's tree of weights, counts a decode step and serves."""
    _copy_benchmark(tmp_path)
    before = _files(tmp_path)
    bench_dir = tmp_path / "chipbench"
    shutil.copy(os.path.join(HERE, "arch_toy_moe_window.py"),
                bench_dir / "architectures" / "toy_moe_window.py")
    (bench_dir / "configs" / "toy-moe-window.json").write_text(
        json.dumps(TOY))
    (bench_dir / "traffic" / "toy-moe-window.chat.json").write_text(
        json.dumps(chipbench_tiny.TINY_TRAFFIC))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "toy-moe-window", "source": TOY["source"],
        "file": "chipbench/configs/toy-moe-window.json", "reduced": [],
        "why": "x"})
    bench["workloads"].append({
        "name": "toy-moe-window.chat", "config": "toy-moe-window",
        "traffic": "toy-moe-window.chat", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(tmp_path), os.path.join(ROOT, "src")]))
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "architecture_probe.py"),
         str(tmp_path), "toy-moe-window.chat"], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["root"] == str(tmp_path)
    assert (got["cell"], got["config"]) == ("toy-moe-window.chat",
                                            "toy-moe-window")
    assert got["rate_rps"] == chipbench_tiny.TINY_TRAFFIC["arrivals"][
        "rate_rps"]
    assert got["family"] == "moe"
    assert got["layer_pattern"] == ["attn:moe", "attn_full:moe"]
    assert (got["window"], got["n_experts"]) == (16, 4)
    assert got["n_remainder_layers"] == 1
    assert got["same_tree"]
    assert got["leaves"] == got["program_leaves"]
    assert any("'rem'][0]['moe']['w_out']" in leaf[0]
               for leaf in got["leaves"])
    assert got["counts_finite"]
    assert all(v > 0 for v in got["counts"].values()), got["counts"]
    # contexts 3, 20 and 40: the two windowed layers read at most 16
    # tokens of each row, the full layer all; each layer writes 3 tokens
    attended = 2 * (3 + 16 + 16) + (3 + 20 + 40)
    assert got["counts"]["attn_bytes"] == 2 * 2 * 16 * 4 * (attended + 9)
    assert got["served"] == {"0": 6, "1": 6}
    _unchanged_but_the_entries(tmp_path, before)


def _no_module(cfg, bench_dir):
    del cfg["architecture"]["module"]
    return r"configs/qwen2.5-3b.json names no architecture.module"


def _no_such_module(cfg, bench_dir):
    cfg["architecture"]["module"] = "no_such_block"
    return (r"configs/qwen2.5-3b.json: architecture.module 'no_such_block': "
            r"no architecture 'no_such_block' at .*architectures/"
            r"no_such_block.py")


def _half_a_module(cfg, bench_dir):
    cfg["architecture"]["module"] = "half_block"
    (bench_dir / "architectures" / "half_block.py").write_text(
        "def model_config(spec):\n    return None\n")
    return (r"configs/qwen2.5-3b.json: architecture.module 'half_block': "
            r"architecture 'half_block' provides no \['shapes', ")


@pytest.mark.parametrize("fault", [_no_module, _no_such_module,
                                   _half_a_module])
def test_a_configuration_must_name_a_whole_architecture_module(
        tmp_path, monkeypatch, fault):
    """There is no default block: a file without ``architecture.module``,
    or naming a module that is not there or lacks a function, is refused
    when the configuration is read, naming the file and the module.  The
    harness runs from the copy, where architecture modules are found."""
    _copy_benchmark(tmp_path)
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    path = tmp_path / "chipbench" / "configs" / "qwen2.5-3b.json"
    cfg = json.loads(path.read_text())
    message = fault(cfg, tmp_path / "chipbench")
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=message):
        cells.config("qwen2.5-3b", str(tmp_path))
    with pytest.raises(ValueError, match=message):
        cells.cell("qwen2.5-3b.chat", str(tmp_path))


def test_every_cell_of_the_benchmark_resolves():
    bench = cells.benchmark()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        cell = cells.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert set(cells.readers(cell)) == {m["name"] for m in cell.per_layer}
        for m in cell.end_to_end:
            assert callable(cells.metric_reader(m["name"]))
        reported = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])
    for c in bench["configs"]:
        spec = cells.config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(spec["reduced"])
        assert c["source"] == spec["source"]
        cells.reference(spec["reference"])
    for n in names + [w["name"] for w in bench["workloads"]]:
        assert NAME.match(n), n


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        cells.cell("nope.chat")
