"""The harness finds a cell's parts by name: a new cell, configuration,
traffic mix and per-layer metric are new files and entries only."""
import json
import os
import re
import shutil

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import cells, modelspec, readers

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "chipbench"), dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_a_new_cell_config_mix_and_metric_are_found_by_name(tmp_path):
    _copy_benchmark(tmp_path)
    before = {p: open(p, "rb").read() for p in
              [str(x) for x in tmp_path.rglob("*") if x.is_file()]}
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
    # no file that was there changed, other than the list of entries
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, p


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
