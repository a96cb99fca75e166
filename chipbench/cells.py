"""Find a cell's configuration, traffic, metrics, architecture and
reference by name.

Nothing here knows a cell: ``BENCHMARK.json`` names them, and each part
is a file of its own under ``chipbench/``.  A new cell, configuration,
traffic mix, per-layer metric or model block is new files and new
entries, never an edit of one that exists.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file, as run
    traffic: dict             # the traffic file
    end_to_end: List[dict]    # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def config(name: str, root: str = ROOT) -> dict:
    """The configuration file ``configs/<name>.json``; the architecture
    module it names has to be there."""
    where = f"configs/{name}.json"
    spec = _load_json(os.path.join(root, "chipbench", where))
    if spec.get("name") != name:
        raise ValueError(f"{where} names {spec.get('name')!r}")
    module = spec.get("architecture", {}).get("module")
    if not module:
        raise ValueError(f"{where} names no architecture.module: the file "
                         f"under chipbench/architectures/ of its block")
    try:
        architecture(module)
    except (FileNotFoundError, AttributeError) as e:
        raise ValueError(f"{where}: architecture.module {module!r}: {e}"
                         ) from e
    return spec


def traffic(name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "chipbench", "traffic",
                                   f"{name}.json"))


def _module(path: str, what: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``: the metric's value, or
    None where the run gave it nothing to read."""
    return _module(os.path.join(root, "chipbench", "metrics", f"{name}.py"),
                   f"reader for metric {name!r}").read


# what an architecture module provides; ``spec`` is a configuration file,
# ``contexts`` the tokens each row of a decode step attends (the new one
# included), and the counts are what the algorithm needs
ARCHITECTURE = (
    "model_config",     # (spec) -> the program's ModelConfig
    "shapes",           # (spec) -> {path: (shape, init, scale)}, see weights
    "decode_flops",     # (spec, contexts) -> FLOPs of one decode step
    "decode_bytes",     # (spec, contexts) -> bytes it reads and writes
    "attn_flops",       # (spec, contexts) -> the attn_kv scope's FLOPs
    "attn_bytes",       # (spec, contexts) -> and its bytes
)


@functools.lru_cache(maxsize=None)
def architecture(name: str):
    """``architectures/<name>.py``: everything that depends on the shape
    of a configuration's block (``ARCHITECTURE``).  Always from this
    checkout, the one whose weights and counts the harness runs, so a
    configuration is checked against the module that ``modelspec.module``
    then gives it."""
    mod = _module(os.path.join(ROOT, "chipbench", "architectures",
                               f"{name}.py"), f"architecture {name!r}")
    missing = [f for f in ARCHITECTURE if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"architecture {name!r} provides no {missing}")
    return mod


def reference(name: str, root: str = ROOT):
    return _module(os.path.join(root, "chipbench", "references",
                                f"{name}.py"), f"reference {name!r}")


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    return Cell(
        name=w["name"], chips=int(w["chips"]),
        config=config(w["config"], root), traffic=traffic(w["traffic"], root),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, w["name"])],
        per_layer=[m for m in bench["per_layer"] if _applies(m, w["name"])])


def readers(c: Cell, root: str = ROOT) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in c.per_layer}
