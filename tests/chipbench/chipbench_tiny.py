"""Shared pieces of the benchmark's own tests: a tiny cell on the CPU.

Imported first by every test module here: it puts the checkout on the
path, so ``chipbench`` imports however pytest was started."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells  # noqa: E402

TINY_TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_rps": 12.0},
    "prompt": {"kind": "uniform_ladder", "ladder": [16, 40]},
    "output": {"kind": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 20},
}


def tiny_spec(config: str) -> dict:
    """A configuration file cut to a CPU-sized model of the same kind."""
    spec = copy.deepcopy(cells.config(config))
    spec.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=192, vocab_size=2048, num_hidden_layers=2)
    spec["serving"].update(n_slots=4, max_len=64)
    # most finished requests are compared: one altered token must show;
    # on the CPU the program's greedy tokens sit exactly at the best
    spec["check"] = {"sample": 16, "gap_max": 1e-3}
    return spec


def tiny_cell(config: str = "qwen2.5-3b.chat") -> cells.Cell:
    cell = cells.cell(config)
    cell.config = tiny_spec(cell.config["name"])
    cell.traffic = copy.deepcopy(TINY_TRAFFIC)
    return cell


class CpuDevice:
    """Stands in for the chip: the harness's look for one is skipped."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}
