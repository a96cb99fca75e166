"""Compile a cell's largest programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py <cell> [--slots N ...]

For each slot count, the engine's decode step at its largest live-page
bucket and its prefill of the mix's longest prompt are compiled for one
chip of a described ``v5e:2x2`` and their ``memory_analysis()`` printed.
Nothing runs: this gives bytes, never times.  The configuration files'
``n_slots`` and depth were set from its output.
"""
import argparse
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import cells, modelspec, openloop, traffic  # noqa: E402
from chipbench import weights as weights_mod  # noqa: E402

# the HBM of one v5e as its compiler reports it ("15.75G")
V5E_HBM = 15.75 * 2**30


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def rehearse(cell, n_slots: int, chip, seconds: float = 45.0) -> dict:
    from repro.models import init_paged_cache
    from repro.serving.engine import _paged_executables
    spec = cell.config
    cfg = modelspec.model_config(spec)
    s = spec["serving"]
    mp = -(-s["max_len"] // s["page_size"])
    prefill, step, _, _ = _paged_executables.__wrapped__(
        cfg, s["max_len"], s["page_size"], n_slots * mp, mp, "xla")
    params = _on(jax.eval_shape(weights_mod.program(spec),
                                jax.random.PRNGKey(0)), chip)
    cache = _on(jax.eval_shape(functools.partial(
        init_paged_cache, cfg, n_slots, page_size=s["page_size"],
        max_len=s["max_len"])), chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=chip)
    top = max(openloop.decode_buckets(spec, cell.traffic, seconds))
    longest = max(traffic.prompt_lengths(cell.traffic, seconds))
    out = {}
    for name, lowered in (
            (f"decode step, mp {top}",
             step.lower(params, cache, i32((n_slots,)), mp=top)),
            (f"prefill of {longest} tokens",
             prefill.lower(params, cache, i32((n_slots,)),
                           i32((1, longest)), i32(())))):
        m = lowered.compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        out[name] = total
        print(f"{cell.name} slots {n_slots} {name}: arguments "
              f"{m.argument_size_in_bytes} B, temporaries "
              f"{m.temp_size_in_bytes} B, output {m.output_size_in_bytes} B, "
              f"aliased {m.alias_size_in_bytes} B; total {total} B = "
              f"{total / V5E_HBM:.3f} of 15.75 GiB", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--slots", type=int, nargs="*")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cell = cells.cell(args.workload)
    for n in args.slots or [cell.config["serving"]["n_slots"]]:
        rehearse(cell, n, chip)


if __name__ == "__main__":
    main()
