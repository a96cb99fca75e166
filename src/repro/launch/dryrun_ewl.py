import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of λPipe's execute-while-load EXECUTION path on the production
mesh: the GPipe-style collective-permute pipeline (distributed.pipeline)
lowered with the trunk sharded into 16 stages over the "data" axis (one
stage per receiving node group) and tensor parallelism on "model" —
the paper's Case 2 (§4.3: cross-node pipelines for multi-GPU models).

  PYTHONPATH=src python -m repro.launch.dryrun_ewl [--arch llama2-7b]
                                                   [--batch 64 --seq 1024]

Reported with the same trip-count-aware roofline terms as dryrun.py.
"""
import argparse        # noqa: E402
import json            # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P    # noqa: E402

from repro.configs import get_config                          # noqa: E402
from repro.distributed.pipeline import pipelined_forward      # noqa: E402
from repro.launch.dryrun import (HBM_BW, LINK_BW, PEAK_FLOPS)  # noqa: E402
from repro.launch.hlo_cost import HloCost                     # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import batch_specs                    # noqa: E402
from repro.models import init_params                          # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b",
                    help="uniform-trunk arch with n_layers %% 16 == 0")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    assert cfg.pattern_len == 1 and cfg.n_layers % 16 == 0, \
        "EWL dry-run needs a uniform trunk divisible into 16 stages"
    mesh = make_production_mesh()            # ("data","model") = 16×16
    params_sh = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    batch = batch_specs(cfg, args.batch, args.seq)

    # stage (block) dim of the trunk shards over "data" inside shard_map;
    # weights within a stage are model-parallel
    def spec_of(path, leaf):
        keys = [str(getattr(k, "key", "")) for k in path]
        if "trunk" in keys and leaf.ndim >= 3:
            s = [None] * leaf.ndim
            if leaf.shape[-1] % 16 == 0:
                s[-1] = "model"
            return P(*s)
        return P()

    p_spec = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          jax.tree_util.tree_map_with_path(spec_of,
                                                           params_sh),
                          is_leaf=lambda x: isinstance(x, P))

    def ewl_forward(params, b):
        return pipelined_forward(cfg, params, b, mesh,
                                 n_microbatches=args.microbatches,
                                 axis="data")

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(ewl_forward, in_shardings=(p_spec, None)
                          ).lower(params_sh, batch)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hc = HloCost(compiled.as_text())
    coll = float(sum(hc.collective_bytes.values()))
    rec = {
        "arch": args.arch, "shape": f"ewl_b{args.batch}_s{args.seq}",
        "mesh": "pod16x16+ewl-pipeline", "status": "ok",
        "n_chips": 256, "compile_s": round(t_compile, 2),
        "hlo_flops": hc.flops, "hlo_bytes": hc.bytes,
        "collective_bytes": {k: float(v)
                             for k, v in hc.collective_bytes.items()},
        "t_compute": hc.flops / PEAK_FLOPS,
        "t_memory": hc.bytes / HBM_BW,
        "t_collective": coll / LINK_BW,
        "memory": {"argument_size_in_bytes": 0, "output_size_in_bytes": 0,
                   "temp_size_in_bytes": int(mem.temp_size_in_bytes)},
        "model_flops": 2.0 * cfg.active_param_count() * args.batch
        * args.seq,
    }
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    print(f"[ok] EWL pipeline {args.arch} b{args.batch} s{args.seq}: "
          f"compile {t_compile:.1f}s | compute {rec['t_compute']*1e3:.1f}ms "
          f"memory {rec['t_memory']*1e3:.1f}ms "
          f"collective {rec['t_collective']*1e3:.1f}ms | "
          f"temp {mem.temp_size_in_bytes/2**30:.2f} GiB")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(
            args.out, f"{args.arch}_ewl_pipeline.json"), "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
