"""Drive one cell of a benchmark checkout through the harness's own
functions, on the CPU, and print what they gave as one JSON line.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout>:<program src> \
        python architecture_probe.py <checkout> <workload>

``chipbench`` is imported from the checkout, so a cell whose files exist
only there is found as the benchmark would find it: its configuration,
the architecture module it names, the weights, the counts and the
engine.  The weights' tree is compared with the program's own
``init_params`` for the same ``ModelConfig``.
"""
import json
import math
import os
import sys

import jax
import numpy as np


def main(root: str, workload: str) -> dict:
    from chipbench import cells, cost, modelspec, openloop, peaks, weights
    from repro.models import init_params
    cell = cells.cell(workload, root)
    spec = cell.config
    cfg = modelspec.model_config(spec)
    made = weights.make(spec, 2**33 + 5)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def leaves(tree):
        return [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]
    arch = modelspec.module(spec)
    contexts = [3, 20, 40]
    counts = {name: getattr(arch, name)(spec, contexts) for name in
              ("decode_flops", "decode_bytes", "attn_flops", "attn_bytes")}
    counts["least_seconds"] = cost.least_seconds(
        spec, contexts, peaks.peak("TPU v5 lite"))
    eng = openloop.engine(spec, made)
    rng = np.random.default_rng(5)
    for n in (5, 30):
        eng.submit(rng.integers(0, spec["vocab_size"], n).tolist(), 6)
    served = eng.run()
    return {
        "root": cells.ROOT, "cell": cell.name, "config": spec["name"],
        "rate_rps": cell.traffic["arrivals"]["rate_rps"],
        "family": cfg.family, "layer_pattern": list(cfg.layer_pattern),
        "window": cfg.window, "n_experts": cfg.n_experts,
        "n_remainder_layers": cfg.n_remainder_layers,
        "same_tree": (jax.tree.structure(made) == jax.tree.structure(want)),
        "leaves": leaves(made), "program_leaves": leaves(want),
        "counts": counts,
        "counts_finite": all(math.isfinite(v) for v in counts.values()),
        "served": {str(r): len(t) for r, t in served.items()},
    }


if __name__ == "__main__":
    print(json.dumps(main(*sys.argv[1:3])), flush=True)
