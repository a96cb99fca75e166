import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract roofline terms from the compiled module.

The two lines above MUST stay the first statements — jax locks the device
count at first init, and the dry-run (only the dry-run) needs 512 host
placeholder devices for the 2×16×16 multi-pod mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b \
      --shape train_4k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per combination this prints/records:
  * compiled.memory_analysis()  — bytes/device (proves it fits)
  * compiled.cost_analysis()    — HLO FLOPs + bytes for §Roofline
  * collective bytes parsed from the compiled HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute)
  * the three roofline terms (compute / memory / collective, seconds)
"""
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import SHAPES, get_config, list_archs        # noqa: E402
from repro.launch.hlo_cost import HloCost                       # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import applicable, build_dryrun         # noqa: E402

# ------------------------------- hardware constants (TPU v5e class) -------
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / ICI link

# §Perf tuned presets (EXPERIMENTS.md) — beyond-paper optimization passes.
PRESETS = {
    "tuned-moe": {"prefill_moe_cf": 2.0, "moe_ep": True,
                  "pad_heads": True},
    "tuned-decode": {"cache_shard": "seq", "fsdp": False},
    "tuned-train": {"tp_pairs": True},
}

def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            fsdp: bool = True, out_dir: str | None = None,
            verbose: bool = True, opts: dict | None = None,
            tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = ("pod2x16x16" if multi_pod else "pod16x16") + tag
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "status": "ok"}
    ok, why = applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        if verbose:
            print(f"[skip] {arch} × {shape_name}: {why}")
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fname = f"{arch}_{shape_name}_{mesh_tag}.json".replace("/", "-")
            with open(os.path.join(out_dir, fname), "w") as f:
                json.dump(rec, f, indent=1)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = len(mesh.devices.reshape(-1))
        fn, args, in_sh = build_dryrun(
            cfg, shape, mesh,
            fsdp=(opts or {}).get("fsdp", fsdp), opts=opts)
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        xla_cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # trip-count-aware cost (XLA's cost_analysis counts loop bodies
        # once — see repro.launch.hlo_cost)
        hc = HloCost(hlo)

        flops = float(hc.flops)
        bytes_acc = float(hc.bytes)
        coll = {k: float(v) for k, v in hc.collective_bytes.items()}
        coll_counts = dict(hc.collective_counts)
        coll_total = float(sum(coll.values()))
        # MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens
        # (prefill) / 2·N_active·batch (decode: one token per sequence)
        n_act = float(cfg.active_param_count())
        if shape.kind == "train":
            model_flops = 6.0 * n_act * shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            model_flops = 2.0 * n_act * shape.global_batch * shape.seq_len
        else:
            model_flops = 2.0 * n_act * shape.global_batch
        rec.update(
            n_chips=n_chips,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={k: int(getattr(mem, k, 0)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")},
            hlo_flops=flops, hlo_bytes=bytes_acc,
            xla_cost_flops=float(xla_cost.get("flops", 0.0)),
            collective_bytes=coll, collective_counts=coll_counts,
            # --- roofline terms (seconds, per device) ---
            t_compute=flops / PEAK_FLOPS,
            t_memory=bytes_acc / HBM_BW,
            t_collective=coll_total / LINK_BW,
            model_flops=model_flops,
        )
        terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
                 "collective": rec["t_collective"]}
        rec["bottleneck"] = max(terms, key=terms.get)
        per_chip_model = rec["model_flops"] / n_chips
        rec["useful_flops_ratio"] = (per_chip_model / flops
                                     if flops else 0.0)
        if verbose:
            print(f"[ok] {arch} × {shape_name} × {mesh_tag}: "
                  f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
                  f"flops/dev {flops:.3e} bytes/dev {bytes_acc:.3e} "
                  f"coll {coll_total:.3e} | "
                  f"compute {rec['t_compute']*1e3:.2f}ms "
                  f"memory {rec['t_memory']*1e3:.2f}ms "
                  f"collective {rec['t_collective']*1e3:.2f}ms "
                  f"-> {rec['bottleneck']}")
            print(f"     memory_analysis: "
                  f"args {rec['memory']['argument_size_in_bytes']/2**30:.2f}"
                  f" GiB out {rec['memory']['output_size_in_bytes']/2**30:.2f}"
                  f" GiB temp {rec['memory']['temp_size_in_bytes']/2**30:.2f}"
                  f" GiB")
    except Exception as e:                                  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] {arch} × {shape_name} × {mesh_tag}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}_{shape_name}_{mesh_tag}.json".replace("/", "-")
        rec_out = dict(rec)
        rec_out.pop("traceback", None)
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec_out, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (see repro.configs)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) combination")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2×16×16 (512 chips) instead of 16×16")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over data axis (baseline DP)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--preset", default=None, choices=list(PRESETS),
                    help="§Perf tuned sharding/capacity presets")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    opts = PRESETS[args.preset] if args.preset else None
    tag = f"+{args.preset}" if args.preset else ""
    results = []
    for a, s in combos:
        results.append(run_one(a, s, multi_pod=args.multi_pod,
                               fsdp=not args.no_fsdp, out_dir=args.out,
                               opts=opts, tag=tag))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_err} errors ==")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
