"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,value,derived`` CSV lines and, per module, writes a
machine-readable ``BENCH_<name>.json`` summary (rows + wall-clock) so the
perf trajectory across PRs can be diffed without parsing stdout.  Modules:
  fig2/3   bench_cache          (§2.3 motivation: keep-alive, miss ratio)
  fig7/8   bench_multicast      (multicast latency, block-arrival CDF)
  fig9-11  bench_throughput     (ramp-up via GDR / local cache / cold)
  fig12/13 bench_latency        (TTFT under stress)
  fig14/15 bench_trace          (BurstGPT: GPU cost + TTFT CDF)
  fig16    bench_kway           (k-way transmission)
  fig17    bench_optimizations  (pre-alloc / tensor-pack / host-mem RDMA)
  fig18    bench_num_blocks     (block-count elbow)
  roofline bench_roofline       (dry-run derived roofline table)
  engine   bench_engine         (live JAX us_per_call micro-benches)
  cbatch   bench_continuous_batching (static vs continuous tokens/s)
  mmodel   bench_multimodel     (§5 tiers: cold/warm/hot scale-up latency)
  autoscale bench_autoscale     (§7.5 closed loop: tail latency + cost
                                 per policy under bursty traces)
  paged    bench_paged          (paged KV: residency, tokens/s, page-
                                 granular handoff + §4.4 crossover)
  prefix   bench_prefix         (CoW prefix sharing: prefill tokens
                                 skipped, TTFT vs no-sharing, residency,
                                 handoff wire dedupe)
  slo      bench_slo            (control plane: EDF + placement arbiter
                                 vs FCFS + independent scaling, per-class
                                 p99 TTFT and SLO attainment)
  overload bench_overload       (overload survival: preemption + page
                                 quotas + shedding vs FCFS collapse under
                                 sustained 3x mixed-class overload)
  disagg   bench_disagg         (prefill/decode disaggregation on the
                                 PackedKV wire: inter-token p99 + TTFT
                                 vs unified serving, priced wire bytes)
  coldstart bench_coldstart     (scale-to-zero: pipelined multi-tier
                                 loading + compile cache vs naive fetch,
                                 GPU-seconds saved vs cold-start SLO)

``benchmarks.diff`` compares two directories of these JSON summaries and
exits non-zero on tail-latency/GPU-cost regressions (the nightly CI gate
against the committed baseline).

A crashing module does not abort the sweep: the remaining modules still
run and write their JSON, the failure is recorded in
``BENCH_<name>.json`` (``"error"`` key), and the process exits non-zero
so CI fails loudly while still uploading every artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (bench_autoscale, bench_cache, bench_coldstart,
                        bench_continuous_batching, bench_disagg,
                        bench_engine, bench_kway, bench_latency,
                        bench_multicast, bench_multimodel,
                        bench_num_blocks, bench_optimizations,
                        bench_overload, bench_paged, bench_prefix,
                        bench_roofline, bench_slo, bench_trace,
                        bench_throughput)
from repro.launch.jax_cache import enable_compile_cache

MODULES = {
    "cache": bench_cache, "multicast": bench_multicast,
    "throughput": bench_throughput, "latency": bench_latency,
    "trace": bench_trace, "kway": bench_kway,
    "optimizations": bench_optimizations, "num_blocks": bench_num_blocks,
    "roofline": bench_roofline, "engine": bench_engine,
    "cbatch": bench_continuous_batching, "mmodel": bench_multimodel,
    "autoscale": bench_autoscale, "paged": bench_paged, "slo": bench_slo,
    "prefix": bench_prefix, "disagg": bench_disagg,
    "overload": bench_overload, "coldstart": bench_coldstart,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<name>.json summaries")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(MODULES)
    enable_compile_cache()

    print("name,value,derived")
    rows = []

    def report(name: str, value: float, derived: str = "") -> None:
        print(f"{name},{value:.6g},{derived}")
        sys.stdout.flush()
        rows.append({"name": name, "value": value, "derived": derived})

    t0 = time.time()
    failed = []
    for name in names:
        mod = MODULES[name]
        t1 = time.time()
        rows = []
        error = None
        try:
            mod.run(report)
        except Exception:                       # noqa: BLE001 — keep going
            error = traceback.format_exc()
            print(f"_meta/{name}/CRASHED,nan,", flush=True)
            print(error, file=sys.stderr)
            failed.append(name)
        seconds = time.time() - t1
        report(f"_meta/{name}/seconds", seconds, "")
        summary = {"benchmark": name, "seconds": seconds, "rows": rows}
        if error is not None:
            summary["error"] = error
        with open(f"{args.json_dir}/BENCH_{name}.json", "w") as f:
            json.dump(summary, f, indent=1)
    print(f"_meta/total_seconds,{time.time() - t0:.6g},")
    if failed:
        raise SystemExit(f"benchmark modules crashed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
