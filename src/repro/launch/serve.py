"""Serving driver: bring up a reduced model behind the inference engine and
replay a batched request stream, reporting TTFT / throughput — optionally
comparing λScale's execute-while-load scaling against the baselines on the
calibrated simulator.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --requests 32
  PYTHONPATH=src python -m repro.launch.serve --continuous --requests 24
  PYTHONPATH=src python -m repro.launch.serve --continuous --published \
      --layers 18          # published widths, depth cut (needs a TPU)
  PYTHONPATH=src python -m repro.launch.serve --sim --model llama2-13b \
      --nodes 12 --rps 50
  PYTHONPATH=src python -m repro.launch.serve --live --nodes 8 --requests 12
  PYTHONPATH=src python -m repro.launch.serve --autoscale --nodes 6 \
      --requests 16
  PYTHONPATH=src python -m repro.launch.serve --slo --nodes 6 --requests 20
  PYTHONPATH=src python -m repro.launch.serve --disagg --requests 8
  PYTHONPATH=src python -m repro.launch.serve --overload
  PYTHONPATH=src python -m repro.launch.serve --coldstart
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig
from repro.launch.jax_cache import enable_compile_cache
from repro.models import init_params, make_batch
from repro.serving import ContinuousBatchingEngine, InferenceEngine
from repro.serving.autoscaler import Autoscaler, AutoscalerConfig
from repro.serving.baselines import POLICIES
from repro.serving.cluster import LiveCluster
from repro.serving.placement import PlacementArbiter
from repro.serving.scheduler import (AdmissionPolicy, EDFPolicy, PageQuota,
                                     StrictPriorityPolicy)
from repro.serving.simulator import Simulator
from repro.serving.tiers import HardwareProfile
from repro.serving.workload import (BATCH, INTERACTIVE, Request,
                                    constant_stress, overload_trace)


def mixed_trace(n: int, prompt: int, tokens: int, seed: int = 0):
    """Mixed-length request list (prompt_len, out_tokens) around the
    requested means — the workload shape where continuous batching wins."""
    rng = np.random.default_rng(seed)
    return [(int(rng.integers(max(4, prompt // 2), prompt * 2)),
             int(rng.integers(max(2, tokens // 2), tokens * 2)))
            for _ in range(n)]


def device_label() -> str:
    """Platform, device kind and count of the devices JAX runs on."""
    devs = jax.devices()
    return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"


def serve_config(args) -> ModelConfig:
    """``--published``: the architecture at its published widths with
    only the depth cut to ``--layers``; otherwise the reduced smoke
    variant at ``--d-model``."""
    cfg = get_config(args.arch)
    if args.published:
        return dataclasses.replace(cfg, n_layers=args.layers or cfg.n_layers)
    return reduced(cfg, d_model=args.d_model, n_layers=args.layers,
                   vocab=2048)


def burst_requests(cfg: ModelConfig, trace, seed: int = 1):
    """Random prompts of the trace's lengths: [(prompt ids, new tokens)]."""
    rng = np.random.default_rng(seed)
    return [(list(map(int, rng.integers(0, cfg.vocab_size, size=p))), t)
            for p, t in trace]


def serve_continuous(cfg: ModelConfig, params, requests, *, n_slots: int,
                     max_len: int, attn_impl: str = "xla"):
    """Serve ``requests`` as one burst (every request arrives at once,
    slots refill mid-decode) on a paged ``ContinuousBatchingEngine``.
    Returns (req_id -> generated ids, engine stats, wall seconds); the
    wall time ends after the last token reached the host."""
    eng = ContinuousBatchingEngine(cfg, params, n_slots=n_slots,
                                   max_len=max_len, attn_impl=attn_impl)
    for prompt, n_new in requests:
        eng.submit(prompt, n_new)
    t0 = time.perf_counter()
    out = eng.run()
    return out, eng.stats, time.perf_counter() - t0


def run_engine(args) -> None:
    cfg = serve_config(args)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_len=args.prompt + args.tokens)
    batch = make_batch(cfg, args.requests, args.prompt,
                       jax.random.PRNGKey(1))
    t0 = time.time()
    out = eng.generate(batch, args.tokens)
    out.block_until_ready()
    dt = time.time() - t0
    total = args.requests * args.tokens
    print(f"arch={cfg.arch_id}: served {args.requests} requests × "
          f"{args.tokens} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s on {device_label()}); "
          f"output shape {out.shape}")


def run_continuous(args) -> None:
    """Drive the continuous-batching engine through a mixed-length spike."""
    if args.requests < 1:
        raise SystemExit("--continuous needs --requests >= 1")
    cfg = serve_config(args)
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = mixed_trace(args.requests, args.prompt, args.tokens)
    out, s, dt = serve_continuous(
        cfg, params, burst_requests(cfg, trace), n_slots=args.slots,
        max_len=max(p + t for p, t in trace))
    total = sum(len(v) for v in out.values())
    print(f"arch={cfg.arch_id} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}) continuous batching: {len(trace)} requests "
          f"({args.slots} slots) → {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s on {device_label()})")
    print(f"  prefills={s['prefills']} decode_ticks={s['decode_ticks']} "
          f"mean decode batch="
          f"{s['decode_tokens']/max(s['decode_ticks'],1):.2f}")


def run_live(args) -> None:
    """Two models on one tiered cluster (multi-model runtime): model A
    hot on its sources, model B host-warm; both scale concurrently while
    a mixed request burst is absorbed through the scheduler-driven
    serving instances (pipelines during load, locals after mode switch)."""
    cfg_a = reduced(get_config(args.arch), d_model=args.d_model, vocab=2048)
    cfg_b = reduced(get_config("stablelm-1.6b"), d_model=args.d_model,
                    vocab=2048)
    max_len = args.prompt + args.tokens + 8
    lc = LiveCluster(n_nodes=args.nodes, n_slots=args.slots, max_len=max_len)
    lc.register("A", cfg_a, init_params(cfg_a, jax.random.PRNGKey(0)),
                n_blocks=4, hot_nodes=[0])
    lc.register("B", cfg_b, init_params(cfg_b, jax.random.PRNGKey(1)),
                n_blocks=4, warm_nodes=[args.nodes - 1])
    half = max(1, (args.nodes - 2) // 2)
    reports = {"A": lc.scale("A", half), "B": lc.scale("B", half)}
    for m, rep in reports.items():
        print(f"scale {m}: {rep.source_tier}-tier source {rep.sources} → "
              f"{len(rep.dests)} dests; first new capacity at "
              f"{rep.t_first_serve*1e3:.1f} ms, complete at "
              f"{rep.t_complete*1e3:.1f} ms (simulated clock)")
    rng = np.random.default_rng(2)
    t0 = time.time()
    for i in range(args.requests):
        model = "A" if i % 2 == 0 else "B"
        cfg = cfg_a if model == "A" else cfg_b
        prompt = list(rng.integers(0, cfg.vocab_size,
                                   size=max(4, args.prompt // 4)))
        lc.submit(model, prompt, args.tokens)
    while lc.step():           # serve while the multicast is in flight
        lc.tick()
    lc.drain_serving()
    dt = time.time() - t0
    out = {m: lc.results(m) for m in ("A", "B")}
    total = sum(len(v) for res in out.values() for v in res.values())
    adopted = sum(e.stats["adopted"] for m in ("A", "B")
                  for e in lc.serving[m].locals_.values())
    print(f"{args.requests} requests across 2 models → {total} tokens "
          f"in {dt:.2f}s on {device_label()}; {adopted} handed off "
          f"mid-generation; "
          f"replicas: A={sorted(lc.serving['A'].locals_)} "
          f"B={sorted(lc.serving['B'].locals_)}")


def run_autoscale(args) -> None:
    """Closed loop on the live runtime: the model starts host-warm with
    ZERO replicas; a bursty trace arrives and the autoscaler does the
    rest — scale-up via k-way multicast from the warm copy, serving
    through EWL pipelines and mode-switched replicas, then keep-alive
    scale-down back to the host tier when the burst passes."""
    cfg = reduced(get_config(args.arch), d_model=args.d_model, vocab=2048)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = max(4, args.prompt // 4) + args.tokens + 8
    lc = LiveCluster(n_nodes=args.nodes, n_slots=args.slots, max_len=max_len)
    lc.register("m", cfg, params, n_blocks=4, warm_nodes=[0])

    rng = np.random.default_rng(2)
    trace = [Request(i, "m", 0.005 + 0.002 * i,
                     max(4, args.prompt // 4), args.tokens)
             for i in range(args.requests)]
    asc = Autoscaler(AutoscalerConfig(cooldown_up=0.05, cooldown_down=0.02,
                                      keepalive=0.15, min_replicas=0,
                                      max_k=2))
    t0 = time.time()
    log = lc.replay(trace, autoscaler=asc, tick_seconds=0.002,
                    tail_seconds=0.5,
                    prompt_fn=lambda r: list(
                        rng.integers(0, cfg.vocab_size, size=r.prompt_len)))
    dt = time.time() - t0
    s = log.summary()
    print(f"closed-loop replay: {int(s['n_finished'])}/{len(trace)} "
          f"requests in {dt:.2f}s wall; sim-clock TTFT "
          f"p50={s['ttft_p50']*1e3:.1f}ms p99={s['ttft_p99']*1e3:.1f}ms; "
          f"gpu_seconds={s['gpu_seconds']:.3f}")
    for e in log.scale_events:
        print(f"  t={e.t*1e3:7.1f}ms {e.kind:6s} {e.detail}")
    print(f"replicas now: {sorted(lc.serving['m'].locals_)} "
          f"(host-warm fallback on {lc._host_payload_nodes('m')})")


def run_slo(args) -> None:
    """Mixed-class demo of the request control plane: the SAME bursty
    two-model trace (interactive + batch SLO classes) replayed twice on
    the live runtime — FCFS admission with independent scaling vs EDF
    admission with the SLO-pressure-weighted placement arbiter — and the
    per-class TTFT tails / SLO attainment printed side by side.  Greedy
    tokens are identical across the two runs; only who waits changes."""
    cfg = reduced(get_config(args.arch), d_model=args.d_model, vocab=2048)
    params = init_params(cfg, jax.random.PRNGKey(0))
    inter, batch = INTERACTIVE.scaled(0.02), BATCH.scaled(0.02)
    rng = np.random.default_rng(3)
    n = max(args.requests, 8)
    trace = []
    for i in range(n):       # batch half arrives first — worst for FCFS
        slo = batch if i < n // 2 else inter
        out = int(rng.integers(5, 8)) if slo is batch \
            else int(rng.integers(3, 5))
        trace.append(Request(i, "a" if i % 2 == 0 else "b",
                             0.004 + 0.0003 * i, max(4, args.prompt // 16),
                             out, slo=slo))
    conditions = {
        "fcfs+independent": (AdmissionPolicy(),
                             PlacementArbiter(slo_weighted=False)),
        "edf+arbiter": (EDFPolicy(), PlacementArbiter()),
    }
    for name, (admission, arbiter) in conditions.items():
        lc = LiveCluster(n_nodes=args.nodes, n_slots=2,
                         max_len=max(4, args.prompt // 16) + 8 + 8,
                         admission=admission, arbiter=arbiter)
        lc.register("a", cfg, params, n_blocks=2, warm_copies=1)
        lc.register("b", cfg, params, n_blocks=2, warm_copies=1)
        asc = Autoscaler(AutoscalerConfig(cooldown_up=0.05, keepalive=0.2,
                                          max_k=2, max_nodes=1))
        log = lc.replay(trace, autoscaler=asc, tick_seconds=0.002,
                        tail_seconds=0.1)
        s = log.summary()
        p99i = s["ttft_p99_interactive"] * 1e3
        p99b = s["ttft_p99_batch"] * 1e3
        print(f"{name:18s} interactive p99={p99i:6.1f}ms  "
              f"batch p99={p99b:6.1f}ms  "
              f"attainment={s['slo_attainment']:.2f} "
              f"(interactive {s['slo_attainment_interactive']:.2f})")


def run_disagg(args) -> None:
    """Prefill/decode disaggregation demo: the SAME mixed trace served
    by a unified cluster and by a role-split one — a prefill pool runs
    the prompt passes, exports finished prompts as deduped PackedKV, and
    a decode pool adopts them straight into generation.  Greedy tokens
    are bit-identical (asserted); only which engine does what changes."""
    cfg = reduced(get_config(args.arch), d_model=args.d_model, vocab=2048)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    n = max(args.requests, 2)
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 size=int(rng.integers(8, args.prompt))))
               for _ in range(n)]
    max_len = args.prompt + args.tokens + 8

    def serve(**pools):
        lc = LiveCluster(n_nodes=args.nodes, n_slots=args.slots,
                         max_len=max_len)
        lc.register("m", cfg, params, n_blocks=4, **pools)
        for i, p in enumerate(prompts):
            lc.submit("m", p, args.tokens, req_id=i)
        t0 = time.time()
        lc.drain_serving()
        return lc, time.time() - t0

    cu, dt_u = serve(hot_nodes=[0, 1])
    cd, dt_d = serve(prefill_nodes=[0], decode_nodes=[1])
    ref, got = cu.results("m"), cd.results("m")
    assert got == ref, "disagg diverged from unified greedy tokens"
    sv = cd.serving["m"]
    pre, dec = sv.prefills[0], sv.locals_[1]
    by_choice = {c: sum(1 for d in cd.handoff_log if d.chosen == c)
                 for c in ("transfer", "recompute", "fresh")}
    priced = sum(d.payload_bytes for d in cd.handoff_log)
    total = sum(len(v) for v in got.values())
    print(f"arch={cfg.arch_id} disagg: {n} requests → {total} tokens, "
          f"bit-equal to unified (unified {dt_u:.2f}s, disagg {dt_d:.2f}s "
          f"on {device_label()})")
    print(f"  prefill pool node 0: prefills={pre.stats['prefills']} "
          f"exported={pre.stats['exported']} "
          f"decode_ticks={pre.stats['decode_ticks']}")
    print(f"  decode pool  node 1: adopted={dec.stats['adopted']} "
          f"decode_ticks={dec.stats['decode_ticks']} "
          f"admitted={dec.stats['admitted']}")
    print(f"  wire: {len(cd.handoff_log)} handoffs priced "
          f"({by_choice['transfer']} transfer / "
          f"{by_choice['recompute']} recompute / "
          f"{by_choice['fresh']} fresh), {priced/1e3:.1f} kB packed KV "
          f"offered (reduced-model bytes)")


def run_overload(args) -> None:
    """Overload-survival demo: a sustained 3× mixed-class overload on
    ONE fixed node (scale-out cannot arrive in time — degradation order
    IS the outcome), served twice.  FCFS admits in arrival order and
    collapses for everyone; the survival stack (strict-priority
    admission + per-class page quotas + page-granular preemption over
    the PackedKV wire + explicit shedding with retry-after hints) keeps
    the interactive class fast and whole while batch work is parked to
    the host tier or shed — every decision in the audit log."""
    cfg = reduced(get_config(args.arch), d_model=64, vocab=2048)
    params = init_params(cfg, jax.random.PRNGKey(0))
    quotas = {"interactive": PageQuota(reserved_frac=0.25),
              "batch": PageQuota(ceiling_frac=0.6)}
    # 1 node × 2 slots at 0.002 s/tick ≈ 140 rps of real capacity
    trace = overload_trace(model="m", capacity_rps=140.0, overload=3.0,
                           duration=0.3, prompt_len=8, out_tokens=6,
                           seed=5)
    conditions = {
        "fcfs collapse": dict(admission=AdmissionPolicy()),
        "survival stack": dict(
            admission=StrictPriorityPolicy(quotas=quotas),
            preemption=True, shed_limit=4, max_park_ticks=400),
    }
    print(f"sustained 3x overload: {len(trace)} mixed-class requests "
          f"over {max(r.t_arrive for r in trace):.2f}s sim-clock, "
          f"1 node / 2 slots\n")
    for name, cond in conditions.items():
        lc = LiveCluster(n_nodes=1, n_slots=2, max_len=48, page_size=16,
                         **cond)
        lc.register("m", cfg, params, n_blocks=2, hot_nodes=[0])
        asc = Autoscaler(AutoscalerConfig(cooldown_up=1e9, keepalive=1e9,
                                          shed_high=0.2))
        log = lc.replay(trace, autoscaler=asc, tick_seconds=0.002,
                        max_ticks=500_000)
        s = log.summary()
        by = log.by_class()
        good = {c: sum(1 for m in ms if m.t_finish is not None) / len(ms)
                for c, ms in by.items()}
        print(f"{name:15s} interactive "
              f"p99={s['ttft_p99_interactive']*1e3:7.1f}ms "
              f"goodput={good.get('interactive', 1.0):.2f}   "
              f"batch goodput={good.get('batch', 1.0):.2f}")
        if "survival" in name:
            kinds = {}
            for e in lc.audit_log:
                kinds[e.kind] = kinds.get(e.kind, 0) + 1
            print(f"{'':15s} audit: " + ", ".join(
                f"{k}={n}" for k, n in sorted(kinds.items())))
            for e in lc.audit_log[:4]:
                extra = (f" retry_after={e.retry_after:.0f} ticks"
                         if e.kind == "shed" else "")
                print(f"{'':15s}   t={e.t*1e3:6.1f}ms {e.kind:8s} "
                      f"req {e.req_id}: {e.detail}{extra}")
            print(f"{'':15s}   ... ({len(lc.audit_log)} audit events; "
                  f"degradation lands on the lowest class first)")


def run_coldstart(args) -> None:
    """Scale-to-zero cold-start demo: a model registered with NO
    placement at all takes a cold burst, idles through a probe-punctuated
    gap long enough for the autoscaler to park it to a block-granular SSD
    snapshot (true zero replicas — health probes are answered at the
    control plane and do not reset the keep-alive), then a second burst
    restores it.  The SAME trace is replayed through the pipelined
    multi-tier loader + persistent compile cache and through the naive
    whole-blob blocking fetch; greedy tokens are bit-equal, only the
    cold-start clock changes."""
    import os
    import tempfile

    from repro.kernels.compile_cache import CompileCache
    from repro.serving.workload import probe_trace

    cfg = reduced(get_config(args.arch), d_model=64, n_layers=6)
    params = init_params(cfg, jax.random.PRNGKey(0))
    hw = HardwareProfile(ssd_bw=2.6e6, host_to_gpu_bw=2.6e6,
                         jit_compile_s=0.3)
    n = max(args.requests, 4)
    trace = [Request(i, "m", 0.005 + 0.01 * i, 6, 5) for i in range(n)]
    trace += [Request(100 + i, "m", 3.0 + 0.01 * i, 6, 5)
              for i in range(n)]
    trace += probe_trace("m", period=0.2, duration=2.9, start=0.5)
    trace.sort(key=lambda r: r.t_arrive)

    outs = {}
    with tempfile.TemporaryDirectory() as td:
        for name, (pipe, cache) in (
                ("pipelined", (True, CompileCache(
                    os.path.join(td, "compile_cpu.json")))),
                ("naive", (False, None))):
            lc = LiveCluster(n_nodes=3, n_slots=2, max_len=48, hw=hw,
                             pipelined_loading=pipe, compile_cache=cache)
            lc.register("m", cfg, params, n_blocks=6)   # fully cold
            asc = Autoscaler(AutoscalerConfig(keepalive=0.3, max_k=2,
                                              coldstart_slo=1.5), hw=hw)
            log = lc.replay(trace, autoscaler=asc, tick_seconds=0.002,
                            tail_seconds=0.2, max_ticks=500_000)
            # probes race the scale plan: one path may serve a probe on
            # a live engine the other answers at the control plane —
            # only real demand is held to the bit-equality bar
            demand = {r.req_id for r in trace if not r.probe}
            outs[name] = {rid: toks
                          for rid, toks in lc.results("m").items()
                          if rid in demand}
            s = log.summary()
            gaps = " + ".join(
                f"{e.tier}: fetch {e.fetch_seconds*1e3:.0f}ms "
                f"compile {e.compile_seconds*1e3:.0f}ms"
                for e in log.cold_starts)
            print(f"{name:10s} cold starts={int(s['cold_starts'])} "
                  f"({gaps})")
            print(f"{'':10s} cold first-token gap "
                  f"p99={s['cold_first_token_gap_p99']*1e3:.0f}ms  "
                  f"slo_misses={s['cold_start_slo_miss']:.0f}"
                  f"/{s['cold_starts']:.0f}  "
                  f"probes answered at control plane: "
                  f"{lc.probe_answers['m']}")
    assert outs["pipelined"] == outs["naive"], \
        "loading path changed the greedy tokens"
    print(f"greedy tokens bit-equal across both loading paths "
          f"({sum(len(v) for v in outs['naive'].values())} tokens); the "
          f"second burst restored from the SSD snapshot with zero "
          f"compile under the cache")


def run_sim(args) -> None:
    hw = HardwareProfile()
    reqs = constant_stress(args.rps, args.duration, model=args.model,
                           out_tokens=16, seed=0)
    print(f"simulating {len(reqs)} requests on {args.nodes} nodes "
          f"({hw.name} profile)")
    for name in ("lambdascale", "serverlessllm", "faasnet", "nccl", "ideal"):
        res = Simulator(POLICIES[name](hw), args.nodes, hw).run(reqs)
        print(f"  {name:14s} p50={res.ttft_percentile(50):6.3f}s "
              f"p90={res.ttft_percentile(90):6.3f}s "
              f"gpu_time={res.gpu_seconds:8.1f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sim", action="store_true",
                    help="simulator comparison instead of the live engine")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine on a mixed-length trace")
    ap.add_argument("--live", action="store_true",
                    help="two-model tiered live cluster (scale + serve)")
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop trace replay: autoscaler drives "
                         "scale-up/EWL/scale-down on the live cluster")
    ap.add_argument("--slo", action="store_true",
                    help="mixed-SLO-class demo: FCFS+independent vs "
                         "EDF+placement-arbiter on the same live trace")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation demo: role-split "
                         "pools on the PackedKV wire vs unified serving")
    ap.add_argument("--overload", action="store_true",
                    help="overload-survival demo: preemption + page "
                         "quotas + shedding vs FCFS collapse under a "
                         "sustained 3x mixed-class overload")
    ap.add_argument("--coldstart", action="store_true",
                    help="scale-to-zero demo: pipelined SSD→host→GPU "
                         "snapshot restore + compile cache vs the naive "
                         "blocking fetch on the same probed trace")
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--published", action="store_true",
                    help="published widths of --arch (engine and "
                         "--continuous); only the depth is cut, by --layers")
    ap.add_argument("--layers", type=int, default=None,
                    help="model depth (default: published depth with "
                         "--published, the reduced depth otherwise)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--model", default="llama2-13b")
    ap.add_argument("--nodes", type=int, default=12)
    ap.add_argument("--rps", type=float, default=50.0)
    ap.add_argument("--duration", type=float, default=5.0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.sim:
        run_sim(args)
    elif args.coldstart:
        run_coldstart(args)
    elif args.overload:
        run_overload(args)
    elif args.disagg:
        run_disagg(args)
    elif args.slo:
        run_slo(args)
    elif args.autoscale:
        run_autoscale(args)
    elif args.live:
        run_live(args)
    elif args.continuous:
        run_continuous(args)
    else:
        run_engine(args)


if __name__ == "__main__":
    main()
