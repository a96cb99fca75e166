"""Smoke test: the serving path runs on a TPU, at published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # λScale's cross-chip path, four chips

One chip: qwen2.5-3b at its published widths, its depth cut to 18 of 36
layers (float32 is the only dtype the serving path takes, and the whole
model's decode step needs 17.16 GB of the chip's 15.75 GB), with random
weights from ``--seed``.  A mixed-length burst is served through
``ContinuousBatchingEngine`` with the paged cache (``max_len`` 1024) on
both decode paths: the XLA gather and the Pallas fused decode step,
compiled.  Every request must get its token count, every id must lie in
the vocabulary, and the two paths' decode logits must agree within
``DECODE_RTOL``.  JAX's persistent compile cache is shown cold and warm.

Four chips (``--chips 4``, that phase alone): ``collectives.multicast``
of packed qwen2.5-3b blocks, 1→4 binomial and 2→4 k-way, byte for byte
against ``multicast_reference``; and ``pipelined_forward`` over four
stages against the one-chip ``forward``, within ``PIPELINE_RTOL``.

Earlier lines report depths, compile and wall seconds and peak device
bytes; they are not metrics.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero, printing no result, when JAX finds no TPU or a phase
fails.  Everything runs in this one process: a chip serves one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.blocks import pack_model  # noqa: E402
from repro.core.multicast import binomial_schedule, kway_schedule  # noqa: E402
from repro.distributed.collectives import (multicast,  # noqa: E402
                                           multicast_reference)
from repro.distributed.pipeline import pipelined_forward  # noqa: E402
from repro.launch.jax_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.serve import (burst_requests, mixed_trace,  # noqa: E402
                                serve_continuous)
from repro.models import (decode_step, forward, init_paged_cache,  # noqa: E402
                          init_params)
from repro.serving import ContinuousBatchingEngine  # noqa: E402

ARCH = "qwen2.5-3b"
# Depths, each from a memory_analysis() of its programs compiled for a
# described v5e:2x2 (15.75 GB of HBM per chip):
# - serving, 18 of 36: the decode step needs 7.10 GB of arguments,
#   2.74 GB of temporaries and 0.30 GB of output; prefill of 512 tokens
#   7.10 + 2.48 + 0.30 GB.  At 36 layers the step needs 17.16 GB.
SERVE_LAYERS = 18
# - multicast, 5: pack_model pads every block to the embedding's block
#   (1.55 GB, 4 blocks); the 1→4 and 2→4 programs then need 6.21 GB of
#   arguments, 7.77 GB of temporaries and, with the input donated, no
#   more for their output: 13.98 GB per chip, as at 4 layers.  Six or
#   more layers make the buffer 7.45 GB and the program 16.75 GB.
MULTICAST_LAYERS = 5
MULTICAST_BLOCKS = 4
# - pipeline, 16 (4 per stage): the one-chip forward of an 8 x 64 batch
#   needs 8.83 GB and one stage 3.16 GB, both held by chip 0 at once;
#   at 24 layers they need 12.46 + 3.96 GB.
PIPELINE_LAYERS = 16
N_SLOTS = 8
MAX_LEN = 1024
N_REQUESTS = 12
PROMPT_MEAN, TOKENS_MEAN = 128, 16

# XLA contracts float32 at the TPU's default precision (one bf16 pass, a
# relative rounding of 2^-8 per operand) while the Mosaic kernel
# contracts at its own, and the difference passes through every layer.
# A wrong page, mask or append moves the logits by their own scale.
DECODE_RTOL = 2e-2
# Both sides run the same layers at the same precision; only the batch
# split into microbatches and XLA's fusion differ.  A stage out of order
# or a lost microbatch moves the logits by their own scale.
PIPELINE_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileMeter:
    """Seconds JAX spent compiling (loads from the persistent cache
    included), programs compiled, and how many came from that cache —
    read from JAX's monitoring events."""

    def __init__(self):
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark) -> str:
        s, n, h = (self.seconds - mark[0], self.programs - mark[1],
                   self.hits - mark[2])
        return f"compile {s:.3f} s over {n} programs, {h} from the cache"

    def mark(self):
        return self.seconds, self.programs, self.hits


def make_weights(cfg, seed: int):
    """Random float32 weights from ``seed``, made by one program on the
    default device (made op by op, they take a minute of compiles)."""
    return jax.block_until_ready(jax.jit(functools.partial(
        init_params, cfg))(jax.random.PRNGKey(seed)))


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "peak_bytes_in_use not reported"
    return f"peak_bytes_in_use {stats['peak_bytes_in_use']}"


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"arguments {m.argument_size_in_bytes} B, temporaries "
            f"{m.temp_size_in_bytes} B, output {m.output_size_in_bytes} B, "
            f"aliased {m.alias_size_in_bytes} B")


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    require(np.isfinite(got).all() and np.isfinite(want).all(),
            "non-finite logits")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ------------------------------------------------------------- one chip
def compile_cache_phase(cfg, params, meter, *, n_slots: int, max_len: int):
    """Compile the XLA decode step, drop every in-memory executable, and
    compile it again: the second compile loads from the persistent
    cache.  Returns (cold, warm) seconds and whether the cold compile was
    itself loaded from the cache (an earlier process wrote it)."""
    cache = jax.eval_shape(functools.partial(
        init_paged_cache, cfg, n_slots, max_len=max_len))
    ids = jax.ShapeDtypeStruct((n_slots,), jnp.int32)

    def compile_step():
        t0 = time.perf_counter()
        compiled = jax.jit(functools.partial(decode_step, cfg)).lower(
            params, cache, ids, ids).compile()
        return time.perf_counter() - t0, compiled

    mark = meter.mark()
    cold, compiled = compile_step()
    cold_hit = meter.hits > mark[2]
    jax.clear_caches()
    mark = meter.mark()
    warm, _ = compile_step()
    require(meter.hits > mark[2],
            "the warm compile did not load from the persistent cache")
    log(f"decode step ({n_slots} slots, max_len {max_len}): "
        f"{memory_line(compiled)}")
    return cold, warm, cold_hit


def check_served(out, requests, vocab: int) -> int:
    require(sorted(out) == list(range(len(requests))),
            f"served {sorted(out)}, submitted {len(requests)} requests")
    for rid, (_, n_new) in enumerate(requests):
        toks = out[rid]
        require(len(toks) == n_new,
                f"request {rid}: {len(toks)} tokens, asked for {n_new}")
        require(all(0 <= t < vocab for t in toks),
                f"request {rid}: a token id outside the vocabulary")
    return sum(len(t) for t in out.values())


def serve_phase(cfg, params, requests, meter, *, n_slots: int,
                max_len: int, attn_impl: str):
    """Serve the burst twice on one decode path: the first pass compiles,
    the second reuses the executables and must give the same tokens."""
    mark = meter.mark()
    out, stats, first = serve_continuous(cfg, params, requests,
                                         n_slots=n_slots, max_len=max_len,
                                         attn_impl=attn_impl)
    compiled = meter.since(mark)
    n_tok = check_served(out, requests, cfg.vocab_size)
    again, _, second = serve_continuous(cfg, params, requests,
                                        n_slots=n_slots, max_len=max_len,
                                        attn_impl=attn_impl)
    require(again == out, f"{attn_impl}: a second pass over the same "
            f"burst gave other tokens")
    log(f"serve [{attn_impl}]: {len(requests)} requests → {n_tok} tokens "
        f"({stats['prefills']} prefills, {stats['decode_ticks']} decode "
        f"ticks); first pass {first:.3f} s wall ({compiled}), second "
        f"pass {second:.3f} s wall; {peak_bytes()}")
    return out


def decode_logits_phase(cfg, params, requests, *, n_slots: int,
                        max_len: int):
    """Prefill one request into every slot, then take the next decode
    step's logits from the same cache on both decode paths.  Returns
    their largest difference over the largest XLA logit."""
    eng = ContinuousBatchingEngine(cfg, params, n_slots=n_slots,
                                   max_len=max_len)
    for prompt, _ in requests[:n_slots]:
        eng.submit(prompt, max_len - len(prompt))
    for _ in range(n_slots):
        eng.step()
    live = [i for i, s in enumerate(eng.sched.slots) if s is not None]
    require(len(live) == min(n_slots, len(requests)),
            f"{len(live)} live slots after the prefills")
    for i in live:
        eng.pages.ensure(i, eng.sched.slots[i].pos)
    cache = dict(eng.cache, pages=eng.pages.device_table())
    logits = {}
    for impl in ("xla", "pallas"):
        step = jax.jit(functools.partial(decode_step, cfg, attn_impl=impl))
        out, _ = step(params, cache, eng._last_tok, cache["pos"])
        logits[impl] = np.asarray(out)[live]
        require(logits[impl].shape == (len(live), cfg.vocab_size),
                f"{impl} logits of shape {logits[impl].shape}")
    err = rel_err(logits["pallas"], logits["xla"])
    same = float((logits["pallas"].argmax(-1)
                  == logits["xla"].argmax(-1)).mean())
    return err, same


def one_chip(cfg, meter, *, seed: int, n_slots: int = N_SLOTS,
             max_len: int = MAX_LEN, n_requests: int = N_REQUESTS,
             prompt_mean: int = PROMPT_MEAN,
             tokens_mean: int = TOKENS_MEAN) -> None:
    full = get_config(cfg.arch_id)
    log(f"{cfg.arch_id}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
        f"{cfg.n_kv_heads} KV heads, d_head {cfg.d_head}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}; depth cut to {cfg.n_layers} of "
        f"{full.n_layers} layers; float32; random weights (seed {seed})")
    t0 = time.perf_counter()
    params = make_weights(cfg, seed)
    log(f"weights: {sum(x.nbytes for x in jax.tree.leaves(params))} B "
        f"made in {time.perf_counter() - t0:.3f} s")

    cold, warm, cold_hit = compile_cache_phase(cfg, params, meter,
                                               n_slots=n_slots,
                                               max_len=max_len)
    first = ("already in the persistent cache" if cold_hit
             else "compiled")
    log(f"compile cache: decode step {cold:.3f} s at first ({first}), "
        f"{warm:.3f} s again (loaded from the persistent cache)")

    trace = mixed_trace(n_requests, prompt_mean, tokens_mean, seed=seed)
    requests = burst_requests(cfg, trace, seed=seed + 1)
    log(f"burst: {len(requests)} requests, prompts "
        f"{sorted(p for p, _ in trace)} tokens, {n_slots} slots, paged "
        f"cache, max_len {max_len}")
    outs = {impl: serve_phase(cfg, params, requests, meter,
                              n_slots=n_slots, max_len=max_len,
                              attn_impl=impl)
            for impl in ("xla", "pallas")}
    same = sum(outs["xla"][r] == outs["pallas"][r] for r in outs["xla"])
    log(f"greedy tokens identical on both paths for {same} of "
        f"{len(requests)} requests (not required: the paths round "
        f"differently)")

    err, argmax_same = decode_logits_phase(cfg, params, requests,
                                           n_slots=n_slots, max_len=max_len)
    require(err <= DECODE_RTOL,
            f"decode logits: pallas vs xla differ by {err:.3e} of the logit "
            f"scale, over the {DECODE_RTOL:.0e} allowed")
    log(f"decode logits: pallas vs xla max |diff| = {err:.3e} of the "
        f"largest logit (allowed {DECODE_RTOL:.0e}); same argmax in "
        f"{argmax_same:.3f} of the slots; {peak_bytes()}")


# ---------------------------------------------------------- four chips
@jax.jit
def _block_equal(buf, blk, want):
    return jnp.array_equal(buf[0, blk], want)


def _check_chips(out, src, name: str) -> None:
    """Every chip's buffer against ``src``, byte for byte, one block at a
    time on the chip: a second whole copy would not fit beside it."""
    for shard in out.addressable_shards:
        node = shard.index[0].start
        for blk in range(src.shape[0]):
            want = jax.device_put(src[blk], shard.device)
            require(bool(_block_equal(shard.data, blk, want)),
                    f"{name}: chip {node} block {blk} differs from "
                    f"multicast_reference")


def multicast_phase(cfg, mesh, *, n_blocks: int, seed: int) -> None:
    """Multicast the packed model from chip 0 (binomial) and from chips
    0 and 1 (k-way) to every chip; each chip's buffer must equal the
    numpy oracle's byte for byte."""
    n = mesh.devices.size
    # packed on the host: a uint8 view of a float32 tensor is laid out
    # with its byte axis padded to 128 lanes on the TPU (32x the bytes)
    with jax.default_device(jax.devices("cpu")[0]):
        src = np.asarray(jax.jit(lambda p: pack_model(cfg, p, n_blocks)[0])(
            make_weights(cfg, seed)))
    shape = (n,) + src.shape
    sharding = NamedSharding(mesh, P("node"))
    index = sharding.devices_indices_map(shape)
    log(f"multicast: {cfg.arch_id} packed at {cfg.n_layers} of "
        f"{get_config(cfg.arch_id).n_layers} layers into {src.shape[0]} "
        f"blocks of {src.shape[1]} B ({src.nbytes} B per chip)")
    b = src.shape[0]
    for name, sched, sources in (
            ("1->4 binomial", binomial_schedule(n, b), (0,)),
            ("2->4 k-way", kway_schedule(n, b, 2), (0, 1))):
        # the oracle moves whole blocks, so it runs on block ids: the
        # bytes it gives chip c are src[ids[c]], and every chip must end
        # with every block in place
        ids = np.full((n, b), -1)
        ids[list(sources)] = np.arange(b)
        require((multicast_reference(ids, sched) == np.arange(b)).all(),
                f"{name}: the oracle leaves a chip without the model")
        rows = [jax.device_put(src[None], dev) if idx[0].start in sources
                else jnp.zeros((1,) + src.shape, jnp.uint8, device=dev)
                for dev, idx in index.items()]
        blocks = jax.make_array_from_single_device_arrays(shape, sharding,
                                                          rows)
        del rows
        compiled = jax.jit(functools.partial(
            multicast, schedule=sched, mesh=mesh,
            initial={s: range(b) for s in sources}),
            donate_argnums=0).lower(blocks).compile()
        t0 = time.perf_counter()
        out = compiled(blocks)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        memory = memory_line(compiled)
        del blocks, compiled          # frees the program's HBM for the check
        devices = {s.device for s in out.addressable_shards}
        require(len(devices) == n, f"{name}: output on {len(devices)} "
                f"devices, not {n}")
        _check_chips(out, src, name)
        del out
        log(f"multicast {name}: {len(sched.steps)} steps, every chip's "
            f"{src.nbytes} B equal to multicast_reference on "
            f"{sorted(d.id for d in devices)}; {dt:.3f} s wall; per chip "
            f"{memory}")


def pipeline_phase(cfg, mesh, *, n_micro: int, batch: int, seq: int,
                   seed: int) -> float:
    """``pipelined_forward`` over one stage per chip against the one-chip
    ``forward`` on chip 0.  Returns the largest logit difference over the
    largest reference logit."""
    n = mesh.devices.size
    params = make_weights(cfg, seed)                         # chip 0
    rep, staged = NamedSharding(mesh, P()), NamedSharding(mesh, P("node"))
    spread = jax.device_put(params, {
        k: jax.tree.map(lambda _, s=(staged if k == "trunk" else rep): s, v)
        for k, v in params.items()})
    for leaf in jax.tree.leaves(spread["trunk"]):
        devs = {s.device for s in leaf.addressable_shards}
        require(len(devs) == n and leaf.addressable_shards[0].data.shape[0]
                == cfg.n_layers // n,
                f"trunk leaf {leaf.shape} on {len(devs)} devices")
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                0, cfg.vocab_size)
    pipe = jax.jit(lambda p, t: pipelined_forward(
        cfg, p, {"tokens": t}, mesh, n_micro)).lower(
            spread, jax.device_put(tokens, rep)).compile()
    got = pipe(spread, jax.device_put(tokens, rep))
    got.block_until_ready()
    log(f"pipelined_forward: {n} stages x {cfg.n_layers // n} layers, "
        f"{n_micro} microbatches; per chip {memory_line(pipe)}")
    del spread
    want = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})["logits"])(
        params, tokens)
    require(got.shape == want.shape == (batch, seq, cfg.vocab_size),
            f"logits {got.shape} vs {want.shape}")
    return rel_err(got, want)


def four_chips(base, mesh, *, seed: int,
               multicast_layers: int = MULTICAST_LAYERS,
               n_blocks: int = MULTICAST_BLOCKS,
               pipeline_layers: int = PIPELINE_LAYERS, batch: int = 8,
               seq: int = 64) -> None:
    multicast_phase(dataclasses.replace(base, n_layers=multicast_layers),
                    mesh, n_blocks=n_blocks, seed=seed)
    cfg = dataclasses.replace(base, n_layers=pipeline_layers)
    log(f"pipeline: {cfg.arch_id}, depth cut to {cfg.n_layers} of "
        f"{get_config(cfg.arch_id).n_layers} layers")
    err = pipeline_phase(cfg, mesh, n_micro=4, batch=batch, seq=seq,
                         seed=seed)
    require(err <= PIPELINE_RTOL,
            f"pipelined logits differ from forward by {err:.3e} of the "
            f"logit scale, over the {PIPELINE_RTOL:.0e} allowed")
    log(f"pipelined vs one-chip forward: max |diff| = {err:.3e} of the "
        f"largest logit (allowed {PIPELINE_RTOL:.0e}); {peak_bytes()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devs)} device(s)")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    base = get_config(ARCH)
    if args.chips == 4:
        four_chips(base, make_test_mesh(4), seed=args.seed)
    else:
        one_chip(dataclasses.replace(base, n_layers=SERVE_LAYERS), meter,
                 seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s "
        f"({meter.since((0.0, 0, 0))})")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
