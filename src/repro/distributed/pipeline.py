"""GPipe-style pipelined forward over a ``node`` mesh axis — the JAX
realization of λPipe's 2-D execution pipelines (§4.3).

Dimension 1 of the paper's 2-D pipelining is the stage (block) axis: each
node applies its contiguous range of trunk layers and hands the activation
to the next stage with ``lax.ppermute``.  Dimension 2 is the in-flight
microbatch axis: while stage s works on microbatch m, stage s-1 already
works on m+1.  Embedding and head are replicated (multicast first in
λScale; see DESIGN.md) so every stage runs an identical program — SPMD.

Used by the execute-while-load demo, the mode-switch tests, and the
pipeline-parallel dry-run configuration.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import model as mm
from repro.serving.scheduler import DEFAULT_SLOTS, Scheduler, SeqState


def stage_params_from_trunk(cfg: ModelConfig, params, n_stages: int):
    """Reshape the scan-stacked trunk into (n_stages, layers_per_stage, ...).

    Requires pattern_len == 1, no remainder, and n_layers % n_stages == 0
    (λScale uses equal-size blocks; the paper's models are uniform)."""
    assert cfg.pattern_len == 1 and cfg.n_remainder_layers == 0, \
        "pipelined runner requires a uniform trunk"
    assert cfg.n_layers % n_stages == 0
    per = cfg.n_layers // n_stages
    return jax.tree.map(
        lambda t: t.reshape((n_stages, per) + t.shape[1:]),
        params["trunk"][0])


def pipelined_forward(cfg: ModelConfig, params, batch: Dict, mesh,
                      n_microbatches: int, axis: str = "node"):
    """Forward pass with the trunk pipelined across ``axis``.

    batch["tokens"]: (B, S) with B % n_microbatches == 0.
    Returns logits (B, S, vocab), numerically equal to
    ``repro.models.forward`` (property-tested on forced host devices)."""
    n_stages = mesh.shape[axis]
    stage_trunk = stage_params_from_trunk(cfg, params, n_stages)
    tokens = batch["tokens"]
    B, S = tokens.shape
    M = n_microbatches
    assert B % M == 0
    mb = B // M
    positions = jnp.broadcast_to(jnp.arange(S)[None], (mb, S))
    embeds = (params["embed"][tokens]).reshape(M, mb, S, cfg.d_model)
    entry = cfg.layer_pattern[0]

    def apply_stage(stage_layers, x):
        def body(xc, lp):
            xc, _, _ = mm._apply_layer_full(lp, xc, cfg, entry, positions,
                                            moe_cf=None)
            return xc, None
        x, _ = jax.lax.scan(body, x, stage_layers)
        return x

    def spmd(stage_layers, embeds):
        # stage_layers leaves: (1, per, ...) — this node's block
        local = jax.tree.map(lambda t: t[0], stage_layers)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros((mb, S, cfg.d_model), embeds.dtype)
        outs = jnp.zeros((M, mb, S, cfg.d_model), embeds.dtype)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        for t in range(M + n_stages - 1):
            if t < M:
                buf = jnp.where(idx == 0, embeds[t], buf)
            y = apply_stage(local, buf)
            if t >= n_stages - 1:
                keep = jnp.where(idx == n_stages - 1, y, jnp.zeros_like(y))
                outs = outs.at[t - (n_stages - 1)].set(keep)
            buf = jax.lax.ppermute(y, axis, fwd)
        # only the last stage wrote non-zeros; make the result replicated
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(spmd, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P())
    hidden = fn(stage_trunk, embeds).reshape(B, S, cfg.d_model)
    return mm._unembed(cfg, params, hidden)


# ================================================= continuous batching (EWL)
class PipelinedEngine:
    """Continuous-batching serving on a λPipe execution pipeline.

    The transitional (execute-while-load) mode keeps no decode cache: a
    pipeline stage only holds the blocks that have arrived, and the mode
    switch will recompute state anyway (§4.4), so each tick re-runs the
    full-sequence pipelined forward over prompt + generated-so-far for
    every live slot and reads the logits at each sequence's last position.
    Token batches are padded to ``pad_to`` multiples (right padding is
    causal-safe) so XLA executables are reused across ticks; the batch
    dimension is always the full ``n_slots`` pool for the same reason.

    Drives the same ``repro.serving.scheduler.Scheduler`` as the local
    ``ContinuousBatchingEngine``; ``drain()`` + ``handoff()`` export live
    slot state for adoption by a local replica at mode-switch time.
    """

    def __init__(self, cfg: ModelConfig,
                 forward_fn: Callable[[jnp.ndarray], jnp.ndarray], *,
                 n_slots: int = DEFAULT_SLOTS, max_len: int = 512,
                 pad_to: int = 16, max_prefill_per_tick: int = 2,
                 policy=None):
        self.cfg = cfg
        self.forward_fn = forward_fn
        self.n_slots = n_slots
        self.max_len = max_len
        self.pad_to = pad_to
        self.sched = Scheduler(n_slots,
                               max_prefill_per_tick=max_prefill_per_tick,
                               policy=policy)
        self._next_id = 0

    @classmethod
    def from_mesh(cls, cfg: ModelConfig, params, mesh, *,
                  n_microbatches: int = 1, axis: str = "node",
                  n_slots: int = DEFAULT_SLOTS, **kw) -> "PipelinedEngine":
        """Real λPipe trunk: the forward is ``pipelined_forward`` over the
        ``axis`` mesh dimension (one stage per node)."""
        assert n_slots % n_microbatches == 0

        def fwd(tokens: jnp.ndarray) -> jnp.ndarray:
            return pipelined_forward(cfg, params, {"tokens": tokens}, mesh,
                                     n_microbatches, axis=axis)
        return cls(cfg, fwd, n_slots=n_slots, **kw)

    # ------------------------------------------------------------- intake
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               req_id: Optional[int] = None,
               eos_id: Optional[int] = None,
               t_arrive: Optional[float] = None, slo=None,
               probe: bool = False) -> int:
        if req_id is None:
            req_id = self._next_id
        self._next_id = max(self._next_id, req_id) + 1
        assert len(prompt) + max_new_tokens <= self.max_len
        self.sched.submit(SeqState(req_id, list(prompt), max_new_tokens,
                                   eos_id=eos_id, t_arrive=t_arrive,
                                   slo=slo, probe=probe))
        return req_id

    # ---------------------------------------------------------- execution
    def _bucket(self, n: int) -> int:
        b = ((n + self.pad_to - 1) // self.pad_to) * self.pad_to
        return min(b, self.max_len)

    def step(self) -> bool:
        tick = self.sched.next_tick()
        if tick.idle:
            return False
        # one padded full-sequence forward serves both the admitted
        # prefills and every in-flight decode this tick
        work: List[Tuple[int, SeqState, bool]] = (
            [(slot, seq, True) for slot, seq in tick.admit]
            + [(slot, self.sched.slots[slot], False)
               for slot in tick.decode])
        L = self._bucket(max(seq.pos for _, seq, _ in work))
        toks = np.zeros((self.n_slots, L), np.int32)
        for slot, seq, _ in work:
            t = seq.tokens_so_far[:L]
            toks[slot, :len(t)] = t     # host assembly: one transfer/tick
        logits = self.forward_fn(jnp.asarray(toks))
        for slot, seq, is_admit in work:
            tok = int(jnp.argmax(logits[slot, seq.pos - 1]))
            if is_admit:
                self.sched.on_prefilled(slot, tok)
            else:
                self.sched.on_decoded(slot, tok)
        return True

    def run(self) -> Dict[int, List[int]]:
        while self.step():
            pass
        return {rid: s.generated for rid, s in self.sched.finished.items()}

    # --------------------------------------------------------- mode switch
    def drain(self) -> None:
        self.sched.drain()

    def handoff(self) -> List[Tuple[SeqState, None]]:
        """Export in-flight sequences for a local replica to adopt.  A
        pipelined instance holds no decode cache, so every pair carries
        ``None`` — ``ContinuousBatchingEngine.adopt`` rebuilds the cache
        once from the tokens (mode-switch recomputation, §4.4)."""
        return [(seq, None) for seq in self.sched.handoff()]

    @property
    def stats(self) -> Dict[str, int]:
        return self.sched.stats
