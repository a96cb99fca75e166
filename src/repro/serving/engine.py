"""JAX inference engines: static-batch and continuous-batching.

The single-replica ("local mode") execution path of λScale's model
manager.  ``InferenceEngine`` is the static loop kept as the reference
implementation (and the baseline the continuous-batching benchmark beats);
``ContinuousBatchingEngine`` executes the request-level schedule from
``repro.serving.scheduler`` over a shared KV store: new arrivals are
prefilled into free slots while every in-flight sequence keeps decoding,
and finished sequences free their slot mid-generation.

The KV store is *paged* by default (``paged=True``): attention K/V live
in a pool of fixed-size token pages addressed through a per-slot page
table (``repro.models.cache_ops.PageTable``), so resident KV bytes scale
with live tokens rather than ``slots × max_len``, and a mode-switch
handoff ships only a sequence's live pages (``PackedKV``).
``paged=False`` keeps the original per-slot full-length stripes — the
baseline ``benchmarks/bench_paged.py`` measures against.

Pipelined (execute-while-load) execution uses
``repro.distributed.pipeline.PipelinedEngine`` for the trunk; mode
switching hands its live slot state to this engine via
``repro.core.mode_switch.handoff_requests`` (drain → adopt, §4.4).
"""
from __future__ import annotations

import copy
import functools
import inspect
import itertools
import weakref
from typing import (TYPE_CHECKING, Any, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import (DEFAULT_PAGE_SIZE, PackedKV, PageTable,
                          PrefixIndex, batch_axes, cache_gather,
                          cache_scatter, decode_step, forward, init_cache,
                          init_paged_cache, pack_single_cache,
                          paged_adopt_scatter, paged_copy_page,
                          paged_geometry, paged_pack,
                          paged_prefill_scatter, paged_suffix_prefill,
                          pages_for, scopes, supports_prefix_sharing)
from repro.serving import trace
from repro.serving.scheduler import (DEFAULT_SLOTS, AdmissionPolicy,
                                     Scheduler, SeqState, SlotState)
from repro.serving.trace import span

# wire-dedupe export tag: every handoff() export of a prefix-sharing
# engine gets a fresh batch id, shared by all its payloads, so adopters
# can remap source page ids without ever confusing two exports
_HANDOFF_BATCH = itertools.count(1)

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.serving.workload import SLOClass


class InferenceEngine:
    """Static-batch reference engine: one prefill, fixed decode loop."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 4096):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(functools.partial(self._prefill_impl, cfg),
                                static_argnames=("cache_len",))
        self._step = jax.jit(functools.partial(self._step_impl, cfg))

    @staticmethod
    def _prefill_impl(cfg, params, batch, *, cache_len):
        out = forward(cfg, params, batch, build_cache=True,
                      cache_len=cache_len, moe_cf=None)
        last = out["logits"][:, -1]
        return last, out["cache"]

    @staticmethod
    def _step_impl(cfg, params, cache, tokens, positions):
        return decode_step(cfg, params, cache, tokens, positions)

    def prefill(self, batch: Dict, cache_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, dict]:
        cache_len = cache_len or self.max_len
        return self._prefill(self.params, batch, cache_len=cache_len)

    def generate(self, batch: Dict, max_new_tokens: int,
                 *, greedy: bool = True, key=None,
                 temperature: float = 1.0,
                 cache_len: Optional[int] = None) -> jnp.ndarray:
        """Returns (B, max_new_tokens) generated token ids."""
        logits, cache = self.prefill(
            batch,
            cache_len=cache_len or batch["tokens"].shape[1] + max_new_tokens)
        toks = []
        tok = self._sample(logits, greedy, key, temperature, 0)
        toks.append(tok)
        for i in range(1, max_new_tokens):
            logits, cache = self._step(self.params, cache, tok, cache["pos"])
            tok = self._sample(logits, greedy, key, temperature, i)
            toks.append(tok)
        return jnp.stack(toks, axis=1)

    def _sample(self, logits, greedy, key, temperature, i):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, i)
        return jax.random.categorical(k, logits / temperature).astype(
            jnp.int32)


# ===================================================== continuous batching
@jax.named_scope(scopes.LM_HEAD)
def _greedy(logits):
    return jnp.argmax(logits, -1).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _cb_executables(cfg: ModelConfig, max_len: int):
    """Jitted (prefill+scatter, decode+argmax) shared across every engine
    built for the same (config, pool length) — a new engine instance must
    not recompile, and slot index / token values are traced so one
    executable serves all slots and (per prompt length) all requests.

    Both executables thread ``last_tok`` (n_slots,) through the device so
    the decode loop never blocks on a host read: greedy continuation and
    count-based retirement are token-value-free, and the actual ids are
    fetched lazily (one gather at flush points, not one per tick)."""
    axes = batch_axes(init_cache(cfg, 2, max_len),
                      init_cache(cfg, 1, max_len))

    def prefill_scatter(params, pool, last_tok, tokens, slot):
        out = forward(cfg, params, {"tokens": tokens}, build_cache=True,
                      cache_len=max_len, moe_cf=None)
        first = _greedy(out["logits"][:, -1])
        last_tok = jax.lax.dynamic_update_slice(last_tok, first, (slot,))
        return last_tok, cache_scatter(pool, out["cache"], slot, axes)

    def step(params, cache, last_tok):
        logits, cache = decode_step(cfg, params, cache, last_tok,
                                    cache["pos"])
        return _greedy(logits), cache

    return jax.jit(prefill_scatter), jax.jit(step), axes


def _split_pool(cache) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(layer state, the rest) of a paged cache: the trunk and remainder
    state a program updates in place, and the page table and positions,
    which callers may keep (the page table's device copy is reused)."""
    rest = dict(cache)
    return {"trunk": rest.pop("trunk"), "rem": rest.pop("rem")}, rest


class _InPlace:
    """A paged program ``fn`` jitted to update the KV pool in place.

    Argument ``argnum`` of ``fn`` is a paged cache.  Its layer state
    (the page pools) is donated, so XLA writes the result's pools into
    the same buffers and the caller's pool arrays are deleted; its page
    table and positions are not.  Calls and ``lower`` take the cache
    whole, as ``fn`` does.  ``bound(engine)`` gives the same program
    tied to the engine whose pool it updates: a call on a cache holding
    that engine's pool leaves the engine holding the result's pool,
    whether or not the caller keeps the result."""

    def __init__(self, fn, argnum: int, static_argnames=()):
        def split(*args, **kw):
            state, rest = args[argnum], args[argnum + 1]
            return fn(*args[:argnum], {**rest, **state},
                      *args[argnum + 2:], **kw)

        # the program keeps ``fn``'s name (``jit_step`` in a trace) and
        # its parameters' names, which name the compiled program's
        # inputs (the trace readers find weights by them)
        split.__name__ = split.__qualname__ = fn.__name__
        sig = inspect.signature(fn)
        ps = list(sig.parameters.values())
        rest = ps[argnum].replace(name=f"{ps[argnum].name}_rest")
        split.__signature__ = sig.replace(
            parameters=ps[:argnum + 1] + [rest] + ps[argnum + 1:])
        self.argnum = argnum
        self._jit = jax.jit(split, donate_argnums=argnum,
                            static_argnames=static_argnames)
        self._owner = None

    def _args(self, args):
        a = self.argnum
        return args[:a] + _split_pool(args[a]) + args[a + 1:]

    def __call__(self, *args, **kw):
        out = self._jit(*self._args(args), **kw)
        eng = self._owner() if self._owner is not None else None
        if eng is not None and args[self.argnum]["trunk"] is \
                eng.cache["trunk"]:
            state, _ = _split_pool(out[-1] if isinstance(out, tuple)
                                   else out)
            eng.cache = {**eng.cache, **state}
        return out

    def lower(self, *args, **kw):
        return self._jit.lower(*self._args(args), **kw)

    def bound(self, engine) -> "_InPlace":
        b = copy.copy(self)
        b._owner = weakref.ref(engine)
        return b


# where each program of ``_paged_executables`` takes the cache, and its
# static arguments.  ``mp`` is static: one decode executable per
# live-page-count bucket (≤ max_pages of them), so attention work tracks
# live tokens.
_PAGED_SIGNATURES = ((1, ()), (1, ("mp",)), (1, ()), (0, ()))


@functools.lru_cache(maxsize=None)
def _paged_executables(cfg: ModelConfig, max_len: int, page_size: int,
                       n_pages: int, max_pages: int, attn_impl: str,
                       block_k=None):
    """Jitted (prefill+page-scatter, paged decode+argmax, suffix
    prefill, page copy) shared across engines of the same pool geometry
    — the paged analogue of ``_cb_executables``.  Each updates the pool
    in place (``_InPlace``).  The page table rides inside the cache
    pytree, so allocation changes between ticks never recompile.
    ``block_k`` tunes the fused Pallas kernel's sub-page KV block
    (autotuner output; the XLA path ignores it)."""

    def prefill_scatter(params, cache, last_tok, tokens, slot):
        out = forward(cfg, params, {"tokens": tokens}, build_cache=True,
                      cache_len=max_len, moe_cf=None)
        first = _greedy(out["logits"][:, -1])
        last_tok = jax.lax.dynamic_update_slice(last_tok, first, (slot,))
        pt_row = cache["pages"][slot]
        # tokens.shape[1] is static per prompt length (one executable
        # each), so the scatter writes only the pages the prompt covers
        return last_tok, paged_prefill_scatter(cfg, cache, out["cache"],
                                               slot, pt_row,
                                               n_tokens=tokens.shape[1])

    def step(params, cache, last_tok, mp=None):
        logits, cache = decode_step(cfg, params, cache, last_tok,
                                    cache["pos"], attn_impl=attn_impl,
                                    block_k=block_k, ctx_pages=mp)
        return _greedy(logits), cache

    def suffix_prefill(params, cache, last_tok, tokens, slot, start):
        # prefix sharing: the slot's leading pages already hold ``start``
        # shared tokens; only the suffix runs through the model (causal
        # masking makes the skip exact).  One executable per suffix
        # length, like prefill_scatter per prompt length.
        logits, cache = paged_suffix_prefill(cfg, params, cache, tokens,
                                             slot, start)
        first = _greedy(logits)
        last_tok = jax.lax.dynamic_update_slice(last_tok, first, (slot,))
        return last_tok, cache

    def copy_page(cache, src, dst):
        # copy-on-write fork: duplicate pool page src into dst
        return paged_copy_page(cfg, cache, src, dst)

    return tuple(_InPlace(fn, *sig) for fn, sig in zip(
        (prefill_scatter, step, suffix_prefill, copy_page),
        _PAGED_SIGNATURES))


class ContinuousBatchingEngine:
    """Slot-pool engine executing the continuous-batching schedule.

    One pooled decode cache of batch size ``n_slots`` lives on device;
    each scheduler tick (a) prefills up to ``max_prefill_per_tick`` queued
    requests into free slots (single-sequence prefill, cache scattered
    into the pool) and (b) advances the whole pool one decode step,
    keeping only the tokens of live slots.  Distinct prompt lengths each
    compile one prefill executable; the decode step compiles once.

    Greedy decoding only: continuous batching re-batches sequences across
    ticks, so per-request sampling streams would not be reproducible
    against the static engine.

    ``role`` specializes the engine to one phase of the request
    lifecycle (prefill/decode disaggregation): a ``prefill`` engine runs
    prompt passes only and streams finished prompt pages out through
    ``export_prefilled()`` (the deduped ``PackedKV`` wire); a ``decode``
    engine takes no fresh prompts and receives everything pre-prefilled
    via ``adopt``.  Non-unified roles require the paged KV layout — the
    wire between the pools IS the page-granular ``PackedKV`` path.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 n_slots: int = DEFAULT_SLOTS, max_len: int = 512,
                 max_prefill_per_tick: int = 1, paged: bool = True,
                 page_size=DEFAULT_PAGE_SIZE,
                 n_pages: Optional[int] = None, attn_impl: str = "xla",
                 block_k: Optional[int] = None,
                 prefix_sharing: bool = True,
                 policy: Optional[AdmissionPolicy] = None,
                 role: str = "unified",
                 shed_limit: Optional[int] = None,
                 preemption: bool = False):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.role = role
        if role != "unified" and not (paged and cfg.family != "encdec"):
            raise ValueError(
                f"{role}-role engine needs the paged KV layout — the "
                f"prefill → decode wire is the page-granular PackedKV "
                f"path")
        # encdec keeps fixed-size cross-attention K/V per slot; it stays
        # on the striped layout (the runtime excludes it anyway)
        self.paged = paged and cfg.family != "encdec"
        # copy-on-write prefix sharing is on by default wherever the
        # layout supports it (attention-only paged configs): recurrent
        # state folds the prefix into one vector and cannot be re-owned
        # at page granularity
        self.prefix_sharing = bool(self.paged and prefix_sharing
                                   and supports_prefix_sharing(cfg))
        if self.paged:
            # "auto" resolves (page_size, block_k) through the autotuner's
            # cached sweep; an explicit block_k overrides the tuned one
            page_size, tuned_bk = paged_geometry(
                cfg, n_slots, max_len, page_size=page_size,
                attn_impl=attn_impl, shared=self.prefix_sharing)
            self.block_k = block_k if block_k is not None else tuned_bk
            self.page_size = page_size
            self.max_pages = pages_for(max_len, page_size)
            self.n_pages = n_pages or n_slots * self.max_pages
            self.pages = PageTable(self.n_pages, page_size, n_slots,
                                   self.max_pages)
            if self.prefix_sharing:
                self.pages.prefix = PrefixIndex(page_size)
            self.sched = Scheduler(
                n_slots, max_prefill_per_tick=max_prefill_per_tick,
                pages=self.pages, policy=policy, role=role,
                shed_limit=shed_limit)
            self.cache = init_paged_cache(
                cfg, n_slots, n_pages=self.n_pages, page_size=page_size,
                max_pages=self.max_pages)
            self.cache["pages"] = self.pages.device_table()
            # a program handed over unjitted (a wrapper around one of
            # ours, say) is jitted here, so the pool is still donated
            # at the outermost call
            (self._prefill_scatter, self._step, self._suffix_prefill,
             self._copy_page) = (
                (p if isinstance(p, _InPlace) else _InPlace(p, *sig))
                .bound(self)
                for p, sig in zip(_paged_executables(
                    cfg, max_len, page_size, self.n_pages, self.max_pages,
                    attn_impl, self.block_k), _PAGED_SIGNATURES))
            self._axes = None
        else:
            self.pages = None
            self.sched = Scheduler(
                n_slots, max_prefill_per_tick=max_prefill_per_tick,
                policy=policy, shed_limit=shed_limit)
            self.cache = init_cache(cfg, n_slots, max_len)
            self._prefill_scatter, self._step, self._axes = \
                _cb_executables(cfg, max_len)
        self._last_tok = jnp.zeros((n_slots,), jnp.int32)
        self._next_id = 0
        # lazily-resolved token ids: (seq, index, slot, device_array).
        # EOS-terminated sequences need token values at schedule time, so
        # any eos_id switches the engine to per-tick host sync.
        self._pending: List[Tuple[SeqState, int, int, jnp.ndarray]] = []
        self._eager = False
        # handed-off sequences waiting for a slot (Scheduler.resume_queue):
        # req_id -> live cache, or None when the cache must be rebuilt
        # (mode-switch recomputation) at resume time.
        self._parked: Dict[int, Any] = {}
        # wire-dedupe adoption state per handoff batch: source-pid → own
        # pool page remap, pages held alive for parked sharers, and the
        # req_ids of batch payloads not yet restored here
        self._dedupe: Dict[int, Dict[str, Any]] = {}
        # overload survival: page-granular preemption packs low-priority
        # victims over the PackedKV wire into this outbox.  The cluster
        # harvests it every tick (take_preempted → host-tier park); a
        # standalone engine re-enqueues it at the NEXT step — one tick
        # late on purpose, so the requester that triggered the
        # preemption takes the freed slot/pages first.
        self.preemption = bool(preemption and self.paged)
        self.preempt_outbox: List[Tuple[SeqState, Any, int]] = []
        # (req_id, slo_class_name, retry_after) of submits the scheduler
        # rejected outright; drained by take_shed()
        self.shed_log: List[Tuple[int, str, float]] = []
        # count, total and longest host seconds of each span (trace.NAMES)
        self.phases = trace.Phases()

    # ------------------------------------------------------------- intake
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               req_id: Optional[int] = None,
               eos_id: Optional[int] = None,
               t_arrive: Optional[float] = None,
               slo: Optional["SLOClass"] = None,
               probe: bool = False) -> int:
        if req_id is None:
            req_id = self._next_id
        self._next_id = max(self._next_id, req_id) + 1
        # a prefill-role pool only ever holds the prompt's KV (the slot
        # is exported before any decode step appends to it)
        need = len(prompt) if self.role == "prefill" \
            else len(prompt) + max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache slots "
                f"but the pool was built with max_len={self.max_len}")
        if self.paged and pages_for(need, self.page_size) > self.n_pages:
            raise ValueError(
                f"request needs more pages than the whole pool holds "
                f"({self.n_pages} × {self.page_size} tokens)")
        if eos_id is not None:
            self._eager = True
        with span(self.phases, trace.SUBMIT, req_id=req_id):
            res = self.sched.submit(SeqState(req_id, list(prompt),
                                             max_new_tokens, eos_id=eos_id,
                                             t_arrive=t_arrive, slo=slo,
                                             probe=probe))
        if res.shed:
            self.shed_log.append((req_id,
                                  slo.name if slo is not None else "",
                                  res.retry_after))
        return req_id

    # ------------------------------------------------------------ execution
    def _record(self, seq: SeqState, slot: int, arr) -> int:
        """Register a device-side token for ``seq``; returns the id to
        append (the real value in eager mode, a placeholder otherwise)."""
        if self._eager:
            return int(arr[slot])
        self._pending.append((seq, len(seq.generated), slot, arr))
        return -1

    def flush(self) -> None:
        """Resolve placeholder token ids (single blocking gather)."""
        if not self._pending:
            return
        with span(self.phases, trace.FLUSH):
            with span(self.phases, trace.FLUSH_WAIT):
                arrs = jax.device_get([a for _, _, _, a in self._pending])
            with span(self.phases, trace.FLUSH_RESOLVE):
                for (seq, idx, slot, _), vals in zip(self._pending, arrs):
                    seq.generated[idx] = int(vals[slot])
                self._pending = []

    def _do_prefill(self, slot: int, seq: SeqState) -> None:
        toks = seq.tokens_so_far
        shared = seq.shared_tokens if self.prefix_sharing else 0
        if shared:
            # the scheduler's bind() attached the cached prefix run to
            # this slot; prefill covers only the suffix.  A mid-page
            # divergence forks the partially-matched page first (CoW:
            # the suffix scatter writes into it, and other owners must
            # never see those writes).
            if shared % self.page_size:
                old, new = self.pages.fork(slot, shared // self.page_size)
                if old != new:
                    self.cache = self._copy_page(
                        self.cache, jnp.asarray(old, jnp.int32),
                        jnp.asarray(new, jnp.int32))
            self.pages.ensure(slot, len(toks))
            self.cache["pages"] = self.pages.step_operand()
            suffix = jnp.asarray(toks[shared:], jnp.int32)[None]
            self._last_tok, self.cache = self._suffix_prefill(
                self.params, self.cache, self._last_tok, suffix, slot,
                jnp.asarray(shared, jnp.int32))
            self.pages.note_device(self.cache["pages"])
        else:
            tokens = jnp.asarray(toks, jnp.int32)[None]
            if self.paged:
                self.pages.ensure(slot, len(toks))
                self.cache["pages"] = self.pages.step_operand()
            self._last_tok, self.cache = self._prefill_scatter(
                self.params, self.cache, self._last_tok, tokens, slot)
            if self.paged:
                self.pages.note_device(self.cache["pages"])
        if self.prefix_sharing:
            # index the prompt's immutable pages so later prompts (and
            # tenants) can share them; decode never appends into an
            # indexed page (first append position >= len(prompt))
            self.pages.prefix.insert(self.pages, seq.prompt,
                                     self.pages.slot_pages(slot))
        self.sched.on_prefilled(slot, self._record(seq, slot,
                                                   self._last_tok))

    # ----------------------------------------------------- wire dedupe state
    def _dedupe_state(self, batch: int) -> Dict[str, Any]:
        return self._dedupe.setdefault(
            batch, {"remap": {}, "holds": [], "pending": set(),
                    "needed": set()})

    def _dedupe_discard(self, req_id: int, payload: Any) -> None:
        """A batch payload left without restoring here (finished while
        parked, or re-exported by a further handoff): drop it from the
        batch's pending set, releasing the batch's page holds once no
        parked payload can reference them anymore."""
        if not (isinstance(payload, PackedKV) and payload.batch is not None):
            return
        st = self._dedupe.get(payload.batch)
        if st is None:
            return
        st["pending"].discard(req_id)
        if not st["pending"]:
            self._dedupe_release(payload.batch)

    def _dedupe_release(self, batch: int) -> None:
        st = self._dedupe.pop(batch, None)
        if st is not None:
            for pid in st["holds"]:
                self.pages.unhold(pid)

    def _restore_shared(self, slot: int, seq: SeqState,
                        payload: PackedKV) -> bool:
        """Restore a wire-deduped payload: its referenced pages rode in
        an earlier payload of the same handoff batch and are resolved
        through the batch remap (source page id → own pool page), shared
        copy-on-write into this slot; only the ``carried`` suffix pages
        are scattered from the wire.  Returns False when a reference
        does not resolve here (the carrier was adopted elsewhere, or
        restored with a different batch) — the caller rebuilds the cache
        from tokens instead."""
        st = self._dedupe_state(payload.batch)
        st["pending"].discard(seq.req_id)
        remap, carried = st["remap"], set(payload.carried)
        refs: List[int] = []
        for p in range(payload.n_pages):
            if p in carried:
                break
            dst = remap.get(payload.page_ids[p])
            if dst is None:
                return False
            refs.append(dst)
        # sharing is prefix-structured: carried pages must be exactly
        # the suffix past the referenced run
        if sorted(carried) != list(range(len(refs), payload.n_pages)):
            return False
        self.pages.share(slot, refs)
        self.pages.ensure(slot, payload.n_tokens)
        self.cache["pages"] = self.pages.device_table()
        fresh = self.pages.slot_pages(slot)[len(refs):]
        self.cache = paged_adopt_scatter(self.cfg, self.cache, payload,
                                         slot, fresh)
        for j, p in enumerate(sorted(carried)):
            src = payload.page_ids[p]
            if src in st["needed"] and src not in remap:
                remap[src] = fresh[j]
                if st["pending"]:
                    # parked batch-mates may reference this page after
                    # this slot retires — hold it until the batch drains
                    self.pages.hold(fresh[j])
                    st["holds"].append(fresh[j])
        return True

    def _index_restored(self, slot: int, seq: SeqState) -> None:
        """A restored sequence's prompt pages are as shareable as a
        freshly-prefilled one's (the scatter laid the tokens out
        linearly, and decode only appends past them) — index them for
        future prompts."""
        if self.prefix_sharing:
            self.pages.prefix.insert(self.pages, seq.prompt,
                                     self.pages.slot_pages(slot))

    def _restore(self, slot: int, seq: SeqState, payload: Any) -> None:
        """Restore a handed-off sequence's KV state into ``slot`` and
        stage its last generated token as the next decode input.

        Payload kinds: a ``PackedKV`` (page-granular wire form, possibly
        wire-deduped against an earlier payload of its handoff batch), a
        raw batch-1 cache (striped engines), or None — the source kept
        no decode cache (λPipe) or the adoption path priced
        recomputation cheaper than the transfer; either way the cache is
        rebuilt once from the tokens (§4.4) and never re-enters the
        prefill queue."""
        if self.paged:
            if isinstance(payload, PackedKV) \
                    and payload.batch is not None \
                    and payload.page_size == self.page_size:
                ok = self._restore_shared(slot, seq, payload)
                st = self._dedupe.get(payload.batch)
                if st is not None and not st["pending"]:
                    self._dedupe_release(payload.batch)
                if ok:
                    self._index_restored(slot, seq)
                    self._last_tok = self._last_tok.at[slot].set(
                        seq.generated[-1])
                    return
                payload = None         # unresolvable refs: rebuild below
            if payload is None:
                from repro.core.mode_switch import handoff_requests
                payload = handoff_requests(
                    self.cfg, self.params, [seq], cache_len=self.max_len,
                    page_size=self.page_size)[seq.req_id]
            elif not isinstance(payload, PackedKV):
                payload = pack_single_cache(self.cfg, payload,
                                            self.page_size)
            if payload.page_size != self.page_size:
                raise ValueError(
                    f"page-size mismatch at adoption: payload "
                    f"{payload.page_size} vs pool {self.page_size}")
            self.pages.ensure(slot, payload.n_tokens)
            self.cache["pages"] = self.pages.device_table()
            ids = self.pages.slot_pages(slot)[:payload.n_pages]
            self.cache = paged_adopt_scatter(self.cfg, self.cache, payload,
                                             slot, ids)
            self._index_restored(slot, seq)
        else:
            if payload is None:     # pipelined source kept no decode cache
                from repro.core.mode_switch import handoff_requests
                payload = handoff_requests(
                    self.cfg, self.params, [seq],
                    cache_len=self.max_len)[seq.req_id]
            elif isinstance(payload, PackedKV):
                raise ValueError(
                    "page-granular payload handed to a striped engine — "
                    "adopt into a paged engine or hand off with None")
            self.cache = cache_scatter(self.cache, payload, slot,
                                       self._axes)
        self._last_tok = self._last_tok.at[slot].set(seq.generated[-1])

    def step(self) -> bool:
        """Run one scheduler tick.  Returns False when nothing ran."""
        with span(self.phases, trace.STEP) as sp:
            return self._tick(sp)

    def _tick(self, sp: span) -> bool:
        with span(self.phases, trace.SCHEDULE):
            # un-harvested preemption victims (standalone engine — no
            # cluster parked them to the host tier last tick) re-enter
            # the resume queue now, AFTER the preempting requester was
            # admitted
            if self.preempt_outbox:
                self.adopt([(s, p) for s, p, _ in self.preempt_outbox])
                self.preempt_outbox = []
            self._maybe_preempt()
            tick = self.sched.next_tick()
            # a parked sequence that finished while parked (EOS in its
            # last handed-off token) is retired by the scheduler without
            # ever taking a slot — drop the cache it was parked with
            if self._parked:
                for rid in [r for r in self._parked
                            if r in self.sched.finished]:
                    self._dedupe_discard(rid, self._parked.pop(rid))
            if tick.idle:
                return False
            # drop back to the sync-free path once no live/queued/parked
            # sequence terminates on EOS (the latch would otherwise cost
            # a host read per token for the rest of the engine's life)
            if self._eager and not any(
                    s is not None and s.eos_id is not None
                    for s in self.sched.slots) and not any(
                    s.eos_id is not None
                    for s in self.sched.queue + self.sched.resume_queue):
                self._eager = False
            # resumed sequences are mid-decode: their caches must land in
            # the pool BEFORE this tick's decode step advances every row
            for slot, seq in tick.resume:
                self._restore(slot, seq, self._parked.pop(seq.req_id, None))
        sp.set(tick=self.sched.tick_count, decoded=len(tick.decode),
               admitted=len(tick.admit))
        # decode first: the pooled decode step advances EVERY cache row,
        # so freshly-prefilled rows must be scattered after it, not before
        # (their ignored pseudo-step would otherwise corrupt pos/KV).
        if tick.decode:
            if self.paged:
                with span(self.phases, trace.PAGES):
                    # the incoming token's page must exist before the
                    # jitted step writes K/V at position seq.pos - 1; the
                    # table rides into the call as a host operand when
                    # dirty so the upload overlaps the in-flight previous
                    # step
                    for slot in tick.decode:
                        self.pages.ensure(slot, self.sched.slots[slot].pos)
                    self.cache["pages"] = self.pages.step_operand()
                    # bucket the step by the max allocated page count over
                    # LIVE slots (not just tick.decode — a resumed slot's
                    # row advances too): attention gathers/masks only
                    # those table columns, so work scales with live tokens
                    mp = max((max(s.pos - 1, 0) // self.page_size) + 1
                             for s in self.sched.slots if s is not None)
                with span(self.phases, trace.DECODE):
                    self._last_tok, self.cache = self._step(
                        self.params, self.cache, self._last_tok,
                        mp=min(mp, self.max_pages))
                    self.pages.note_device(self.cache["pages"])
            else:
                with span(self.phases, trace.DECODE):
                    self._last_tok, self.cache = self._step(
                        self.params, self.cache, self._last_tok)
            for slot in tick.decode:
                seq = self.sched.slots[slot]
                self.sched.on_decoded(slot, self._record(seq, slot,
                                                         self._last_tok))
        for slot, seq in tick.admit:
            with span(self.phases, trace.PREFILL, req_id=seq.req_id):
                self._do_prefill(slot, seq)
        return True

    def run(self) -> Dict[int, List[int]]:
        """Drive ticks until queue and slots are empty; returns
        req_id -> generated tokens."""
        while self.step():
            pass
        self.flush()
        return {rid: s.generated for rid, s in self.sched.finished.items()}

    # --------------------------------------------------------- mode switch
    def drain(self) -> None:
        self.sched.drain()

    def _pack_slot(self, slot: int, seq: SeqState, batch: Optional[int],
                   shipped: set) -> PackedKV:
        """Pack one live slot's KV pages into the ``PackedKV`` wire form
        (shared by drain-time ``handoff`` and the steady-state
        ``export_prefilled`` stream).  With a dedupe ``batch``, pages
        already shipped in this export ride as references only."""
        # the cache holds seq.pos - 1 tokens: the last generated token
        # is the next decode input, not yet written
        n_tok = seq.pos - 1
        ids = self.pages.slot_pages(slot)[:pages_for(n_tok,
                                                     self.page_size)]
        if batch is not None:
            carried = tuple(p for p, pid in enumerate(ids)
                            if pid not in shipped)
            payload = paged_pack(self.cfg, self.cache, slot, ids, n_tok,
                                 self.page_size,
                                 ship=[ids[p] for p in carried])
            payload.page_ids = tuple(ids)
            payload.carried = carried
            payload.batch = batch
            shipped.update(ids)
        else:
            payload = paged_pack(self.cfg, self.cache, slot, ids, n_tok,
                                 self.page_size)
        return payload

    # ------------------------------------------------------- preemption
    def _maybe_preempt(self) -> None:
        """Preempt low-priority decode slots when the policy's next
        fresh admission is a HIGHER class that cannot be admitted for
        lack of a slot or pages.  Victims are packed over the PackedKV
        wire into ``preempt_outbox`` before the tick plans admissions,
        so the requester takes the freed capacity this very tick."""
        if not self.preemption or self.role == "prefill" \
                or self.sched.draining or not self.sched.queue:
            return
        sched = self.sched
        head = sched.queue[sched._pick(sched.queue)]
        if head.priority <= 0:
            return                  # lowest class preempts nobody
        free = sched.free_slots()
        if free and self.pages.can_admit(sched.admit_tokens(head),
                                         prompt=head.prompt):
            return                  # plain admission takes it this tick
        if sched._quota_blocked(head):
            return         # quota would veto it — don't shed live work
        # worst-case incremental pages still missing (slot_claim sums
        # are worst-case too, so coverage implies admissibility)
        headroom = self.pages.n_pages - self.pages.n_reserved
        need = pages_for(sched.admit_tokens(head), self.page_size) \
            - max(headroom, 0)
        victims = sched.pick_victims(need, head.slo,
                                     need_slot=not free)
        if victims:
            self.preempt_export(victims)

    def preempt_export(self, slots: Sequence[int]
                       ) -> List[Tuple[SeqState, Any, int]]:
        """Pack the live pages of each victim slot into the deduped
        ``PackedKV`` wire form and evict it (``Scheduler.preempt``):
        the slot and its pages free immediately (CoW sharers keep their
        references — pack copies page contents, so the payload is
        self-contained), and the (seq, payload, pages_reclaimed)
        triples land in ``preempt_outbox`` for the cluster to park to
        the host tier — or for the engine itself to re-enqueue next
        step.  The sequence later re-enters through the ordinary
        ``enqueue_resume``/adopt machinery, so its greedy tokens stay
        bit-equal with an uninterrupted run."""
        if not self.paged:
            raise RuntimeError("preemption needs the paged KV layout")
        self.flush()       # _restore stages seq.generated[-1] at resume
        batch = next(_HANDOFF_BATCH) if self.prefix_sharing else None
        shipped: set = set()
        out: List[Tuple[SeqState, Any, int]] = []
        for slot in slots:
            seq = self.sched.slots[slot]
            if seq is None or seq.finished:
                continue           # EOS landed at flush — retires instead
            claim = self.pages.slot_claim(slot)
            payload = self._pack_slot(slot, seq, batch, shipped)
            self.sched.preempt(slot)
            out.append((seq, payload, claim))
        self.preempt_outbox.extend(out)
        return out

    def take_preempted(self) -> List[Tuple[SeqState, Any, int]]:
        """Drain the preemption outbox — (seq, payload, pages_reclaimed)
        triples the caller must now own (park to the host tier and
        re-enter them later, or hand them to ``adopt``)."""
        out, self.preempt_outbox = self.preempt_outbox, []
        return out

    def take_shed(self) -> List[Tuple[int, str, float]]:
        """Drain the shed log — (req_id, slo_class_name, retry_after)
        for every submit the scheduler rejected since the last drain."""
        out, self.shed_log = self.shed_log, []
        return out

    def evict_parked(self, req_id: int) -> Tuple[SeqState, Any]:
        """Remove a parked (resume-queue) sequence from this engine so
        the caller can re-route it to a less wedged instance.  Returns
        (seq, payload); the payload degrades to None when it was
        wire-deduped against THIS engine's adoption state — its page
        references resolve nowhere else, so the target rebuilds the
        cache from tokens instead (§4.4 recompute, still bit-equal)."""
        seq = next(s for s in self.sched.resume_queue
                   if s.req_id == req_id)
        self.sched.resume_queue.remove(seq)
        payload = self._parked.pop(req_id, None)
        if isinstance(payload, PackedKV) and payload.batch is not None:
            self._dedupe_discard(req_id, payload)
            payload = None
        return seq, payload

    # ----------------------------------------------------- disagg export
    def export_prefilled(self) -> List[Tuple[SeqState, Any]]:
        """Stream out every prefilled slot (prefill-role wire).

        The disaggregation fast path: each slot whose prompt pass has
        produced its first token is packed through the same batch-deduped
        ``PackedKV`` export as ``handoff()`` and its slot freed for the
        next prompt — but unlike a drain the engine keeps serving, and
        queued/parked state stays put.  Sequences that finished AT
        prefill (one-token budget, or EOS first) retire here and are not
        exported.  Policy order decides who ships first (who gets the
        decode pool's free slots)."""
        if self.role != "prefill":
            raise RuntimeError(
                "export_prefilled() is the prefill-role wire — unified "
                "engines hand off at drain time instead")
        ready = self.sched.prefilled_slots()
        if not ready:
            return []
        self.flush()      # adopters need concrete first-token ids (§4.4)
        ready = [s for s in ready          # EOS may have landed at flush
                 if not self.sched.slots[s].finished]
        pairs = [(s, self.sched.slots[s]) for s in ready]
        pairs = [pairs[i] for i in
                 sorted(range(len(pairs)),
                        key=lambda i: self.sched.policy_key(pairs[i][1],
                                                            i))]
        batch = next(_HANDOFF_BATCH) if self.prefix_sharing else None
        shipped: set = set()
        out: List[Tuple[SeqState, Any]] = []
        for slot, seq in pairs:
            payload = self._pack_slot(slot, seq, batch, shipped)
            self.sched.export_slot(slot)
            out.append((seq, payload))
        return out

    def handoff(self) -> List[Tuple[SeqState, Any]]:
        """Export in-flight sequences with their live KV state.

        A paged engine packs only each sequence's live pages into a
        ``PackedKV`` wire payload (page-granular handoff); a striped
        engine gathers the whole ``max_len`` slot stripe.  Sequences
        still queued (never prefilled) carry ``None``.  The export list
        is ordered by the admission policy (who gets the adopting
        instance's free slots first); FCFS keeps slot order.

        A prefix-sharing engine dedupes shared pages on the wire: the
        export gets one ``batch`` tag, each source page ships in the
        FIRST payload whose run holds it, and later payloads carry only
        their un-shipped suffix plus the source page ids the adopter
        needs to remap.  Payloads are packed in policy order so carriers
        always precede the payloads that reference them."""
        self.flush()          # adopters need concrete token ids (§4.4)
        out: List[Tuple[SeqState, Any]] = []
        live = [(i, s) for i, s in enumerate(self.sched.slots)
                if s is not None and not s.finished
                and self.sched.state[i] is not SlotState.FREE]
        live = [live[i] for i in
                sorted(range(len(live)),
                       key=lambda i: self.sched.policy_key(live[i][1], i))]
        batch = next(_HANDOFF_BATCH) if self.prefix_sharing else None
        shipped: set = set()
        for slot, seq in live:
            if self.paged:
                out.append((seq, self._pack_slot(slot, seq, batch,
                                                 shipped)))
            else:
                out.append((seq, cache_gather(self.cache, slot,
                                              self._axes)))
        have = {s.req_id for s, _ in out}
        for seq in self.sched.handoff():     # releases slots (and pages)
            if seq.req_id not in have:
                # parked sequences keep the payload they arrived with;
                # a parked wire-deduped payload stops referencing THIS
                # engine's remap once exported, so its batch holds drop
                payload = self._parked.pop(seq.req_id, None)
                self._dedupe_discard(seq.req_id, payload)
                out.append((seq, payload))
        # un-harvested preemption victims ride along: they sit in no
        # scheduler queue, but their packed payloads are live state
        for seq, payload, _ in self.preempt_outbox:
            if seq.req_id not in have:
                out.append((seq, payload))
        self.preempt_outbox = []
        return out

    def adopt(self, pairs: Sequence[Tuple[SeqState, Any]]) -> None:
        """Adopt handed-off sequences (mode switch, §4.4).

        A sequence arriving with a live cache is scattered straight into
        a free slot; one arriving without (e.g. from a pipelined instance
        that keeps no decode cache) has its cache rebuilt once via
        ``repro.core.mode_switch.handoff_requests`` — either way it
        resumes in DECODE and never re-enters the prefill queue.  When
        more live sequences arrive than slots are free (a multi-pipeline
        mode switch converging on one replica), the overflow parks in the
        scheduler's resume queue and enters DECODE as slots retire.
        Sequences that never started decode are submitted normally."""
        if self.role == "prefill":
            raise RuntimeError(
                "prefill-role engine runs prompt passes only — adopt "
                "into a decode-role (or unified) engine")
        if any(s.eos_id is not None for s, _ in pairs):
            self._eager = True
        started = [(s, c) for s, c in pairs if s.generated]
        fresh = [s for s, c in pairs if not s.generated]
        # register every wire-dedupe batch payload BEFORE placement: the
        # first restored carrier must see its batch-mates as pending so
        # it holds the pages a later (possibly parked) sharer references.
        # Only source pages some OTHER payload references (non-carried
        # positions) need a retention hold — holding every carried page
        # would pin private suffix pages for the batch's whole lifetime
        # and overcommit small pools.
        for s, payload in started:
            if isinstance(payload, PackedKV) and payload.batch is not None:
                st = self._dedupe_state(payload.batch)
                st["pending"].add(s.req_id)
                st.setdefault("needed", set()).update(
                    payload.page_ids[p] for p in range(payload.n_pages)
                    if p not in payload.carried)
        # the ADOPTING scheduler's policy decides who takes the free
        # slots and who parks (stable: FCFS keeps the handoff order)
        started = [started[i] for i in
                   sorted(range(len(started)),
                          key=lambda i: self.sched.policy_key(
                              started[i][0], i))]
        free = self.sched.free_slots()
        placed = 0
        parked_any = False
        for seq, payload in started:
            # a paged pool admits by page budget as well as by slot: an
            # adoption that doesn't fit parks and resumes as pages free
            # up.  Once one pair parks, every later pair parks too —
            # same no-small-request-bypass FCFS the scheduler applies on
            # this PageTable (resume order == handoff order).
            if not parked_any and placed < len(free) and (
                    not self.paged
                    or self.pages.can_admit(seq.total_tokens)):
                slot = free[placed]
                placed += 1
                self._restore(slot, seq, payload)
                self.sched.adopt(seq, slot)
            else:
                parked_any = True
                self._parked[seq.req_id] = payload
                self.sched.enqueue_resume(seq)
        for seq in fresh:
            self.sched.submit(seq)

    def set_role(self, role: str) -> None:
        """Switch between the ``decode`` and ``unified`` roles in place
        (the cluster's fallback when a model's prefill pool empties:
        decode replicas relax to unified so prompts are never stranded).
        Both roles size admission by the full generation budget, so the
        switch only toggles the submit gate; prefill conversions are
        refused — prompt-sized reservations on live slots cannot
        retroactively cover a generation budget."""
        if role == self.role:
            return
        if "prefill" in (role, self.role):
            raise ValueError(
                f"cannot convert a live engine {self.role!r} → {role!r}: "
                f"only decode ↔ unified share an admission sizing")
        self.role = role
        self.sched.role = role

    # ------------------------------------------------------------- status
    @property
    def stats(self) -> Dict[str, int]:
        return self.sched.stats
