"""Model builder: init / forward / decode for all six assigned families.

The trunk is expressed as ``cfg.layer_pattern`` repeated ``n_pattern_reps``
times (scanned — one stacked parameter pytree per pattern position) plus an
unrolled remainder.  This layout is what λScale's block partitioning slices:
a *model block* is a contiguous range of trunk layers (see
``repro.core.blocks``).

API:
  init_params(cfg, key, dtype)                      -> params
  forward(cfg, params, batch, build_cache=..., cache_len=...)
        -> {"logits": (B,S,V), "aux": scalar, "cache": ...?}
  decode_step(cfg, params, cache, tokens (B,), positions (B,))
        -> (logits (B,V), new_cache)
  init_cache(cfg, batch_size, max_len, dtype)       -> zeroed decode cache
  make_batch(cfg, shape_or_dims, key)               -> concrete sample batch
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models import scopes
from repro.models import xlstm as X

Params = Dict[str, Any]

# Beyond max_len for a decode request, "global" (attn_full) layers fall back
# to a windowed cache so 524k-token decode stays bounded (DESIGN.md §8).
LONG_CONTEXT_THRESHOLD = 100_000
POS_TABLE = 4096  # learned-position table size (whisper)


def _mixer_window(cfg: ModelConfig, mixer: str,
                  max_len: Optional[int] = None) -> Optional[int]:
    """Effective attention window for masking/cache sizing."""
    if mixer == "attn_full":
        if (max_len is not None and max_len > LONG_CONTEXT_THRESHOLD
                and cfg.window is not None):
            return cfg.window
        return None
    return cfg.window


# ===================================================================== init
def _init_layer(cfg: ModelConfig, entry: str, key, dtype) -> Params:
    mixer, ffn = entry.split(":")
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_norm(cfg, dtype)}
    if mixer in ("attn", "attn_full"):
        p["attn"] = L.init_attention(cfg, ks[0], dtype)
    elif mixer == "rec":
        p["rec"] = R.init_rec(cfg, ks[0], dtype)
    elif mixer == "mlstm":
        p["mlstm"] = X.init_mlstm(cfg, ks[0], dtype)
    elif mixer == "slstm":
        p["slstm"] = X.init_slstm(cfg, ks[0], dtype)
    else:
        raise ValueError(f"unknown mixer {mixer}")
    if cfg.family == "encdec" and mixer in ("attn", "attn_full"):
        p["norm_x"] = L.init_norm(cfg, dtype)
        p["xattn"] = L.init_attention(cfg, ks[1], dtype)
    if ffn == "dense":
        p["norm2"] = L.init_norm(cfg, dtype)
        p["ffn"] = L.init_ffn(cfg, ks[2], dtype)
    elif ffn == "moe":
        p["norm2"] = L.init_norm(cfg, dtype)
        p["moe"] = M.init_moe(cfg, ks[2], dtype)
    return p


def _init_enc_layer(cfg: ModelConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {"norm1": L.init_norm(cfg, dtype),
            "attn": L.init_attention(cfg, ks[0], dtype),
            "norm2": L.init_norm(cfg, dtype),
            "ffn": L.init_ffn(cfg, ks[1], dtype)}


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {"embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                                       dtype)}
    if cfg.rope_pct == 0.0:
        p["pos_embed"] = (jax.random.normal(
            keys[1], (POS_TABLE, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype)
    if cfg.n_patches:
        p["patch_proj"] = L.dense_init(keys[2], cfg.d_model, cfg.d_model,
                                       dtype)
    # trunk: one stacked pytree per pattern position
    reps = cfg.n_pattern_reps
    trunk = []
    for pi, entry in enumerate(cfg.layer_pattern):
        ks = jax.random.split(jax.random.fold_in(keys[3], pi), reps)
        stacked = jax.vmap(lambda k: _init_layer(cfg, entry, k, dtype))(ks)
        trunk.append(stacked)
    p["trunk"] = tuple(trunk)
    rem = []
    for ri in range(cfg.n_remainder_layers):
        entry = cfg.layer_pattern[ri % cfg.pattern_len]
        rem.append(_init_layer(cfg, entry,
                               jax.random.fold_in(keys[4], ri), dtype))
    p["rem"] = tuple(rem)
    p["final_norm"] = L.init_norm(cfg, dtype)
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(keys[5], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.family == "encdec":
        eks = jax.random.split(keys[6], cfg.n_enc_layers)
        p["enc"] = {
            "pos": (jax.random.normal(keys[7], (cfg.enc_seq, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dtype),
            "layers": jax.vmap(lambda k: _init_enc_layer(cfg, k, dtype))(eks),
            "final_norm": L.init_norm(cfg, dtype),
        }
    return p


# ============================================================ layer (full)
@jax.named_scope(scopes.MLP)
def _mlp(p: Params, x, cfg: ModelConfig):
    """The dense feed-forward block on the normed residual."""
    return L.apply_ffn(p["ffn"], L.apply_norm(p["norm2"], x, cfg), cfg)


def _apply_layer_full(p: Params, x, cfg: ModelConfig, entry: str, positions,
                      *, enc_out=None, build_cache=False, cache_len=None,
                      moe_cf=1.25):
    """Full-sequence layer application.  Returns (x, cache_or_zero, aux)."""
    mixer, ffn = entry.split(":")
    aux = jnp.zeros((), jnp.float32)
    cache: Any = jnp.zeros(())
    B, S, _ = x.shape
    rope = cfg.rope_pct > 0.0
    h = L.apply_norm(p["norm1"], x, cfg)
    if mixer in ("attn", "attn_full"):
        win = _mixer_window(cfg, mixer, cache_len)
        a, (k, v) = L.full_attention(p["attn"], h, cfg, positions,
                                     causal=True, rope=rope, window=win)
        x = x + a
        if build_cache:
            cache = L.kv_cache_from_prefill(
                cfg, k, v, positions, cache_len,
                window=win if win is not None else None)
        if cfg.family == "encdec":
            hx = L.apply_norm(p["norm_x"], x, cfg)
            xk = (enc_out @ p["xattn"]["wk"])
            xv = (enc_out @ p["xattn"]["wv"])
            if cfg.qkv_bias:
                xk, xv = xk + p["xattn"]["bk"], xv + p["xattn"]["bv"]
            Se = enc_out.shape[1]
            xk = xk.reshape(B, Se, cfg.n_kv_heads, cfg.d_head)
            xv = xv.reshape(B, Se, cfg.n_kv_heads, cfg.d_head)
            ca, _ = L.full_attention(p["xattn"], hx, cfg, positions,
                                     causal=False, rope=False,
                                     kv_override=(xk, xv))
            x = x + ca
            if build_cache:
                cache = {"self": cache, "xk": xk, "xv": xv}
    elif mixer == "rec":
        out, st = R.apply_rec(p["rec"], h, cfg)
        x = x + out
        if build_cache:
            cache = st
    elif mixer == "mlstm":
        out, st = X.apply_mlstm(p["mlstm"], h, cfg)
        x = x + out
        if build_cache:
            cache = st
    elif mixer == "slstm":
        out, st = X.apply_slstm(p["slstm"], h, cfg)
        x = x + out
        if build_cache:
            cache = st
    if ffn == "dense":
        x = x + _mlp(p, x, cfg)
    elif ffn == "moe":
        mo, a = M.apply_moe(p["moe"], L.apply_norm(p["norm2"], x, cfg), cfg,
                            capacity_factor=moe_cf)
        x = x + mo
        aux = aux + a
    return x, cache, aux


# ========================================================== layer (decode)
def _apply_layer_decode(p: Params, x, cfg: ModelConfig, entry: str,
                        positions, cache, *, page_table=None,
                        attn_impl: str = "xla", block_k=None,
                        page_ctx=None, layer=None):
    """Single-token layer application. x: (B,1,d); positions (B,).

    ``page_table`` switches attention layers to the paged pool layout
    (``cache`` then holds {"k","v"} page pools instead of per-slot
    stripes; with ``layer`` they are the whole stacked pools and this
    layer's is ``[layer]``); non-attention state stays slot-indexed
    either way.  ``page_ctx`` is the tick-level table expansion shared
    by every paged layer (hoisted out of the trunk scan by
    ``decode_step``)."""
    mixer, ffn = entry.split(":")
    rope = cfg.rope_pct > 0.0
    h = L.apply_norm(p["norm1"], x, cfg)
    if mixer in ("attn", "attn_full"):
        self_cache = cache["self"] if cfg.family == "encdec" else cache
        win = _mixer_window(cfg, mixer)
        # ring caches smaller than max_len imply the windowed fallback
        if page_table is not None:
            a, new_self = L.paged_decode_attention(
                p["attn"], h, self_cache, cfg, positions, page_table,
                rope=rope, window=win, impl=attn_impl, block_k=block_k,
                page_ctx=page_ctx, layer=layer)
        else:
            a, new_self = L.decode_attention(p["attn"], h, self_cache, cfg,
                                             positions, rope=rope,
                                             window=win)
        x = x + a
        if cfg.family == "encdec":
            hx = L.apply_norm(p["norm_x"], x, cfg)
            ca, _ = L.decode_attention(p["xattn"], hx, None, cfg, positions,
                                       rope=False,
                                       cross_kv=(cache["xk"], cache["xv"]))
            x = x + ca
            new_cache: Any = {"self": new_self, "xk": cache["xk"],
                              "xv": cache["xv"]}
        else:
            new_cache = new_self
    elif mixer == "rec":
        out, new_cache = R.apply_rec_step(p["rec"], h, cfg, cache)
        x = x + out
    elif mixer == "mlstm":
        out, new_cache = X.apply_mlstm_step(p["mlstm"], h, cfg, cache)
        x = x + out
    elif mixer == "slstm":
        out, new_cache = X.apply_slstm_step(p["slstm"], h, cfg, cache)
        x = x + out
    if ffn == "dense":
        x = x + _mlp(p, x, cfg)
    elif ffn == "moe":
        mo, _ = M.apply_moe(p["moe"], L.apply_norm(p["norm2"], x, cfg), cfg,
                            capacity_factor=None)
        x = x + mo
    return x, new_cache


# ================================================================= encoder
def _encode(cfg: ModelConfig, enc_p: Params, frames) -> jnp.ndarray:
    """frames: (B, enc_seq, d) stubbed frontend embeddings."""
    x = frames + enc_p["pos"][None]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(xc, lp):
        h = L.apply_norm(lp["norm1"], xc, cfg)
        a, _ = L.full_attention(lp["attn"], h, cfg, positions,
                                causal=False, rope=False)
        xc = xc + a
        xc = xc + _mlp(lp, xc, cfg)
        return xc, None

    x, _ = jax.lax.scan(body, x, enc_p["layers"])
    return L.apply_norm(enc_p["final_norm"], x, cfg)


# ================================================================== embed
@jax.named_scope(scopes.EMBED)
def _embed_tokens(cfg: ModelConfig, params: Params, tokens, positions,
                  patches=None):
    x = params["embed"][tokens]
    if cfg.family == "hybrid":          # gemma-style embedding scale
        x = x * math.sqrt(cfg.d_model)
    if patches is not None:
        pe = patches.astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([pe, x], axis=1)
    if "pos_embed" in params:
        x = x + params["pos_embed"][jnp.minimum(positions, POS_TABLE - 1)]
    return x


@jax.named_scope(scopes.LM_HEAD)
def _unembed(cfg: ModelConfig, params: Params, x):
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["head"]


# ================================================================= forward
def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, build_cache: bool = False, cache_len: Optional[int] = None,
            moe_cf=1.25) -> Dict[str, Any]:
    """Train / prefill forward.

    batch: {"tokens": (B, S_text)} plus "patches" (vlm) / "frames" (encdec).
    """
    tokens = batch["tokens"]
    B = tokens.shape[0]
    patches = batch.get("patches") if cfg.n_patches else None
    S_total = tokens.shape[1] + (patches.shape[1] if patches is not None else 0)
    positions = jnp.broadcast_to(jnp.arange(S_total)[None], (B, S_total))
    if cache_len is None:
        cache_len = S_total
    x = _embed_tokens(cfg, params, tokens, positions, patches)
    enc_out = _encode(cfg, params["enc"], batch["frames"]) \
        if cfg.family == "encdec" else None

    def rep_body(carry, lp_tuple):
        xc, auxc = carry
        caches = []
        for pi, entry in enumerate(cfg.layer_pattern):
            xc, c, a = _apply_layer_full(
                lp_tuple[pi], xc, cfg, entry, positions, enc_out=enc_out,
                build_cache=build_cache, cache_len=cache_len, moe_cf=moe_cf)
            caches.append(c)
            auxc = auxc + a
        return (xc, auxc), tuple(caches)

    rep_body_ck = jax.checkpoint(rep_body)
    (x, aux), trunk_caches = jax.lax.scan(
        rep_body_ck, (x, jnp.zeros((), jnp.float32)), params["trunk"])

    rem_caches = []
    for ri, lp in enumerate(params["rem"]):
        entry = cfg.layer_pattern[ri % cfg.pattern_len]
        x, c, a = _apply_layer_full(lp, x, cfg, entry, positions,
                                    enc_out=enc_out, build_cache=build_cache,
                                    cache_len=cache_len, moe_cf=moe_cf)
        rem_caches.append(c)
        aux = aux + a

    out: Dict[str, Any] = {"logits": _unembed(cfg, params, x), "aux": aux}
    if build_cache:
        out["cache"] = {"trunk": trunk_caches, "rem": tuple(rem_caches),
                        "pos": positions[:, -1] + 1}
    return out


# ============================================================== decode step
def _paged_pool_dims(cfg: ModelConfig, cache):
    """(P, page_size) of the first paged pool, or None (no attention)."""
    for where, i, entry in _layer_entries(cfg):
        if _is_paged_entry(entry):
            leaf = (cache["trunk"] if where == "trunk"
                    else cache["rem"])[i]["k"]
            return leaf.shape[-3], leaf.shape[-2]
    return None


def decode_step(cfg: ModelConfig, params: Params, cache, tokens, positions,
                *, attn_impl: str = "xla",
                block_k=None, ctx_pages=None) -> Tuple[jnp.ndarray, Any]:
    """tokens: (B,) int32 — last generated token; positions: (B,) int32.
    Returns (logits (B, V), new_cache).

    A cache carrying a ``"pages"`` table (``init_paged_cache``) decodes
    attention layers against the shared page pool; otherwise the classic
    per-slot striped layout is used.  The page-table expansion (gather
    indices, write target, validity mask per distinct window) is
    computed ONCE here and threaded through the trunk scan — it is
    loop-invariant, so hoisting it keeps the per-layer work at the
    attention math itself.  The stacked page pools ride the scan's
    carry, not its inputs and outputs: each layer writes its token into
    and gathers its pages from the one stacked buffer by layer index,
    so a donated pool is updated in place instead of sliced per layer
    and restacked.  ``block_k`` tunes the Pallas fused kernel's
    sub-page KV block (``attn_impl="pallas"``; autotuned via
    ``repro.kernels.autotune``).

    ``ctx_pages`` (static) bounds the attended context to the first
    ``ctx_pages`` page-table columns: with pages allocated on demand,
    attention work can scale with the LIVE sequence lengths instead of
    ``max_pages``, so the caller (the engine, which knows every live
    slot's position) passes the max allocated page count this tick.
    Every live token sits inside those pages by construction and FREE
    rows stay ``-1`` → trash page, so outputs are bit-identical to the
    full-table walk."""
    page_table = cache.get("pages")
    ctx_table = page_table
    if (page_table is not None and ctx_pages is not None
            and ctx_pages < page_table.shape[1]):
        ctx_table = page_table[:, :ctx_pages]
    page_ctx = None
    if page_table is not None and attn_impl != "pallas":
        dims = _paged_pool_dims(cfg, cache)
        if dims is not None:
            P, ps = dims
            wins = tuple({_mixer_window(cfg, entry.split(":")[0])
                          for _, _, entry in _layer_entries(cfg)
                          if _is_paged_entry(entry)})
            page_ctx = L.paged_page_context(ctx_table, positions, ps, P,
                                            windows=wins)
    pos2 = positions[:, None]
    x = _embed_tokens(cfg, params, tokens[:, None], pos2)
    layer_kw = dict(page_table=ctx_table, attn_impl=attn_impl,
                    block_k=block_k, page_ctx=page_ctx)
    # paged pools are carried whole; other layer state is scanned
    carried = tuple(
        c if page_table is not None and _is_paged_entry(entry) else None
        for entry, c in zip(cfg.layer_pattern, cache["trunk"]))
    scanned = tuple(None if p is not None else c
                    for p, c in zip(carried, cache["trunk"]))

    def rep_body(carry, xs):
        xc, pools = carry
        lp_tuple, c_tuple, li = xs
        pools, new_caches = list(pools), []
        for pi, entry in enumerate(cfg.layer_pattern):
            if pools[pi] is not None:
                xc, pools[pi] = _apply_layer_decode(
                    lp_tuple[pi], xc, cfg, entry, positions, pools[pi],
                    layer=li, **layer_kw)
                new_caches.append(None)
            else:
                xc, nc = _apply_layer_decode(lp_tuple[pi], xc, cfg, entry,
                                             positions, c_tuple[pi],
                                             **layer_kw)
                new_caches.append(nc)
        return (xc, tuple(pools)), tuple(new_caches)

    (x, pools), ys = jax.lax.scan(
        rep_body, (x, carried),
        (params["trunk"], scanned, jnp.arange(cfg.n_pattern_reps)))
    new_trunk = tuple(p if p is not None else c for p, c in zip(pools, ys))
    new_rem = []
    for ri, lp in enumerate(params["rem"]):
        entry = cfg.layer_pattern[ri % cfg.pattern_len]
        x, nc = _apply_layer_decode(lp, x, cfg, entry, positions,
                                    cache["rem"][ri], **layer_kw)
        new_rem.append(nc)
    logits = _unembed(cfg, params, x)[:, 0]
    out_cache = {"trunk": new_trunk, "rem": tuple(new_rem),
                 "pos": positions + 1}
    if page_table is not None:
        out_cache["pages"] = page_table
    return logits, out_cache


# ================================================================== caches
def _init_layer_cache(cfg: ModelConfig, entry: str, batch: int, max_len: int,
                      dtype):
    mixer, _ = entry.split(":")
    if mixer in ("attn", "attn_full"):
        win = _mixer_window(cfg, mixer, max_len)
        W = min(win, max_len) if win is not None else max_len
        c: Any = L.init_kv_cache(cfg, batch, max_len, dtype, window=W)
        if cfg.family == "encdec":
            kv, dh = cfg.n_kv_heads, cfg.d_head
            c = {"self": c,
                 "xk": jnp.zeros((batch, cfg.enc_seq, kv, dh), dtype),
                 "xv": jnp.zeros((batch, cfg.enc_seq, kv, dh), dtype)}
        return c
    if mixer == "rec":
        return R.init_rec_state(cfg, batch, dtype)
    if mixer == "mlstm":
        return X.init_mlstm_state(cfg, batch, dtype)
    if mixer == "slstm":
        return X.init_slstm_state(cfg, batch, dtype)
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Zeroed decode cache (used by serving engine and dry-run specs)."""
    reps = cfg.n_pattern_reps
    trunk = []
    for entry in cfg.layer_pattern:
        one = _init_layer_cache(cfg, entry, batch, max_len, dtype)
        trunk.append(jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape), one))
    rem = tuple(
        _init_layer_cache(cfg, cfg.layer_pattern[ri % cfg.pattern_len],
                          batch, max_len, dtype)
        for ri in range(cfg.n_remainder_layers))
    return {"trunk": tuple(trunk), "rem": rem,
            "pos": jnp.zeros((batch,), jnp.int32)}


# ============================================================ paged caches
# Attention K/V live in a shared pool of fixed-size token pages addressed
# through one per-slot page table (shared by every attention layer — the
# same token occupies the same page slot in each layer's pool, so one
# allocation covers the whole stack).  A page stores its tokens' heads
# side by side, (page_size, kv * dh): the minor dimension is a whole
# number of 128-lane tiles whenever kv * dh is a multiple of 128, so the
# TPU keeps the pool row-major and unpadded even where dh alone is not.
# Heads are split out again only on gathered pages and on the PackedKV
# wire, which stays (..., page_size, kv, dh).  Non-attention state
# (RG-LRU / xLSTM) is O(d) per slot, not O(tokens), and stays
# slot-indexed.
def _lanes(x):
    """(..., kv, dh) → the pool's (..., kv * dh)."""
    return x.reshape(x.shape[:-2] + (-1,))


def _heads(cfg: ModelConfig, x):
    """The pool's (..., kv * dh) → (..., kv, dh)."""
    return x.reshape(x.shape[:-1] + (cfg.n_kv_heads, cfg.d_head))


def _is_paged_entry(entry: str) -> bool:
    return entry.split(":")[0] in ("attn", "attn_full")


def _layer_entries(cfg: ModelConfig):
    """Yield ("trunk", i, entry) / ("rem", i, entry) in cache order."""
    for pi, entry in enumerate(cfg.layer_pattern):
        yield "trunk", pi, entry
    for ri in range(cfg.n_remainder_layers):
        yield "rem", ri, cfg.layer_pattern[ri % cfg.pattern_len]


def init_paged_cache(cfg: ModelConfig, n_slots: int, *,
                     page_size=None, n_pages: Optional[int] = None,
                     max_pages: Optional[int] = None,
                     max_len: Optional[int] = None, dtype=jnp.float32,
                     attn_impl: str = "xla"):
    """Zeroed paged decode cache: per-layer page pools of shape
    (n_pages + 1, page_size, kv * dh) carry ONE extra trash page (index
    n_pages) that absorbs writes from FREE slots, and the top level
    holds the shared device page table.

    ``page_size`` may be ``"auto"`` (requires ``max_len``): the pool
    geometry is resolved through ``cache_ops.paged_geometry``, which
    consults the autotuner's cached sweep.  ``n_pages``/``max_pages``
    default from ``max_len`` when omitted."""
    if cfg.family == "encdec":
        raise ValueError("paged caches cover decoder-only families "
                         "(cross-attention K/V is fixed-size per slot)")
    from repro.models.cache_ops import (DEFAULT_PAGE_SIZE, paged_geometry,
                                        pages_for)
    if page_size is None:
        page_size = DEFAULT_PAGE_SIZE
    if page_size == "auto" or max_pages is None or n_pages is None:
        if max_len is None:
            raise ValueError("page_size='auto' or defaulted n_pages/"
                             "max_pages need max_len")
        page_size, _ = paged_geometry(cfg, n_slots, max_len,
                                      page_size=page_size,
                                      attn_impl=attn_impl)
        if max_pages is None:
            max_pages = pages_for(max_len, page_size)
        if n_pages is None:
            n_pages = n_slots * max_pages
    reps = cfg.n_pattern_reps
    shape = (n_pages + 1, page_size, cfg.n_kv_heads * cfg.d_head)

    def one(entry):
        if _is_paged_entry(entry):
            return {"k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype)}
        return _init_layer_cache(cfg, entry, n_slots, 0, dtype)

    trunk = []
    for entry in cfg.layer_pattern:
        trunk.append(jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape),
            one(entry)))
    rem = tuple(one(cfg.layer_pattern[ri % cfg.pattern_len])
                for ri in range(cfg.n_remainder_layers))
    return {"trunk": tuple(trunk), "rem": rem,
            "pos": jnp.zeros((n_slots,), jnp.int32),
            "pages": jnp.full((n_slots, max_pages), -1, jnp.int32)}


def _page_targets(spos, pt_row, page_size, n_pool_pages):
    """Map stored positions (W,) to (page, offset) write targets; entries
    with spos < 0 (empty ring slots) land on the trash page."""
    pg = pt_row[jnp.clip(spos, 0, None) // page_size]
    pg = jnp.where((spos >= 0) & (pg >= 0), pg, n_pool_pages - 1)
    return pg, spos % page_size


@jax.named_scope(scopes.ATTN_KV)
def paged_prefill_scatter(cfg: ModelConfig, cache, single_cache, slot,
                          pt_row, n_tokens=None):
    """Scatter a freshly-built batch-1 (ring-layout) decode cache into
    the paged pool for ``slot``.  Pure jnp, traces with a traced slot and
    page-table row, so the engine fuses prefill + scatter into one
    executable — and doubles as the pooled→paged converter at adoption
    time (mode-switch recomputation hands back a ring cache).

    ``n_tokens`` (static) bounds the page-granular fast path to the
    pages actually covering the prompt: positions past it are masked at
    every read until decode overwrites them, so the zero tail needs no
    write and scatter work scales with prompt length, not
    ``max_pages``.  ``None`` writes every page (adoption-time callers
    that convert a full-width cache)."""
    new_cache = {"pos": jax.lax.dynamic_update_slice(
        cache["pos"], single_cache["pos"].astype(cache["pos"].dtype),
        (slot,)), "pages": cache["pages"]}
    trunk, rem = list(cache["trunk"]), list(cache["rem"])
    for where, i, entry in _layer_entries(cfg):
        dst = trunk[i] if where == "trunk" else rem[i]
        src = (single_cache["trunk"] if where == "trunk"
               else single_cache["rem"])[i]
        if _is_paged_entry(entry):
            P, ps = dst["k"].shape[-3], dst["k"].shape[-2]
            MP = pt_row.shape[0]
            W = src["k"].shape[-3]
            if W == MP * ps:
                # page-granular fast path: a full-length linear cache
                # (non-windowed layers never wrap, stored position ==
                # index) scatters MP whole pages instead of W per-token
                # (page, offset) pairs.  Unallocated rows land on the
                # trash page; the zero tail of the prompt's last page
                # overwrites like-for-like zeros, and masked reads keep
                # attention exact either way.
                npg = (MP if n_tokens is None
                       else max(min(-(-n_tokens // ps), MP), 1))
                pg = jnp.where(pt_row >= 0, pt_row, P - 1)[:npg]
                if where == "trunk":
                    reps = src["k"].shape[0]
                    pages = lambda leaf: leaf[:, 0].reshape(
                        (reps, MP, ps, -1))[:, :npg]
                    upd = {"k": dst["k"].at[:, pg].set(pages(src["k"])),
                           "v": dst["v"].at[:, pg].set(pages(src["v"]))}
                else:
                    pages = lambda leaf: leaf[0].reshape(
                        (MP, ps, -1))[:npg]
                    upd = {"k": dst["k"].at[pg].set(pages(src["k"])),
                           "v": dst["v"].at[pg].set(pages(src["v"]))}
            elif where == "trunk":
                spos = src["pos"][0, 0]                       # (W,)
                pg, off = _page_targets(spos, pt_row, ps, P)
                upd = {"k": dst["k"].at[:, pg, off].set(
                           _lanes(src["k"][:, 0])),
                       "v": dst["v"].at[:, pg, off].set(
                           _lanes(src["v"][:, 0]))}
            else:
                spos = src["pos"][0]
                pg, off = _page_targets(spos, pt_row, ps, P)
                upd = {"k": dst["k"].at[pg, off].set(_lanes(src["k"][0])),
                       "v": dst["v"].at[pg, off].set(_lanes(src["v"][0]))}
        else:
            ax = 1 if where == "trunk" else 0
            upd = jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                    d, s.astype(d.dtype), slot, axis=ax), dst, src)
        if where == "trunk":
            trunk[i] = upd
        else:
            rem[i] = upd
    new_cache["trunk"] = tuple(trunk)
    new_cache["rem"] = tuple(rem)
    return new_cache


def supports_prefix_sharing(cfg: ModelConfig) -> bool:
    """CoW prefix sharing covers configs whose every layer keeps paged
    attention state: recurrent/xLSTM layers carry O(d) state that folds
    the whole prefix into one vector, which cannot be re-owned at page
    granularity (and encdec stays striped entirely)."""
    return cfg.family != "encdec" and all(
        _is_paged_entry(e) for _, _, e in _layer_entries(cfg))


def _apply_layer_suffix(p: Params, x, cfg: ModelConfig, entry: str,
                        positions, pool, pt_row, layer=None):
    """Suffix-prefill layer application (prefix sharing; attention-only
    configs — ``supports_prefix_sharing`` gates callers)."""
    mixer, ffn = entry.split(":")
    assert mixer in ("attn", "attn_full"), \
        "prefix sharing covers attention-only configs"
    h = L.apply_norm(p["norm1"], x, cfg)
    a, new_pool = L.paged_suffix_attention(
        p["attn"], h, pool, cfg, positions, pt_row,
        rope=cfg.rope_pct > 0.0, window=_mixer_window(cfg, mixer),
        layer=layer)
    x = x + a
    if ffn == "dense":
        x = x + _mlp(p, x, cfg)
    elif ffn == "moe":
        mo, _ = M.apply_moe(p["moe"], L.apply_norm(p["norm2"], x, cfg), cfg,
                            capacity_factor=None)
        x = x + mo
    return x, new_pool


def paged_suffix_prefill(cfg: ModelConfig, params: Params, cache, tokens,
                         slot, start) -> Tuple[jnp.ndarray, Any]:
    """Prefill ONLY the un-cached suffix of a prompt whose shared prefix
    already sits in ``slot``'s leading pages (prefix sharing).

    tokens: (1, S_suffix) int32; ``start`` (traced scalar) is the shared
    token count, so positions run start..start+S-1.  Each layer scatters
    the suffix K/V into the slot's pages and attends suffix queries over
    the slot's full table — causal masking makes prefix activations
    depend only on the prefix, so skipping its recompute is exact.  The
    stacked pools ride the scan's carry, as in ``decode_step``.
    Returns (last-position logits (1, V), new paged cache); compute
    scales with the suffix, not the prompt."""
    S = tokens.shape[1]
    pt_row = cache["pages"][slot]
    positions = (start + jnp.arange(S, dtype=jnp.int32))[None]
    x = _embed_tokens(cfg, params, tokens, positions)

    def rep_body(carry, xs):
        xc, pools = carry
        lp_tuple, li = xs
        pools = list(pools)
        for pi, entry in enumerate(cfg.layer_pattern):
            xc, pools[pi] = _apply_layer_suffix(
                lp_tuple[pi], xc, cfg, entry, positions, pools[pi], pt_row,
                layer=li)
        return (xc, tuple(pools)), None

    (x, new_trunk), _ = jax.lax.scan(
        rep_body, (x, cache["trunk"]),
        (params["trunk"], jnp.arange(cfg.n_pattern_reps)))
    new_rem = []
    for ri, lp in enumerate(params["rem"]):
        entry = cfg.layer_pattern[ri % cfg.pattern_len]
        x, nc = _apply_layer_suffix(lp, x, cfg, entry, positions,
                                    cache["rem"][ri], pt_row)
        new_rem.append(nc)
    logits = _unembed(cfg, params, x)[:, -1]
    return logits, {"trunk": new_trunk, "rem": tuple(new_rem),
                    "pos": cache["pos"].at[slot].set(
                        (start + S).astype(cache["pos"].dtype)),
                    "pages": cache["pages"]}


def paged_copy_page(cfg: ModelConfig, cache, src, dst):
    """Fork-on-write device copy: duplicate pool page ``src`` into
    ``dst`` across every paged layer (trunk pools keep their leading
    pattern-repetition axis).  Page ids trace, so one executable serves
    every fork."""
    trunk, rem = list(cache["trunk"]), list(cache["rem"])
    for where, i, entry in _layer_entries(cfg):
        if not _is_paged_entry(entry):
            continue
        tgt = trunk[i] if where == "trunk" else rem[i]
        if where == "trunk":
            upd = {"k": tgt["k"].at[:, dst].set(tgt["k"][:, src]),
                   "v": tgt["v"].at[:, dst].set(tgt["v"][:, src])}
            trunk[i] = upd
        else:
            upd = {"k": tgt["k"].at[dst].set(tgt["k"][src]),
                   "v": tgt["v"].at[dst].set(tgt["v"][src])}
            rem[i] = upd
    return {"trunk": tuple(trunk), "rem": tuple(rem),
            "pos": cache["pos"], "pages": cache["pages"]}


def paged_pack(cfg: ModelConfig, cache, slot: int, page_ids,
               n_tokens: int, page_size: int, *, ship=None):
    """Gather ``slot``'s live pages (and its slot-state leaves) out of
    the paged cache into a page-granular handoff payload.  ``page_size``
    is the owning engine's — it cannot be inferred for models with no
    attention layers (pure-recurrent caches carry no pools).  ``ship``
    restricts the pool gather to a subset of the page ids (wire dedupe:
    pages already carried by an earlier payload of the same export are
    referenced, not re-shipped)."""
    from repro.models.cache_ops import PackedKV
    ids = jnp.asarray(list(page_ids if ship is None else ship), jnp.int32)
    trunk, rem = [], []
    for where, i, entry in _layer_entries(cfg):
        src = (cache["trunk"] if where == "trunk" else cache["rem"])[i]
        if _is_paged_entry(entry):
            assert src["k"].shape[-2] == page_size, \
                (src["k"].shape, page_size)
            if where == "trunk":
                out = {"k": _heads(cfg, src["k"][:, ids]),
                       "v": _heads(cfg, src["v"][:, ids])}
            else:
                out = {"k": _heads(cfg, src["k"][ids]),
                       "v": _heads(cfg, src["v"][ids])}
        else:
            ax = 1 if where == "trunk" else 0
            out = jax.tree.map(
                lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, axis=ax),
                src)
        (trunk if where == "trunk" else rem).append(out)
    return PackedKV(int(n_tokens), page_size,
                    {"trunk": tuple(trunk), "rem": tuple(rem)})


def paged_adopt_scatter(cfg: ModelConfig, cache, packed, slot: int,
                        page_ids):
    """Copy-on-adopt: write a handed-off ``PackedKV`` into freshly
    allocated pages of THIS engine's pool (never aliasing the source)."""
    ids = jnp.asarray(list(page_ids), jnp.int32)
    new_cache = {"pos": cache["pos"].at[slot].set(packed.n_tokens),
                 "pages": cache["pages"]}
    trunk, rem = list(cache["trunk"]), list(cache["rem"])
    for where, i, entry in _layer_entries(cfg):
        dst = trunk[i] if where == "trunk" else rem[i]
        src = packed.kv["trunk" if where == "trunk" else "rem"][i]
        if _is_paged_entry(entry):
            at = (slice(None), ids) if where == "trunk" else ids
            upd = {n: dst[n].at[at].set(_lanes(src[n]).astype(dst[n].dtype))
                   for n in ("k", "v")}
        else:
            ax = 1 if where == "trunk" else 0
            upd = jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                    d, s.astype(d.dtype), slot, axis=ax), dst, src)
        if where == "trunk":
            trunk[i] = upd
        else:
            rem[i] = upd
    new_cache["trunk"] = tuple(trunk)
    new_cache["rem"] = tuple(rem)
    return new_cache


def pack_single_cache(cfg: ModelConfig, single_cache, page_size: int):
    """Repack a batch-1 (ring-layout) decode cache into the page-granular
    wire form — ``core.mode_switch.handoff_requests`` uses this so a
    recomputed cache ships (or adopts) exactly like a live-gathered one."""
    from repro.models.cache_ops import PackedKV, pages_for
    n_tokens = int(single_cache["pos"][0])
    n_pages = max(pages_for(n_tokens, page_size), 1)
    width = n_pages * page_size
    trunk, rem = [], []
    for where, i, entry in _layer_entries(cfg):
        src = (single_cache["trunk"] if where == "trunk"
               else single_cache["rem"])[i]
        if _is_paged_entry(entry):
            if where == "trunk":
                spos = src["pos"][0, 0]                        # (W,)
                idx = jnp.where(spos >= 0, spos, width)        # W → dropped

                def lin(leaf):
                    arr = jnp.zeros((leaf.shape[0], width + 1) +
                                    leaf.shape[3:], leaf.dtype)
                    arr = arr.at[:, idx].set(leaf[:, 0])
                    return arr[:, :width].reshape(
                        (leaf.shape[0], n_pages, page_size) + leaf.shape[3:])
            else:
                spos = src["pos"][0]
                idx = jnp.where(spos >= 0, spos, width)

                def lin(leaf):
                    arr = jnp.zeros((width + 1,) + leaf.shape[2:],
                                    leaf.dtype)
                    arr = arr.at[idx].set(leaf[0])
                    return arr[:width].reshape(
                        (n_pages, page_size) + leaf.shape[2:])
            out = {"k": lin(src["k"]), "v": lin(src["v"])}
        else:
            out = src                                          # batch-1
        (trunk if where == "trunk" else rem).append(out)
    return PackedKV(n_tokens, page_size,
                    {"trunk": tuple(trunk), "rem": tuple(rem)})


# ============================================================== batch maker
def make_batch(cfg: ModelConfig, batch: int, seq_len: int, key=None,
               dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Concrete random batch matching ``input_specs`` (for smoke tests)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    s_text = seq_len
    out: Dict[str, jnp.ndarray] = {}
    if cfg.n_patches:
        s_text = seq_len - cfg.n_patches
        out["patches"] = jax.random.normal(
            k2, (batch, cfg.n_patches, cfg.d_model), dtype)
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            k2, (batch, cfg.enc_seq, cfg.d_model), dtype)
    out["tokens"] = jax.random.randint(k1, (batch, s_text), 0,
                                       cfg.vocab_size, jnp.int32)
    return out
