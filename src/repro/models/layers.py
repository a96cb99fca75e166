"""Shared neural-net layers: norms, RoPE, attention (full / sliding-window /
cross / decode-with-cache), dense & gated FFNs.

All functions are pure; parameters are plain pytrees of jnp arrays. Attention
over long sequences is query-chunked (lax.scan over query blocks) so the
materialized score tensor stays at (chunk × kv_span) — the XLA-level analogue
of the Pallas flash kernel in ``repro.kernels.flash_attention`` (which is the
TPU-target implementation of the same computation).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.scopes import ATTN_KV, ATTN_PROJ

Params = Dict[str, Any]

NEG_INF = -1e30


# ---------------------------------------------------------------- init utils
def dense_init(key, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------- norms
def init_norm(cfg, dtype):
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "ln":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(p, x, cfg):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + cfg.norm_eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- RoPE
def rope_dim(cfg) -> int:
    d = int(cfg.d_head * cfg.rope_pct)
    return d - d % 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S) int32."""
    rd = rope_dim(cfg)
    if rd == 0:
        return x
    half = rd // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs          # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., :half], xr[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([rot.astype(x.dtype), xp], -1)


# ----------------------------------------------------------------- attention
def init_attention(cfg, key, dtype) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh, dtype),
        "wk": dense_init(ks[1], d, kv * dh, dtype),
        "wv": dense_init(ks[2], d, kv * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((kv * dh,), dtype)
        p["bv"] = jnp.zeros((kv * dh,), dtype)
    if cfg.out_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    return p


@jax.named_scope(ATTN_PROJ)
def _qkv(p, x, cfg, positions, rope: bool):
    B, S, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, dh)
    k = k.reshape(B, S, kv, dh)
    v = v.reshape(B, S, kv, dh)
    if rope:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    return q, k, v


@jax.named_scope(ATTN_PROJ)
def _out_proj(p, o, cfg):
    """Heads (B,S,H,dh) back to the model width through ``wo``."""
    B, S = o.shape[0], o.shape[1]
    out = o.reshape(B, S, cfg.n_heads * cfg.d_head) @ p["wo"]
    if cfg.out_bias:
        out = out + p["bo"]
    return out


def _gqa_scores(q, k):
    """q: (B,Sq,kv,g,dh), k: (B,Sk,kv,dh) -> (B,kv,g,Sq,Sk) fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _sdpa(q, k, v, mask, cfg):
    """Masked softmax attention. q:(B,Sq,H,dh) k/v:(B,Sk,kv,dh),
    mask:(B,Sq,Sk) bool (True = attend). Returns (B,Sq,H,dh)."""
    B, Sq, H, dh = q.shape
    kv = k.shape[2]
    g = H // kv
    qg = q.reshape(B, Sq, kv, g, dh) / math.sqrt(dh)
    s = _gqa_scores(qg, k)                              # (B,kv,g,Sq,Sk)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)
    return o.reshape(B, Sq, H, dh)


def full_attention(p, x, cfg, positions, *, causal=True, rope=True,
                   q_chunk: int = 1024, window: Optional[int] = None,
                   kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """Full (or cross) attention over a whole sequence, query-chunked.

    Returns (out, (k, v)) where k/v are the full-sequence keys/values
    (for building decode caches)."""
    B, S, _ = x.shape
    if kv_override is not None:
        h, dh = cfg.n_heads, cfg.d_head
        q = x @ p["wq"]
        if cfg.qkv_bias:
            q = q + p["bq"]
        q = q.reshape(B, S, h, dh)
        k, v = kv_override
        causal = False
    else:
        q, k, v = _qkv(p, x, cfg, positions, rope)
    with jax.named_scope(ATTN_KV):
        o = _attend_full(q, k, v, cfg, positions, causal, q_chunk, window,
                         kv_override is not None)
    return _out_proj(p, o, cfg), (k, v)


def _attend_full(q, k, v, cfg, positions, causal, q_chunk, window, cross):
    """Scores and values of ``full_attention``, query-chunked."""
    B, S = q.shape[0], q.shape[1]
    Sk = k.shape[1]
    nchunk = max(1, S // q_chunk) if S % q_chunk == 0 else 1
    if nchunk <= 1:
        kpos = positions if not cross else \
            jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
        mask = (positions[:, :, None] >= kpos[:, None, :]) if causal else \
            jnp.ones((B, S, Sk), bool)
        if causal and window is not None:
            mask &= positions[:, :, None] - kpos[:, None, :] < window
        o = _sdpa(q, k, v, mask, cfg)
    else:
        qc = q.reshape(B, nchunk, q_chunk, cfg.n_heads, cfg.d_head)
        pc = positions.reshape(B, nchunk, q_chunk)

        def body(_, xs):
            qi, pi = xs                                   # (B,C,H,dh),(B,C)
            kpos = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
            if causal:
                m = pi[:, :, None] >= kpos[:, None, :]
                if window is not None:
                    m &= pi[:, :, None] - kpos[:, None, :] < window
            else:
                m = jnp.ones((B, qi.shape[1], Sk), bool)
            return None, _sdpa(qi, k, v, m, cfg)

        _, oc = jax.lax.scan(body, None, (qc.swapaxes(0, 1), pc.swapaxes(0, 1)))
        o = oc.swapaxes(0, 1).reshape(B, S, cfg.n_heads, cfg.d_head)
    return o


# ------------------------------------------------------------- decode caches
def init_kv_cache(cfg, batch, max_len, dtype, *, window=None):
    """Ring-buffer (windowed) or linear KV cache for ONE attention layer."""
    W = window if window is not None else max_len
    kv, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, W, kv, dh), dtype),
        "v": jnp.zeros((batch, W, kv, dh), dtype),
        "pos": jnp.full((batch, W), -1, jnp.int32),   # position stored per slot
    }


def decode_attention(p, x, cache, cfg, positions, *, rope=True,
                     window: Optional[int] = None, cross_kv=None):
    """Single-token decode. x: (B,1,d); positions: (B,) int32.
    Returns (out (B,1,d), new_cache)."""
    B = x.shape[0]
    h, dh = cfg.n_heads, cfg.d_head
    pos2 = positions[:, None]                              # (B,1)
    if cross_kv is not None:
        q = x @ p["wq"]
        if cfg.qkv_bias:
            q = q + p["bq"]
        q = q.reshape(B, 1, h, dh)
        k, v = cross_kv
        Sk = k.shape[1]
        mask = jnp.ones((B, 1, Sk), bool)
        with jax.named_scope(ATTN_KV):
            o = _sdpa(q, k, v, mask, cfg)
        return _out_proj(p, o, cfg), cache
    q, k_new, v_new = _qkv(p, x, cfg, pos2, rope)          # (B,1,·,dh)
    with jax.named_scope(ATTN_KV):
        W = cache["k"].shape[1]
        slot = positions % W                               # (B,)
        bidx = jnp.arange(B)
        k = cache["k"].at[bidx, slot].set(k_new[:, 0])
        v = cache["v"].at[bidx, slot].set(v_new[:, 0])
        spos = cache["pos"].at[bidx, slot].set(positions)
        valid = (spos >= 0) & (spos <= pos2)               # (B,W)
        if window is not None:
            valid &= pos2 - spos < window
        o = _sdpa(q, k, v, valid[:, None, :], cfg)
    return _out_proj(p, o, cfg), {"k": k, "v": v, "pos": spos}


@jax.named_scope(ATTN_KV)
def paged_page_context(page_table, positions, ps: int, P: int,
                       windows=(None,)):
    """Precompute the per-tick page-table expansions every attention
    layer shares: the trash-clamped gather table, the new token's write
    target, and the validity mask per distinct attention window.  The
    model's decode step hoists this OUT of the (scanned) trunk so the
    work happens once per tick instead of once per layer."""
    B, MP = page_table.shape
    bidx = jnp.arange(B)
    pg = page_table[bidx, jnp.clip(positions // ps, 0, MP - 1)]
    t = jnp.arange(MP * ps)[None]
    pos2 = positions[:, None]
    base = (t <= pos2) & (jnp.repeat(page_table, ps, axis=1) >= 0)
    valid = {}
    for win in set(windows):
        valid[win] = base if win is None else base & (pos2 - t < win)
    return {
        "pt": jnp.where(page_table >= 0, page_table, P - 1),
        "pg": jnp.where(pg >= 0, pg, P - 1),               # FREE → trash
        "off": positions % ps,
        "valid": valid,
    }


def paged_decode_attention(p, x, pool, cfg, positions, page_table, *,
                           rope=True, window: Optional[int] = None,
                           impl: str = "xla", block_k: Optional[int] = None,
                           page_ctx=None, layer=None):
    """Single-token decode against the shared page pool.

    x: (B,1,d) with B == n_slots; positions: (B,) int32;
    pool: {"k","v"} of shape (P, page_size, kv * dh) where the LAST page
    is the trash page (absorbs writes from FREE slots whose page-table
    row is cleared), or with ``layer`` the stacked (reps, P, page_size,
    kv * dh) pools of which this layer's is ``[layer]``; page_table:
    (B, MP) int32 page ids, -1 empty.

    The new token's K/V land in the page covering ``positions`` (the
    engine guarantees it is allocated for live slots), then attention
    runs over the sequence's own pages only — tokens on unallocated
    table entries or beyond ``positions`` are masked exactly like the
    pooled path, so greedy tokens match the striped cache bit-for-bit
    when page_size divides the pool width.  ``impl="pallas"`` runs the
    FUSED decode-step kernel (``repro.kernels.paged_attention.
    paged_decode_step``): append + gather + softmax in one launch on
    this layer's pool, reshaped to the kernel's (P, page_size, kv, dh).
    ``page_ctx`` (``paged_page_context``) carries the tick-level table
    expansions so the XLA path does no per-layer table work.  Returns
    (out (B,1,d), new_pool)."""
    q, k_new, v_new = _qkv(p, x, cfg, positions[:, None], rope)
    with jax.named_scope(ATTN_KV):
        o, k_pool, v_pool = _paged_decode_kv(
            q, k_new, v_new, pool, positions, page_table, cfg, window,
            impl, block_k, page_ctx, layer)
    return _out_proj(p, o, cfg), {"k": k_pool, "v": v_pool}


def _paged_decode_kv(q, k_new, v_new, pool, positions, page_table, cfg,
                     window, impl, block_k, page_ctx, layer):
    """The new token's K/V into its page, then attention over the
    slot's pages: ``paged_decode_attention`` between its projections.
    The write and the gather index the stacked pool by ``layer``
    directly (``pool[layer, pt]``, never ``pool[layer][pt]``), so no
    layer's pool is sliced out."""
    B = q.shape[0]
    kv, dh = cfg.n_kv_heads, cfg.d_head
    P, ps = pool["k"].shape[-3], pool["k"].shape[-2]
    MP = page_table.shape[1]
    at = () if layer is None else (layer,)
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_decode_step
        heads = [(a if layer is None else a[layer]).reshape(P, ps, kv, dh)
                 for a in (pool["k"], pool["v"])]
        o, k_pool, v_pool = paged_decode_step(
            q[:, 0], k_new[:, 0], v_new[:, 0], *heads,
            page_table, positions + 1, window=window, block_k=block_k)
        new = [a.reshape(P, ps, kv * dh) for a in (k_pool, v_pool)]
        if layer is not None:
            new = [a.at[layer].set(n)
                   for a, n in zip((pool["k"], pool["v"]), new)]
        return o[:, None], new[0], new[1]
    if page_ctx is None:
        page_ctx = paged_page_context(page_table, positions, ps, P,
                                      windows=(window,))
    tgt = at + (page_ctx["pg"], page_ctx["off"])
    k_pool = pool["k"].at[tgt].set(k_new[:, 0].reshape(B, kv * dh))
    v_pool = pool["v"].at[tgt].set(v_new[:, 0].reshape(B, kv * dh))
    kg = k_pool[at + (page_ctx["pt"],)].reshape(B, MP * ps, kv, dh)
    vg = v_pool[at + (page_ctx["pt"],)].reshape(B, MP * ps, kv, dh)
    o = _sdpa(q, kg, vg, page_ctx["valid"][window][:, None, :], cfg)
    return o, k_pool, v_pool


def paged_suffix_attention(p, x, pool, cfg, positions, pt_row, *,
                           rope=True, window: Optional[int] = None,
                           layer=None):
    """Suffix-only prefill against the shared page pool (prefix sharing).

    x: (1,S,d) — the un-cached suffix of one prompt whose shared prefix
    K/V already sit in the slot's leading pages; positions: (1,S)
    absolute token positions (shared_tokens + arange(S)); pt_row: (MP,)
    the slot's page-table row; pool and ``layer`` as in
    ``paged_decode_attention``.  The suffix K/V are scattered into the
    slot's pages at their (page, offset) targets, then every suffix
    query attends over the slot's whole table expansion — shared prefix
    pages and just-written suffix alike — masked to its own causal
    position.  Key order in the expansion equals position order, so
    outputs are bit-identical to a full prefill that recomputed the
    prefix (same summation order; masked tail entries underflow to
    exact zeros).  Returns (out (1,S,d), new_pool)."""
    kv, dh = cfg.n_kv_heads, cfg.d_head
    q, k_new, v_new = _qkv(p, x, cfg, positions, rope)     # (1,S,·,dh)
    P, ps = pool["k"].shape[-3], pool["k"].shape[-2]
    MP = pt_row.shape[0]
    S = x.shape[1]
    at = () if layer is None else (layer,)
    with jax.named_scope(ATTN_KV):
        tpos = positions[0]                                # (S,)
        pg = pt_row[jnp.clip(tpos // ps, 0, MP - 1)]
        pg = jnp.where(pg >= 0, pg, P - 1)                 # FREE → trash
        tgt = at + (pg, tpos % ps)
        k_pool = pool["k"].at[tgt].set(k_new[0].reshape(S, kv * dh))
        v_pool = pool["v"].at[tgt].set(v_new[0].reshape(S, kv * dh))
        pt = at + (jnp.where(pt_row >= 0, pt_row, P - 1),)
        kg = k_pool[pt].reshape(1, MP * ps, kv, dh)
        vg = v_pool[pt].reshape(1, MP * ps, kv, dh)
        t = jnp.arange(MP * ps)[None, None, :]
        qpos = positions[:, :, None]                       # (1,S,1)
        valid = (t <= qpos) & \
            (jnp.repeat(pt_row, ps) >= 0)[None, None, :]
        if window is not None:
            valid &= qpos - t < window
        o = _sdpa(q, kg, vg, valid, cfg)
    return _out_proj(p, o, cfg), {"k": k_pool, "v": v_pool}


@jax.named_scope(ATTN_KV)
def kv_cache_from_prefill(cfg, k, v, positions, max_len, *, window=None):
    """Convert full-sequence prefill K/V (B,S,kv,dh) into a decode cache."""
    B, S = k.shape[0], k.shape[1]
    W = window if window is not None else max_len
    cache = init_kv_cache(cfg, B, max_len, k.dtype, window=window)
    if W >= S:
        cache = {
            "k": cache["k"].at[:, :S].set(k),
            "v": cache["v"].at[:, :S].set(v),
            "pos": cache["pos"].at[:, :S].set(positions),
        }
    else:
        # keep the last W entries, placed at their ring slots
        kt, vt, pt = k[:, -W:], v[:, -W:], positions[:, -W:]
        slot = pt % W
        bidx = jnp.arange(B)[:, None]
        cache = {
            "k": cache["k"].at[bidx, slot].set(kt),
            "v": cache["v"].at[bidx, slot].set(vt),
            "pos": cache["pos"].at[bidx, slot].set(pt),
        }
    return cache


# ----------------------------------------------------------------------- FFN
def gated_mlp(cfg) -> bool:
    # SwiGLU-style for silu archs and for RecurrentGemma's GeGLU
    return cfg.act == "silu" or cfg.family == "hybrid"


def init_ffn(cfg, key, dtype, d_ff=None) -> Params:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], cfg.d_model, d_ff, dtype),
         "w_out": dense_init(ks[1], d_ff, cfg.d_model, dtype)}
    if gated_mlp(cfg):
        p["w_gate"] = dense_init(ks[2], cfg.d_model, d_ff, dtype)
    if cfg.mlp_bias:
        p["b_in"] = jnp.zeros((d_ff,), dtype)
        p["b_out"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_ffn(p, x, cfg):
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = x @ p["w_in"]
    if cfg.mlp_bias:
        h = h + p["b_in"]
    if "w_gate" in p:
        h = act(x @ p["w_gate"]) * h
    else:
        h = act(h)
    out = h @ p["w_out"]
    if cfg.mlp_bias:
        out = out + p["b_out"]
    return out
