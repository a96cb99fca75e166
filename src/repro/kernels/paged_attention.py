"""Pallas TPU paged decode attention: the paged serving fast path.

K/V for every live sequence sit in a single pool of fixed-size token
pages (``repro.models.cache_ops.PageTable`` allocates them).  Two kernels
share one online-softmax body:

* ``paged_decode_attention`` — read-only attention over a sequence's
  pages.  The page gather is FUSED into the softmax loop: the page table
  rides in as a scalar-prefetch operand so the BlockSpec index map
  resolves each grid step straight to the page the sequence owns, and
  gathered pages are never materialized in HBM.
* ``paged_decode_step`` — the fused decode step: attention AND the new
  token's KV append (page write-through at the slot's tail position) in
  ONE launch.  The pools are aliased input→output buffers, so the append
  is an in-place page write rather than a separate scatter dispatch —
  ``ContinuousBatchingEngine`` decode drops from two device round trips
  (scatter, then attention) to one.

The grid is (B, max_pages · ps/bk): one grid row per SLOT, every head
processed per step (batched ``dot_general`` over KV heads), so the
per-slot table walk is batched across the decode batch instead of being
re-dispatched per (slot, head).  A short sequence still iterates every
block, but all unallocated table entries resolve to the ONE trash page
(index P-1, hot after its first fetch), so *distinct* HBM page traffic is
bounded by the sequence's live pages rather than a per-slot ``max_len``
stripe — the paged layout's point (§5 pre-allocation without stripes).
``block_k`` (autotunable, see ``repro.kernels.autotune``) splits each
page into sub-blocks so the score tile shape can be tuned independently
of the allocator's page size.

Layouts: q (B,H,dh); k_pages/v_pages (P, ps, KVH, dh) — the LAST page
(index P-1) is the engine's trash page and never appears in a table;
page_table (B, MP) int32 page ids, -1 = unallocated; lens (B,) int32
token counts.  For ``paged_decode_attention`` lens counts tokens already
IN the pool; for ``paged_decode_step`` lens counts tokens INCLUDING the
new one being appended (``positions + 1``), i.e. the new token lands at
position lens-1 and only lens-1 pool tokens are attended from storage —
the new token's contribution is merged analytically from the operand, so
FREE slots (whole table row -1) write only the trash page and read
nothing live.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _resolve_bk(ps: int, block_k) -> int:
    """Sub-page KV block edge: divides the page size (falls back to the
    whole page when the requested block doesn't)."""
    if block_k is None:
        return ps
    bk = min(int(block_k), ps)
    return bk if bk > 0 and ps % bk == 0 else ps


def _online_update(s, v, m_scr, l_scr, acc_scr):
    """One flash-style online-softmax accumulation step.
    s: (KVH, g, bk) fp32 scores; v: (KVH, bk, dh) fp32."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])                    # (KVH, g, bk)
    corr = jnp.exp(m_prev - m_new)                       # (KVH, g)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))))              # (KVH, g, dh)
    m_scr[...] = m_new


def _attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, ps: int, bk: int, window,
                 scale: float, n_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    spp = ps // bk                                       # sub-blocks/page

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale             # (KVH, g, dh)
    k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (KVH, bk, dh)
    v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))))
    n = len_ref[b]
    ip = j // spp
    t = ip * ps + (j % spp) * bk + jax.lax.iota(jnp.int32, bk)
    valid = (t < n) & (pt_ref[b, ip] >= 0)
    if window is not None:
        valid &= (n - 1) - t < window
    _online_update(jnp.where(valid[None, None, :], s, NEG_INF), v,
                   m_scr, l_scr, acc_scr)

    @pl.when(j == n_blocks - 1)
    def _fin():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def _step_kernel(pt_ref, len_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                 o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, *,
                 ps: int, bk: int, window, scale: float, n_blocks: int,
                 max_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    spp = ps // bk

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n = len_ref[b]                        # token count INCLUDING the new one
    q = q_ref[0].astype(jnp.float32) * scale             # (KVH, g, dh)
    k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (KVH, bk, dh)
    v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))))
    ip = j // spp
    t = ip * ps + (j % spp) * bk + jax.lax.iota(jnp.int32, bk)
    # only n-1 tokens are in storage; position n-1 is the operand kn/vn
    valid = (t < n - 1) & (pt_ref[b, ip] >= 0)
    if window is not None:
        valid &= (n - 1) - t < window
    _online_update(jnp.where(valid[None, None, :], s, NEG_INF), v,
                   m_scr, l_scr, acc_scr)

    # ---- append: write the new token through to the slot's tail page.
    # The whole target sub-block is rewritten (copy + one replaced row),
    # so the constant-per-slot output block is fully defined at flush.
    n1 = jnp.maximum(n - 1, 0)
    tj = jnp.minimum(n1 // ps, max_pages - 1) * spp + (n1 % ps) // bk

    @pl.when(j == tj)
    def _append():
        # the row mask is built at the block's full (bk, KVH, dh) shape and
        # the new row broadcast along the major axis: Mosaic refuses the
        # lane→major shape cast of a 1-D mask expanded with [:, None, None]
        shape = k_ref.shape[1:]
        sel = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == (n1 % ps) % bk
        ko_ref[0] = jnp.where(sel, jnp.broadcast_to(kn_ref[...], shape),
                              k_ref[0]).astype(ko_ref.dtype)
        vo_ref[0] = jnp.where(sel, jnp.broadcast_to(vn_ref[...], shape),
                              v_ref[0]).astype(vo_ref.dtype)

    @pl.when(j == n_blocks - 1)
    def _fin():
        # merge the new token analytically (always attended: distance 0)
        kn = kn_ref[0].astype(jnp.float32)               # (KVH, dh)
        vn = vn_ref[0].astype(jnp.float32)
        sn = jnp.sum(q * kn[:, None, :], axis=-1)        # (KVH, g)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, sn)
        pn = jnp.exp(sn - m_new)
        corr = jnp.exp(m_prev - m_new)
        l = l_scr[...] * corr + pn
        acc = (acc_scr[...] * corr[..., None]
               + pn[..., None] * vn[:, None, :])
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, lens, *,
                           window=None, block_k=None,
                           interpret: bool | None = None):
    """q: (B,H,dh); k/v_pages: (P,ps,KVH,dh); page_table: (B,MP) int32
    (-1 = unallocated, mapped to the trash page P-1 and masked);
    lens: (B,) int32 live token counts -> (B,H,dh).  A row with lens == 0
    has every score masked and degenerates to a uniform average of the
    (masked) garbage — exactly like the oracle's softmax, so even that
    edge stays differentially testable; engines never read such rows."""
    B, H, dh = q.shape
    P, ps, KVH, _ = k_pages.shape
    g = H // KVH
    MP = page_table.shape[1]
    bk = _resolve_bk(ps, block_k)
    spp = ps // bk
    scale = 1.0 / math.sqrt(dh)
    kernel = functools.partial(_attn_kernel, ps=ps, bk=bk, window=window,
                               scale=scale, n_blocks=MP * spp)

    def kv_map(b, j, pt, ln):
        # unallocated entries resolve to the trash page so the DMA stays
        # in bounds; the kernel masks those tokens out via pt >= 0
        pid = pt[b, j // spp]
        return (jnp.where(pid >= 0, pid, P - 1), j % spp, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP * spp),
        in_specs=[
            pl.BlockSpec((1, KVH, g, dh), lambda b, j, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, KVH, dh), kv_map),
            pl.BlockSpec((1, bk, KVH, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KVH, g, dh),
                               lambda b, j, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, g), jnp.float32),
            pltpu.VMEM((KVH, g), jnp.float32),
            pltpu.VMEM((KVH, g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(page_table, lens, q.reshape(B, KVH, g, dh), k_pages, v_pages)
    return out.reshape(B, H, dh)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def paged_decode_step(q, k_new, v_new, k_pages, v_pages, page_table,
                      lens, *, window=None, block_k=None,
                      interpret: bool | None = None):
    """Fused decode step: append k_new/v_new at position lens-1 of each
    slot's tail page AND attend over the sequence in one launch.

    q: (B,H,dh); k_new/v_new: (B,KVH,dh); k/v_pages: (P,ps,KVH,dh);
    page_table: (B,MP); lens: (B,) token counts INCLUDING the new token
    (``positions + 1``).  Returns (out (B,H,dh), k_pages', v_pages') —
    the pools are donated (input_output_aliases), so the append never
    copies the pool.  A slot whose target table entry is -1 (FREE slots:
    the allocator cleared the whole row) writes the trash page P-1 and
    its live pages are untouched — the trash-page guarantee the striped
    path's masked ring writes provided."""
    B, H, dh = q.shape
    P, ps, KVH, _ = k_pages.shape
    g = H // KVH
    MP = page_table.shape[1]
    bk = _resolve_bk(ps, block_k)
    spp = ps // bk
    scale = 1.0 / math.sqrt(dh)
    kernel = functools.partial(_step_kernel, ps=ps, bk=bk, window=window,
                               scale=scale, n_blocks=MP * spp,
                               max_pages=MP)

    def kv_map(b, j, pt, ln):
        pid = pt[b, j // spp]
        return (jnp.where(pid >= 0, pid, P - 1), j % spp, 0, 0)

    def tgt_map(b, j, pt, ln):
        # constant per slot: the output block flushes once per grid row,
        # after the `j == tj` step rewrote it in full
        n1 = jnp.maximum(ln[b] - 1, 0)
        pid = pt[b, jnp.minimum(n1 // ps, MP - 1)]
        return (jnp.where(pid >= 0, pid, P - 1), (n1 % ps) // bk, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP * spp),
        in_specs=[
            pl.BlockSpec((1, KVH, g, dh), lambda b, j, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, KVH, dh), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, KVH, dh), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, bk, KVH, dh), kv_map),
            pl.BlockSpec((1, bk, KVH, dh), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, g, dh), lambda b, j, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, KVH, dh), tgt_map),
            pl.BlockSpec((1, bk, KVH, dh), tgt_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KVH, g), jnp.float32),
            pltpu.VMEM((KVH, g), jnp.float32),
            pltpu.VMEM((KVH, g, dh), jnp.float32),
        ],
    )
    out, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, g, dh), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices COUNT the scalar-prefetch operands:
        # (table, lens, q, k_new, v_new, k_pages, v_pages)
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(page_table, lens, q.reshape(B, KVH, g, dh), k_new, v_new,
      k_pages, v_pages)
    return out.reshape(B, H, dh), k_out, v_out
