"""Pallas TPU flash attention: causal, sliding-window, GQA.

TPU-target kernel (pl.pallas_call + explicit BlockSpec VMEM tiling) for the
prefill/training hot spot; validated on CPU with interpret=True against
``ref.flash_attention_ref``.  Online-softmax accumulation runs across the
innermost ("arbitrary") grid dimension over KV blocks; fully-masked KV
blocks are skipped by bounding the ik range per query block, which is what
makes the sliding-window variant sub-quadratic on real hardware.

Layouts: q (B, H, S, dh); k/v (B, KVH, S, dh); out (B, H, S, dh).
Block sizes default to MXU-aligned (128, 128).
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, causal: bool, window, scale: float,
            n_kblocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    q_start = iq * bq
    # ik ranges that can contribute under causal/window masking
    last_blk = jnp.minimum(
        (q_start + bq - 1) // bk, n_kblocks - 1) if causal \
        else n_kblocks - 1
    if window is not None:
        first_blk = jnp.maximum((q_start - window + 1) // bk, 0)
    else:
        first_blk = 0

    active = (ik >= first_blk) & (ik <= last_blk)

    @pl.when(ik == first_blk)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ik == last_blk)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q: (B,H,S,dh), k/v: (B,KVH,S,dh) -> (B,H,S,dh)."""
    B, H, S, dh = q.shape
    KVH = k.shape[1]
    g = H // KVH
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(dh)
    grid = (B * H, nq, nk)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=scale, n_kblocks=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh),
                         lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, iq, ik, g=g, H=H: (
                             (bh % H) // g + (bh // H) * KVH, ik, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, iq, ik, g=g, H=H: (
                             (bh % H) // g + (bh // H) * KVH, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q.reshape(B * H, S, dh), k.reshape(B * KVH, S, dh),
      v.reshape(B * KVH, S, dh)).reshape(B, H, S, dh)
