"""Pallas TPU decode attention: one query token against a (ring) KV cache.

The serving decode hot spot — memory-bandwidth bound: the kernel streams KV
blocks HBM→VMEM once and applies online softmax with position-validity
masking (ring-buffer slots carry their stored position; -1 = empty), which
directly supports λScale's pre-allocated cache layout (§5) and the windowed
caches used for long-context decode.

Layouts: q (B,H,dh); k/v (B,W,KVH,dh); spos (B,W) int32; pos (B,) int32.
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


def _kernel(q_ref, k_ref, v_ref, spos_ref, pos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, bk: int, window, scale: float,
            n_kblocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (dh,)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(k, q, (((1,), (0,)), ((), ())))   # (bk,)
    spos = spos_ref[0]                                # (bk,)
    pos = pos_ref[0, 0]
    valid = (spos >= 0) & (spos <= pos)
    if window is not None:
        valid &= pos - spos < window
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, s.max())
    p = jnp.exp(s - m_new)                            # (bk,)
    corr = jnp.exp(m_prev - m_new)
    l_scr[0, 0] = l_scr[0, 0] * corr + p.sum()
    acc_scr[0, ...] = acc_scr[0, ...] * corr + jax.lax.dot_general(
        p, v, (((0,), (0,)), ((), ())))
    m_scr[0, 0] = m_new

    @pl.when(ik == n_kblocks - 1)
    def _fin():
        o_ref[0, ...] = (acc_scr[0] /
                         jnp.maximum(l_scr[0, 0], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q, k, v, spos, pos, *, window=None, bk: int = 128,
                     interpret: bool | None = None):
    """q: (B,H,dh), k/v: (B,W,KVH,dh), spos: (B,W), pos: (B,) -> (B,H,dh)."""
    B, H, dh = q.shape
    W, KVH = k.shape[1], k.shape[2]
    g = H // KVH
    bk = min(bk, W)
    assert W % bk == 0
    nk = W // bk
    scale = 1.0 / math.sqrt(dh)

    kT = k.transpose(0, 2, 1, 3).reshape(B * KVH, W, dh)
    vT = v.transpose(0, 2, 1, 3).reshape(B * KVH, W, dh)
    kernel = functools.partial(_kernel, bk=bk, window=window, scale=scale,
                               n_kblocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nk),
        in_specs=[
            pl.BlockSpec((1, dh), lambda bh, ik: (bh, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, ik: ((bh // H) * KVH + (bh % H) // g,
                                         ik, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, ik: ((bh // H) * KVH + (bh % H) // g,
                                         ik, 0)),
            pl.BlockSpec((1, bk), lambda bh, ik: (bh // H, ik)),
            pl.BlockSpec((1, 1), lambda bh, ik: (bh // H, 0)),
        ],
        out_specs=pl.BlockSpec((1, dh), lambda bh, ik: (bh, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q.reshape(B * H, dh), kT, vT, spos, pos.reshape(B, 1))
    return out.reshape(B, H, dh)
