"""Pallas TPU kernel for chunkwise mLSTM (xLSTM's matrix-memory mixer).

Grid: (B·H, time-chunks) with the chunk dimension sequential — the
(dh×dh) matrix state C, normalizer n and stabilizer m live in VMEM
scratch across chunks, so HBM traffic is one pass over q/k/v/gates and
the output: the same roofline shape as flash attention, but with the
cross-chunk recurrence the XLA scan implementation pays extra
materialization for.

Math (per head, chunk of length L, stabilized):
  b_j   = Σ_{s≤j} logσ(f_s)                (within-chunk cumulative)
  D_js  = b_j − b_s + i_s   (s ≤ j)        (intra-chunk decay)
  m_j   = max(b_j + m_prev, max_s D_js)
  h_j   = [e^{b_j+m_prev−m_j}(q_j C) + Σ_s e^{D_js−m_j}(q_j·k_s) v_s]
          / max(|denom_j|, e^{−m_j})
  state: m' = max(g+m, max_s(g−b_s+i_s)),  g = b_L
         C' = e^{g+m−m'} C + Σ_s e^{g−b_s+i_s−m'} k_s v_sᵀ   (n' likewise)

Matches ``repro.models.xlstm._mlstm_chunk`` (the pure-jnp oracle used by
the model); validated in interpret mode in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, i_ref, f_ref, o_ref,
            C_scr, n_scr, m_scr, *, L: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)

    q = q_ref[0].astype(jnp.float32)            # (L, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    i_pre = i_ref[0].astype(jnp.float32)        # (1, L)
    f_pre = f_ref[0].astype(jnp.float32)
    logf = jax.nn.log_sigmoid(f_pre)
    b = jnp.cumsum(logf, axis=-1)               # (1, L)
    g = b[0, L - 1]

    C = C_scr[...]
    n = n_scr[...]                              # (1, dh)
    m_prev = m_scr[0, 0]

    D = b.reshape(L, 1) - b.reshape(1, L) + i_pre.reshape(1, L)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    D = jnp.where(causal, D, NEG)
    m_intra = jnp.max(D, axis=1)                # (L,)
    m_inter = b[0] + m_prev                     # (L,)
    m_j = jnp.maximum(m_intra, m_inter)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (L, L)
    w = scores * jnp.exp(D - m_j[:, None])
    inter = jnp.exp(m_inter - m_j)              # (L,)
    qC = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ())))      # (L, dh)
    numer = inter[:, None] * qC + jax.lax.dot_general(
        w, v, (((1,), (0,)), ((), ())))
    qn = jax.lax.dot_general(q, n, (((1,), (1,)), ((), ())))[:, 0]
    denom = inter * qn + w.sum(axis=1)
    h = numer / jnp.maximum(jnp.abs(denom), jnp.exp(-m_j))[:, None]
    o_ref[0, ...] = h.astype(o_ref.dtype)

    # ---- state update ----
    s_gate = g - b[0] + i_pre[0]                # (L,)
    m_new = jnp.maximum(g + m_prev, jnp.max(s_gate))
    carry = jnp.exp(g + m_prev - m_new)
    kv_w = jnp.exp(s_gate - m_new)              # (L,)
    C_scr[...] = carry * C + jax.lax.dot_general(
        k * kv_w[:, None], v, (((0,), (0,)), ((), ())))
    n_scr[...] = carry * n + jnp.sum(k * kv_w[:, None], axis=0,
                                     keepdims=True)
    m_scr[0, 0] = m_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                    interpret: bool | None = None):
    """q,k,v: (B,H,S,dh); i_pre,f_pre: (B,H,S) raw gate pre-activations.
    k must already be scaled by 1/sqrt(dh).  Returns h (B,H,S,dh)."""
    B, H, S, dh = q.shape
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    BH = B * H
    kernel = functools.partial(_kernel, L=L)
    out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, L, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, L, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, L, dh), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, L), lambda bh, ic: (bh, 0, ic)),
            pl.BlockSpec((1, 1, L), lambda bh, ic: (bh, 0, ic)),
        ],
        out_specs=pl.BlockSpec((1, L, dh), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q.reshape(BH, S, dh), k.reshape(BH, S, dh), v.reshape(BH, S, dh),
      i_pre.reshape(BH, 1, S), f_pre.reshape(BH, 1, S))
    return out.reshape(B, H, S, dh)
