"""Pallas TPU kernel for the RG-LRU linear recurrence
h_t = a_t ⊙ h_{t-1} + b_t (RecurrentGemma's recurrent hot spot).

Grid: (batch, d-tiles, time-chunks) with the time dimension sequential
("arbitrary") — the carry lives in VMEM scratch across time chunks, so HBM
traffic is exactly one read of (a, b) and one write of h: the kernel is
purely memory-bound, matching the roofline expectation for recurrent
mixers.  d is tiled to the 128-lane vector width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, bt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)[None]

    a = a_ref[0].astype(jnp.float32)        # (bt, bd)
    b = b_ref[0].astype(jnp.float32)

    def body(i, h):
        h = a[i] * h + b[i]
        o_ref[0, pl.dslice(i, 1), :] = h.astype(o_ref.dtype)[None]
        return h

    h = jax.lax.fori_loop(0, bt, body, h_scr[0])
    h_scr[...] = h[None]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def rglru_scan(a, b, h0, *, bt: int = 256, bd: int = 128,
               interpret: bool | None = None):
    """a, b: (B,S,d); h0: (B,d) -> h: (B,S,d)."""
    B, S, d = a.shape
    bt = min(bt, S)
    bd = min(bd, d)
    assert S % bt == 0 and d % bd == 0
    grid = (B, d // bd, S // bt)
    kernel = functools.partial(_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda ib, id_, it: (ib, it, id_)),
            pl.BlockSpec((1, bt, bd), lambda ib, id_, it: (ib, it, id_)),
            pl.BlockSpec((1, bd), lambda ib, id_, it: (ib, id_)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd),
                               lambda ib, id_, it: (ib, it, id_)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, b, h0)
