"""Differential kernel fuzz harness (fast CI tier).

Seeded randomized sweeps holding every Pallas attention kernel
(interpret mode) to its pure-jnp oracle in ``kernels/ref.py`` within
per-dtype tolerances: paged decode attention, the fused paged decode
STEP (attention + KV append, pools compared byte-for-byte), ring-cache
decode attention, and flash attention.

Shapes are drawn from a fixed bucket pool so the jit/trace cache is
reused across cases (the 200+ cases per kernel cost ~one compile per
bucket, not per case); everything else is randomized per case from a
deterministic seed — data, dtype-independent masks, ragged ``lens``
including 0, 1 and page-boundary ±1, and NON-CONTIGUOUS page tables
(page ids drawn from a shuffled permutation, never sorted).  Failure
messages carry (kernel, case index, bucket, seed) AND a one-line repro
command so any case replays standalone; ``REPRO_FUZZ_SEED`` overrides
the base seed (both to replay a past failure exactly and to widen the
sweep from CI without touching the file).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (decode_attention, flash_attention,
                               paged_decode_attention, paged_decode_step)
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               paged_decode_attention_ref,
                               paged_decode_step_ref)

N_CASES = 210            # per kernel (acceptance floor: 200+)
CHUNK = 30               # cases per pytest item (fail fast, stay readable)
BASE_SEED = int(os.environ.get("REPRO_FUZZ_SEED", "20260809"))


def _repro(test: str, i: int) -> str:
    """One-line command replaying the pytest item holding case ``i``."""
    return (f"repro: REPRO_FUZZ_SEED={BASE_SEED} python -m pytest -x "
            f"'tests/test_kernel_fuzz.py::{test}[{i - i % CHUNK}]'")

# jit the oracles too: per-bucket tracing instead of per-case eager
# dispatch keeps the whole harness inside the fast-tier budget
_paged_ref = functools.partial(jax.jit, static_argnames=("window",))(
    paged_decode_attention_ref)
_step_ref = functools.partial(jax.jit, static_argnames=("window",))(
    paged_decode_step_ref)
_decode_ref = functools.partial(jax.jit, static_argnames=("window",))(
    decode_attention_ref)
_flash_ref = functools.partial(jax.jit,
                               static_argnames=("causal", "window"))(
    flash_attention_ref)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _chunks():
    return [range(s, min(s + CHUNK, N_CASES))
            for s in range(0, N_CASES, CHUNK)]


# shape buckets: (B, H, KVH, dh, ps, MP, window, dtype)
PAGED_BUCKETS = [
    (3, 4, 2, 32, 8, 4, None, jnp.float32),
    (2, 4, 4, 16, 16, 3, 12, jnp.float32),
    (1, 2, 1, 32, 8, 5, None, jnp.bfloat16),
    (4, 8, 2, 16, 4, 6, 7, jnp.float32),
    (2, 4, 2, 16, 16, 2, None, jnp.bfloat16),
    (3, 2, 2, 8, 8, 3, 5, jnp.bfloat16),
    (2, 6, 3, 16, 8, 4, None, jnp.float32),
]
# sub-page KV block per bucket (None = whole page), exercising block_k
PAGED_BLOCK_KS = [None, 4, 2, None, 8, None, 4]


def _ragged_len(rng, ps, MP, *, lo=0):
    """Edge-heavy length draw: 0/1, page boundaries ±1, full, uniform."""
    hi = MP * ps
    kp = int(rng.integers(1, MP + 1)) * ps
    picks = [0, 1, ps - 1, ps, ps + 1, kp - 1, kp, kp + 1, hi,
             int(rng.integers(0, hi + 1))]
    return int(np.clip(picks[int(rng.integers(len(picks)))], lo, hi))


def _page_table(rng, B, P, MP, ps, lens):
    """Per-slot page lists drawn from a SHUFFLED pool permutation —
    non-contiguous, never sorted; unallocated entries -1; the pool keeps
    garbage everywhere to catch masking bugs (page P-1 is trash)."""
    table = np.full((B, MP), -1, np.int32)
    free = list(rng.permutation(P - 1))
    for b, n in enumerate(lens):
        for i in range(-(-n // ps)):
            table[b, i] = free.pop()
    return jnp.asarray(table)


@pytest.mark.parametrize("cases", _chunks(), ids=lambda r: f"{r[0]}")
def test_fuzz_paged_attention(cases):
    for i in cases:
        bidx = i % len(PAGED_BUCKETS)
        B, H, KVH, dh, ps, MP, window, dtype = PAGED_BUCKETS[bidx]
        bk = PAGED_BLOCK_KS[bidx]
        rng = np.random.default_rng([BASE_SEED, 1, i])
        P = B * MP + 2
        lens = [_ragged_len(rng, ps, MP) for _ in range(B)]
        q = jnp.asarray(rng.standard_normal((B, H, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), dtype)
        table = _page_table(rng, B, P, MP, ps, lens)
        L = jnp.asarray(lens, jnp.int32)
        out = paged_decode_attention(q, k, v, table, L, window=window,
                                     block_k=bk, interpret=True)
        ref = _paged_ref(q, k, v, table, L, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype),
            err_msg=f"paged case={i} bucket={PAGED_BUCKETS[bidx]} "
                    f"block_k={bk} lens={lens} seed={[BASE_SEED, 1, i]}\n"
                    + _repro("test_fuzz_paged_attention", i))


@pytest.mark.parametrize("cases", _chunks(), ids=lambda r: f"{r[0]}")
def test_fuzz_paged_decode_step(cases):
    """The fused kernel: output within tolerance AND pools byte-identical
    to the oracle's append outside the trash page (inside it, write
    order between FREE slots is unspecified on both sides)."""
    for i in cases:
        bidx = i % len(PAGED_BUCKETS)
        B, H, KVH, dh, ps, MP, window, dtype = PAGED_BUCKETS[bidx]
        bk = PAGED_BLOCK_KS[bidx]
        rng = np.random.default_rng([BASE_SEED, 2, i])
        P = B * MP + 2
        # lens counts tokens INCLUDING the appended one; a FREE slot
        # (lens drawn 0 → no pages allocated) exercises the trash path
        lens = [_ragged_len(rng, ps, MP) for _ in range(B)]
        q = jnp.asarray(rng.standard_normal((B, H, dh)), dtype)
        kn = jnp.asarray(rng.standard_normal((B, KVH, dh)), dtype)
        vn = jnp.asarray(rng.standard_normal((B, KVH, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((P, ps, KVH, dh)), dtype)
        table = _page_table(rng, B, P, MP, ps, lens)
        L = jnp.asarray(lens, jnp.int32)
        out, ko, vo = paged_decode_step(q, kn, vn, k, v, table, L,
                                        window=window, block_k=bk,
                                        interpret=True)
        ref, kr, vr = _step_ref(q, kn, vn, k, v, table, L, window=window)
        msg = (f"step case={i} bucket={PAGED_BUCKETS[bidx]} block_k={bk} "
               f"lens={lens} seed={[BASE_SEED, 2, i]}\n"
               + _repro("test_fuzz_paged_decode_step", i))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype), err_msg=msg)
        np.testing.assert_array_equal(
            np.asarray(ko[:P - 1], np.float32),
            np.asarray(kr[:P - 1], np.float32), err_msg=msg)
        np.testing.assert_array_equal(
            np.asarray(vo[:P - 1], np.float32),
            np.asarray(vr[:P - 1], np.float32), err_msg=msg)


# (B, H, KVH, W, dh, window, dtype)
DECODE_BUCKETS = [
    (2, 4, 2, 32, 32, None, jnp.float32),
    (2, 4, 1, 64, 16, 24, jnp.float32),
    (1, 8, 8, 32, 16, None, jnp.bfloat16),
    (3, 2, 2, 64, 32, 16, jnp.bfloat16),
    (2, 4, 2, 64, 16, None, jnp.float32),
    (1, 2, 1, 32, 64, 8, jnp.float32),
]


@pytest.mark.parametrize("cases", _chunks(), ids=lambda r: f"{r[0]}")
def test_fuzz_decode_attention(cases):
    for i in cases:
        bidx = i % len(DECODE_BUCKETS)
        B, H, KVH, W, dh, window, dtype = DECODE_BUCKETS[bidx]
        rng = np.random.default_rng([BASE_SEED, 3, i])
        q = jnp.asarray(rng.standard_normal((B, H, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((B, W, KVH, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((B, W, KVH, dh)), dtype)
        # per-row fill: edge-heavy incl. wrap-around rings (fill > W)
        spos = np.full((B, W), -1, np.int32)
        pos = np.zeros((B,), np.int32)
        for b in range(B):
            fill = _ragged_len(rng, W, 2, lo=1)   # 1 .. 2W, wraps past W
            for t in range(fill):
                spos[b, t % W] = t
            pos[b] = fill - 1
        out = decode_attention(q, k, v, jnp.asarray(spos),
                               jnp.asarray(pos), window=window,
                               interpret=True)
        ref = _decode_ref(q, k, v, jnp.asarray(spos), jnp.asarray(pos),
                          window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype),
            err_msg=f"decode case={i} bucket={DECODE_BUCKETS[bidx]} "
                    f"pos={pos.tolist()} seed={[BASE_SEED, 3, i]}\n"
                    + _repro("test_fuzz_decode_attention", i))


# (B, H, KVH, S, dh, causal, window, bq, bk, dtype)
FLASH_BUCKETS = [
    (2, 4, 2, 64, 32, True, None, 32, 32, jnp.float32),
    (1, 4, 4, 128, 16, True, 48, 64, 64, jnp.float32),
    (2, 2, 1, 64, 16, False, None, 32, 32, jnp.bfloat16),
    (1, 8, 2, 64, 32, True, 16, 16, 16, jnp.bfloat16),
    (1, 2, 2, 128, 32, True, None, 64, 32, jnp.float32),
    (2, 4, 2, 64, 16, True, 64, 32, 64, jnp.float32),
]


@pytest.mark.parametrize("cases", _chunks(), ids=lambda r: f"{r[0]}")
def test_fuzz_flash_attention(cases):
    for i in cases:
        bidx = i % len(FLASH_BUCKETS)
        B, H, KVH, S, dh, causal, window, bq, bk, dtype = \
            FLASH_BUCKETS[bidx]
        rng = np.random.default_rng([BASE_SEED, 4, i])
        q = jnp.asarray(rng.standard_normal((B, H, S, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((B, KVH, S, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((B, KVH, S, dh)), dtype)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=True)
        ref = _flash_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype),
            err_msg=f"flash case={i} bucket={FLASH_BUCKETS[bidx]} "
                    f"seed={[BASE_SEED, 4, i]}\n"
                    + _repro("test_fuzz_flash_attention", i))
