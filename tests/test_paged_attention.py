"""Paged decode-attention kernel equivalence (Pallas interpret mode).

Runs in the FAST CI tier (no ``slow`` marker, shapes kept small): the
paged kernel gathers K/V through a scalar-prefetched page table, so a
regression in the table indexing or the online softmax must surface
without accelerator hardware.  The oracle is the pure-jnp
``paged_decode_attention_ref``, cross-validated here against the dense
``decode_attention_ref`` on an equivalent linear cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import paged_decode_attention
from repro.kernels.ref import decode_attention_ref, paged_decode_attention_ref

RNG = np.random.default_rng(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _page_setup(B, P, MP, ps, lens):
    """Disjoint per-slot page lists covering ``lens`` tokens (-1 padded);
    unallocated pool pages keep garbage to catch masking bugs."""
    table = np.full((B, MP), -1, np.int32)
    free = list(range(P - 1))          # last page is the trash page
    for b, n in enumerate(lens):
        for i in range(-(-n // ps)):
            table[b, i] = free.pop()
    return jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,dh,ps,MP,window,lens", [
    (3, 4, 2, 32, 8, 4, None, (5, 17, 26)),     # GQA, partial pages
    (2, 4, 4, 16, 16, 3, 12, (30, 9)),          # MHA sliding window
    (1, 2, 1, 64, 8, 6, None, (41,)),           # MQA, many pages
    (2, 8, 2, 32, 4, 5, 7, (20, 1)),            # tiny pages + window
])
def test_paged_kernel_matches_ref(B, H, KVH, dh, ps, MP, window, lens,
                                  dtype):
    P = B * MP + 1
    q = jnp.asarray(RNG.standard_normal((B, H, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)), dtype)
    table = _page_setup(B, P, MP, ps, lens)
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, k, v, table, lens, window=window,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, k, v, table, lens, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_paged_ref_matches_dense_decode_ref():
    """Semantics cross-check: paging a linear cache changes nothing —
    the paged oracle equals the dense ring-cache oracle on the same
    tokens (which the Pallas kernel above is held to)."""
    B, H, KVH, dh, ps, MP = 2, 4, 2, 32, 8, 4
    W = MP * ps
    lens = (19, 27)
    P = B * MP + 1
    q = jnp.asarray(RNG.standard_normal((B, H, dh)), jnp.float32)
    k_lin = jnp.asarray(RNG.standard_normal((B, W, KVH, dh)), jnp.float32)
    v_lin = jnp.asarray(RNG.standard_normal((B, W, KVH, dh)), jnp.float32)
    table = _page_setup(B, P, MP, ps, lens)
    k_pages = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)),
                          jnp.float32)
    v_pages = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)),
                          jnp.float32)
    for b in range(B):
        for i in range(MP):
            pid = int(table[b, i])
            if pid >= 0:
                k_pages = k_pages.at[pid].set(k_lin[b, i * ps:(i + 1) * ps])
                v_pages = v_pages.at[pid].set(v_lin[b, i * ps:(i + 1) * ps])
    spos = np.full((B, W), -1, np.int32)
    for b, n in enumerate(lens):
        spos[b, :n] = np.arange(n)
    pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    dense = decode_attention_ref(q, k_lin, v_lin, jnp.asarray(spos), pos)
    paged = paged_decode_attention_ref(q, k_pages, v_pages, table,
                                       jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
    out = paged_decode_attention(q, k_pages, v_pages, table,
                                 jnp.asarray(lens, jnp.int32),
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_fused_step_free_slot_writes_only_trash_page():
    """Regression for the fused append's aliased pool writes: a FREE
    slot (page table row all -1, garbage ``lens``) must land its KV
    write on the trash page P-1 and NOTHING else — a bad target index
    map would silently corrupt a live slot's pages.  Live slots may
    touch only their own tail page."""
    from repro.kernels.ops import paged_decode_step

    B, H, KVH, dh, ps, MP = 4, 4, 2, 16, 8, 3
    P = B * MP + 2
    lens = (11, 0, 23, 0)                     # slots 1 and 3 are FREE
    # kernel lens INCLUDES the appended token; FREE slots carry garbage
    step_lens = jnp.asarray([12, 777, 24, 999], jnp.int32)
    q = jnp.asarray(RNG.standard_normal((B, H, dh)), jnp.float32)
    kn = jnp.asarray(RNG.standard_normal((B, KVH, dh)), jnp.float32)
    vn = jnp.asarray(RNG.standard_normal((B, KVH, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((P, ps, KVH, dh)), jnp.float32)
    table = _page_setup(B, P + 1, MP, ps, lens)  # P-1 stays unallocated
    table = jnp.where(table >= P - 1, -1, table)

    _, ko, vo = paged_decode_step(q, kn, vn, k, v, table, step_lens,
                                  interpret=True)

    # pages a correct kernel may touch: each live slot's tail page + trash
    allowed = {P - 1}
    wpos = [int(step_lens[b]) - 1 for b in range(B)]   # append position
    for b, n in enumerate(lens):
        if n:
            allowed.add(int(table[b, min(wpos[b] // ps, MP - 1)]))
    for pool, new in ((ko, k), (vo, v)):
        changed = {p for p in range(P)
                   if not np.array_equal(np.asarray(pool[p]),
                                         np.asarray(new[p]))}
        assert changed <= allowed, (sorted(changed), sorted(allowed))
    # and the live slots' writes really landed where the table says
    for b, n in enumerate(lens):
        if n:
            pid = int(table[b, min(wpos[b] // ps, MP - 1)])
            np.testing.assert_array_equal(
                np.asarray(ko[pid, wpos[b] % ps]), np.asarray(kn[b]))
