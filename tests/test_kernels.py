"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
run in interpret mode on CPU (TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (decode_attention, flash_attention,
                               mlstm_chunkwise, rglru_scan)
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               mlstm_chunkwise_ref, rglru_scan_ref)

pytestmark = pytest.mark.slow    # Pallas interpret-mode shape/dtype sweeps

RNG = np.random.default_rng(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,S,dh,causal,window", [
    (2, 4, 2, 256, 64, True, None),      # GQA causal
    (1, 4, 4, 256, 128, True, 64),       # MHA sliding window
    (2, 8, 2, 512, 64, False, None),     # bidirectional (encoder)
    (1, 2, 1, 384, 128, True, 128),      # MQA window
    (1, 8, 8, 128, 256, True, None),     # wide head dim
])
def test_flash_attention_sweep(B, H, KVH, S, dh, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, S, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, KVH, S, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, KVH, S, dh)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,W,dh,window,fill", [
    (2, 4, 2, 256, 64, None, 200),       # partially filled linear cache
    (2, 4, 1, 256, 128, 128, 300),       # wrapped ring + window mask
    (1, 8, 8, 512, 64, None, 512),       # full cache MHA
    (3, 2, 2, 128, 256, 64, 100),        # wide heads, ring
])
def test_decode_attention_sweep(B, H, KVH, W, dh, window, fill, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, W, KVH, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, W, KVH, dh)), dtype)
    slots = np.full((B, W), -1, np.int32)
    for t in range(fill):
        slots[:, t % W] = t
    spos = jnp.asarray(slots)
    pos = jnp.full((B,), fill - 1, jnp.int32)
    out = decode_attention(q, k, v, spos, pos, window=window,
                           interpret=True)
    ref = decode_attention_ref(q, k, v, spos, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,d,bt", [
    (2, 512, 256, 256),
    (1, 1024, 128, 128),
    (3, 256, 384, 64),
    (1, 64, 128, 64),       # single time chunk
])
def test_rglru_scan_sweep(B, S, d, bt, dtype):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (B, S, d)), dtype)
    b = jnp.asarray(RNG.standard_normal((B, S, d)) * 0.1, dtype)
    h0 = jnp.asarray(RNG.standard_normal((B, d)), jnp.float32)
    out = rglru_scan(a, b, h0, bt=bt, interpret=True)
    ref = rglru_scan_ref(a.astype(jnp.float32), b.astype(jnp.float32), h0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_flash_attention_matches_model_attention():
    """Kernel ≡ the model's query-chunked XLA attention path."""
    from repro.models.layers import _sdpa
    B, H, KVH, S, dh = 2, 4, 2, 256, 64
    q = jnp.asarray(RNG.standard_normal((B, S, H, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KVH, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KVH, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mask = pos[:, :, None] >= pos[:, None, :]
    ref = _sdpa(q, k, v, mask, None)
    out = flash_attention(q.transpose(0, 2, 1, 3),
                          k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,dh,chunk", [
    (2, 3, 512, 64, 128),
    (1, 2, 256, 128, 64),
    (1, 4, 128, 256, 128),     # single chunk
])
def test_mlstm_chunkwise_sweep(B, H, S, dh, chunk, dtype):
    import math
    q = jnp.asarray(RNG.standard_normal((B, H, S, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, S, dh)) / math.sqrt(dh),
                    dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, S, dh)), dtype)
    i_pre = jnp.asarray(RNG.standard_normal((B, H, S)), jnp.float32)
    f_pre = jnp.asarray(RNG.standard_normal((B, H, S)) + 3.0, jnp.float32)
    out = mlstm_chunkwise(q, k, v, i_pre, f_pre, chunk=chunk,
                          interpret=True)
    ref = mlstm_chunkwise_ref(q.astype(jnp.float32),
                              k.astype(jnp.float32),
                              v.astype(jnp.float32), i_pre, f_pre,
                              chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
