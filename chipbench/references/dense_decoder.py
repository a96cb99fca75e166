"""Plain reference of a dense decoder: Qwen2 and StableLM-2 blocks.

A straightforward ``jax.numpy`` forward pass over a whole sequence: no
cache, no kernel, no batching, nothing of the program.  It follows the
published modeling code:
- RMSNorm (Qwen2) or LayerNorm with bias (StableLM-2), in float32;
- q/k/v projections with bias, o projection without;
- rotary embedding (rotate-half) on the first ``partial_rotary_factor``
  of each head's dims, theta from the file;
- grouped-query or multi-head attention, causal, scaled by 1/sqrt(dh);
- gated SiLU MLP: down(silu(gate(x)) * up(x)); sequential residual;
- tied (Qwen2.5-3B) or separate (StableLM-2) output head.
Departure: none in the mathematics.  Weights are random (the same
leaves as ``chipbench.weights``); their names are the program's pytree
names, which only says where each matrix sits.

``precision="highest"`` runs float32 at ``Precision.HIGHEST`` (the
reference); ``"bfloat16"`` runs every matmul, the residual stream and
the stored weights in bfloat16 with float32 norms and softmax (the
control, one precision below the float32 the configuration states);
``"float8"`` is ``"bfloat16"`` with both operands of every projection,
MLP and head matmul rounded to float8 e4m3 under a per-tensor scale
(the control one step further down).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import modelspec

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(t):
    """``t`` rounded to float8 e4m3 under a per-tensor scale, in bf16."""
    t = t.astype(jnp.float32)
    s = jnp.max(jnp.abs(t)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return ((t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * s).astype(jnp.bfloat16)


def _matmul(precision: str):
    if precision == "highest":
        return functools.partial(jnp.matmul, precision=HI)
    if precision == "float8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b))
    return jnp.matmul


def _norm(x, p, spec, eps):
    xf = x.astype(jnp.float32)
    if spec["architecture"]["norm"] == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = y * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _rope(x, pos, rd, theta):
    """x: (S, H, dh); rotate-half on the first ``rd`` dims."""
    if rd == 0:
        return x
    half = rd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]        # (S, half)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]   # (S, 1, rd)
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    xr = x[..., :rd].astype(jnp.float32)
    rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    out = (xr * cos + rot * sin).astype(x.dtype)
    return jnp.concatenate([out, x[..., rd:]], -1)


def hidden(spec: dict, w: dict, tokens, precision: str):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    z = modelspec.dims(spec)
    a = spec["architecture"]
    eps = modelspec.norm_eps(spec)
    rd = modelspec.rope_dims(spec)
    theta = float(spec["rope_theta"])
    dt = jnp.float32 if precision == "highest" else jnp.bfloat16
    prec = HI if precision == "highest" else None
    mm = _matmul(precision)
    S, H, KVH, dh = tokens.shape[0], z["h"], z["kvh"], z["dh"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(dt)

    def layer(x, lw):
        lw = jax.tree.map(lambda t: t.astype(dt), lw)
        at = lw["attn"]
        h = _norm(x, lw["norm1"], spec, eps)
        q, k, v = mm(h, at["wq"]), mm(h, at["wk"]), mm(h, at["wv"])
        if a["qkv_bias"]:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q = _rope(q.reshape(S, H, dh), pos, rd, theta)
        k = _rope(k.reshape(S, KVH, dh), pos, rd, theta)
        v = v.reshape(S, KVH, dh)
        rep = H // KVH
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=prec).reshape(S, H * dh)
        o = mm(o, at["wo"])
        if a["o_bias"]:
            o = o + at["bo"]
        x = x + o
        f = lw["ffn"]
        h = _norm(x, lw["norm2"], spec, eps)
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_in"]),
                   f["w_out"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["trunk"][0])
    return _norm(x, jax.tree.map(lambda t: t.astype(dt), w["final_norm"]),
                 spec, eps)


def logits(spec: dict, w: dict, h, precision: str):
    """(S, vocab) float32 logits from hidden states ``h``."""
    head = w["embed"].T if spec.get("tie_word_embeddings") else w["head"]
    if precision == "float8":
        h, head = _fp8(h), _fp8(head)
    return jnp.matmul(h, head.astype(h.dtype),
                      precision=HI if precision == "highest" else None,
                      preferred_element_type=jnp.float32)
