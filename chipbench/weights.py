"""Random weights from the seed, in the pytree the engine takes.

One jitted program makes every leaf on the device, in the dtype served.
Each leaf draws from the seed folded with its own path, so the reference
can make the same weights again after the program's state is freed,
without taking anything from the program.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.modelspec import dims

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def shapes(spec: dict) -> dict:
    """{path: (shape, init, scale)}: init is "normal" (N(0, scale)) or
    "one" (1 + N(0, scale)).  Trunk leaves carry the layer axis first."""
    z, a = dims(spec), spec["architecture"]
    d, L, q, kv = z["d"], z["layers"], z["h"] * z["dh"], z["kvh"] * z["dh"]
    std = float(spec["initializer_range"])
    out = {"embed": ((z["vocab"], d), "normal", std)}

    def norm(prefix, lead=()):
        out[f"{prefix}/scale"] = (lead + (d,), "one", 0.02)
        if a["norm"] == "ln":
            out[f"{prefix}/bias"] = (lead + (d,), "normal", 0.02)

    t = "trunk/0"
    norm(f"{t}/norm1", (L,))
    norm(f"{t}/norm2", (L,))
    for name, (i, o) in {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                         "wo": (q, d)}.items():
        out[f"{t}/attn/{name}"] = ((L, i, o), "normal", 1 / math.sqrt(i))
    if a["qkv_bias"]:
        for name, o in {"bq": q, "bk": kv, "bv": kv}.items():
            out[f"{t}/attn/{name}"] = ((L, o), "normal", 0.02)
    if a["o_bias"]:
        out[f"{t}/attn/bo"] = ((L, d), "normal", 0.02)
    for name, (i, o) in {"w_in": (d, z["ff"]), "w_gate": (d, z["ff"]),
                         "w_out": (z["ff"], d)}.items():
        out[f"{t}/ffn/{name}"] = ((L, i, o), "normal", 1 / math.sqrt(i))
    norm("final_norm")
    if not z["tied"]:
        out["head"] = ((d, z["vocab"]), "normal", 1 / math.sqrt(d))
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    tree["trunk"] = (tree["trunk"]["0"],)
    tree["rem"] = ()
    return tree


@functools.lru_cache(maxsize=None)
def _maker(spec_items: tuple, dtype_name: str):
    table = dict(spec_items)
    dtype = DTYPES[dtype_name]

    def make(key):
        flat = {}
        for path, (shape, init, scale) in sorted(table.items()):
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            x = jax.random.normal(k, shape, jnp.float32) * scale
            flat[path] = (x + 1.0 if init == "one" else x).astype(dtype)
        return _nest(flat)
    return jax.jit(make)


def program(spec: dict):
    """The jitted program that makes the weights from a PRNG key."""
    return _maker(tuple(sorted(shapes(spec).items())), spec["torch_dtype"])


def make(spec: dict, seed: int):
    """The weights of ``spec`` for ``seed``, made on the default device."""
    return jax.block_until_ready(program(spec)(seed_key(seed)))
