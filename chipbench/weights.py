"""Random weights from the seed, in the pytree the engine takes.

One jitted program makes every leaf on the device, in the dtype served.
Each leaf draws from the seed folded with its own path, so the reference
can make the same weights again after the program's state is freed,
without taking anything from the program.

The leaves are the architecture module's ``shapes(spec)``: ``{path:
(shape, init, scale)}``, the path the leaf's place in the program's
pytree (``trunk/<i>/...`` for pattern position ``i``, its layer axis
first; ``rem/<i>/...`` for remainder layer ``i``), ``init`` "normal"
(N(0, scale)) or "one" (1 + N(0, scale)).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import modelspec

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _nest(flat: dict) -> dict:
    """The pytree of ``{"a/b/c": leaf}``: a node whose keys are all
    numbers is a tuple, in their order, as the program's ``trunk`` (one
    stacked group per pattern position) and ``rem`` (one per remainder
    layer) are; both are there even when empty."""
    tree: dict = {"trunk": {}, "rem": {}}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return _tuples(tree)


def _tuples(node):
    if not isinstance(node, dict):
        return node
    node = {k: _tuples(v) for k, v in node.items()}
    if not all(k.isdigit() for k in node):
        return node
    if sorted(map(int, node)) != list(range(len(node))):
        raise ValueError(f"positions {sorted(node)} are not 0..{len(node) - 1}")
    return tuple(node[str(i)] for i in range(len(node)))


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
    table = modelspec.module(spec).shapes(spec)
    return _maker(tuple(sorted(table.items())), spec["torch_dtype"])


def make(spec: dict, seed: int):
    """The weights of ``spec`` for ``seed``, made on the default device."""
    return jax.block_until_ready(program(spec)(seed_key(seed)))
