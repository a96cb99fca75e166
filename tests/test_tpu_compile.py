"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so the main path's kernels and
programs are compiled here for a ``v5e:2x2`` topology: what the chip's
compiler would refuse (a layout Mosaic cannot lower, a program larger
than HBM) fails here, at no chip time.  Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every pytest worker imports every test file.  These tests live in
this one file so that a single worker loads it.
"""
import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import backend
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_step)
from repro.models import init_paged_cache, init_params, pages_for, scopes
from repro.serving.engine import _paged_executables

from conftest import REPO

sys.path.insert(0, REPO)

from chipbench import modelspec  # noqa: E402

# the usable HBM of one v5e chip as its compiler reports it ("15.75G")
V5E_HBM_BYTES = 15.75 * 2**30
# qwen2.5-3b at published widths; float32 fits one chip at 18 of 36 layers
SERVE_LAYERS = 18
SLOTS, MAX_LEN, PAGE = 8, 1024, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip would be written to the persistent
    # cache but could never be read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


@pytest.fixture(scope="module")
def qwen():
    return dataclasses.replace(get_config("qwen2.5-3b"),
                               n_layers=SERVE_LAYERS)


@pytest.mark.parametrize("kernel,page,block_k", [
    ("step", 16, None), ("step", 16, 8), ("step", 32, None),
    ("attention", 16, None),
])
def test_paged_kernels_compile_for_v5e(one_chip, qwen, kernel, page,
                                       block_k):
    """The paged kernels lower through Mosaic at qwen2.5-3b widths."""
    B, H, KVH, dh = SLOTS, qwen.n_heads, qwen.n_kv_heads, qwen.d_head
    MP = MAX_LEN // page
    P = B * MP + 1
    f32, i32 = jnp.float32, jnp.int32
    q = _spec((B, H, dh), f32, one_chip)
    kv_new = _spec((B, KVH, dh), f32, one_chip)
    pool = _spec((P, page, KVH, dh), f32, one_chip)
    table = _spec((B, MP), i32, one_chip)
    lens = _spec((B,), i32, one_chip)
    if kernel == "step":
        fn = functools.partial(paged_decode_step, block_k=block_k,
                               interpret=False)
        args = (q, kv_new, kv_new, pool, pool, table, lens)
    else:
        fn = functools.partial(paged_decode_attention, block_k=block_k,
                               interpret=False)
        args = (q, pool, pool, table, lens)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    return compiled.as_text()


def _engine_programs(cfg, attn_impl):
    # unwrapped: fresh jits, so nothing traced for the described chip
    # stays in the engine's shared executable cache
    mp = pages_for(MAX_LEN, PAGE)
    return _paged_executables.__wrapped__(cfg, MAX_LEN, PAGE, SLOTS * mp,
                                          mp, attn_impl)


def _engine_state(cfg, sharding):
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(functools.partial(
        init_paged_cache, cfg, SLOTS, page_size=PAGE, max_len=MAX_LEN))
    last_tok = _spec((SLOTS,), jnp.int32, sharding)
    return _on(params, sharding), _on(cache, sharding), last_tok


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_engine_decode_step_fits_v5e(one_chip, qwen, attn_impl,
                                     monkeypatch):
    """The engine's jitted paged decode step at published widths and 18
    layers compiles for one v5e and fits its HBM; the Pallas path
    carries the compiled fused kernel."""
    # the kernels decide interpret mode from the platform, which is the
    # CPU here; compile them as they would be on the chip
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    _, step, _, _ = _engine_programs(qwen, attn_impl)
    params, cache, last_tok = _engine_state(qwen, one_chip)
    text = _fits(step.lower(params, cache, last_tok).compile())
    assert ("tpu_custom_call" in text) == (attn_impl == "pallas")


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_engine_decode_step_names_its_scopes(one_chip, qwen, attn_impl,
                                             monkeypatch):
    """Each named scope of the model step reaches the compiled decode
    step's op metadata, where a trace reader maps device ops to it."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    _, step, _, _ = _engine_programs(qwen, attn_impl)
    params, cache, last_tok = _engine_state(qwen, one_chip)
    text = step.lower(params, cache, last_tok).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes.ALL:
        assert any(f"/{scope}/" in n for n in names), scope


def test_engine_prefill_fits_v5e(one_chip, qwen):
    """Prefill of a 512-token prompt into the page pool fits one v5e."""
    prefill, _, _, _ = _engine_programs(qwen, "xla")
    params, cache, last_tok = _engine_state(qwen, one_chip)
    tokens = _spec((1, 512), jnp.int32, one_chip)
    slot = _spec((), jnp.int32, one_chip)
    _fits(prefill.lower(params, cache, last_tok, tokens, slot).compile())


# the chip benchmark's cells: (configuration, the largest decode bucket
# ``mp`` the cell's traffic reaches)
CELLS = {"stablelm-1.6b.longctx": ("stablelm-1.6b", 124),
         "qwen2.5-3b.chat": ("qwen2.5-3b", 64)}


def _cell_geometry(name):
    """The cell's ``ModelConfig`` as the benchmark builds it, its serving
    geometry, and its largest decode bucket."""
    config, mp = CELLS[name]
    with open(os.path.join(REPO, "chipbench", "configs",
                           f"{config}.json")) as f:
        spec = json.load(f)
    return modelspec.model_config(spec), spec["serving"], mp


def _cell_programs(name, sharding):
    cfg, s, mp = _cell_geometry(name)
    max_pages = pages_for(s["max_len"], s["page_size"])
    programs = _paged_executables.__wrapped__(
        cfg, s["max_len"], s["page_size"], s["n_slots"] * max_pages,
        max_pages, "xla")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(functools.partial(
        init_paged_cache, cfg, s["n_slots"], page_size=s["page_size"],
        max_len=s["max_len"]))
    last_tok = _spec((s["n_slots"],), jnp.int32, sharding)
    return (programs, _on(params, sharding), _on(cache, sharding), last_tok,
            mp)


def _pool(cache):
    """Bytes of the paged pools and their page count P."""
    leaves = jax.tree.leaves((cache["trunk"], cache["rem"]))
    return (sum(x.size * x.dtype.itemsize for x in leaves),
            leaves[0].shape[-3])


def _pool_traffic(text, n_pool_pages):
    """Copies, slices and slice updates of a pool-sized array in an
    optimised HLO module: instructions (fused ones included) whose shape
    has the pool's page count as a dimension."""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* "
                         r"(copy|dynamic-slice|dynamic-update-slice)\(",
                         text, re.M):
        if str(n_pool_pages) in m.group(2).split(","):
            found.append(f"{m.group(3)} {m.group(1)} [{m.group(2)}]")
    return found


@pytest.mark.parametrize("cell", list(CELLS))
def test_engine_decode_step_updates_the_pool_in_place(one_chip, cell):
    """At each benchmark cell's geometry the compiled decode step writes
    its result into the donated pool (the output aliases every pool
    byte) and never copies, slices or restacks a pool-sized array."""
    (_, step, _, _), params, cache, last_tok, mp = _cell_programs(
        cell, one_chip)
    compiled = step.lower(params, cache, last_tok, mp=mp).compile()
    text = _fits(compiled)
    nbytes, n_pool_pages = _pool(cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    assert _pool_traffic(text, n_pool_pages) == []


def test_engine_prefill_updates_the_pool_in_place(one_chip):
    """The chat cell's prefill of a 256-token prompt scatters into the
    donated pool without a whole-pool copy."""
    (prefill, _, _, _), params, cache, last_tok, _ = _cell_programs(
        "qwen2.5-3b.chat", one_chip)
    tokens = _spec((1, 256), jnp.int32, one_chip)
    slot = _spec((), jnp.int32, one_chip)
    compiled = prefill.lower(params, cache, last_tok, tokens,
                             slot).compile()
    text = _fits(compiled)
    nbytes, n_pool_pages = _pool(cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    assert _pool_traffic(text, n_pool_pages) == []
