"""Multi-device tests (subprocess with forced host devices): real data
movement for λPipe multicast, pipelined execution ≡ dense forward, and a
miniature multi-pod dry-run.  These must run in fresh processes because
jax locks the device count at first init."""
import pytest

pytestmark = pytest.mark.slow    # multi-device subprocess runs

MULTICAST = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.multicast import binomial_schedule, kway_schedule
from repro.distributed.collectives import multicast, multicast_reference
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(8)
rng = np.random.default_rng(0)
N, b, P = 8, 6, 384
src = rng.integers(0, 255, (b, P), dtype=np.uint8)

# 1->8
blocks = np.zeros((N, b, P), np.uint8); blocks[0] = src
sched = binomial_schedule(N, b)
out = np.asarray(multicast(jnp.asarray(blocks), sched, mesh, {0: range(b)}))
assert (out == multicast_reference(blocks, sched)).all()
assert all((out[n] == src).all() for n in range(N))

# 2->8 k-way (Algorithm 1 orders)
blocks = np.zeros((N, b, P), np.uint8); blocks[0] = src; blocks[1] = src
sched = kway_schedule(N, b, 2)
out = np.asarray(multicast(jnp.asarray(blocks), sched, mesh,
                           {0: range(b), 1: range(b)}))
assert all((out[n] == src).all() for n in range(N))

# 3->7 (non-power-of-two, greedy schedule) on a 7-node submesh? use 8 nodes
sched = kway_schedule(8, b, 3)
blocks = np.zeros((N, b, P), np.uint8)
for s in range(3): blocks[s] = src
out = np.asarray(multicast(jnp.asarray(blocks), sched, mesh,
                           {s: range(b) for s in range(3)}))
assert all((out[n] == src).all() for n in range(N))
print("MULTICAST-OK")
"""

PIPELINE = r"""
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import init_params, forward, make_batch
from repro.distributed.pipeline import pipelined_forward
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(4)
for arch in ("qwen2.5-3b", "stablelm-1.6b"):
    cfg = dataclasses.replace(reduced(get_config(arch)), n_layers=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 8, 32)
    ref = forward(cfg, params, batch)["logits"]
    out = pipelined_forward(cfg, params, batch, mesh, n_microbatches=4)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 5e-4, (arch, err)
print("PIPELINE-OK")
"""

MINI_DRYRUN = r"""
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced, SHAPES
from repro.launch.mesh import _make_mesh
from repro.launch.specs import build_dryrun
import dataclasses

# mini production mesh: (pod, data, model) = (2, 2, 2) on 8 host devices
mesh = _make_mesh((2, 2, 2), ("pod", "data", "model"))
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=128, global_batch=8)
for arch in ("qwen2.5-3b", "qwen2-moe-a2.7b"):
    cfg = reduced(get_config(arch))
    fn, args, in_sh = build_dryrun(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0
    # decode too
    dshape = dataclasses.replace(SHAPES["decode_32k"], seq_len=256,
                                 global_batch=8)
    fn, args, in_sh = build_dryrun(cfg, dshape, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
print("MINIDRYRUN-OK")
"""

EWL_END_TO_END = r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.core.blocks import pack_model, unpack_model
from repro.core.ewl import plan_scale
from repro.distributed.collectives import multicast
from repro.launch.mesh import make_test_mesh
from repro.models import init_params, forward, make_batch

# End-to-end execute-while-load correctness: pack a model on the source,
# multicast its blocks with the λPipe schedule across 8 'nodes', unpack on
# a destination, and verify identical logits.
mesh = make_test_mesh(8)
cfg = dataclasses.replace(reduced(get_config("qwen2.5-3b")), n_layers=8)
params = init_params(cfg, jax.random.PRNGKey(0))
stacked, specs = pack_model(cfg, params, 6)   # (6, P) uint8
assert stacked.shape[0] == 6
plan = plan_scale(8, 6, k=1)
N, b, P = 8, 6, stacked.shape[1]
blocks = np.zeros((N, b, P), np.uint8)
blocks[0] = np.asarray(stacked)
out = np.asarray(multicast(jnp.asarray(blocks), plan.schedule, mesh,
                           {0: range(b)}))
params7 = unpack_model(cfg, jnp.asarray(out[7]), specs)
batch = make_batch(cfg, 2, 32)
ref = forward(cfg, params, batch)["logits"]
got = forward(cfg, params7, batch)["logits"]
assert float(jnp.max(jnp.abs(ref - got))) == 0.0
print("EWL-OK")
"""


@pytest.mark.slow
def test_multicast_on_devices(subproc):
    assert "MULTICAST-OK" in subproc(MULTICAST, 8)


@pytest.mark.slow
def test_pipelined_forward_equals_dense(subproc):
    assert "PIPELINE-OK" in subproc(PIPELINE, 4)


@pytest.mark.slow
def test_mini_multipod_dryrun(subproc):
    assert "MINIDRYRUN-OK" in subproc(MINI_DRYRUN, 8)


@pytest.mark.slow
def test_execute_while_load_end_to_end(subproc):
    assert "EWL-OK" in subproc(EWL_END_TO_END, 8)


CB_PIPELINE = r"""
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.distributed.pipeline import PipelinedEngine
from repro.launch.mesh import make_test_mesh
from repro.models import init_params
from repro.serving.engine import ContinuousBatchingEngine, InferenceEngine

cfg = dataclasses.replace(reduced(get_config("qwen2.5-3b"), d_model=64),
                          n_layers=4)
params = init_params(cfg, jax.random.PRNGKey(0))
mesh = make_test_mesh(4)
pipe = PipelinedEngine.from_mesh(cfg, params, mesh, n_microbatches=2,
                                 n_slots=2, max_len=48, pad_to=8)
ref = InferenceEngine(cfg, params, max_len=48)
prompts = {0: list(range(1, 9)), 1: list(range(3, 15)), 2: [5, 4, 3, 2, 1]}
want = {i: list(map(int, ref.generate(
            {"tokens": jnp.asarray(p, jnp.int32)[None]}, 6,
            cache_len=48)[0])) for i, p in prompts.items()}
for i, p in prompts.items():
    pipe.submit(p, 6, req_id=i)
for _ in range(4):                      # serve mid-multicast...
    pipe.step()
pipe.drain()                            # ...then mode-switch
local = ContinuousBatchingEngine(cfg, params, n_slots=4, max_len=48)
local.adopt(pipe.handoff())
done = {r: s.generated for r, s in pipe.sched.finished.items()}
done.update(local.run())
assert done == want, (done, want)
assert local.stats["adopted"] >= 1
print("CB-PIPELINE-OK")
"""


@pytest.mark.slow
def test_continuous_batching_on_pipelined_mesh(subproc):
    """λPipe shard_map trunk drives the continuous-batching scheduler and
    hands off to a local replica with exact token equality."""
    assert "CB-PIPELINE-OK" in subproc(CB_PIPELINE, 4)
