"""``chip_smoke.py`` off the chip: its phases at reduced width on the CPU,
and its refusal to report a result anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO

sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402


def _run_script(cwd, env_extra=None):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_smoke_refuses_a_non_tpu_platform():
    proc = _run_script(REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compile cache in a temporary directory, with JAX's
    config restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_one_chip_phases_at_reduced_width(compile_cache, capsys):
    cfg = reduced(get_config("qwen2.5-3b"), d_model=64, n_layers=2,
                  vocab=256)
    chip_smoke.one_chip(cfg, chip_smoke.CompileMeter(), seed=0, n_slots=4,
                        max_len=64, n_requests=6, prompt_mean=12,
                        tokens_mean=5)
    out = capsys.readouterr().out
    assert "serve [xla]: 6 requests" in out
    assert "serve [pallas]: 6 requests" in out
    assert "decode logits: pallas vs xla" in out
    assert "loaded from the persistent cache" in out


def test_check_served_rejects_short_and_out_of_vocab_outputs():
    requests = [([1, 2], 3), ([4], 2)]
    assert chip_smoke.check_served({0: [5, 6, 7], 1: [1, 0]}, requests,
                                   vocab=8) == 5
    with pytest.raises(RuntimeError, match="2 tokens, asked for 3"):
        chip_smoke.check_served({0: [5, 6], 1: [1, 0]}, requests, vocab=8)
    with pytest.raises(RuntimeError, match="outside the vocabulary"):
        chip_smoke.check_served({0: [5, 6, 8], 1: [1, 0]}, requests,
                                vocab=8)


def test_check_chips_finds_one_changed_byte():
    import jax.numpy as jnp
    src = np.arange(3 * 256, dtype=np.uint8).reshape(3, 256)
    chip_smoke._check_chips(jnp.asarray(src[None]), src, "copy")
    bad = src.copy()
    bad[2, 17] ^= 1
    with pytest.raises(RuntimeError, match="block 2 differs"):
        chip_smoke._check_chips(jnp.asarray(bad[None]), src, "copy")


FOUR_CHIPS = r"""
import sys
sys.path.insert(0, {repo!r})
import chip_smoke
from repro.configs import get_config, reduced
base = reduced(get_config("qwen2.5-3b"), d_model=64, vocab=256)
chip_smoke.four_chips(base, chip_smoke.make_test_mesh(4), seed=0,
                      multicast_layers=4, n_blocks=4, pipeline_layers=8,
                      batch=8, seq=16)
print("FOUR-CHIPS-OK")
"""


def test_four_chip_phases_on_host_devices(subproc):
    out = subproc(FOUR_CHIPS.format(repo=REPO), 4)
    assert out.count("equal to multicast_reference on [0, 1, 2, 3]") == 2
    assert "pipelined vs one-chip forward" in out
    assert "FOUR-CHIPS-OK" in out
