"""A whole run of a tiny cell on the CPU, with the harness's look for a
chip skipped: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.  The faults are those a served
cell can have on one chip: a step that returns its state unchanged,
half of the batch left out, and a token altered where it is produced.
(A one-chip cell has no exchange between chips to leave out.)"""
import importlib.util
import os
import sys
import time

import jax.numpy as jnp
import pytest

from chipbench_tiny import ROOT, CpuDevice, tiny_cell
import repro.serving.engine as engine_mod

_spec = importlib.util.spec_from_file_location(
    "chipbench_run", os.path.join(ROOT, "chipbench", "run.py"))
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)


def _unchanged(step):
    def broken(params, cache, last_tok, mp=None):
        tok, _ = step(params, cache, last_tok, mp=mp)
        return tok, cache
    return broken


def _half_batch(step):
    def broken(params, cache, last_tok, mp=None):
        tok, new = step(params, cache, last_tok, mp=mp)
        keep = jnp.arange(tok.shape[0]) < tok.shape[0] // 2
        return jnp.where(keep, tok, last_tok), new
    return broken


def _altered_token(step):
    def broken(params, cache, last_tok, mp=None):
        tok, new = step(params, cache, last_tok, mp=mp)
        return tok.at[0].set((tok[0] + 1) % 2048), new
    return broken


FAULTS = {"sound": None, "state_unchanged": _unchanged,
          "half_batch": _half_batch, "token_altered": _altered_token}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_correct_only_when_the_timed_path_is_sound(fault, monkeypatch):
    if FAULTS[fault] is not None:
        fresh = engine_mod._paged_executables.__wrapped__

        def executables(*args, **kw):
            pre, step, suffix, copy = fresh(*args, **kw)
            return pre, FAULTS[fault](step), suffix, copy
        monkeypatch.setattr(engine_mod, "_paged_executables", executables)
    cell = tiny_cell()
    out = run.run_cell(cell, 2**32 + 77, 2.0, False, CpuDevice(),
                       time.perf_counter())
    assert out["attempted"] > 5
    assert set(out["metrics"]) == {"ttft_p95_ms", "ttft_p50_ms", "itl_p95_ms",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["correct"] is (fault == "sound"), out["checks"]


def test_the_control_run_is_judged_by_the_same_limits():
    """``control.py``'s path: one run reads program and controls; the
    program passes the cell's limits and the bfloat16 and float8
    controls do not.  ``sweep.py`` reads its backlog from the same run's
    window."""
    sys.path.insert(0, os.path.join(ROOT, "chipbench"))
    import sweep
    out, win = run.measure(tiny_cell(), 2**31 + 9, 2.0, False, CpuDevice(),
                           time.perf_counter(), control=True)
    assert out["correct"] is True, out["checks"]
    assert set(out["control"]) == {"bfloat16", "float8"}
    for p, c in out["control"].items():
        assert c["correct"] is False, (p, c)
        assert set(c["checks"]) == set(out["checks"])
        assert out["readings"][p]["gap_max"] > \
            out["readings"]["program"]["gap_max"]
    assert 0 < out["pool_pages"]["peak"] <= out["pool_pages"]["held"]
    b = sweep.backlog(win)
    assert b["due"] == out["attempted"] and b["finished"] <= b["admitted"]
