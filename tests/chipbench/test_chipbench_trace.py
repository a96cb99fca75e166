"""The reduction from trace events to busy time, program times and
labelled idle gaps."""
import glob

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import tracefile
from chipbench.tracefile import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
PROGRAMS = {"prefill": "prefill_scatter", "decode": "step"}
LABELS = ("submit", "step", "flush", "sleep")


def _synthetic():
    ms = 1e6
    return [
        Event(HOST, "python", "window", 0, 100 * ms),
        Event(HOST, "python", "step", 0, 30 * ms),
        Event(HOST, "python", "flush", 30 * ms, 40 * ms),
        Event(HOST, "python", "sleep", 40 * ms, 80 * ms),
        Event(HOST, "python", "step", 80 * ms, 100 * ms),
        Event(DEV, "XLA Modules", "jit_step(17)", 10 * ms, 30 * ms),
        Event(DEV, "XLA Modules", "jit_step_other(3)", 85 * ms, 90 * ms),
        Event(DEV, "XLA Modules", "jit_prefill_scatter(4)", 90 * ms,
              99 * ms),
        Event(DEV, "XLA Ops", "fusion.1", 10 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "fusion.2", 18 * ms, 30 * ms),   # overlaps
        Event(DEV, "XLA Ops", "copy.4", 38 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "copy.3", 85 * ms, 90 * ms),
        Event(DEV, "XLA Ops", "fusion.1", 90 * ms, 99 * ms),
        Event(DEV, "XLA Ops", "fusion.9", 100 * ms, 120 * ms),  # after hi
    ]


def test_busy_idle_programs_and_gaps():
    ev = _synthetic()
    lo, hi = tracefile.window(ev, "window")
    r = tracefile.reduce(ev, lo, hi, PROGRAMS, LABELS)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.036)          # 20 + 2 + 5 + 9 ms
    assert r.idle_share == pytest.approx(0.64)
    # jit_step_other is another function: not counted as the step
    assert r.programs["decode"] == (1, pytest.approx(0.020))
    assert r.programs["prefill"] == (1, pytest.approx(0.009))
    assert r.top_ops[0] == ("fusion.1", pytest.approx(0.019))
    labels = dict((k.split(":")[0], v) for k, v in r.idle_gaps)
    # a gap goes whole to the span overlapping it most: 0-10 and 99-100
    # to step, 30-38 to flush, 40-85 to sleep (40 ms of it, 5 in step)
    assert labels["sleep"] == pytest.approx(0.045)
    assert labels["step"] == pytest.approx(0.011)
    assert labels["flush"] == pytest.approx(0.008)
    assert r.idle_gaps[0][0] == "sleep: 1 gaps, longest 0.045000 s"


def test_no_device_operations_is_an_error():
    ev = [e for e in _synthetic() if e.plane == HOST]
    with pytest.raises(ValueError, match="no device"):
        tracefile.reduce(ev, 0, 1e8, PROGRAMS, LABELS)


def test_load_reads_the_benchmark_spans_from_a_profiler_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = tracefile.load(path, ("window", "step"))
    lo, hi = tracefile.window(ev, "window")
    step = [e for e in ev if e.name == "step"]
    assert len(step) == 1 and lo <= step[0].start <= step[0].end <= hi
