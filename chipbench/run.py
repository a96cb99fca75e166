"""One run of one benchmark cell on the chip this process is started on.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the weights from the seed, builds the engine and runs
every program the window will use; then the open loop measures for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` traces the window and reports its per-layer metrics.  After
the window the engine is freed and a sample of what it served is
compared with the plain reference.  The last line of standard output is
one JSON object; the last lines of standard error give each number
compared beside its limit.  Off a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the compile cache lives in the checkout, at a fixed path (the path is
# part of the cache's key); the program takes the directory given here.
# No size limit: JAX's eviction reads an access-time file beside every
# entry, and one entry without it fails every later write, so a limit
# set in the environment would leave set-up compiling in every run.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

import jax  # noqa: E402

from chipbench import (cells, check, modelspec, openloop, peaks,  # noqa: E402
                       readers, tracefile, traffic, weights)

PROGRAMS = {"prefill": "prefill_scatter", "decode": "step"}


def require_chip(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX "
                         f"found {len(devs)}")
    return devs


def finished_requests(win):
    out = []
    for s in win.served:
        seq = win.seqs.get(s.rid)
        if seq is not None and len(s.stamps) == s.n_out \
                and s.stamps[-1] <= win.t_close:
            out.append((s.rid, list(seq.prompt), list(seq.generated),
                        s.n_out))
    return out


def _traced(tdir):
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} traces written under {tdir}")
    events = tracefile.load(found[0], openloop.SPANS + (openloop.WINDOW_SPAN,))
    lo, hi = tracefile.window(events, openloop.WINDOW_SPAN)
    try:
        return tracefile.reduce(events, lo, hi, PROGRAMS, openloop.SPANS)
    except ValueError as e:
        raise RuntimeError(f"{e}; the trace holds "
                           f"{tracefile.layout(found[0])}") from e


def measure(cell, seed: int, seconds: float, trace: bool, device,
            t_start: float, root: str = cells.ROOT, control: bool = False):
    """Set-up, window, reference check: the one path of every run.
    Returns the result object and the window.  With ``control`` the
    result also holds the control's numbers under ``control``: the tools
    that set the limits read both sides from the same run."""
    spec = cell.config
    peak = peaks.peak(device.device_kind)
    vocab = modelspec.dims(spec)["vocab"]
    requests = traffic.generate(cell.traffic, seed, seconds, vocab)
    counter = openloop.CompileCounter()
    params = weights.make(spec, seed)
    eng = openloop.warm(spec, params, cell.traffic, seconds, seed)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
    setup_s = time.perf_counter() - t_start
    before = counter.count
    win = openloop.drive(eng, requests, seconds,
                         jax.profiler.TraceAnnotation if trace else None)
    compiles = counter.count - before
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = _traced(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    stats = device.memory_stats() or {}
    finished = finished_requests(win)
    shed = len(eng.take_shed())
    del eng, params
    gc.collect()
    judged = check.judge(spec, seed, finished, control)

    ctx = readers.Context(spec, win, setup_s, compiles, peak, reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.metric_reader(m["name"], root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        elif not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    out = {"correct": check.passed(judged.checks),
           "attempted": len(
               [s for s in win.served if win.t_open <= s.due < win.t_close]),
           "failed": shed, "metrics": metrics, "device": dev,
           "pool_pages": {"peak": win.pages_peak, "held": win.pages}}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops,
                            "idle_gaps": reduced.idle_gaps}
    if control:
        out["control"] = {p: {"correct": check.passed(c), "checks": c}
                          for p, c in judged.control.items()}
        out["readings"] = judged.readings
    out["checks"] = judged.checks
    return out, win


def run_cell(*args, **kw) -> dict:
    """The result object of one run (see ``measure``)."""
    return measure(*args, **kw)[0]


def setup_process(chips: int):
    """The chip this process runs on, with the compile cache on."""
    devs = require_chip(chips)
    from repro.launch.jax_cache import enable_compile_cache
    enable_compile_cache()
    return devs[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    device = setup_process(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
