"""Find a cell's knee: the highest rate whose backlog does not grow.

    python chipbench/sweep.py <cell> --rates 2 4 6 8 --seconds 20

Run once on the chip when a cell is defined; the rate it finds is
written into the cell's traffic file by hand.  One process; each rate is
one whole run (``run.measure``) with the traffic file's rate replaced.
For each rate it prints the requests due, admitted and finished, the
queue at half time and at the close, tokens/s, the tails, the KV pool's
peak pages and whether the run was correct.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, run, tails  # noqa: E402


def backlog(win) -> dict:
    lo, hi = win.t_open, win.t_close
    due = tails.due_in(win.served, lo, hi)

    def waiting(t):
        return sum(1 for s in win.served
                   if s.due <= t and (s.issued is None or s.issued > t))

    def p(xs, q):
        return 1e3 * tails.percentile(xs, q)
    ttft, itl = tails.ttft(win.served, lo, hi), tails.itl(win.served, lo, hi)
    return {"due": len(due),
            "admitted": sum(1 for s in due if s.issued is not None),
            "finished": sum(1 for s in due if len(s.stamps) == s.n_out),
            "queue_half": waiting(lo + (hi - lo) / 2),
            "queue_close": waiting(hi),
            "out_tok_s": tails.tokens_in(win.served, lo, hi) / (hi - lo),
            "ttft_p50_ms": p(ttft, 50), "ttft_p95_ms": p(ttft, 95),
            "itl_p50_ms": p(itl, 50), "itl_p95_ms": p(itl, 95),
            "decode_steps": len(win.decode_steps),
            "mean_decode_rows": sum(map(len, win.decode_steps))
            / max(1, len(win.decode_steps))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    device = run.setup_process(cell.chips)
    for rate in args.rates:
        at = copy.deepcopy(cell)
        at.traffic["arrivals"]["rate_rps"] = rate
        t0 = time.perf_counter()
        out, win = run.measure(at, args.seed, args.seconds, False, device, t0)
        print(json.dumps(dict({"rate_rps": rate}, **backlog(win),
                              correct=out["correct"],
                              pool_pages=out["pool_pages"],
                              memory_peak_bytes=out["device"]
                              ["memory_peak_bytes"])), flush=True)


if __name__ == "__main__":
    main()
