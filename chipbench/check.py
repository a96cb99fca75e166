"""Whether what the timed path served is correct.

After the window, a sample of the requests it finished (drawn from the
seed, the longest among them) is run once through the plain reference:
each prompt with its served tokens, as one sequence.  At every position
that produced a served token, the gap is how far that token's reference
logit lies below the reference's best, over the standard deviation of
the reference's logits there.  Greedy tokens match the reference's best
up to rounding, so most gaps are 0; a wrong page, mask, append or token
moves one by the logits' own scale.  Two numbers are read from the gaps:
the widest (``gap_max``) and the mean over every compared token
(``gap_mean``); a configuration compares those it gives a limit for.

A control puts the reference computed at a lower precision in the
program's place: at the same positions, the gap of the token that the
lower precision ranks first.  ``CONTROLS`` are read: bfloat16, one
step below the float32 the configuration states, and float8, one step
further.  ``judge(..., control=True)`` reads the same numbers from each,
so ``passed`` decides program and controls alike.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cells, modelspec, weights

Sample = Tuple[int, List[int], List[int]]      # rid, prompt, served tokens
CONTROLS = ("bfloat16", "float8")


def sample(finished: Sequence[Sample], seed: int, k: int) -> List[Sample]:
    """``k`` finished requests drawn from the seed, the one holding the
    most tokens always among them."""
    if not finished:
        return []
    by_rid = sorted(finished, key=lambda s: s[0])
    longest = max(by_rid, key=lambda s: len(s[1]) + len(s[2]))
    rest = [s for s in by_rid if s is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _gap_program(spec_json: str, control: bool):
    spec = json.loads(spec_json)
    ref = cells.reference(spec["reference"])

    def gaps(w, tokens, target, mask):
        hi = ref.logits(spec, w, ref.hidden(spec, w, tokens, "highest"),
                        "highest")
        top, std = hi.max(-1), hi.std(-1)

        def gap(tok):
            got = jnp.take_along_axis(hi, tok[:, None], -1)[:, 0]
            return jnp.where(mask, (top - got) / std, 0.0)
        ctl = {}
        for p in (CONTROLS if control else ()):
            lo = ref.logits(spec, w, ref.hidden(spec, w, tokens, p), p)
            ctl[p] = gap(lo.argmax(-1).astype(jnp.int32))
        return gap(target), ctl
    return jax.jit(gaps)


def served_gaps(spec: dict, w, samples: Sequence[Sample],
                control: bool = False):
    """Per sampled request, the gap at each served token (and, with
    ``control``, each control's gaps at the same positions, by its
    precision).  Every sequence is padded to ``max_len``, so one program
    serves all."""
    S = spec["serving"]["max_len"]
    fn = _gap_program(json.dumps(spec, sort_keys=True), control)
    out, ctl = [], {p: [] for p in CONTROLS}
    for _, prompt, served in samples:
        seq = prompt + served[:-1]
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq)] = seq
        target = np.zeros(S, np.int32)
        target[len(prompt) - 1:len(seq)] = served
        mask = np.zeros(S, bool)
        mask[len(prompt) - 1:len(seq)] = True
        g, c = fn(w, tokens, target, mask)
        out.append(np.asarray(g)[mask])
        for p, gp in c.items():
            ctl[p].append(np.asarray(gp)[mask])
    return out, (ctl if control else None)


def token_errors(spec: dict, finished: Sequence[Tuple[int, list, list, int]]
                 ) -> int:
    """Finished requests served the wrong number of tokens, or a token
    outside the vocabulary.  Items: (rid, prompt, served, asked)."""
    vocab = modelspec.dims(spec)["vocab"]
    return sum(1 for _, _, served, asked in finished
               if len(served) != asked
               or any(not 0 <= t < vocab for t in served))


NUMBERS = {
    "gap_max": lambda g: float(g.max()) if g.size else float("inf"),
    "gap_mean": lambda g: float(g.mean()) if g.size else float("inf"),
}


def readings(gaps) -> dict:
    """Every number ``NUMBERS`` reads from per-request gaps, and the
    share of compared tokens that are not the reference's best."""
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {name: fn(flat) for name, fn in NUMBERS.items()}
    out["flip_share"] = float((flat > 0).mean()) if flat.size else 0.0
    out["tokens"] = int(flat.size)
    return out


@dataclasses.dataclass
class Judged:
    checks: dict                    # name -> {"value", "limit"}
    control: Optional[dict]         # precision -> its checks, same limits
    readings: dict                  # "program" / precision -> readings()


def judge(spec: dict, seed: int, finished, control: bool = False) -> Judged:
    """The numbers compared, each beside its limit; with ``control``, the
    control's numbers beside the same limits.  ``finished`` holds (rid,
    prompt, served, asked) of the requests finished in the window; the
    program's state must be freed before this runs."""
    lim = spec["check"]
    picked = sample([f[:3] for f in finished], seed, lim["sample"])
    w = weights.make(spec, seed)
    gaps, ctl = served_gaps(spec, w, picked, control)
    del w
    common = {
        "token_errors": {"value": token_errors(spec, finished), "limit": 0},
        "sample_short": {"value": lim["sample"] - len(picked), "limit": 0},
    }
    read = {"program": readings(gaps)}
    for p, g in (ctl or {}).items():
        read[p] = readings(g)

    def compared(r):
        return dict(common, **{name: {"value": r[name], "limit": lim[name]}
                               for name in NUMBERS if name in lim})
    return Judged(compared(read["program"]),
                  {p: compared(read[p]) for p in ctl} if control else None,
                  read)


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
