"""Open-loop traffic from a traffic file and a seed.

One generator serves every mix; a mix is data.  Sizes and gaps are drawn
as a stratified sample (the distribution's quantiles at (i + 1/2)/n),
and the seed orders them and draws the prompt ids.  So every seed sends
the same work in another order.  The order is dealt in rounds of
``BLOCK``: the sample, ascending, is cut into ``BLOCK`` strata, and each
round holds one item of every stratum, so any stretch of the run holds
much the same work whatever the seed.  A window that ends with requests
still queued (a cell above its knee) then serves the same work on every
seed, and bursts of short gaps are as frequent in every stretch.

Traffic file keys:
  arrivals: {"kind": "poisson", "rate_rps": r}   exponential gaps
  prompt / output: one of
    {"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"kind": "lognormal_ladder", "median": m, "sigma": s, "ladder": [...]}
        (snapped to the nearest rung, in log distance)
    {"kind": "uniform_ladder", "ladder": [...]}  every rung equally often

Every prompt starts with a token no other prompt of the run starts
with: the prompts share no prefix, not even one token, so the engine's
prefix index finds nothing to share.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


BLOCK = 8


@dataclasses.dataclass
class Request:
    rid: int
    due: float               # seconds after the window opens
    prompt: List[int]
    n_out: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(dist: dict, n: int) -> np.ndarray:
    """n sizes of ``dist``, in ascending order (the stratified sample)."""
    u = _quantiles(n)
    kind = dist["kind"]
    if kind not in ("uniform_ladder", "lognormal", "lognormal_ladder"):
        raise ValueError(f"unknown size distribution {kind!r}")
    if kind == "uniform_ladder":
        ladder = sorted(dist["ladder"])
        return np.array([ladder[int(x * len(ladder))] for x in u])
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    if kind == "lognormal":
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)
    ladder = np.array(sorted(dist["ladder"]), float)
    idx = np.abs(np.log(x)[:, None] - np.log(ladder)[None]).argmin(1)
    return ladder[idx].astype(int)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    if arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['kind']!r}")
    return -np.log1p(-_quantiles(n)) / float(arrivals["rate_rps"])


def dealt(sample: np.ndarray, rng: np.random.Generator,
          block: int = BLOCK) -> np.ndarray:
    """``sample`` (ascending) in an order drawn from ``rng``: item i is in
    stratum i * block // n, and round r holds the r-th item, in a drawn
    order, of every stratum that has one, the strata in a drawn order."""
    n = len(sample)
    strata = [rng.permutation(np.flatnonzero(np.arange(n) * block // n == j))
              for j in range(block)]
    order = []
    for r in range(max(len(st) for st in strata)):
        order.extend(st[r] for st in (strata[j] for j in
                                      rng.permutation(block)) if r < len(st))
    return np.asarray(sample)[np.asarray(order, int)]


def count(traffic: dict, seconds: float) -> int:
    """Requests generated for a window of ``seconds`` (a few fall past
    its end: the gaps' sum is about ``seconds``)."""
    return max(1, int(math.ceil(float(traffic["arrivals"]["rate_rps"])
                                * seconds)))


def generate(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Request]:
    rng = np.random.default_rng(int(seed))
    n = count(traffic, seconds)
    due = np.cumsum(dealt(gaps(traffic["arrivals"], n), rng))
    plen = dealt(sizes(traffic["prompt"], n), rng)
    nout = dealt(sizes(traffic["output"], n), rng)
    if n > vocab:
        raise ValueError(f"{n} requests but only {vocab} distinct first "
                         f"tokens")
    first = rng.choice(vocab, size=n, replace=False)
    out = []
    for i in range(n):
        rest = rng.integers(0, vocab, size=int(plen[i]) - 1)
        out.append(Request(i, float(due[i]),
                           [int(first[i])] + rest.tolist(), int(nout[i])))
    return out


def prompt_lengths(traffic: dict, seconds: float) -> List[int]:
    """Every prompt length the mix sends (the same for every seed)."""
    return sorted(set(sizes(traffic["prompt"],
                            count(traffic, seconds)).tolist()))


def longest(traffic: dict, seconds: float) -> int:
    """The most tokens one request of the mix holds."""
    n = count(traffic, seconds)
    return int(sizes(traffic["prompt"], n).max()
               + sizes(traffic["output"], n).max())
