"""The traffic generator: deterministic, ladder lengths only, the rate
that the mix states, and prompts that share no first token."""
import collections

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import cells, traffic

# each traffic file with the configuration it is sent to
MIXES = {"qwen2.5-3b.chat": "qwen2.5-3b",
         "stablelm-1.6b.longctx": "stablelm-1.6b"}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests_other_seed_same_work(name):
    mix = cells.traffic(name)
    vocab = cells.config(MIXES[name])["vocab_size"]
    a = traffic.generate(mix, BIG_SEED, 30, vocab)
    b = traffic.generate(mix, BIG_SEED, 30, vocab)
    c = traffic.generate(mix, 7, 30, vocab)
    assert [(r.due, r.prompt, r.n_out) for r in a] == \
        [(r.due, r.prompt, r.n_out) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # another seed sends the same sizes in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.n_out for r in a) == sorted(r.n_out for r in c)
    assert a[-1].due == pytest.approx(c[-1].due)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_on_the_ladder_and_within_limits(name):
    mix = cells.traffic(name)
    spec = cells.config(MIXES[name])
    reqs = traffic.generate(mix, 3, 30, spec["vocab_size"])
    assert {len(r.prompt) for r in reqs} <= set(mix["prompt"]["ladder"])
    out = mix["output"]
    assert all(out["min"] <= r.n_out <= out["max"] for r in reqs)
    assert all(0 <= t < spec["vocab_size"] for r in reqs for t in r.prompt)
    assert max(len(r.prompt) + r.n_out for r in reqs) \
        <= spec["serving"]["max_len"]
    assert traffic.longest(mix, 30) <= spec["serving"]["max_len"]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_arrival_rate_matches_the_mix(name):
    mix = cells.traffic(name)
    rate = mix["arrivals"]["rate_rps"]
    reqs = traffic.generate(mix, 5, 60, 100_000)
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) > 0)
    assert len(reqs) == int(np.ceil(rate * 60))
    in_window = (due < 60).sum()
    assert abs(in_window / 60 - rate) / rate < 0.05


def test_prompts_share_no_first_token():
    mix = cells.traffic("qwen2.5-3b.chat")
    reqs = traffic.generate(mix, 9, 60, 151_936)
    firsts = collections.Counter(r.prompt[0] for r in reqs)
    assert max(firsts.values()) == 1


def test_lognormal_ladder_snaps_and_keeps_the_median():
    dist = {"kind": "lognormal_ladder", "median": 256, "sigma": 0.6,
            "ladder": [64, 128, 256, 512, 768]}
    got = traffic.sizes(dist, 101)
    assert set(got.tolist()) <= set(dist["ladder"])
    assert np.median(got) == 256
    assert list(got) == sorted(got)


def test_uniform_ladder_sends_every_rung_equally():
    dist = {"kind": "uniform_ladder", "ladder": [3, 1, 2]}
    assert collections.Counter(traffic.sizes(dist, 9).tolist()) == \
        {1: 3, 2: 3, 3: 3}


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        traffic.sizes({"kind": "zipf"}, 4)


def test_every_round_holds_one_item_of_each_stratum():
    """Any stretch of a run holds the same work whatever the seed: each
    round of ``BLOCK`` requests takes one item from every stratum."""
    n = 61
    sample = np.arange(n) * 10
    for seed in (1, 2**33 + 3):
        got = traffic.dealt(sample, np.random.default_rng(seed))
        assert sorted(got.tolist()) == sample.tolist()
        strata = (got // 10) * traffic.BLOCK // n
        for r in range(0, n, traffic.BLOCK):
            part = strata[r:r + traffic.BLOCK].tolist()
            assert len(set(part)) == len(part)
    a = traffic.dealt(sample, np.random.default_rng(5))
    assert a.tolist() == traffic.dealt(sample, np.random.default_rng(5)).tolist()
    assert a.tolist() != traffic.dealt(sample, np.random.default_rng(6)).tolist()

