"""The plain reference against the program at a tiny size: logits
agree, and the reference computed one precision lower does not."""
import jax
import numpy as np
import pytest

from chipbench_tiny import tiny_spec
from chipbench import cells, check, modelspec, weights

CONFIGS = ["qwen2.5-3b", "stablelm-1.6b"]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_logits_match_the_program(config):
    from repro.models import forward
    spec = tiny_spec(config)
    w = weights.make(spec, 2**33 + 5)
    ref = cells.reference(spec["reference"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (48,), 0,
                                spec["vocab_size"])
    cfg = modelspec.model_config(spec)
    with jax.default_matmul_precision("highest"):
        prog = forward(cfg, w, {"tokens": tokens[None]})["logits"][0]
    want = ref.logits(spec, w, ref.hidden(spec, w, tokens, "highest"),
                      "highest")
    low = ref.logits(spec, w, ref.hidden(spec, w, tokens, "bfloat16"),
                     "bfloat16")
    assert _rel(prog, want) < 1e-5
    assert _rel(low, want) > 1e-3


@pytest.mark.parametrize("config", CONFIGS)
def test_served_tokens_pass_and_the_bf16_control_fails(config):
    """The engine's greedy tokens through the paged cache sit at the
    reference's best; the control's do not, at the tiny cell's limit."""
    from repro.serving import ContinuousBatchingEngine
    spec = tiny_spec(config)
    seed = 11
    w = weights.make(spec, seed)
    eng = ContinuousBatchingEngine(
        modelspec.model_config(spec), w, n_slots=4,
        max_len=spec["serving"]["max_len"], page_size=16)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, spec["vocab_size"], n).tolist()
               for n in (5, 16, 17, 30, 33, 40)]
    for p in prompts:
        eng.submit(p, 20)
    out = eng.run()
    samples = [(rid, prompts[rid], out[rid]) for rid in sorted(out)]
    prog, ctl = check.served_gaps(spec, w, samples, control=True)
    assert sum(len(g) for g in prog) == 6 * 20
    limit = spec["check"]["gap_max"]
    assert max(g.max() for g in prog) <= limit
    for p in ("bfloat16", "float8"):
        assert max(g.max() for g in ctl[p]) > limit, p
    # an altered token is far past the limit
    bad = [(samples[0][0], samples[0][1],
            [(samples[0][2][0] + 1) % spec["vocab_size"]] + samples[0][2][1:])]
    g, _ = check.served_gaps(spec, w, bad)
    assert g[0][0] > 100 * limit


def test_sample_keeps_the_longest_and_follows_the_seed():
    fin = [(i, [0] * (5 + i % 7), [1] * (3 + i % 5)) for i in range(30)]
    a = check.sample(fin, 5, 8)
    assert len(a) == 8 and a[0] == max(fin, key=lambda s: len(s[1]) + len(s[2]))
    assert a == check.sample(fin, 5, 8)
    assert [s[0] for s in a] != [s[0] for s in check.sample(fin, 6, 8)]
    assert check.sample(fin[:3], 5, 8) and len(check.sample(fin[:3], 5, 8)) == 3
    assert check.sample([], 5, 8) == []


def test_token_errors_counts_short_and_out_of_vocab():
    spec = tiny_spec("qwen2.5-3b")
    fin = [(0, [1], [1, 2, 3], 3), (1, [1], [1, 2], 3),
           (2, [1], [1, 2, spec["vocab_size"]], 3)]
    assert check.token_errors(spec, fin) == 2
