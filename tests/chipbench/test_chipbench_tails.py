"""The tail arithmetic over all requests, censored ones included."""
import math

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import tails
from chipbench.tails import Served


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert tails.percentile(xs, 95) == 95
    assert tails.percentile(xs, 50) == 50
    assert tails.percentile([7.0], 95) == 7.0
    assert math.isnan(tails.percentile([], 95))


def _served():
    return [
        Served(0, due=1.0, prompt_len=8, n_out=3, issued=1.2,
               stamps=[1.5, 1.7, 2.0]),
        Served(1, due=2.0, prompt_len=8, n_out=3, issued=2.5,
               stamps=[3.0, 3.4, 9.5]),         # last token after close
        Served(2, due=8.0, prompt_len=8, n_out=3),   # censored at close
        Served(3, due=0.5, prompt_len=8, n_out=3, issued=0.6,
               stamps=[0.7]),                  # due before the window
    ]


def test_ttft_counts_a_censored_request_with_its_wait_so_far():
    got = tails.ttft(_served(), t_open=1.0, t_close=9.0)
    assert got == [0.5, 1.0, 1.0]              # 9.0 - 8.0 for request 2


def test_itl_takes_gaps_inside_the_window_only():
    got = tails.itl(_served(), t_open=1.0, t_close=9.0)
    assert [round(g, 6) for g in got] == [0.2, 0.3, 0.4]


def test_queue_wait_and_tokens_in_window():
    s = _served()
    assert [round(x, 6) for x in tails.queue_wait(s, 1.0, 9.0)] == \
        [0.2, 0.5, 1.0]
    assert tails.tokens_in(s, 1.0, 9.0) == 5
    # the 95th percentile of ttft over all due requests is the censored one
    assert tails.percentile(tails.ttft(s, 1.0, 9.0), 95) == 1.0
