"""Percentile and window arithmetic: tokens by timestamp, the 32-token
floor, what counts as due, the sample counts."""

import numpy as np
import pytest

from benchmark import window as w
from benchmark.window import Served


def rec(i, prompt_len, due, times, max_new=None):
    return Served(index=i, prompt_len=prompt_len,
                  max_new_tokens=max_new or len(times), due=due,
                  token_times=list(times), tokens=[1] * len(times))


def test_percentile_interpolates_like_numpy():
    v = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 50, 90, 99, 100):
        assert w.percentile(v, q) == pytest.approx(np.percentile(v, q))
    with pytest.raises(ValueError):
        w.percentile([], 50)


def test_tokens_are_counted_by_timestamp_whichever_request():
    t0, t1 = 10.0, 20.0
    a = rec(0, 100, 0.0, [9.0, 9.5, 10.0, 10.5, 19.999, 20.0, 21.0])
    b = rec(1, 50, 0.0, [12.0, 13.0])      # first token inside: prompt counts
    c = rec(2, 70, 0.0, [25.0])            # wholly after
    d = rec(3, 30, 0.0, [])                # never produced anything
    assert w.tokens_in_window([a], t0, t1) == 3       # 10.0, 10.5, 19.999
    assert w.tokens_in_window([b], t0, t1) == 2 + 50
    assert w.tokens_in_window([a, b, c, d], t0, t1) == 3 + 52


def test_prompt_tokens_count_while_the_prefill_runs():
    t0, t1 = 10.0, 20.0
    # admitted at 9.0, first token at 11.0: half of the prefill is inside
    e = rec(4, 1000, 0.0, [11.0])
    e.admit = 9.0
    assert w.tokens_in_window([e], t0, t1) == pytest.approx(1 + 500)
    # prefill straddles the window's end: 19.5 .. 20.5, first token outside
    f = rec(5, 800, 0.0, [20.5])
    f.admit = 19.5
    assert w.tokens_in_window([f], t0, t1) == pytest.approx(400)
    # wholly inside: the whole prompt and the token
    g = rec(6, 64, 0.0, [12.0, 12.1])
    g.admit = 11.9
    assert w.tokens_in_window([g], t0, t1) == pytest.approx(2 + 64)
    # the two halves of a window split add up to the whole
    assert w.tokens_in_window([e, f, g], 10.0, 15.0) + \
        w.tokens_in_window([e, f, g], 15.0, 20.0) == pytest.approx(
            w.tokens_in_window([e, f, g], t0, t1))


def test_ttft_runs_from_the_due_time_over_requests_due_in_the_window():
    t0, t1, grace = 10.0, 20.0, 2.0
    reqs = [rec(0, 8, 9.9, [10.2]),          # due before the window: out
            rec(1, 8, 10.0, [10.4]),         # 0.4
            rec(2, 8, 19.9, [21.5]),         # first token in the grace: 1.6
            rec(3, 8, 19.0, [22.5]),         # after the grace: failed
            rec(4, 8, 15.0, []),             # never: failed
            rec(5, 8, 20.0, [20.1])]         # due at t1: out
    s = w.ttft_samples(reqs, t0, t1, grace)
    assert s["failed"] == 2
    assert sorted(round(v, 6) for v in s["values"]) == [0.4, 1.6, 3.0, 7.0]


def test_tpot_needs_32_tokens_inside_the_window():
    t0, t1 = 0.0, 100.0
    enough = rec(0, 8, 0.0, [1.0 + 0.1 * k for k in range(32)])
    short = rec(1, 8, 0.0, [1.0 + 0.1 * k for k in range(31)])
    straddle = rec(2, 8, 0.0, [-5.0 + 0.2 * k for k in range(60)])
    out = w.tpot_samples([enough, short, straddle], t0, t1)
    assert len(out) == 2                      # the sample count of a run
    assert out[0] == pytest.approx(0.1)
    assert out[1] == pytest.approx(0.2)       # only the tokens inside count
    assert w.TPOT_MIN_TOKENS == 32


def test_live_rows_and_decode_bytes():
    t0, t1 = 0.0, 10.0
    a = rec(0, 100, 0.0, [2.0, 3.0, 4.0, 7.0])      # live 2..7 -> 5 s
    b = rec(1, 10, 0.0, [-1.0, 12.0])               # live through -> 10 s
    assert w.live_rows_mean([a, b], t0, t1) == pytest.approx(1.5)
    # token k >= 1 is a decode step over prompt_len + k positions
    assert w.decode_read_bytes([a], t0, t1, 2) == 2 * (101 + 102 + 103)
    assert w.decode_read_bytes([a], 3.5, 10.0, 2) == 2 * (102 + 103)


def test_live_tokens_mean_counts_prompt_from_admit_and_each_token_from_its_time():
    t0, t1 = 0.0, 10.0
    # admitted at 1, tokens at 2, 3, 4 (finished): the prompt's 100 slots
    # are held 1..4 (3 s), the tokens' 2 + 1 + 0 s
    a = rec(0, 100, 0.0, [2.0, 3.0, 4.0])
    a.admit = 1.0
    assert w.live_tokens_mean([a], t0, t1) == pytest.approx(
        (100 * 3 + 3) / 10)
    # still running at the window's end: held to t1; admitted before t0:
    # held from t0; a token from before the window is held from t0 too
    b = rec(1, 50, 0.0, [-1.0, 6.0], max_new=8)
    b.admit = -2.0
    assert w.live_tokens_mean([b], t0, t1) == pytest.approx(
        (50 * 10 + 10 + 4) / 10)
    # no admit event: from the first token; never admitted: nothing
    c = rec(2, 10, 0.0, [5.0, 7.0])
    d = rec(3, 10, 0.0, [])
    assert w.live_tokens_mean([c, d], t0, t1) == pytest.approx(
        (10 * 2 + 2) / 10)
    # finished before the window
    e = rec(4, 10, 0.0, [-3.0, -2.0])
    assert w.live_tokens_mean([e], t0, t1) == 0.0
