"""The stratified generator: every seed offers the same multiset of work at
the same mean rate, in another order."""

import numpy as np
import pytest

from benchmark import harness
from benchmark import traffic_gen as tg

SEEDS = (0, 7, 2 ** 31 + 12345)      # the driver's seeds pass 32 signed bits
MIXES = ["chat_steady", "docqa_batch"]
# tok_s of each backlog cell as last accepted (PERF_LEDGER.jsonl, PR 46).  A
# cell that a later PR adds brings its own case in its own test file.
ACCEPTED_TOK_S = {
    "mistral7b.docqa_batch": 11278, "evabyte.longdoc_batch": 5344,
    "granite4h.gen_batch": 6579, "solar2.reason_batch": 13059,
    "laguna.mixed_batch": 29291, "mimo.agent_batch": 20216}


def _schedules(name, seconds=51, **changed):
    traffic = dict(tg.load_traffic(name), **changed)
    return traffic, [tg.make_schedule(traffic, s, seconds, 32768)
                     for s in SEEDS]


def _blocks(traffic, sched, of):
    block = traffic["block"] * (traffic.get("repeat") or {"times": 1})["times"]
    reqs = sched.requests
    return [sorted(of(r) for r in reqs[lo:lo + block])
            for lo in range(0, len(reqs) - block + 1, block)]


@pytest.mark.parametrize("name", MIXES)
def test_the_file_fixes_the_order_and_the_seed_draws_the_ids(name):
    traffic, scheds = _schedules(name)
    a, b = scheds[0].requests, scheds[2].requests
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_a_mix_without_schedule_seed_is_refused():
    traffic = tg.load_traffic("chat_steady")
    del traffic["schedule_seed"]
    with pytest.raises(KeyError):
        tg.make_schedule(traffic, 1, 51, 32768)


@pytest.mark.parametrize("name", MIXES)
def test_another_schedule_seed_offers_the_same_multiset_in_another_order(name):
    traffic, (ref, _, _) = _schedules(name)
    _, (other, _, _) = _schedules(name, schedule_seed=traffic["schedule_seed"] + 1)
    assert len(other.requests) == len(ref.requests)
    assert _blocks(traffic, ref, lambda r: r.max_new_tokens) == \
        _blocks(traffic, other, lambda r: r.max_new_tokens)
    if not traffic.get("suffix"):
        assert _blocks(traffic, ref, lambda r: len(r.prompt)) == \
            _blocks(traffic, other, lambda r: len(r.prompt))
    assert [r.max_new_tokens for r in ref.requests] != \
        [r.max_new_tokens for r in other.requests]


def test_same_seed_same_schedule():
    traffic = tg.load_traffic("chat_steady")
    a = tg.make_schedule(traffic, 2 ** 31 + 5, 51, 32768)
    b = tg.make_schedule(traffic, 2 ** 31 + 5, 51, 32768)
    assert [r.due_s for r in a.requests] == [r.due_s for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


def test_open_loop_mean_rate_is_the_files():
    traffic, scheds = _schedules("chat_steady")
    rate, block = traffic["arrivals"]["rate_rps"], traffic["block"]
    for s in scheds:
        due = np.array([r.due_s for r in s.requests])
        assert np.all(np.diff(due) > 0)
        # every whole block of arrivals takes exactly block / rate seconds
        for k in range(block, len(due) + 1, block):
            assert due[k - 1] == pytest.approx(k / rate, rel=1e-9)
        assert due[-1] >= s.ramp_s + 51 + s.grace_s


def test_blocks_are_reshuffled():
    traffic, (s, _, _) = _schedules("chat_steady")
    b = traffic["block"]
    first = [r.max_new_tokens for r in s.requests[:b]]
    second = [r.max_new_tokens for r in s.requests[b:2 * b]]
    assert sorted(first) == sorted(second) and first != second


def test_lengths_follow_the_file():
    traffic = tg.load_traffic("chat_steady")
    v = tg.quantile_values(traffic["prompt"], 4096)
    assert v.min() >= 64 and v.max() <= 2048
    assert abs(np.median(v) - 512) <= 2
    o = tg.quantile_values(traffic["output"], 4096)
    assert o.min() >= 32 and o.max() <= 512 and abs(np.median(o) - 128) <= 1
    gaps = tg.quantile_values({"dist": "exponential", "mean": 0.5}, 64)
    assert gaps.mean() == pytest.approx(0.5) and gaps.min() > 0


def test_repeat_places_copies_stride_apart_with_other_questions():
    traffic = tg.load_traffic("docqa_batch")
    s = tg.make_schedule(traffic, 11, 51, 32768)
    stride, times = traffic["repeat"]["stride"], traffic["repeat"]["times"]
    reqs = s.requests
    for i in range(stride):
        copies = [reqs[i + k * stride] for k in range(times)]
        assert len({c.group for c in copies}) == 1
        doc = min(len(c.prompt) for c in copies) - 64
        assert doc >= 2048 - 64
        assert all(np.array_equal(c.prompt[:doc], copies[0].prompt[:doc])
                   for c in copies)
        tails = {tuple(c.prompt[-16:]) for c in copies}
        assert len(tails) == times
    assert all(2048 + 32 <= len(r.prompt) <= 7680 + 64 for r in reqs)
    assert all(32 <= r.max_new_tokens <= 128 for r in reqs)
    # every document length is a whole number of 256-token passages
    assert all((len(r.prompt) - 32) // 256 * 256 >= 2048 for r in reqs)


@pytest.mark.parametrize("cell", sorted(ACCEPTED_TOK_S))
def test_a_backlog_outlasts_the_run_at_twice_the_accepted_rate(cell):
    """A backlog that a program drains before the window's end reads that
    program LOWER the faster it is (``tok_s`` is tokens over the whole
    window): the queue holds what twice the accepted rate delivers over
    ramp + window."""
    spec = harness.load_spec()
    name = harness.find_cell(spec, cell)["traffic"]
    traffic, rate = tg.load_traffic(name), ACCEPTED_TOK_S[cell]
    assert traffic["arrivals"]["kind"] == "backlog"
    sched = tg.make_schedule(traffic, 1, spec["run_seconds"], 32768)
    tokens = sum(len(r.prompt) + r.max_new_tokens for r in sched.requests)
    drained_above = tokens / (sched.ramp_s + spec["run_seconds"])
    assert drained_above >= 2 * rate, (
        f"{cell}: the backlog's {tokens:,} tokens are spent before the "
        f"window ends by any program over {drained_above:,.0f} tokens/s, "
        f"{drained_above / rate:.2f} x the accepted {rate:,}; at least 2 x "
        f"is the rule.  A `benchmark` PR raises arrivals.requests in "
        f"benchmark/traffic/{name}.json; a `perf_opt` that would double "
        f"this cell's tok_s asks for that first")
