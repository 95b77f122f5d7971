"""EVA attention under the lagged carry (``pipeline_depth=None`` resolves
to 1 where the model's own cache has closed ``suspend``: EVA's entry pages):
a window is closed where its last position is DISPATCHED, behind the block
that fills it, and the streams are the synchronous loop's, token for token.

The model, the helpers and the two checks that run under either loop are
``tests/test_eva.py``'s (a file of its own so that one stays the size it
was: pytest-xdist hands out files largest first).
"""

import pytest

from test_eva import (PS, W, Request, batcher,
                      check_entries_follow_E_of_T,
                      check_warmup_compiles_what_the_loop_dispatches,
                      make_cfg, make_params, prompt_of)


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_params(cfg)


# ``pipeline_depth=None`` resolves to the carry under EVA: a window is closed
# in the tick that DISPATCHES its last position, behind that block.  name ->
# (rows, n_pages, [(prompt length, new tokens, stop at token index or None)]).
# A prompt of n closes n // W windows in prefill; decode block j (1, 2, ..)
# brings the row to position n + j and samples token j.
LAG_CASES = {
    # 0..3 windows closed in prefill, 1-2 while decoding, rows reused
    "windows_in_prefill_and_decode": (2, None, [
        (19, 50, None), (40, 30, None), (70, 30, None), (101, 60, None),
        (64, 40, None)]),
    # the quota's last token lands on a window's end: no close is owed
    "finish_at_a_window_end": (2, None, [
        (50, 15, None), (20, 45, None), (90, 39, None), (33, 32, None)]),
    # a stop token sampled by the block that fills a window (index 14 of a
    # prompt of 50: position 64), by the block before it (13: the close
    # rides the overshoot block), and two blocks before (12: no block is
    # left to close anything)
    "stop_token_around_a_close": (2, None, [
        (50, 30, 14), (50, 30, 13), (50, 30, 12), (82, 40, 14),
        (18, 40, 13)]),
    # one row slot: every admission takes the row, and the pages, that the
    # request before it freed a tick after its last close
    "a_freed_row_is_taken_at_once": (1, None, [
        (50, 16, None), (41, 24, None), (95, 3, None), (60, 6, None),
        (30, 4, None)]),
    # 9 pages beside the sink: two rows of these fit only by entries, and
    # a request waits for the pages a close or a finish returns
    "tight_pool": (2, 10, [
        (70, 30, None), (60, 10, None), (45, 25, None), (31, 8, None),
        (90, 12, None), (26, 40, None)]),
}


def lag_requests(cfg, params, spec):
    """The case's requests; a stop index becomes the token the request
    emits there (probed once on the synchronous loop), on a prompt drawn
    so that the token does not come earlier in its stream."""
    out = []
    for i, (n, new, stop_at) in enumerate(spec):
        if stop_at is None:
            out.append(dict(prompt=prompt_of(n, seed=i),
                            max_new_tokens=new))
            continue
        for seed in range(100, 140):
            prompt = prompt_of(n, seed=seed)
            (c,) = batcher(cfg, params, rows=1).run(
                [Request(prompt=prompt, max_new_tokens=stop_at + 1)])
            toks = list(c.tokens)
            if toks[stop_at] not in toks[:stop_at]:
                break
        else:
            raise AssertionError(f"no prompt of {n} stops first at {stop_at}")
        out.append(dict(prompt=prompt, max_new_tokens=new,
                        stop_token=int(toks[stop_at])))
    return lambda: [Request(**kw) for kw in out]


def serve(cfg, params, requests, **kw):
    """Serve the case.  At K = 1 every tick's account also holds each row
    to the pages of the entries it holds, no more (a close that trimmed
    late, or not at all, shows here): ``E(pos)``, but for a row whose last
    owed position filled a window, which nothing closes."""
    b = batcher(cfg, params, **kw)
    account = b._eva_account

    def spy(active):
        account(active)
        for r, row in active.items():
            held = cfg.cache_entries(row.pos)
            if (row.pos % W == 0 and row.step > 1
                    and row.step >= row.req.max_new_tokens):
                held = cfg.cache_entries(row.pos - 1) + 1   # awaits its retire
            assert b.alloc.allocated(r) == -(-held // PS), (r, row.pos)

    if kw.get("multi_step", 1) == 1:
        b._eva_account = spy
    done = {c.rid: (len(c.request.prompt), c.request.max_new_tokens,
                    list(c.tokens)) for c in b.run(requests())}
    assert b.alloc.free_count() == b.n_pages - 1        # all but the sink
    assert b._inflight is None and b._pipe_carry is None
    return b, done


def closes_while_decoding(n, quota, last, lag):
    """Windows a row closes after its prefill at K = 1: prompt ``n``, its
    last token at index ``last`` (``quota - 1``, or a stop token's).  The
    synchronous loop closes behind block j unless the row finished in it;
    the carry closes where block j is DISPATCHED and tokens are still owed
    by the quota, and dispatches one block past a stop token it has not
    read yet."""
    blocks = last if not lag or last == quota - 1 else last + 1
    return sum(1 for j in range(1, blocks + 1)
               if (n + j) % W == 0 and j + 1 < quota and (lag or j < last))


@pytest.mark.parametrize("case", sorted(LAG_CASES))
def test_the_carry_closes_windows_at_dispatch_with_the_same_streams(
        cfg, params, case):
    """Token for token the synchronous loop's, whatever a close falls
    beside; ``eva_rolls`` equal on both sides but for the closes a stop
    token still in flight made needless, which are counted; every page
    comes back; the ring says which loop ran."""
    rows, n_pages, spec = LAG_CASES[case]
    requests = lag_requests(cfg, params, spec)
    kw = dict(rows=rows, n_pages=n_pages)
    sync, want = serve(cfg, params, requests, pipeline_depth=0, **kw)
    lag, got = serve(cfg, params, requests, pipeline_depth=None, **kw)
    assert lag.pipeline_depth == 1 and lag._pipelined
    assert lag.pipeline_bypass_reason is None
    assert lag.suspend_bypass_reason == "eva summary pages"
    assert got == want and len(got) == len(spec)
    modes = lambda b: {r["mode"] for r in b.flight.snapshot()
                       if r["name"] == "decode.block"}
    assert modes(sync) == {"sync"} and modes(lag) == {"pipelined"}
    rolls = {side: sum(n // W + closes_while_decoding(n, quota,
                                                      len(toks) - 1, side)
                       for n, quota, toks in want.values())
             for side in (False, True)}
    assert sync.eva_rolls == rolls[False] and lag.eva_rolls == rolls[True]
    wasted = {"stop_token_around_a_close": 4}.get(case, 0)
    assert lag.eva_rolls - sync.eva_rolls == wasted
    for b in (sync, lag):
        recs = [r for r in b.flight.snapshot() if "eva_rolls" in r]
        assert sum(r["eva_rolls"] for r in recs) == b.eva_rolls
    if n_pages is not None:
        assert lag.peak_pages_used <= n_pages


@pytest.mark.parametrize("case", sorted(LAG_CASES))
def test_the_carry_splits_a_K_block_at_a_windows_end(cfg, params, case):
    """``multi_step`` 4 under the carry: a block that would carry a row
    across a window's end runs as single steps (the positions are the
    dispatched ones), the close follows the step that fills the window,
    and the streams do not depend on K or on the lag."""
    rows, n_pages, spec = LAG_CASES[case]
    requests = lag_requests(cfg, params, spec)
    kw = dict(rows=rows, n_pages=n_pages)
    sync, want = serve(cfg, params, requests, pipeline_depth=0, **kw)
    lag, got = serve(cfg, params, requests, pipeline_depth=None,
                     multi_step=4, **kw)
    assert lag._pipelined and lag._decode1 is not lag._decode
    assert got == want
    blocks = [r for r in lag.flight.snapshot()
              if r["name"] == "decode.block"]
    assert {r["mode"] for r in blocks} == {"pipelined"}
    assert {r["k"] for r in blocks} == {1, 4}
    # a needless close takes a stop token still in flight: up to 2 a request
    assert 0 <= lag.eva_rolls - sync.eva_rolls <= 2 * sum(
        s is not None for _, _, s in spec)


@pytest.mark.parametrize("multi_step", [1, 4])
def test_entries_held_follow_E_of_T_under_the_carry(cfg, params, multi_step):
    """The host's view is the dispatched one and reads the same: a window
    is closed, and its pages trimmed, in the tick that dispatches its last
    position."""
    check_entries_follow_E_of_T(cfg, params, multi_step, lag=None)


def test_warmup_compiles_what_the_carry_dispatches(cfg, params):
    check_warmup_compiles_what_the_loop_dispatches(cfg, params, lag=None)
