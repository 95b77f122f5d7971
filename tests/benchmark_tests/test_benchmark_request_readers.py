"""The readers of the batcher's request ring and of the tick ring's stall
counters: on hand-made rings against values computed by hand, on a program
without them, and in a CPU rehearsal of the serving driver on the tiny
stand-in cell, with the profiler on and off (never a measurement)."""

import bisect

import pytest

from benchmark import harness, request_readers, tick_readers, tiny
from benchmark.drivers import serve

import spec_checks

SEED = 2 ** 31 + 83
REQUEST_METRICS = ("queue_wait_p90_ms", "submit_to_first_p90_ms",
                   "admit_to_first_ms_per_ktok_p50")
TICK_METRICS = ("stall_share", "gc_pause_share", "ready_on_arrival_share")
METRICS = REQUEST_METRICS + TICK_METRICS
CHAT = {"mistral7b.chat_steady"}
BATCH = {"mistral7b.docqa_batch", "evabyte.longdoc_batch",
         "granite4h.gen_batch", "solar2.reason_batch"}
#: entry -> (cells it must list, the end-to-end metric it moves, source)
ENTRIES = {
    "queue_wait_p90_ms": (CHAT, "tpot_p90_ms", "program_span"),
    "submit_to_first_p90_ms": (CHAT, "tpot_p90_ms", "program_span"),
    "admit_to_first_ms_per_ktok_p50": (CHAT, "tpot_p90_ms", "program_span"),
    "admit_to_first_ms_per_ktok_p50.docqa": (BATCH, "tok_s", "program_span"),
    "stall_share": (CHAT, "tpot_p90_ms", "program_counter"),
    "stall_share.docqa": (BATCH, "tok_s", "program_counter"),
    "gc_pause_share": (CHAT, "tpot_p90_ms", "program_counter"),
    "gc_pause_share.docqa": (BATCH, "tok_s", "program_counter"),
    "ready_on_arrival_share.docqa": (
        {"granite4h.gen_batch", "solar2.reason_batch"}, "tok_s",
        "program_counter"),
}


def request(submit, admit, first, prefill_tokens=1000, status="completed"):
    return {"name": "request", "rid": 0, "batcher": 0, "status": status,
            "t_submit": submit, "t_admit": admit, "t_first": first,
            "t_done": (first or admit or submit) + 1.0,
            "prompt_tokens": 900, "prefill_tokens": prefill_tokens,
            "out_tokens": 8, "admit_tick": 1, "first_tick": 1,
            "done_tick": 2}


#: a window [100, 110) with the profiler on over [104, 105]
REQUESTS = [
    request(98.0, 99.99, 100.5),                    # admitted before t0
    request(99.0, 100.0, 100.2),                    # at t0: waited 1 s
    request(100.1, 100.11, 100.21, prefill_tokens=2000),
    request(100.2, 100.22, 100.30, prefill_tokens=0),       # an import
    request(100.3, 100.33, None, status="expired"),         # no first token
    request(100.4, None, None, status="shed"),              # never admitted
    request(103.0, 103.6, 103.7),                   # the profiler's lead
    request(104.0, 104.5, 104.6),                   # traced
    request(107.0, 107.9, 108.0),                   # settling
    request(108.0, 108.04, 108.54, prefill_tokens=500),
    request(109.0, 110.0, 110.1),                   # admitted at t1
]
RUN = {"t0": 100.0, "t1": 110.0, "trace_window": (104.0, 105.0)}
UNTRACED = dict(RUN, trace_window=(None, None))


def tick(t, wall_ms, kind="decode", gc_ms=0.0, ready=0, compiles=0,
         idle_ms=0.0, **phases):
    return {"name": "decode.block" if kind != "idle" else "tick",
            "tick": int(t * 100), "batcher": 0, "t": t, "wall_ms": wall_ms,
            "kind": kind, "rows": 4, "idle_ms": idle_ms,
            "compiles": compiles, "gc_ms": gc_ms, "ready": ready,
            "phases": {"batcher." + k: v for k, v in phases.items()}}


#: decode ticks of 20, 30 and 40 ms: the median is 30, 8 x 30 = 240 < 250
TICKS = [
    tick(99.0, 5000.0, readback=4990.0),                    # before t0
    tick(100.0, 20.0, readback=15.0),
    tick(100.02, 30.0, readback=25.0, gc_ms=6.0, ready=1),
    tick(100.05, 40.0, readback=35.0),
    # held 249 ms: over 8 x the median and under the 250 ms floor
    tick(100.1, 249.0, readback=244.0),
    # a stall: 1300 ms inside readback, 1270 of them lost
    tick(100.4, 1300.0, readback=1295.0, gc_ms=14.0),
    # a long prefill is no stall: 900 ms of which 700 admit and sync
    tick(101.7, 900.0, kind="mixed", admit=300.0, prefill_sync=400.0,
         readback=190.0, ready=1),
    # held 600 ms behind it: a stall, 570 lost
    tick(102.6, 1000.0, kind="mixed", admit=100.0, prefill_sync=300.0,
         readback=590.0),
    # an idle pull of 2 s is no stall, and reads no block back
    tick(103.0, 2010.0, kind="idle", idle_ms=2000.0, pull=2000.5,
         ready=None),
    # a tick that compiled says so itself
    tick(103.4, 3000.0, compiles=1, dispatch=2990.0),
    tick(104.5, 9000.0, readback=8990.0, gc_ms=99.0),       # traced
    tick(110.0, 9000.0, readback=8990.0),                   # at t1
]
SPAN_MS = 20 + 30 + 40 + 249 + 1300 + 900 + 1000 + 2010 + 3000


@pytest.fixture
def rings(monkeypatch):
    monkeypatch.setattr(request_readers, "ring", lambda: list(REQUESTS))
    monkeypatch.setattr(tick_readers, "ring", lambda: list(TICKS))


def test_readers_take_the_requests_admitted_in_the_undisturbed_window(rings):
    assert [r["t_admit"] for r in request_readers.requests(RUN)] == [
        100.0, 100.11, 100.22, 100.33, 108.04]
    assert [r["t_admit"] for r in request_readers.requests(UNTRACED)] == [
        100.0, 100.11, 100.22, 100.33, 103.6, 104.5, 107.9, 108.04]


def test_request_readers_against_hand_computed_values(rings):
    # waits 1000, 10, 20, 30, 40 ms: p90 is 4/10 of the way from 40 to 1000
    assert request_readers.queue_wait_p90_ms(RUN) == pytest.approx(
        40.0 + 0.6 * 960.0)
    # to the first token 1200, 110, 100, 540 ms (the expired one has none):
    # p90 of four is 7/10 of the way from 540 to 1200
    assert request_readers.submit_to_first_p90_ms(RUN) == pytest.approx(
        540.0 + 0.7 * 660.0)
    # admit to first per 1,000 tokens dispatched: 200 / 1.0, 100 / 2.0,
    # 500 / 0.5 (the import dispatched none): the median of 200, 50, 1000
    assert request_readers.admit_to_first_ms_per_ktok_p50(
        RUN) == pytest.approx(200.0)


def test_stall_readers_against_hand_computed_values(rings):
    assert [r["t"] for r in tick_readers.ticks(RUN)] == [
        100.0, 100.02, 100.05, 100.1, 100.4, 101.7, 102.6, 103.0, 103.4]
    # decode ticks 20, 30, 40, 249, 1300, 3000: the median is 144.5, so the
    # floor is 8 x 144.5 = 1156: the 1300 ms tick alone is a stall
    assert request_readers.stall_share(RUN) == pytest.approx(
        100.0 * (1300.0 - 144.5) / SPAN_MS)
    assert request_readers.gc_pause_share(RUN) == pytest.approx(
        100.0 * 20.0 / SPAN_MS)
    # eight ticks read a block back, two found it ready
    assert request_readers.ready_on_arrival_share(RUN) == pytest.approx(
        100.0 * 2 / 8)


def test_the_stall_rule_has_two_thresholds_and_spares_honest_work(
        monkeypatch):
    """With a median of 30 ms (8 x 30 = 240): 249 ms held is under the
    250 ms floor, a long prefill and an idle pull are not held time, a
    compile names itself, and what is left of a tick behind a prefill is
    judged like any other."""
    quiet = [t for t in TICKS if 100.0 <= t["t"] < 100.1]
    assert [t["wall_ms"] for t in quiet] == [20.0, 30.0, 40.0]
    run = dict(RUN, trace_window=(None, None), t1=104.0)

    def share(extra):
        monkeypatch.setattr(tick_readers, "ring", lambda: quiet + extra)
        return request_readers.stall_share(run) * sum(
            t["wall_ms"] for t in quiet + extra) / 100.0

    assert request_readers.STALL_MIN_MS == 250.0
    assert request_readers.STALL_FACTOR == 8.0
    assert share([]) == 0.0
    assert share([TICKS[4]]) == 0.0                     # 249 ms
    assert share([tick(100.1, 251.0, kind="mixed", readback=250.0)]) \
        == pytest.approx(251.0 - 30.0)
    assert share([TICKS[6]]) == 0.0                     # the long prefill
    assert share([TICKS[7]]) == pytest.approx(600.0 - 30.0)
    assert share([TICKS[8]]) == 0.0                     # the idle pull
    assert share([TICKS[9]]) == 0.0                     # the compile
    # under the floor of 8 medians where ticks are long: 40 ms x 8 = 320
    slow = [tick(100.0 + i, 40.0, readback=35.0) for i in range(3)]
    monkeypatch.setattr(tick_readers, "ring", lambda: slow + [
        tick(103.5, 300.0, kind="mixed", readback=299.0)])
    assert request_readers.stall_share(run) == 0.0
    # no decode tick, no median: nothing to read
    monkeypatch.setattr(tick_readers, "ring", lambda: [TICKS[6]])
    assert request_readers.stall_share(run) is None


def test_the_readers_rule_is_the_programs():
    from tfmesos_tpu import serving
    assert request_readers.REQUEST_COMPONENT == serving.REQUEST_COMPONENT
    assert request_readers.STALL_FACTOR == serving.STALL_FACTOR
    assert request_readers.STALL_MIN_MS == serving.STALL_MIN_MS


@pytest.mark.parametrize("metric", METRICS)
def test_reader_on_a_program_without_the_rings_reports_nothing(
        monkeypatch, metric):
    """The parent of this PR: ``flight`` hands out an empty recorder under
    the request ring's name, and its tick records carry neither ``gc_ms``
    nor ``ready``; an empty window has no decode tick."""
    monkeypatch.setattr(request_readers, "REQUEST_COMPONENT", "no.such.ring")
    assert request_readers.ring() == []
    old = [{k: v for k, v in t.items() if k not in ("gc_ms", "ready")}
           for t in TICKS]
    monkeypatch.setattr(tick_readers, "ring", lambda: old)
    names = (metric, metric + ".docqa")
    if metric == "stall_share":     # read from what every tick ring has had
        for name in names:
            assert harness.load_reader(name)(RUN) == pytest.approx(
                100.0 * (1300.0 - 144.5) / SPAN_MS)
        monkeypatch.setattr(tick_readers, "ring", lambda: [])
    for name in names:
        assert harness.load_reader(name)(RUN) is None


def spec_with_request_metrics():
    spec = tiny.tiny_spec()         # tiny.py is the benchmark's: append here
    for name in METRICS:
        spec["per_layer"] += [
            {"name": name, "unit": "x", "moves": "tpot_p90_ms",
             "workloads": ["tiny.open"]},
            {"name": name + ".batch", "unit": "x", "moves": "tok_s",
             "workloads": ["tiny.backlog"]}]
    return spec


def check(values):
    """Every entry the synchronous cells list (chat_steady's, docqa_batch's)
    reads a value.  ``ready_on_arrival_share`` reads a share where the tiny
    cell's loop carries a lagged block and nothing where it does not: which
    of the two the batcher chooses for a plain stack is the program's
    (tests/test_serving.py holds ``ready`` to 0 or 1 on a pipelined loop's
    ticks), not this test's."""
    values = {k: v for k, v in values.items() if v is not None}
    assert set(METRICS) - {"ready_on_arrival_share"} <= set(values) \
        <= set(METRICS)
    assert 0.0 <= values.get("ready_on_arrival_share", 0.0) <= 100.0
    assert 0.0 <= values["queue_wait_p90_ms"] <= \
        values["submit_to_first_p90_ms"] < 6e4
    assert 0.0 < values["admit_to_first_ms_per_ktok_p50"] < 6e4
    for name in ("stall_share", "gc_pause_share"):
        assert 0.0 <= values[name] <= 100.0


def test_rehearsal_reports_the_request_metrics_traced_and_untraced():
    spec = spec_with_request_metrics()
    # traced, a backlog: the readers take what lies outside the profiler's
    # reach (it runs from 1 s into the window)
    res = serve.run_cell(spec, spec["workloads"][1], tiny.config(),
                         tiny.TINY_BACKLOG, seed=SEED, seconds=2, trace=True,
                         t_start=0.0, require_chip=False,
                         out=lambda line: None)
    assert res["correct"] is True
    check({k[:-len(".batch")]: v["value"] for k, v in res["metrics"].items()
           if k.split(".")[0] in METRICS})
    # untraced, open loop: a --trace 0 run prints end-to-end metrics only,
    # so read the rings the run left behind as harness.per_layer would
    res = serve.run_cell(spec, spec["workloads"][0], tiny.config(),
                         tiny.TINY_OPEN, seed=SEED + 1, seconds=2,
                         trace=False, t_start=0.0, require_chip=False,
                         out=lambda line: None)
    assert res["correct"] is True
    run = {"t0": res["t0"], "t1": res["t1"], "trace_window": (None, None)}
    check({m: harness.load_reader(m)(run) for m in METRICS})
    recs = request_readers.requests(run)
    assert len({r["batcher"] for r in recs}) == 1
    # the program's stamps beside the driver's own, taken from outside on
    # the same clock: it stamps the submission right after the driver does,
    # admits before the driver's tap hears of it, and has the first token
    # before the driver's callback runs
    served = sorted((r for r in res["records"] if r.submit is not None),
                    key=lambda r: r.submit)
    submits = [r.submit for r in served]
    assert recs
    for rec in recs:
        seen = served[bisect.bisect_right(submits, rec["t_submit"]) - 1]
        assert 0.0 <= rec["t_submit"] - seen.submit < 0.05
        assert rec["prompt_tokens"] == seen.prompt_len
        assert rec["t_submit"] <= rec["t_admit"] <= seen.admit
        if rec["t_first"] is not None:
            assert rec["t_first"] <= seen.token_times[0]


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_new_entry_of_the_spec_names_its_reader_and_layer(spec):
    """The entries are found by name, wherever they stand in ``per_layer``
    and however many cells a later PR joins to them."""
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert set(ENTRIES) <= set(by_name)
    cells = {w["name"] for w in spec["workloads"]}
    for name, (must, moves, source) in ENTRIES.items():
        m = by_name[name]
        base = name.split(".")[0]
        assert harness.load_reader(name) is getattr(request_readers, base)
        assert must <= set(m["workloads"]) <= cells
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "batcher", moves, source, "lower")
        assert m["unit"] == ("%" if base.endswith("_share") else "ms")
        for cell in m["workloads"]:     # the cell reports what it moves
            assert moves in {e["name"] for e in harness.cell_metrics(
                spec, cell, "end_to_end")}


def test_every_structural_check_holds_on_the_spec_with_the_new_entries():
    """What ``spec_checks.py`` runs against a later PR's copy, run here
    against the checkout's own BENCHMARK.json."""
    spec = harness.load_spec()
    ran = [name for name, fn in spec_checks.checks() if fn(spec) is None]
    assert len(ran) >= 10 and any("request_readers" in n for n in ran)
