"""The readers of the batcher's tick ring: on a hand-made ring against
values computed by hand, on a program without the ring, and in a CPU
rehearsal of the serving driver on the tiny stand-in cell, with the
profiler on and off (never a measurement)."""

import pytest

from benchmark import harness, tick_readers, tiny
from benchmark.drivers import serve

SEED = 2 ** 31 + 79
METRICS = ("tick_host_ms_p50", "host_gap_share", "prefill_stall_share",
           "compiles_in_window")


def tick(t, wall_ms, block=True, rows=4, idle_ms=0.0, compiles=0, **phases):
    return {"name": "decode.block" if block else "tick", "tick": int(t * 10),
            "batcher": 0, "t": t, "wall_ms": wall_ms, "rows": rows,
            "idle_ms": idle_ms, "compiles": compiles,
            "phases": {"batcher." + k: v for k, v in phases.items()}}


#: a window [100, 110) with the profiler on over [104, 105]
RING = [
    tick(99.0, 50.0, prep=1.0, dispatch=2.0, readback=45.0),    # before t0
    # a decode tick behind a prefill: 60 ms, 38 of them waiting
    tick(100.0, 60.0, pull=0.5, admit=6.0, prefill_sync=8.0, prep=1.0,
         dispatch=2.0, readback=30.0, retire=1.5, emit=1.0),
    tick(100.06, 40.0, prep=1.0, dispatch=2.0, readback=35.0, retire=1.0,
         emit=0.5),
    # nothing active: 400 ms asleep in the idle pull, then an admission
    tick(100.1, 500.0, block=False, rows=0, idle_ms=400.0, pull=400.2,
         admit=9.8, prefill_sync=80.0),
    tick(100.6, 50.0, compiles=2, prep=2.0, dispatch=3.0, readback=40.0,
         retire=2.0, emit=1.0),
    tick(103.6, 70.0, prep=9.0, dispatch=9.0, readback=40.0),   # lead
    tick(104.5, 900.0, prep=99.0, dispatch=99.0, readback=40.0),  # traced
    tick(107.9, 300.0, prep=99.0, dispatch=99.0, readback=40.0),  # settling
    tick(110.0, 50.0, prep=1.0, dispatch=2.0, readback=45.0),   # at t1
]
RUN = {"t0": 100.0, "t1": 110.0, "trace_window": (104.0, 105.0)}


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(tick_readers, "ring", lambda: list(RING))


def test_readers_take_the_windows_undisturbed_ticks(ring):
    assert tick_readers.TRACE_LEAD_S == 0.5
    assert 105.0 + tick_readers.TRACE_SETTLE_S >= 107.9
    assert [r["t"] for r in tick_readers.ticks(RUN)] == [
        100.0, 100.06, 100.1, 100.6]
    untraced = dict(RUN, trace_window=(None, None))
    assert [r["t"] for r in tick_readers.ticks(untraced)] == [
        100.0, 100.06, 100.1, 100.6, 103.6, 104.5, 107.9]


def test_readers_against_hand_computed_values(ring):
    # block ticks: 60 - 38, 40 - 35, 50 - 40 -> 22, 5, 10
    assert tick_readers.tick_host_ms_p50(RUN) == pytest.approx(10.0)
    # the four ticks span 0.65 s; host phases 12 + 4.5 + (410 - 400) + 8
    assert tick_readers.host_gap_share(RUN) == pytest.approx(
        100.0 * 0.0345 / 0.65)
    # only the first tick had rows behind its admit + prefill_sync: 14 ms
    assert tick_readers.prefill_stall_share(RUN) == pytest.approx(
        100.0 * 0.014 / 0.65)
    assert tick_readers.compiles_in_window(RUN) == 2.0


@pytest.mark.parametrize("metric", METRICS)
def test_reader_on_a_program_without_the_ring_reports_nothing(
        monkeypatch, metric):
    """The parent of the PR that brought the ring: ``flight`` hands out an
    empty recorder under the name, and the metric is left off the line."""
    monkeypatch.setattr(tick_readers, "TICK_COMPONENT", "no.such.ring")
    assert tick_readers.ring() == []
    for name in (metric, metric + ".docqa"):
        assert harness.load_reader(name)(RUN) is None


def spec_with_tick_metrics():
    spec = tiny.tiny_spec()         # tiny.py is the benchmark's: append here
    for name in METRICS:
        spec["per_layer"] += [
            {"name": name, "unit": "x", "moves": "tpot_p90_ms",
             "workloads": ["tiny.open"]},
            {"name": name + ".batch", "unit": "x", "moves": "tok_s",
             "workloads": ["tiny.backlog"]}]
    return spec


def check(values):
    assert set(values) == set(METRICS)
    assert 0.0 < values["tick_host_ms_p50"] < 1e3
    assert 0.0 < values["host_gap_share"] <= 100.0
    assert 0.0 <= values["prefill_stall_share"] <= 100.0
    assert values["compiles_in_window"] >= 0.0


def test_rehearsal_reports_the_tick_metrics_traced_and_untraced():
    spec = spec_with_tick_metrics()
    # traced: the profiler runs from 1 s into the window for 4 s, and the
    # readers take the ticks outside its reach
    res = serve.run_cell(spec, spec["workloads"][1], tiny.config(),
                         tiny.TINY_BACKLOG, seed=SEED, seconds=2, trace=True,
                         t_start=0.0, require_chip=False,
                         out=lambda line: None)
    assert res["correct"] is True
    check({k[:-len(".batch")]: v["value"] for k, v in res["metrics"].items()
           if k.split(".")[0] in METRICS})
    # untraced: a --trace 0 run prints end-to-end metrics only, so read the
    # ring the run left behind as harness.per_layer would
    res = serve.run_cell(spec, spec["workloads"][0], tiny.config(),
                         tiny.TINY_OPEN, seed=SEED + 1, seconds=2,
                         trace=False, t_start=0.0, require_chip=False,
                         out=lambda line: None)
    assert res["correct"] is True
    run = {"t0": res["t0"], "t1": res["t1"], "trace_window": (None, None)}
    check({m: harness.load_reader(m)(run) for m in METRICS})
    recs = tick_readers.ticks(run)
    assert len({r["batcher"] for r in recs}) == 1
    assert all(res["t0"] <= r["t"] < res["t1"] for r in recs)
    # every admit event the driver's tap saw from the first of these ticks
    # on is counted in the tick that made it
    admits = [r.admit for r in res["records"] if r.admit is not None
              and recs[0]["t"] <= r.admit < res["t1"]]
    assert admits and sum(r["admitted"] for r in recs) >= len(admits)


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_new_entry_of_the_spec_names_its_reader_and_layer(spec):
    """The ring's entries are found by name, wherever they stand in
    ``per_layer`` and however many cells' suffixes there are."""
    new = [m for m in spec["per_layer"] if m["name"].split(".")[0] in METRICS]
    assert {m["name"] for m in new} >= {base + s for base in METRICS
                                        for s in ("", ".docqa")}
    for m in new:
        base = m["name"].split(".")[0]
        assert harness.load_reader(m["name"]) is getattr(tick_readers, base)
        assert m["layer"] == ("model step" if base == "compiles_in_window"
                              else "batcher")
        assert m["source"] == ("program_counter"
                               if base == "compiles_in_window"
                               else "program_span")
