"""End-to-end request tracing (tfmesos_tpu/fleet/tracing.py) — all
jax-free: FlightRecorder bounds, TraceContext hop-local spans and
cross-hop stitching, TraceBook tail-based retention, Prometheus
exposition round-trip, the metrics consistency contract under
concurrent mixed deadline/priority traffic, chaos-fault attribution,
and the flagship waterfall: one request that was WFQ-queued, routed
with a retry, and drain-migrated (suspend → resume on a survivor)
reconstructed hop by hop from a single ``trace`` op fetch."""

import random
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from tfmesos_tpu import wire
from tfmesos_tpu.chaos import Fault, FaultPlan
from tfmesos_tpu.fleet import tracing
from tfmesos_tpu.fleet.admission import (AdmissionController, Overloaded,
                                         PriorityClass, RateLimited)
from tfmesos_tpu.fleet.client import FleetClient, RequestFailed
from tfmesos_tpu.fleet.gateway import Gateway
from tfmesos_tpu.fleet.metrics import FleetMetrics, Histogram
from tfmesos_tpu.fleet.registry import ReplicaRegistry
from tfmesos_tpu.fleet.replica import ReplicaServer
from tfmesos_tpu.fleet.router import Router
from tfmesos_tpu.fleet.tracing import (FlightRecorder, TraceBook,
                                       TraceContext, format_waterfall)


def _wait(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- core primitives ---------------------------------------------------------


def test_flight_recorder_bounded_ring():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record({"name": "e", "i": i})
    snap = rec.snapshot()
    assert [e["i"] for e in snap] == [6, 7, 8, 9]   # oldest dropped
    assert rec.total == 10
    rec.grow(6)         # a reader's larger ask keeps what is there
    rec.record({"name": "e", "i": 10})
    assert [e["i"] for e in rec.snapshot()] == [6, 7, 8, 9, 10]
    with pytest.raises(ValueError):
        FlightRecorder(0)


def test_trace_context_spans_events_and_cap():
    tr = TraceContext(trace_id="abc", detailed=True, max_spans=3)
    tr.event("gateway", "recv", cls="default")
    t0 = time.perf_counter()
    time.sleep(0.01)
    tr.span_between("batcher", "prefill", t0, time.perf_counter(), rid=7)
    tr.add("router", "attempt", 1.0, 2.5, addr="x", outcome="ok")
    tr.event("router", "overflow")          # 4th: dropped at the cap
    spans = tr.export()
    assert len(spans) == 3 and tr.dropped == 1
    assert spans[0]["name"] == "recv" and spans[0]["cls"] == "default"
    assert spans[1]["dur"] >= 9.0 and spans[1]["rid"] == 7
    # A span reaches its trace and no component recorder: the rings are
    # for what is read (the batcher's ticks, requests and stalls).
    for component in ("gateway", "batcher", "router"):
        assert tracing.flight(component).snapshot() == []


def test_trace_absorb_reanchors_hop_local_spans():
    tr = TraceContext(trace_id="t1")
    hop = [{"component": "replica", "name": "recv", "t0": 0.0,
            "dur": 0.0},
           {"component": "batcher", "name": "decode", "t0": 1.5,
            "dur": 4.0, "rid": 3}]
    tr.absorb(hop, base_ms=100.0, addr="r1:1")
    tr.absorb(["junk", {"t0": "NaN?", "dur": object()}], base_ms=0.0)
    spans = tr.export()
    assert len(spans) == 2                  # malformed entries dropped
    assert spans[1]["t0"] == 101.5 and spans[1]["dur"] == 4.0
    assert spans[1]["addr"] == "r1:1" and spans[1]["rid"] == 3


def test_current_trace_is_thread_local():
    tr = TraceContext()
    seen = []

    def other():
        seen.append(tracing.current())
        tracing.cur_event("x", "noop")      # no current trace: no-op

    with tracing.activate(tr):
        assert tracing.current() is tr
        t0 = tracing.cur_elapsed()
        tr.add("router", "attempt", t0, tr.elapsed_ms() - t0, addr="a")
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert tracing.current() is None
    assert seen == [None]
    assert [s["name"] for s in tr.export()] == ["attempt"]


def test_tracebook_tail_retention_rules():
    book = TraceBook(sample=0.0, slow_ms=50.0)
    # Healthy + fast + unsampled: summary only.
    tr = book.begin()
    tr.event("gateway", "recv")
    rec = book.finish(tr, "completed", cls="default")
    assert rec["detailed"] is False and "spans" not in rec
    assert book.get(tr.trace_id)["summary"] == {"cls": "default"}
    # Failed: detail retained no matter the sampling.
    tr = book.begin()
    tr.event("router", "retry", cause="timeout")
    rec = book.finish(tr, "deadline_exceeded")
    assert rec["detailed"] and rec["spans"][0]["name"] == "retry"
    # Sampled (client asked): detail retained.
    tr = book.begin(want_detail=True)
    assert tr.detailed
    assert book.finish(tr, "completed")["detailed"]
    # Head sampling via the book's rng is deterministic under a seed.
    book2 = TraceBook(sample=0.5, rng=random.Random(7))
    picks = [book2.begin().detailed for _ in range(8)]
    book3 = TraceBook(sample=0.5, rng=random.Random(7))
    assert picks == [book3.begin().detailed for _ in range(8)]
    assert any(picks) and not all(picks)


def test_tracebook_slow_request_retains_detail():
    book = TraceBook(sample=0.0, slow_ms=10.0)
    tr = book.begin()
    tr.event("gateway", "recv")
    time.sleep(0.02)                        # slower than slow_ms
    rec = book.finish(tr, "completed")
    assert rec["detailed"] and rec["spans"]


def test_tracebook_eviction_moves_detailed_to_retained():
    book = TraceBook(capacity=4, retain=2, sample=0.0, slow_ms=1e9)
    kept = []
    for i in range(3):
        tr = book.begin()
        book.finish(tr, "unavailable")      # detailed (failure)
        kept.append(tr.trace_id)
    for _ in range(8):                      # flood of healthy traffic
        book.finish(book.begin(), "completed")
    # The oldest detailed record was evicted from recent AND from the
    # retained ring's own bound; the newer two survive the flood.
    assert book.get(kept[0]) is None
    assert book.get(kept[1]) is not None
    assert book.get(kept[2]) is not None
    d = book.describe()
    assert d["recent"] == 4 and d["retained"] == 2
    assert d["finished"] == 11 and d["detailed"] == 3
    # Query surfaces: failed() finds the retained failures, slowest()
    # orders by total.
    assert {r["trace_id"] for r in book.failed(10)} >= {kept[1], kept[2]}
    slows = book.slowest(3)
    assert [r["total_ms"] for r in slows] == sorted(
        (r["total_ms"] for r in slows), reverse=True)


def test_format_waterfall_renders_spans_and_summary_only():
    rec = {"trace_id": "t9", "status": "completed", "total_ms": 10.0,
           "summary": {"cls": "interactive"},
           "spans": [
               {"component": "admission", "name": "queue_wait",
                "t0": 0.0, "dur": 4.0, "cls": "interactive"},
               {"component": "router", "name": "attempt", "t0": 4.0,
                "dur": 6.0, "addr": "r:1", "outcome": "ok"}]}
    out = format_waterfall(rec)
    assert "trace t9" in out and "cls=interactive" in out
    assert "admission.queue_wait" in out and "router.attempt" in out
    assert "outcome=ok" in out and "#" in out
    summary = format_waterfall({"trace_id": "s", "status": "completed",
                                "total_ms": 1.0})
    assert "summary only" in summary


# -- metrics satellites ------------------------------------------------------


def test_histogram_nan_sample_dropped_regression():
    """A NaN sample used to increment _count while landing in no
    bucket, skewing every percentile's rank toward the high edges."""
    h = Histogram()
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    h.observe(float("nan"))
    snap = h.snapshot()
    assert snap["count"] == 3
    # With the NaN counted, rank p99*4 would walk past every bucket the
    # three real samples landed in and report the max instead of 5.0.
    assert snap["p99"] == 5.0
    # FleetMetrics path stays consistent too.
    m = FleetMetrics()
    m.observe("lat_ms", 1.0)
    m.observe("lat_ms", float("nan"))
    m.observe("lat_ms", "not-a-number")
    assert m.snapshot()["histograms"]["lat_ms"]["count"] == 1


_PROM_LINE = re.compile(
    r"([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})?\s+(\S+)")
_PROM_TYPE = re.compile(
    r"# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)")


def _parse_prom(text):
    """Tiny exposition parser: {family: kind} and [(name, labels,
    value)] — every line must be well-formed or the test fails."""
    types, samples = {}, []
    for line in text.strip().splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _PROM_TYPE.fullmatch(line)
            assert m, f"malformed TYPE line: {line!r}"
            types[m.group(1)] = m.group(2)
            continue
        m = _PROM_LINE.fullmatch(line)
        assert m, f"malformed sample line: {line!r}"
        val = m.group(3)
        samples.append((m.group(1), m.group(2) or "",
                        float("inf") if val == "+Inf" else float(val)))
    return types, samples


def test_prometheus_text_round_trips_as_valid_exposition():
    m = FleetMetrics()
    m.inc("received", 5)
    m.inc("shed_queue")
    for v in (3.0, 12.0, 700.0):
        m.observe("ttft_ms", v)
    m.observe("queue_wait_ms_class a!", 4.0)    # hostile class label
    m.register_gauge("retry_budget", lambda: 0.75)
    m.register_gauge("queue_depths", lambda: {"hi": 2, "lo": 0,
                                              "nested": {"x": 1}})
    m.register_gauge("boom", lambda: 1 / 0)     # must cost its series
    m.register_gauge("ewma", lambda: float("nan"))  # NaN != dead scrape
    text = m.prometheus_text()
    types, samples = _parse_prom(text)
    by_name = {}
    for name, labels, val in samples:
        by_name.setdefault(name, []).append((labels, val))
    assert types["fleet_received_total"] == "counter"
    assert by_name["fleet_received_total"] == [("", 5.0)]
    assert by_name["fleet_retry_budget"] == [("", 0.75)]
    assert types["fleet_ttft_ms"] == "histogram"
    assert ('{key="hi"}', 2.0) in by_name["fleet_queue_depths"]
    assert all("nested" not in lbl
               for lbl, _ in by_name["fleet_queue_depths"])
    assert "fleet_boom" not in types
    # A NaN-valued gauge emits the legal "NaN" literal instead of
    # killing the whole scrape with int(nan).
    assert [v != v for _, v in by_name["fleet_ewma"]] == [True]
    # Histogram contract: buckets cumulative non-decreasing, +Inf
    # bucket == _count, sum matches the observations.
    buckets = by_name["fleet_ttft_ms_bucket"]
    vals = [v for _, v in buckets]
    assert vals == sorted(vals)
    assert buckets[-1][0] == '{le="+Inf"}'
    assert buckets[-1][1] == by_name["fleet_ttft_ms_count"][0][1] == 3.0
    assert by_name["fleet_ttft_ms_sum"][0][1] == pytest.approx(715.0)
    # The sanitized hostile class name parses (it would not have,
    # unsanitized) and every family got a TYPE line.
    assert any(n.startswith("fleet_queue_wait_ms_class")
               for n in types)
    for name in by_name:
        family = re.sub(r"_(bucket|sum|count|total)$", "", name)
        assert name in types or family in types, name


def test_metrics_http_server_serves_exposition():
    m = FleetMetrics()
    m.inc("received", 2)
    m.observe("ttft_ms", 5.0)
    server = m.start_http_server(0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5.0).read()
        types, _ = _parse_prom(body.decode())
        assert types["fleet_received_total"] == "counter"
        jbody = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5.0).read()
        assert b'"received": 2' in jbody
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5.0)
    finally:
        server.shutdown()
        server.server_close()


# -- stub fleet plumbing -----------------------------------------------------


@pytest.fixture()
def stub_fleet():
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=0.5, dead_after=1.0,
                          evict_after=5.0, sweep_interval=0.05).start()
    servers = []
    try:
        yield token, reg, servers
    finally:
        for s in servers:
            s.stop()
        reg.stop()


def _hop_spans(head, *names):
    """What a traced replica piggybacks: a hop-local context with one
    event per name — exercising the REAL TraceContext the fleet
    replica uses."""
    tid = head.get("trace_id")
    if not isinstance(tid, str):
        return None
    tr = TraceContext(trace_id=tid,
                      detailed=bool(head.get("trace_detail")))
    for name in names:
        tr.event("replica", name)
    return tr.export()


def _stub(token, reg_addr, handler, extra=None):
    return ReplicaServer(handler, token=token, capacity=4,
                         registry_addr=reg_addr,
                         heartbeat_interval=0.05,
                         extra_info=extra).start()


def _summary_for(prompt, page=16):
    from tfmesos_tpu import prefixhash

    return {"page": page, "first": page, "seed": "",
            "hashes": [d.hex()
                       for d in prefixhash.prompt_digests(prompt, page)]}


def _suspended_meta(version="v1", tokens=(4, 9, 2)):
    return {"op": "suspended", "gen": 0, "weights_version": version,
            "version": 1, "page_size": 16, "prefix_len": 0,
            "shared_len": 0, "pos": 5, "prompt_len": 3,
            "first_token": tokens[0], "step": len(tokens),
            "tokens": list(tokens), "rid": 0, "quantized": False,
            "arrays": []}


# -- the flagship waterfall (tox-lint tracing smoke) -------------------------


def test_trace_waterfall_e2e_queued_retry_migrated(stub_fleet):
    """ONE `trace` op fetch reconstructs the full cross-component
    waterfall for a request that (a) waited in the WFQ admission queue,
    (b) was routed with a retry (first attempt timed out on a
    black-holed replica), and (c) was drain-migrated — suspended by the
    victim, resumed on a same-version survivor — with the replica-side
    hop spans stitched into the gateway's timeline."""
    token, reg, servers = stub_fleet
    prompt = list(range(32))

    # Replica 1: a black hole — alive per heartbeat, never replies, and
    # advertises a prefix summary matching the prompt so affinity
    # deterministically routes the FIRST attempt here.
    def black_hole(msg, reply):
        pass

    servers.append(_stub(
        token, reg.addr, black_hole,
        extra=lambda: {"prefix_cache": _summary_for(prompt)}))
    assert _wait(lambda: len(reg.alive()) == 1)

    # Replica 2: the drain-migration victim — suspends every generate,
    # piggybacking its hop spans on the raw frame's meta.
    body = b"\xbb" * 64

    def suspender(msg, reply):
        head = msg.meta if isinstance(msg, wire.RawFrame) else msg
        meta = dict(_suspended_meta(), id=head.get("id"))
        spans = _hop_spans(head, "recv", "suspend")
        if spans:
            meta["trace"] = spans
        reply(wire.RawFrame(meta, body))

    servers.append(_stub(token, reg.addr, suspender,
                         extra=lambda: {"weights_version": "v1"}))
    assert _wait(lambda: len(reg.alive()) == 2)

    # Replica 3: the survivor — resumes the artifact, piggybacking its
    # own hop spans on the completion.
    def resumer(msg, reply):
        assert isinstance(msg, wire.RawFrame), "resume must be raw"
        out = {"op": "completion", "id": msg.meta.get("id"),
               "tokens": list(msg.meta.get("tokens") or ()) + [5],
               "ttft_ms": 0.5, "total_ms": 1.0}
        spans = _hop_spans(msg.meta, "recv", "resume_decode")
        if spans:
            out["trace"] = spans
        reply(out)

    servers.append(_stub(token, reg.addr, resumer,
                         extra=lambda: {"weights_version": "v1"}))
    assert reg.wait_for(3, timeout=5.0)
    blackhole_addr = servers[0].addr
    suspender_addr = servers[1].addr
    resumer_addr = servers[2].addr

    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01,
                    request_timeout=0.4)
    book = TraceBook(sample=0.0, slow_ms=60000.0)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=1, tracebook=book).start()
    try:
        client = FleetClient(gw.addr, token, timeout=30.0)
        # Occupy the single dispatcher so the traced request measurably
        # WFQ-queues behind it (it rides the same timeout+migrate path).
        filler_done = []

        def filler():
            filler_done.append(
                client.generate(prompt, 8, timeout=30.0))

        t = threading.Thread(target=filler)
        t.start()
        time.sleep(0.15)            # filler is mid-flight on the worker
        out = client.generate(prompt, 8, trace=True, timeout=30.0)
        t.join(timeout=30.0)
        assert out["tokens"] == [4, 9, 2, 5]        # resumed stream
        tid = out["trace_id"]
        assert isinstance(tid, str) and tid
        assert "trace" not in out   # span payloads never reach clients

        # ONE fetch reconstructs the whole story.
        recs = client.trace(trace_id=tid)
        assert len(recs) == 1
        rec = recs[0]
        assert rec["detailed"] and rec["status"] == "completed"
        spans = rec["spans"]
        by = {}
        for s in spans:
            by.setdefault((s["component"], s["name"]), []).append(s)

        # (a) WFQ-queued: gateway receipt + a real queue wait.
        assert ("gateway", "recv") in by
        qw = by[("admission", "queue_wait")][0]
        assert qw["dur"] > 50.0

        # (b) routed with >= 1 retry: attempt 1 timed out on the black
        # hole, the retry taxonomy names the cause, attempt 2 reached
        # the victim and came back suspended.
        attempts = by[("router", "attempt")]
        assert [a["outcome"] for a in attempts] == ["timeout",
                                                    "suspended"]
        assert attempts[0]["addr"] == blackhole_addr
        assert attempts[0]["dur"] >= 300.0          # the timeout slice
        assert attempts[1]["addr"] == suspender_addr
        retry = by[("router", "retry")][0]
        assert retry["cause"] == "timeout"
        assert ("router", "budget_debit") in by

        # (c) drain-migrated: the victim's hop spans are stitched in,
        # attributed to its addr, and the resume landed on the
        # survivor with ITS hop spans following.
        victim_spans = [s for s in spans
                        if s.get("addr") == suspender_addr
                        and s["component"] == "replica"]
        assert {s["name"] for s in victim_spans} == {"recv", "suspend"}
        resume = by[("router", "resume")][0]
        assert resume["outcome"] == "ok"
        assert resume["addr"] == resumer_addr
        assert ("router", "migration_resume") in by
        survivor_spans = [s for s in spans
                          if s.get("addr") == resumer_addr
                          and s["component"] == "replica"]
        assert {s["name"] for s in survivor_spans} == {"recv",
                                                       "resume_decode"}

        # Every hop carries a duration and the timeline is coherent:
        # queue wait before the first attempt, attempts in order, and
        # stitched hop spans inside their attempt's window.
        assert all(isinstance(s["dur"], float) and s["dur"] >= 0.0
                   for s in spans)
        assert qw["t0"] <= attempts[0]["t0"] <= attempts[1]["t0"]
        assert attempts[1]["t0"] <= victim_spans[0]["t0"]
        assert resume["t0"] <= survivor_spans[0]["t0"]

        # The waterfall renders every hop.
        art = format_waterfall(rec)
        for needle in ("admission.queue_wait", "router.attempt",
                       "outcome=timeout", "outcome=suspended",
                       "router.resume", "replica.suspend",
                       "replica.resume_decode"):
            assert needle in art, f"{needle} missing from waterfall"

        # The untraced filler finished too and kept only a summary
        # (sample=0, healthy, fast): tail-based retention at work.
        assert filler_done and filler_done[0]["tokens"] == [4, 9, 2, 5]
        filler_rec = client.trace(trace_id=filler_done[0]["trace_id"])[0]
        assert filler_rec["detailed"] is False
        assert "spans" not in filler_rec
        client.close()
    finally:
        gw.stop()


def test_client_supplied_trace_id_and_failed_listing(stub_fleet):
    """A client-chosen trace id rides end to end; a failed request's
    trace retains detail and surfaces in the failed listing."""
    token, reg, servers = stub_fleet
    book = TraceBook(sample=0.0, slow_ms=60000.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2, tracebook=book).start()
    try:
        client = FleetClient(gw.addr, token, timeout=10.0)
        # No replicas at all: unavailable, but still traced.
        with pytest.raises(RequestFailed) as ei:
            client.generate([1, 2, 3], 4, trace="my-chosen-id")
        assert ei.value.trace_id == "my-chosen-id"
        rec = client.trace(trace_id="my-chosen-id")[0]
        assert rec["status"] == "unavailable" and rec["detailed"]
        assert any(r["trace_id"] == "my-chosen-id"
                   for r in client.trace(failed=True))
        assert client.trace(trace_id="no-such-id") == []
        client.close()
    finally:
        gw.stop()


def test_chaos_fault_records_into_active_trace(stub_fleet):
    """A FaultPlan firing lands on the ACTIVE request trace — the soak
    anomaly becomes attributable to the exact injected fault."""
    token, reg, servers = stub_fleet

    def ok(msg, reply):
        reply({"op": "completion", "id": msg.get("id"), "tokens": [1],
               "ttft_ms": 1.0, "total_ms": 2.0})

    servers.append(_stub(token, reg.addr, ok))
    assert reg.wait_for(1, timeout=5.0)
    addr = servers[0].addr
    plan = FaultPlan([Fault("delay", "wire.send", nth=1, target=addr,
                            delay_s=0.02)], seed=3)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    tr = TraceContext(detailed=True)
    try:
        with plan.installed():
            out = router.route({"op": "generate", "prompt": [1, 2],
                                "max_new_tokens": 2, "_trace": tr})
        assert out["tokens"] == [1]
        faults = [s for s in tr.export()
                  if s["component"] == "chaos" and s["name"] == "fault"]
        assert len(faults) == 1
        assert faults[0]["action"] == "delay"
        assert faults[0]["site"] == "wire.send"
        assert addr in faults[0]["key"]
        # The attempt span swallowed the injected delay.
        att = [s for s in tr.export()
               if s["component"] == "router" and s["name"] == "attempt"]
        assert att[0]["dur"] >= 20.0
    finally:
        router.close()


# -- the metrics consistency contract (satellite) ----------------------------


def test_metrics_consistency_contract_under_mixed_traffic(stub_fleet):
    """The documented contract (metrics.py:10-16) under CONCURRENT
    mixed deadline/priority traffic: ``admitted == completed +
    failed`` exactly, and ``received`` decomposes into admitted +
    queue/rate sheds + admission-time deadline sheds — with the
    queued-expiry portion of ``shed_deadline`` reconciled through
    ``failed``/``deadline_exceeded`` (those requests were admitted)."""
    token, reg, servers = stub_fleet

    def slowish(msg, reply):
        def work():
            time.sleep(0.01)
            reply({"op": "completion", "id": msg.get("id"),
                   "tokens": [1], "ttft_ms": 1.0, "total_ms": 2.0})

        threading.Thread(target=work, daemon=True).start()

    servers.append(_stub(token, reg.addr, slowish))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    adm = AdmissionController(
        max_queue=2,
        classes=[PriorityClass("interactive", weight=4.0, rank=1),
                 PriorityClass("background", weight=1.0, rank=0)])
    gw = Gateway(router, adm, metrics, token=token, workers=2).start()
    outcomes = {"completed": 0, "overloaded": 0, "rate_limited": 0,
                "deadline_exceeded": 0, "other": 0}
    lock = threading.Lock()
    n_threads, per_thread = 4, 12

    def one(kind):
        with lock:
            outcomes[kind] += 1

    def feeder(idx):
        client = FleetClient(gw.addr, token, timeout=30.0)
        for i in range(per_thread):
            prio = "interactive" if (idx + i) % 2 else "background"
            # A third of the traffic carries an already-hopeless
            # deadline: shed at admission, swept from the queue, or
            # failed fast by the router — every path must keep the
            # books consistent.
            dl = 0.001 if i % 3 == 0 else (30000.0 if i % 3 == 1
                                           else None)
            try:
                client.generate([1, 2, 3], 2, priority=prio,
                                deadline_ms=dl, timeout=30.0)
                one("completed")
            except RateLimited:
                one("rate_limited")
            except Overloaded:
                one("overloaded")
            except RequestFailed as e:
                one(e.kind if e.kind == "deadline_exceeded"
                    else "other")
        client.close()

    try:
        threads = [threading.Thread(target=feeder, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        total = n_threads * per_thread
        c = metrics.snapshot()["counters"]
        assert outcomes["other"] == 0, outcomes
        assert sum(outcomes.values()) == total
        # The contract, verbatim.
        assert c["received"] == total
        assert c["admitted"] == c.get("completed", 0) + c.get("failed", 0)
        assert c.get("completed", 0) == outcomes["completed"]
        assert c.get("shed_queue", 0) == outcomes["overloaded"]
        # shed_deadline counts admission-time AND queued-expiry sheds;
        # the queued ones were admitted (and count under failed, which
        # otherwise holds only relayed deadline errors here) — so:
        queued_deadline = c.get("failed", 0) - c.get("deadline_exceeded", 0)
        assert queued_deadline >= 0
        assert c["received"] == (
            c["admitted"] + c.get("shed_queue", 0)
            + c.get("shed_rate_limited", 0)
            + c.get("shed_deadline", 0) - queued_deadline)
        # Client-observed deadline outcomes reconcile too: every
        # deadline_exceeded answer came from an admission shed, a
        # queue sweep (both in shed_deadline), or a relayed
        # router/replica deadline error (deadline_exceeded).
        assert outcomes["deadline_exceeded"] == \
            c.get("shed_deadline", 0) + c.get("deadline_exceeded", 0)
    finally:
        gw.stop()
