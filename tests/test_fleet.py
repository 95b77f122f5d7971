"""Fleet serving gateway (tfmesos_tpu/fleet/): unit tests over stub
replicas (no JAX — the fleet machinery is model-agnostic), then the
end-to-end acceptance path: a gateway fronting 2 ``LocalBackend``-
launched batcher replicas on CPU must serve concurrent requests to the
exact offline-greedy completions, retry onto the survivor when a
replica is killed mid-stream, shed with explicit Overloaded rejections
past the ingress bound (never a hang), and keep its metrics snapshot
consistent throughout."""

import threading
import time

import numpy as np
import pytest

from tfmesos_tpu import wire
from tfmesos_tpu.fleet.admission import (AdmissionController, Overloaded,
                                         RateLimited, TokenBucket)
from tfmesos_tpu.fleet.client import (CallTimeout, ConnectionLost,
                                      FleetClient, MuxConnection)
from tfmesos_tpu.fleet.gateway import Gateway
from tfmesos_tpu.fleet.metrics import FleetMetrics
from tfmesos_tpu.fleet.registry import DEAD, DRAINING, ReplicaRegistry
from tfmesos_tpu.fleet.replica import ReplicaServer
from tfmesos_tpu.fleet.router import Router, RoutingError


def _wait(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- admission --------------------------------------------------------------


def test_token_bucket_refill_and_burst():
    t = [0.0]
    tb = TokenBucket(rate=10.0, burst=2, clock=lambda: t[0])
    assert tb.try_acquire() and tb.try_acquire()
    assert not tb.try_acquire()         # burst spent
    t[0] += 0.1                         # refills exactly one token
    assert tb.try_acquire()
    assert not tb.try_acquire()
    t[0] += 100.0                       # refill caps at burst
    assert tb.try_acquire() and tb.try_acquire()
    assert not tb.try_acquire()


def test_admission_queue_bound_sheds():
    adm = AdmissionController(max_queue=2)
    adm.admit("a")
    adm.admit("b")
    with pytest.raises(Overloaded):
        adm.admit("c")
    assert adm.get(timeout=0.1) == "a"  # a pop frees a slot
    adm.admit("c")
    assert adm.depth() == 2


def test_admission_rate_limit_sheds_with_distinct_kind():
    adm = AdmissionController(max_queue=16, rate=1.0, burst=1)
    adm.admit("a")
    with pytest.raises(RateLimited) as e:
        adm.admit("b")
    assert e.value.kind == "rate_limited"
    assert isinstance(e.value, Overloaded)   # one except-clause catches both


# -- metrics ----------------------------------------------------------------


def test_metrics_snapshot_and_report_line():
    m = FleetMetrics()
    m.inc("admitted", 3)
    m.inc("shed_queue")
    for v in (5.0, 10.0, 400.0):
        m.observe("ttft_ms", v)
    m.observe("ttft_ms", None)          # non-numeric samples are dropped
    m.register_gauge("queue_depth", lambda: 7)
    snap = m.snapshot()
    assert snap["counters"] == {"admitted": 3, "shed_queue": 1}
    assert snap["gauges"]["queue_depth"] == 7
    h = snap["histograms"]["ttft_ms"]
    assert h["count"] == 3 and h["max"] == 400.0
    assert h["p50"] == 10.0             # bucket upper edge of the median
    line = m.report_line()
    assert "admitted=3" in line and "queue_depth=7" in line


def test_metrics_http_server_port_in_use_falls_back():
    """With N gateway processes on one host only the first wins a fixed
    --metrics-port; the rest fall back to an OS-assigned port and
    REPORT it (the metrics_http_port gauge) instead of dying unscraped."""
    import json
    import urllib.request

    m1, m2 = FleetMetrics(), FleetMetrics()
    s1 = m1.start_http_server(0)
    s2 = None
    try:
        taken = s1.server_address[1]
        assert m1.snapshot()["gauges"]["metrics_http_port"] == taken
        s2 = m2.start_http_server(taken)    # in use: must not raise
        bound = s2.server_address[1]
        assert bound not in (0, taken)
        assert m2.snapshot()["gauges"]["metrics_http_port"] == bound
        with urllib.request.urlopen(
                f"http://127.0.0.1:{bound}/metrics.json",
                timeout=5.0) as resp:
            snap = json.loads(resp.read())
        assert snap["gauges"]["metrics_http_port"] == bound
    finally:
        s1.shutdown()
        if s2 is not None:
            s2.shutdown()


# -- registry ---------------------------------------------------------------


def test_registry_heartbeat_lifecycle_and_eviction():
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=0.25, dead_after=0.6,
                          evict_after=1.5, sweep_interval=0.05).start()
    try:
        sock = wire.connect(reg.addr)
        wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.1:7",
                             "capacity": 4}, token)
        assert _wait(lambda: len(reg.alive()) == 1)
        wire.send_msg(sock, {"op": "heartbeat", "addr": "10.0.0.1:7",
                             "outstanding": 3}, token)
        assert _wait(lambda: reg.alive() and reg.alive()[0].outstanding == 3)
        # Stop heartbeating (socket stays open): alive -> draining ->
        # dead -> evicted on the sweep timeouts alone.
        assert _wait(lambda: any(r["state"] == DRAINING
                                 for r in reg.snapshot()), timeout=2.0)
        assert _wait(lambda: any(r["state"] == DEAD
                                 for r in reg.snapshot()), timeout=2.0)
        assert _wait(lambda: not reg.snapshot(), timeout=3.0)
        # A heartbeat after eviction re-registers from scratch.
        wire.send_msg(sock, {"op": "heartbeat", "addr": "10.0.0.1:7",
                             "capacity": 4}, token)
        assert _wait(lambda: len(reg.alive()) == 1)
        sock.close()
    finally:
        reg.stop()


def test_registry_heartbeat_eof_marks_dead_immediately():
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=5.0, dead_after=10.0,
                          evict_after=20.0, sweep_interval=0.05).start()
    try:
        sock = wire.connect(reg.addr)
        wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.2:7"}, token)
        assert _wait(lambda: len(reg.alive()) == 1)
        sock.close()    # the process died: its heartbeat conn goes EOF
        # Dead well before the 10s heartbeat timeout could fire.
        assert _wait(lambda: [r["state"] for r in reg.snapshot()] == [DEAD],
                     timeout=2.0)
    finally:
        reg.stop()


def test_registry_rejects_wrong_token_and_drain_excludes():
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=30.0, dead_after=60.0,
                          sweep_interval=0.05).start()
    try:
        bad = wire.connect(reg.addr)
        wire.send_msg(bad, {"op": "hello", "addr": "evil:1"},
                      "wrong-token")
        good = wire.connect(reg.addr)
        wire.send_msg(good, {"op": "hello", "addr": "10.0.0.3:7"}, token)
        assert _wait(lambda: len(reg.alive()) == 1)
        assert reg.alive()[0].addr == "10.0.0.3:7"   # evil never joined
        wire.send_msg(good, {"op": "drain", "addr": "10.0.0.3:7"}, token)
        assert _wait(lambda: not reg.alive())        # draining != routable
        assert reg.snapshot()[0]["state"] == DRAINING
        bad.close()
        good.close()
    finally:
        reg.stop()


# -- stub replicas (no JAX) -------------------------------------------------


def _stub_replica(token, registry_addr, tokens, delay=0.0):
    """A ReplicaServer whose handler replies canned tokens — the fleet
    path minus the model."""

    def handler(msg, reply):
        def work():
            if delay:
                time.sleep(delay)
            reply({"op": "completion", "id": msg.get("id"),
                   "tokens": list(tokens), "ttft_ms": 1.0,
                   "total_ms": 2.0})

        threading.Thread(target=work, daemon=True).start()

    return ReplicaServer(handler, token=token, capacity=4,
                         registry_addr=registry_addr,
                         heartbeat_interval=0.05).start()


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


def test_mux_connection_concurrent_calls_and_timeout(stub_fleet):
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(7,), delay=0.05))
    mux = MuxConnection(servers[0].addr, token)
    out = [None] * 8

    def one(i):
        out[i] = mux.call({"op": "generate", "prompt": [i]}, timeout=10.0)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    assert all(r["tokens"] == [7] for r in out)
    with pytest.raises(CallTimeout):
        # Slow handler vs a tiny deadline: the call times out cleanly.
        mux.call({"op": "generate", "prompt": [0]}, timeout=0.01)
    mux.close()
    with pytest.raises(ConnectionLost):
        mux.call({"op": "generate"}, timeout=1.0)


def test_router_balances_across_replicas(stub_fleet):
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(1,), delay=0.2))
    servers.append(_stub_replica(token, reg.addr, tokens=(2,), delay=0.2))
    assert reg.wait_for(2, timeout=5.0)
    router = Router(reg, FleetMetrics(), token=token)
    try:
        results = [None] * 6

        def one(i):
            results[i] = router.route({"op": "generate", "prompt": [i]})

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
            time.sleep(0.02)    # let each call register its slot so the
            # next pick() sees real outstanding counts (p2c balances on
            # them)
        for t in threads:
            t.join(timeout=10.0)
        served_by = {tuple(r["tokens"]) for r in results}
        # Least-outstanding p2c must use BOTH replicas for 6 concurrent
        # slow requests — a single-replica pile-up is a routing bug.
        assert served_by == {(1,), (2,)}
    finally:
        router.close()


def test_router_retries_on_dead_replica_and_gives_up(stub_fleet):
    token, reg, servers = stub_fleet
    # A "replica" that is just a closed port, registered FIRST (ties in
    # least-outstanding break by registration order, so the first route
    # deterministically tries it).
    dead_sock = wire.bind_ephemeral("127.0.0.1")
    dead_addr = wire.sock_addr(dead_sock, advertise_host="127.0.0.1")
    dead_sock.close()
    feeder = wire.connect(reg.addr)
    wire.send_msg(feeder, {"op": "hello", "addr": dead_addr}, token)
    assert _wait(lambda: len(reg.alive()) == 1)
    servers.append(_stub_replica(token, reg.addr, tokens=(9,)))
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        reply = router.route({"op": "generate", "prompt": [1]})
        assert reply["tokens"] == [9]           # failover to the survivor
        assert metrics.get("retries") >= 1
        assert _wait(lambda: [r["state"] for r in reg.snapshot()
                              if r["addr"] == dead_addr] == [DEAD])
        # Kill the survivor too: the bounded retry loop must FAIL, not
        # hang.
        servers[0].stop()
        reg.mark_dead(servers[0].addr)
        with pytest.raises(RoutingError):
            router.route({"op": "generate", "prompt": [2]})
    finally:
        router.close()
        feeder.close()


def test_router_retries_on_mid_request_eof(stub_fleet):
    token, reg, servers = stub_fleet

    # A replica that accepts, reads the request, then slams the
    # connection — the shape of a process dying mid-stream.
    flaky_listen = wire.bind_ephemeral("127.0.0.1")
    flaky_addr = wire.sock_addr(flaky_listen, advertise_host="127.0.0.1")

    def flaky():
        while True:
            try:
                conn, _ = flaky_listen.accept()
            except OSError:
                return
            try:
                conn.recv(65536)
                conn.close()
            except OSError:
                pass

    threading.Thread(target=flaky, daemon=True).start()
    feeder = wire.connect(reg.addr)
    wire.send_msg(feeder, {"op": "hello", "addr": flaky_addr}, token)
    assert _wait(lambda: len(reg.alive()) == 1)
    servers.append(_stub_replica(token, reg.addr, tokens=(5,)))
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        reply = router.route({"op": "generate", "prompt": [1]})
        assert reply["tokens"] == [5]
        assert metrics.get("retries") >= 1
    finally:
        router.close()
        feeder.close()
        flaky_listen.close()


def test_gateway_over_stub_replicas(stub_fleet):
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(4, 2)))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2).start()
    try:
        client = FleetClient(gw.addr, token)
        out = client.generate([1, 2, 3], max_new_tokens=2)
        assert out["tokens"] == [4, 2]
        snap = client.metrics()
        assert snap["counters"]["received"] == 1
        assert snap["counters"]["admitted"] == 1
        assert snap["counters"]["completed"] == 1
        assert snap["gauges"]["replicas_alive"] == 1
        # Unauthenticated clients never reach the handler.
        intruder = wire.connect(gw.addr)
        wire.send_msg(intruder, {"op": "generate"}, "wrong-token")
        with pytest.raises((OSError, wire.WireError)):
            for _ in range(10):
                wire.recv_msg(intruder, "wrong-token")
        intruder.close()
        client.close()
    finally:
        gw.stop()


# -- prefix-affinity routing (stub replicas, no JAX) ------------------------


def _summary_for(prompt, page=16):
    """What a replica caching ``prompt``'s full chunks would advertise."""
    from tfmesos_tpu import prefixhash

    return {"page": page, "first": page, "seed": "",
            "hashes": [d.hex()
                       for d in prefixhash.prompt_digests(prompt, page)]}


def test_replica_heartbeat_carries_prefix_summary(stub_fleet):
    """ReplicaServer's extra_info rides every heartbeat and lands on
    the registry's ReplicaInfo.prefix — the channel prefix-affinity
    routing reads."""
    token, reg, servers = stub_fleet
    summ = _summary_for(list(range(32)))
    server = ReplicaServer(lambda msg, reply: reply({}), token=token,
                           capacity=4, registry_addr=reg.addr,
                           heartbeat_interval=0.05,
                           extra_info=lambda: {"prefix_cache": summ})
    servers.append(server.start())
    assert _wait(lambda: reg.alive()
                 and reg.alive()[0].prefix == summ)
    assert reg.alive()[0].capacity == 4


def test_router_prefix_affinity_longest_match_and_fallback(stub_fleet):
    """pick(prompt=...) prefers the replica advertising the longest
    chunk-chain match, falls back to p2c when nothing matches, and
    skips a saturated favorite instead of piling onto it."""
    token, reg, servers = stub_fleet
    prompt_a = list(range(100, 148))            # 3 chunks of 16
    prompt_b = list(range(500, 532))            # disjoint prefix
    # Replica "deep" caches all of prompt_a, "shallow" only 1 chunk.
    deep = ReplicaServer(
        lambda m, r: r({}), token=token, capacity=4,
        registry_addr=reg.addr, heartbeat_interval=0.05,
        extra_info=lambda: {"prefix_cache": _summary_for(prompt_a)})
    shallow_summ = _summary_for(prompt_a[:16])
    shallow = ReplicaServer(
        lambda m, r: r({}), token=token, capacity=4,
        registry_addr=reg.addr, heartbeat_interval=0.05,
        extra_info=lambda: {"prefix_cache": shallow_summ})
    servers.extend([deep.start(), shallow.start()])
    assert _wait(lambda: len([r for r in reg.alive()
                              if r.prefix is not None]) == 2)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    try:
        for _ in range(6):      # deterministic, not a p2c coin flip
            assert router.pick(prompt=prompt_a) == deep.addr
        assert metrics.get("affinity_hits") == 6
        # The shallow replica still wins prompts only IT has.
        assert router.pick(prompt=prompt_a[:16]) in (deep.addr,
                                                     shallow.addr)
        # No replica caches prompt_b: p2c fallback, counted as a miss.
        before = metrics.get("affinity_misses")
        assert router.pick(prompt=prompt_b) in (deep.addr, shallow.addr)
        assert metrics.get("affinity_misses") == before + 1
        # Prompts shorter than one chunk can never match.
        assert router.pick(prompt=prompt_a[:8]) in (deep.addr,
                                                    shallow.addr)
        # Saturated favorite: outstanding >= capacity diverts to p2c
        # over the remaining replicas.
        real_outstanding = router.outstanding
        router.outstanding = (
            lambda addr: 4 if addr == deep.addr else 0)
        assert router.pick(prompt=prompt_a) == shallow.addr
        router.outstanding = real_outstanding
        # Excluded favorite (failed once): affinity respects exclude.
        assert router.pick(exclude=[deep.addr],
                           prompt=prompt_a) == shallow.addr
    finally:
        router.close()


def test_router_affinity_ignores_malformed_summaries(stub_fleet):
    token, reg, servers = stub_fleet
    bad = ReplicaServer(
        lambda m, r: r({}), token=token, capacity=2,
        registry_addr=reg.addr, heartbeat_interval=0.05,
        extra_info=lambda: {"prefix_cache": {"page": "x",
                                             "hashes": ["zz"]}})
    ok = ReplicaServer(lambda m, r: r({}), token=token, capacity=2,
                       registry_addr=reg.addr, heartbeat_interval=0.05)
    servers.extend([bad.start(), ok.start()])
    assert _wait(lambda: len(reg.alive()) == 2)
    router = Router(reg, FleetMetrics(), token=token)
    try:
        # Malformed advertisement must not break routing — p2c covers.
        assert router.pick(prompt=list(range(32))) in (bad.addr, ok.addr)
    finally:
        router.close()


# -- disaggregated routing (stub replicas, no JAX) --------------------------


def _stub_prefill_replica(token, registry_addr, first_token=7,
                          body=b"\xaa" * 2048, headroom=100):
    """A prefill-role ReplicaServer: replies to the prefill op with one
    raw KV frame; refuses generate like the real prefill handler."""

    def handler(msg, reply):
        if isinstance(msg, wire.RawFrame) or msg.get("op") != "prefill":
            reply({"op": "error", "id": (msg.meta if isinstance(
                msg, wire.RawFrame) else msg).get("id"),
                "kind": "bad_request", "error": "prefill role"})
            return
        reply(wire.RawFrame(
            {"op": "prefilled", "id": msg.get("id"),
             "first_token": first_token, "pos": len(msg["prompt"]),
             "prefill_ms": 1.0}, body))

    return ReplicaServer(
        handler, token=token, capacity=4, registry_addr=registry_addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "prefill",
                            "kv_headroom": headroom}).start()


def _stub_decode_replica(token, registry_addr, bodies=None, headroom=50):
    """A decode-role ReplicaServer: accepts only RAW generate frames
    (the KV import) and echoes the artifact's first token."""
    bodies = bodies if bodies is not None else []

    def handler(msg, reply):
        if not isinstance(msg, wire.RawFrame):
            reply({"op": "error", "id": msg.get("id"),
                   "kind": "bad_request",
                   "error": "decode stub wants raw frames"})
            return
        bodies.append(msg.body)
        reply({"op": "completion", "id": msg.meta.get("id"),
               "tokens": [msg.meta["first_token"], 2, 3],
               "ttft_ms": 0.5, "total_ms": 9.5})

    server = ReplicaServer(
        handler, token=token, capacity=4, registry_addr=registry_addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "decode",
                            "kv_headroom": headroom}).start()
    return server, bodies


def test_registry_role_and_headroom_fields(stub_fleet):
    """role / kv_headroom heartbeat fields land on ReplicaInfo and in
    the per-role summary (counts + aggregate outstanding)."""
    token, reg, servers = stub_fleet
    sock = wire.connect(reg.addr)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:1",
                         "capacity": 4, "role": "decode",
                         "kv_headroom": 42, "outstanding": 3}, token)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:2",
                         "capacity": 4, "role": "prefill",
                         "kv_headroom": 17}, token)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:3",
                         "capacity": 4}, token)
    assert _wait(lambda: len(reg.alive()) == 3)
    by_addr = {r.addr: r for r in reg.alive()}
    assert by_addr["10.0.0.9:1"].role == "decode"
    assert by_addr["10.0.0.9:1"].kv_headroom == 42
    assert by_addr["10.0.0.9:2"].role == "prefill"
    assert by_addr["10.0.0.9:3"].role == "unified"   # never advertised
    summary = reg.role_summary()
    assert summary["decode"]["alive"] == 1
    assert summary["decode"]["outstanding"] == 3
    assert summary["decode"]["kv_headroom"] == 42
    assert summary["prefill"]["alive"] == 1
    assert summary["unified"]["alive"] == 1
    # A malformed kv_headroom costs the field, never the beat.
    wire.send_msg(sock, {"op": "heartbeat", "addr": "10.0.0.9:1",
                         "kv_headroom": "lots", "role": "bogus"}, token)
    time.sleep(0.1)
    assert {r.addr for r in reg.alive()} >= {"10.0.0.9:1"}
    assert by_addr["10.0.0.9:1"].role == "decode"
    sock.close()


def test_registry_device_field_feeds_the_devices_gauge(stub_fleet):
    """What a replica says it runs on ({platform, kind, id, chips}) rides
    its beats into ``device_summary`` — keyed by task node, or by address
    for a replica launched outside the scheduler — and a malformed field
    costs the field, never the beat."""
    token, reg, servers = stub_fleet
    sock = wire.connect(reg.addr)
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "id": 0, "chips": "2"}
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:1", "capacity": 4,
                         "node": "replica:0", "device": tpu}, token)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:2", "capacity": 4,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "id": 0}}, token)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.0.9:3",
                         "capacity": 4}, token)
    assert _wait(lambda: len(reg.alive()) == 3)
    assert reg.device_summary() == {
        "replica:0": tpu,
        "10.0.0.9:2": {"platform": "cpu", "kind": "cpu", "id": 0,
                       "chips": ""}}
    wire.send_msg(sock, {"op": "heartbeat", "addr": "10.0.0.9:1",
                         "device": {"platform": "tpu", "id": "x"}}, token)
    time.sleep(0.1)
    assert reg.device_summary()["replica:0"] == tpu
    view = {r["addr"]: r for r in reg.registry_view()["replicas"]}
    assert view["10.0.0.9:1"]["device"] == tpu
    assert "device" not in view["10.0.0.9:3"]
    sock.close()


def test_registry_spec_field_and_fleet_acceptance_rate(stub_fleet):
    """The spec observability satellite, jax-free: the ``spec``
    heartbeat field lands on ReplicaInfo, and spec_summary() (the
    gateway's ``spec`` gauge) aggregates the fleet-wide draft
    acceptance rate from the per-replica sums — (committed −
    row_rounds) / (row_rounds × n_draft), so replicas weigh by their
    actual traffic.  A draft-less fleet omits the rate entirely (no
    poisoned gauge), and a malformed field costs the field, never the
    beat."""
    token, reg, servers = stub_fleet
    assert reg.spec_summary() == {"replicas": 0, "rounds": 0,
                                  "committed": 0}
    sock = wire.connect(reg.addr)
    # Replica 1: 10 row-rounds x 4 proposals, 30 committed -> 20/40.
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.1.1:1",
                         "capacity": 4,
                         "spec": {"acceptance_rate": 0.5, "rounds": 6,
                                  "row_rounds": 10, "committed": 30,
                                  "n_draft": 4}}, token)
    # Replica 2: 10 x 4, 50 committed -> 40/40 (perfect draft).
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.1.1:2",
                         "capacity": 4,
                         "spec": {"acceptance_rate": 1.0, "rounds": 2,
                                  "row_rounds": 10, "committed": 50,
                                  "n_draft": 4}}, token)
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.1.1:3",
                         "capacity": 4}, token)      # no draft
    assert _wait(lambda: len(reg.alive()) == 3)
    by_addr = {r.addr: r for r in reg.alive()}
    assert by_addr["10.0.1.1:1"].spec["n_draft"] == 4
    assert by_addr["10.0.1.1:3"].spec is None
    agg = reg.spec_summary()
    assert agg["replicas"] == 2
    assert agg["rounds"] == 8 and agg["committed"] == 80
    assert agg["acceptance_rate"] == 0.75       # (80 - 20) / 80
    # Malformed spec field: field lost, beat kept, aggregate intact.
    wire.send_msg(sock, {"op": "heartbeat", "addr": "10.0.1.1:1",
                         "spec": "nope"}, token)
    time.sleep(0.1)
    assert {r.addr for r in reg.alive()} >= {"10.0.1.1:1"}
    assert reg.spec_summary()["replicas"] == 2
    # ATOMIC folding: a replica advertising committed counts but a
    # malformed row_rounds must contribute NOTHING to the rate — a
    # numerator without its denominator would inflate the gauge past
    # 1.0 (the mixed-version-fleet shape).
    wire.send_msg(sock, {"op": "hello", "addr": "10.0.1.1:4",
                         "capacity": 4,
                         "spec": {"rounds": 9, "committed": 500,
                                  "row_rounds": "lots",
                                  "n_draft": 4}}, token)
    assert _wait(lambda: len(reg.alive()) == 4)
    agg = reg.spec_summary()
    assert agg["replicas"] == 3 and agg["committed"] == 80
    assert agg["acceptance_rate"] == 0.75       # unchanged
    sock.close()


def test_disagg_stub_round_trip(stub_fleet):
    """The tox-lint disagg smoke: gateway → prefill replica → raw-frame
    KV transfer → decode replica → completion, all stubbed (no JAX).
    The completion's TTFT is the router-measured prefill phase, its
    decode_ms the decode replica's own turnaround, and the KV bytes
    are counted."""
    token, reg, servers = stub_fleet
    servers.append(_stub_prefill_replica(token, reg.addr))
    dec, bodies = _stub_decode_replica(token, reg.addr)
    servers.append(dec)
    assert _wait(lambda: sorted(r.role for r in reg.alive())
                 == ["decode", "prefill"])
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2).start()
    try:
        client = FleetClient(gw.addr, token)
        out = client.generate([1, 2, 3], max_new_tokens=3)
        assert out["tokens"] == [7, 2, 3]
        assert out["decode_ms"] == pytest.approx(9.0)
        assert out["ttft_ms"] > 0 and out["total_ms"] >= out["ttft_ms"]
        assert bodies == [b"\xaa" * 2048]
        snap = client.metrics()
        c = snap["counters"]
        assert c["disagg_prefills"] == 1 and c["disagg_decodes"] == 1
        assert c["disagg_requests"] == 1
        assert c["kv_transfer_bytes"] == 2048
        assert c["completed"] == 1
        assert snap["histograms"]["queue_wait_ms"]["count"] == 1
        roles = snap["gauges"]["roles"]
        assert roles["prefill"]["alive"] == 1
        assert roles["decode"]["alive"] == 1
        client.close()
    finally:
        gw.stop()


def test_gateway_rejects_misdirected_raw_frame(stub_fleet):
    """A raw frame sent to the GATEWAY (raw frames are replica-to-
    replica transport) fails FAST: the public port's framer rejects
    the raw bit at the length prefix — keeping its pre-auth buffering
    bound at MAX_FRAME — and drops the connection, so the caller gets
    ConnectionLost promptly, never a hang until its timeout."""
    token, reg, servers = stub_fleet
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=1).start()
    try:
        mux = MuxConnection(gw.addr, token)
        with pytest.raises(ConnectionLost):
            mux.call_raw({"op": "generate", "prompt": [1, 2]},
                         b"\x00" * 64, timeout=5.0)
        mux.close()
    finally:
        gw.stop()


def test_disagg_falls_back_to_unified_when_tier_empty(stub_fleet):
    """With a prefill tier but NO decode tier (and vice versa) the
    request falls back to the unified replica — existing deployments
    are unaffected by role-aware routing."""
    token, reg, servers = stub_fleet
    servers.append(_stub_prefill_replica(token, reg.addr))
    servers.append(_stub_replica(token, reg.addr, tokens=(5,)))
    assert _wait(lambda: len(reg.alive()) == 2)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    try:
        out = router.route({"op": "generate", "prompt": [1, 2],
                            "max_new_tokens": 1})
        assert out["tokens"] == [5]         # the unified replica served
        assert metrics.get("disagg_prefills") == 0
        # A LONE tier is a fallback (a tier is down); it is counted.
        assert metrics.get("disagg_fallback") == 1
    finally:
        router.close()


def test_disagg_internal_error_retries_then_falls_back_to_unified(
        stub_fleet):
    """A transient replica-side failure (kind: internal) must NOT be
    returned to the client while a healthy unified tier exists — only
    bad_request is deterministic.  Both phases: a failing prefill
    replica and a failing decode replica each end at the unified
    fallback."""
    token, reg, servers = stub_fleet

    def broken(msg, reply):
        head = msg.meta if isinstance(msg, wire.RawFrame) else msg
        reply({"op": "error", "id": head.get("id"), "kind": "internal",
               "error": "transient device failure"})

    servers.append(ReplicaServer(
        broken, token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "prefill", "kv_headroom": 9}).start())
    dec, _ = _stub_decode_replica(token, reg.addr)
    servers.append(dec)
    servers.append(_stub_replica(token, reg.addr, tokens=(6,)))
    assert _wait(lambda: len(reg.alive()) == 3)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [1, 2],
                            "max_new_tokens": 1})
        assert out["tokens"] == [6]         # unified served, not the error
        assert metrics.get("disagg_fallback") >= 1
    finally:
        router.close()
    # Decode-phase internal errors fall back the same way.
    servers[0].stop()
    reg.mark_dead(servers[0].addr)
    servers[0] = _stub_prefill_replica(token, reg.addr)
    dec.stop()
    reg.mark_dead(dec.addr)
    servers[1] = ReplicaServer(
        broken, token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "decode", "kv_headroom": 9}).start()
    assert _wait(lambda: sorted(r.role for r in reg.alive())
                 == ["decode", "prefill", "unified"])
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [3],
                            "max_new_tokens": 1})
        assert out["tokens"] == [6]
        assert metrics.get("disagg_prefills") == 1  # prefill ran ONCE:
        assert metrics.get("disagg_fallback") >= 1  # no wasted re-prefill
    finally:
        router.close()


def test_disagg_decode_bad_request_falls_back_to_unified(stub_fleet):
    """A decode-tier bad_request (the tiers disagree about the KV
    artifact — e.g. mismatched --page-size) is deterministic for the
    ARTIFACT, not the request: the router falls back to the unified
    tier instead of failing the client outright."""
    token, reg, servers = stub_fleet
    servers.append(_stub_prefill_replica(token, reg.addr))

    def rejecting(msg, reply):
        head = msg.meta if isinstance(msg, wire.RawFrame) else msg
        reply({"op": "error", "id": head.get("id"),
               "kind": "bad_request",
               "error": "KV artifact page_size 8 does not match 16"})

    servers.append(ReplicaServer(
        rejecting, token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "decode", "kv_headroom": 9}).start())
    servers.append(_stub_replica(token, reg.addr, tokens=(6,)))
    assert _wait(lambda: len(reg.alive()) == 3)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [1, 2],
                            "max_new_tokens": 1})
        assert out["tokens"] == [6]         # unified served the request
        assert metrics.get("disagg_fallback") >= 1
    finally:
        router.close()


def test_mux_raw_encode_rejection_spares_the_connection(stub_fleet):
    """A call_raw whose meta overflows MAX_RAW_META is rejected at
    encode time, BEFORE any bytes hit the socket: the caller gets the
    WireError, the slot is released (outstanding returns to 0), and
    the connection keeps serving — an unshippable payload must never
    read as a dead peer."""
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(4,)))
    mux = MuxConnection(servers[0].addr, token)
    try:
        with pytest.raises(wire.WireError):
            mux.call_raw({"op": "generate",
                          "pad": "x" * (wire.MAX_RAW_META + 1)},
                         b"", timeout=5.0)
        assert mux.outstanding == 0         # the slot did not leak
        assert not mux.closed
        out = mux.call({"op": "generate", "prompt": [1]}, timeout=10.0)
        assert out["tokens"] == [4]
    finally:
        mux.close()


def test_disagg_oversized_artifact_meta_falls_back_to_unified(
        stub_fleet):
    """A KV artifact whose decode meta (prefill manifest + prompt)
    overflows the raw bounds cannot ship to ANY decode replica: the
    encode-time WireError is deterministic for the ARTIFACT, so the
    router falls back to unified without dropping the healthy decode
    link, marking the replica dead, or re-shipping the doomed bytes."""
    token, reg, servers = stub_fleet
    pad = "x" * (wire.MAX_RAW_META - 2048)

    def padded_prefill(msg, reply):
        reply(wire.RawFrame(
            {"op": "prefilled", "id": msg.get("id"), "first_token": 7,
             "pos": len(msg["prompt"]), "prefill_ms": 1.0, "pad": pad},
            b"\xaa" * 64))

    servers.append(ReplicaServer(
        padded_prefill, token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"role": "prefill",
                            "kv_headroom": 9}).start())
    dec, bodies = _stub_decode_replica(token, reg.addr)
    servers.append(dec)
    servers.append(_stub_replica(token, reg.addr, tokens=(6,)))
    assert _wait(lambda: len(reg.alive()) == 3)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        # The prompt's tokens push the decode meta past MAX_RAW_META.
        out = router.route({"op": "generate", "prompt": [7] * 6000,
                            "max_new_tokens": 1})
        assert out["tokens"] == [6]         # unified served the request
        assert not bodies                   # nothing reached the decode tier
        assert metrics.get("disagg_fallback") >= 1
        # No retry churn: the artifact was not re-sent within the tier,
        # and the healthy decode replica was never marked dead.
        assert metrics.get("retries") == 0
        assert any(r.addr == dec.addr for r in reg.alive())
    finally:
        router.close()


def test_disagg_decode_failure_retries_then_falls_back(stub_fleet):
    """A dead decode replica: the handoff retries onto a live one; with
    no decode replica left the request falls back to unified."""
    token, reg, servers = stub_fleet
    servers.append(_stub_prefill_replica(token, reg.addr))
    # A decode-role "replica" that is just a closed port, with MORE
    # advertised headroom so the scorer prefers it first.
    dead_sock = wire.bind_ephemeral("127.0.0.1")
    dead_addr = wire.sock_addr(dead_sock, advertise_host="127.0.0.1")
    dead_sock.close()
    feeder = wire.connect(reg.addr)
    wire.send_msg(feeder, {"op": "hello", "addr": dead_addr,
                           "role": "decode", "kv_headroom": 10_000},
                  token)
    dec, bodies = _stub_decode_replica(token, reg.addr, headroom=5)
    servers.append(dec)
    assert _wait(lambda: len(reg.alive()) == 3)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [1, 2],
                            "max_new_tokens": 3})
        assert out["tokens"] == [7, 2, 3]   # live decode replica served
        assert metrics.get("retries") >= 1
        # Now kill the last decode replica: disagg cannot complete and
        # there is no unified tier -> explicit RoutingError, no hang.
        dec.stop()
        reg.mark_dead(dec.addr)
        reg.mark_dead(dead_addr)
        with pytest.raises(RoutingError):
            router.route({"op": "generate", "prompt": [3],
                          "max_new_tokens": 1})
    finally:
        router.close()
        feeder.close()


def test_plain_generate_never_routes_to_role_replicas(stub_fleet):
    """pick() (the unified path) excludes prefill- and decode-role
    replicas: the role split must not leak plain prefill work into the
    decode tier or generates into the prefill tier."""
    token, reg, servers = stub_fleet
    servers.append(_stub_prefill_replica(token, reg.addr))
    dec, _ = _stub_decode_replica(token, reg.addr)
    servers.append(dec)
    servers.append(_stub_replica(token, reg.addr, tokens=(8,)))
    assert _wait(lambda: len(reg.alive()) == 3)
    router = Router(reg, FleetMetrics(), token=token)
    try:
        for _ in range(8):
            assert router.pick() == servers[-1].addr
        assert router.pick_prefill() == servers[0].addr
        assert router.pick_decode() == dec.addr
    finally:
        router.close()


def test_pick_decode_prefers_headroom_and_skips_saturated(stub_fleet):
    token, reg, servers = stub_fleet
    feeder = wire.connect(reg.addr)
    wire.send_msg(feeder, {"op": "hello", "addr": "10.1.1.1:1",
                           "role": "decode", "kv_headroom": 5,
                           "capacity": 4}, token)
    wire.send_msg(feeder, {"op": "hello", "addr": "10.1.1.1:2",
                           "role": "decode", "kv_headroom": 500,
                           "capacity": 4}, token)
    assert _wait(lambda: len(reg.alive()) == 2)
    router = Router(reg, FleetMetrics(), token=token)
    try:
        assert router.pick_decode() == "10.1.1.1:2"     # more headroom
        # Saturate the favorite: outstanding >= capacity diverts.
        real = router.outstanding
        router.outstanding = lambda a: 4 if a == "10.1.1.1:2" else 0
        assert router.pick_decode() == "10.1.1.1:1"
        router.outstanding = real
        assert router.pick_decode(
            exclude=["10.1.1.1:2"]) == "10.1.1.1:1"
    finally:
        router.close()
        feeder.close()


# -- the warming state (no JAX) ---------------------------------------------


def test_warming_replica_never_routed(stub_fleet):
    """A replica registered with ``status: warming`` is present in the
    table but invisible to EVERY router tier — unified, prefill, and
    decode picks all skip it — and flips routable the moment its beats
    drop the status (ReplicaServer.set_status(None) after warmup)."""
    token, reg, servers = stub_fleet
    warming = ReplicaServer(lambda m, r: r({"op": "completion"}),
                            token=token, capacity=4,
                            registry_addr=reg.addr,
                            heartbeat_interval=0.05,
                            status="warming").start()
    servers.append(warming)
    assert _wait(lambda: any(r["state"] == "warming"
                             for r in reg.snapshot()))
    router = Router(reg, FleetMetrics(), token=token)
    assert router.pick() is None            # warming != routable
    assert router.pick_prefill() is None
    assert router.pick_decode() is None
    assert reg.alive() == []
    # An alive peer takes ALL the traffic while the other warms.
    peer = _stub_replica(token, reg.addr, tokens=(3,))
    servers.append(peer)
    assert _wait(lambda: len(reg.alive()) == 1)
    for _ in range(8):
        assert router.pick() == peer.addr != warming.addr
    # Warmup returns: the replica flips itself alive by dropping the
    # status field — no registry-side action needed.
    warming.set_status(None)
    assert _wait(lambda: len(reg.alive()) == 2)
    assert _wait(lambda: router.pick(exclude=(peer.addr,))
                 == warming.addr)


def test_warming_role_tier_falls_back_like_empty(stub_fleet):
    """A role tier whose only member is warming behaves exactly like an
    EMPTY tier: the disaggregated path falls back to the unified tier
    (same rules as a missing tier) instead of waiting on the compile."""
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(8, 9)))
    # A warming prefill replica + an alive decode replica: the prefill
    # tier is effectively empty, so generate must take the unified path.
    pre = ReplicaServer(lambda m, r: None, token=token, capacity=4,
                        registry_addr=reg.addr, heartbeat_interval=0.05,
                        status="warming",
                        extra_info=lambda: {"role": "prefill"}).start()
    servers.append(pre)
    dec, _ = _stub_decode_replica(token, reg.addr)
    servers.append(dec)
    assert _wait(lambda: len(reg.alive()) == 2
                 and any(r["state"] == "warming" for r in reg.snapshot()))
    m = FleetMetrics()
    router = Router(reg, m, token=token)
    out = router.route({"op": "generate", "prompt": [1, 2],
                        "max_new_tokens": 2})
    assert out["tokens"] == [8, 9]          # unified served it
    assert m.get("disagg_fallback") == 1
    assert m.get("disagg_prefills") == 0    # warming tier never entered


def test_registry_warming_lifecycle_drain_beats_warming():
    """Direct wire-level state machine: warming on the hello, alive on
    the first status-free beat, and a drain announcement is terminal
    against LATE warming beats (an exiting replica must not re-enter
    the table through its own warmup) while a plain beat still
    self-heals."""
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=30.0, dead_after=60.0,
                          sweep_interval=0.05).start()
    try:
        sock = wire.connect(reg.addr)
        wire.send_msg(sock, {"op": "hello", "addr": "w:1", "capacity": 2,
                             "status": "warming"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["warming"])
        assert reg.alive() == [] and len(reg.warming()) == 1
        wire.send_msg(sock, {"op": "heartbeat", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["alive"])
        wire.send_msg(sock, {"op": "drain", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == [DRAINING])
        # Draining beats warming: the late warming beat refreshes
        # liveness but never revives the entry.
        wire.send_msg(sock, {"op": "heartbeat", "addr": "w:1",
                             "status": "warming"}, token)
        time.sleep(0.2)
        assert [r["state"] for r in reg.snapshot()] == [DRAINING]
        # A plain (routable) beat still self-heals — the existing
        # drain-then-revive semantics are unchanged.
        wire.send_msg(sock, {"op": "heartbeat", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["alive"])
        # And a drain against a WARMING replica drains it too.
        wire.send_msg(sock, {"op": "heartbeat", "addr": "w:1",
                             "status": "warming"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["warming"])
        wire.send_msg(sock, {"op": "drain", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == [DRAINING])
        sock.close()
    finally:
        reg.stop()


def test_registry_relaunch_on_reused_addr_shows_warming():
    """An announced drain dies with the process: once the entry is
    DEAD, a relaunched replica reusing the same addr that registers
    with ``status: warming`` must SHOW as warming (gauges, start()'s
    'still warming' diagnostic) — not stay pinned in the old process's
    dead/drained state for its whole compile."""
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=30.0, dead_after=60.0,
                          sweep_interval=0.05).start()
    try:
        sock = wire.connect(reg.addr)
        wire.send_msg(sock, {"op": "hello", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["alive"])
        # Old process announces a drain, then dies (router-observed).
        wire.send_msg(sock, {"op": "drain", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == [DRAINING])
        reg.mark_dead("w:1")
        assert [r["state"] for r in reg.snapshot()] == ["dead"]
        # Relaunch on the SAME addr: its warming hello must take.
        wire.send_msg(sock, {"op": "hello", "addr": "w:1",
                             "status": "warming"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["warming"])
        assert reg.alive() == [] and len(reg.warming()) == 1
        wire.send_msg(sock, {"op": "heartbeat", "addr": "w:1"}, token)
        assert _wait(lambda: [r["state"] for r in reg.snapshot()]
                     == ["alive"])
        sock.close()
    finally:
        reg.stop()


def test_registry_malformed_status_costs_field_not_beat():
    """A bogus ``status`` value defaults the state to alive and still
    counts as a beat — exactly like the other optional heartbeat
    fields (a flaky advertiser must not get a healthy replica marked
    dead)."""
    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=30.0, dead_after=60.0,
                          sweep_interval=0.05).start()
    try:
        sock = wire.connect(reg.addr)
        for bad in (42, "warm", None, ["warming"]):
            wire.send_msg(sock, {"op": "heartbeat", "addr": "m:1",
                                 "status": bad, "outstanding": 7}, token)
        assert _wait(lambda: reg.alive()
                     and reg.alive()[0].outstanding == 7)
        assert [r["state"] for r in reg.snapshot()] == ["alive"]
        sock.close()
    finally:
        reg.stop()


def test_fleet_server_replica_cmd_carries_warmup_flags():
    """FleetServer threads --warmup / --pipeline-depth into the Mode-B
    replica command line, so EVERY launch of that cmd — boot or a later
    elastic relaunch — re-warms before taking traffic."""
    import types

    from tfmesos_tpu.fleet.launcher import FleetServer

    fs = FleetServer(replicas=1, warmup=True, pipeline_depth=1)
    fs.registry = types.SimpleNamespace(addr="reg:1")
    cmd = fs._replica_cmd()
    assert "--warmup" in cmd.split()
    assert "--pipeline-depth 1" in cmd
    fs2 = FleetServer(replicas=1)
    fs2.registry = types.SimpleNamespace(addr="reg:1")
    cmd2 = fs2._replica_cmd()
    assert "--warmup" not in cmd2 and "--pipeline-depth" not in cmd2


# -- end to end: gateway + 2 LocalBackend-launched batcher replicas --------


N_E2E_REPLICAS = 2
E2E_ROWS = 4


@pytest.fixture(scope="module")
def fleet():
    """Gateway + registry + 2 tiny-model replicas launched as Mode-B
    tasks through LocalBackend (CPU subprocesses).  Replicas run the
    cross-request prefix cache, so every exactness assertion in this
    module also exercises warm-hit serving."""
    from tfmesos_tpu.fleet.launcher import FleetServer

    fs = FleetServer(replicas=N_E2E_REPLICAS, rows=E2E_ROWS, tiny=True,
                     max_len=64, page_size=16, prefill_bucket=16,
                     prefix_cache_pages=16,
                     # TWO front doors over the one registry/router
                     # view: every e2e assertion in this module also
                     # exercises the multi-gateway topology (clients
                     # carry both addrs and could fail over).  Workers
                     # are PER GATEWAY — 4+4 keeps total dispatch
                     # width at the single-gateway suite's 8 (the
                     # SIGKILL test's mass-failover debit is sized to
                     # the retry budget at that width).
                     gateways=2,
                     workers=4, max_queue=64, request_timeout=300.0,
                     start_timeout=240.0)
    fs.start()
    yield fs
    fs.stop()


@pytest.fixture(scope="module")
def tiny_offline():
    """The replicas' exact model (tiny_model is deterministic from its
    seed), plus the offline greedy reference continuation."""
    import jax.numpy as jnp

    from tfmesos_tpu.fleet.replica import tiny_model
    from tfmesos_tpu.models import transformer

    cfg, params = tiny_model(seed=0)

    def offline(prompt, max_new_tokens, stop_token=None):
        out = transformer.generate(
            cfg, params, jnp.asarray(np.asarray(prompt, np.int32)[None]),
            max_new_tokens, temperature=0.0, stop_token=stop_token)
        row = np.asarray(out)[0, len(prompt):].tolist()
        if stop_token is not None and stop_token in row:
            row = row[:row.index(stop_token) + 1]
        return row

    return cfg, offline


def _e2e_prompts(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        size=rng.randint(3, 16)).astype(np.int32)
            for _ in range(n)]


def test_fleet_serves_concurrent_requests_correctly(fleet, tiny_offline):
    """Acceptance: >= 16 concurrent requests through the gateway come
    back with the exact offline-greedy completions, and the metrics
    ledger balances."""
    cfg, offline = tiny_offline
    prompts = _e2e_prompts(cfg, 16, seed=1)
    wants = [2 + (i % 5) for i in range(16)]
    client = fleet.client(timeout=300.0)
    results = [None] * 16
    errors = []

    def one(i):
        try:
            results[i] = client.generate(prompts[i], wants[i])
        except Exception as e:   # collected, not raised mid-thread
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    assert not errors, errors
    assert all(not t.is_alive() for t in threads)
    for i in range(16):
        assert results[i]["tokens"] == offline(prompts[i], wants[i]), \
            f"request {i} diverged from offline generation"
        assert results[i]["ttft_ms"] >= 0.0
        assert results[i]["total_ms"] >= results[i]["ttft_ms"]
    snap = fleet.snapshot()
    c = snap["counters"]
    assert c["received"] == c["admitted"] + c.get("shed_queue", 0) + \
        c.get("shed_rate_limited", 0)
    assert c["admitted"] == c["completed"] + c.get("failed", 0)
    assert c["completed"] >= 16
    assert c.get("shed_queue", 0) == 0
    assert snap["histograms"]["ttft_ms"]["count"] == c["completed"]
    client.close()


def test_fleet_overload_sheds_explicitly(fleet, tiny_offline):
    """Acceptance: driving the ingress queue past its bound yields
    explicit Overloaded rejections — and never a hang.  Uses its own
    gateway (1 worker, queue bound 2) over the SAME live replicas."""
    cfg, _ = tiny_offline
    metrics = FleetMetrics()
    router = Router(fleet.registry, metrics, token=fleet.token,
                    request_timeout=300.0)
    adm = AdmissionController(max_queue=2)
    gw = Gateway(router, adm, metrics, token=fleet.token,
                 workers=1).start()
    prompts = _e2e_prompts(cfg, 32, seed=2)
    client = FleetClient(gw.addr, fleet.token, timeout=300.0)
    done, shed, failures = [], [], []

    def one(i):
        try:
            done.append(client.generate(prompts[i], 4))
        except Overloaded:
            shed.append(i)
        except Exception as e:
            failures.append((i, e))

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        assert all(not t.is_alive() for t in threads), "a request hung"
        assert not failures, failures
        assert len(done) + len(shed) == 32
        assert shed, "queue bound 2 with 1 worker must shed a 32-burst"
        assert done, "some requests must still be served while shedding"
        c = metrics.snapshot()["counters"]
        assert c["received"] == 32
        assert c["admitted"] == len(done)
        assert c["shed_queue"] == len(shed)
        assert c["completed"] == len(done)
        client.close()
    finally:
        gw.stop()


def test_fleet_prefix_affinity_end_to_end(fleet, tiny_offline):
    """Acceptance: shared-system-prompt requests through the live fleet
    (a) come back exactly equal to offline generation even when served
    from WARM cached pages, (b) lead replicas to advertise their cache
    summaries on heartbeats, and (c) get steered by prefix-affinity
    routing (affinity_hits counts it)."""
    cfg, offline = tiny_offline
    rng = np.random.RandomState(11)
    system = rng.randint(0, cfg.vocab_size, size=32).astype(np.int32)
    prompts = [np.concatenate(
                   [system, np.random.RandomState(40 + i).randint(
                       0, cfg.vocab_size, size=4).astype(np.int32)])
               for i in range(8)]
    client = fleet.client(timeout=300.0)
    # Prime: publishes the system prefix into some replica's cache...
    first = client.generate(prompts[0], 6)
    assert first["tokens"] == offline(prompts[0], 6)
    # ... whose summary must reach the registry on a heartbeat.
    assert _wait(lambda: any(
        isinstance(r.prefix, dict) and r.prefix.get("hashes")
        for r in fleet.registry.alive()), timeout=30.0), \
        "no replica advertised a prefix-cache summary"
    results = [None] * 8
    errors = []

    def one(i):
        try:
            results[i] = client.generate(prompts[i], 6)
        except Exception as e:
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    assert not errors, errors
    for i in range(8):
        assert results[i]["tokens"] == offline(prompts[i], 6), \
            f"warm request {i} diverged from offline generation"
    c = fleet.snapshot()["counters"]
    assert c.get("affinity_hits", 0) >= 1, \
        "prefix-affinity routing never fired"
    client.close()


def test_fleet_drain_migration_no_lost_requests(fleet, tiny_offline):
    """e2e drain-migrate-kill slice over the live fixture fleet: pin a
    control-plane drain on the replica that actually has work in
    flight, ask it to migrate — every request still completes with the
    EXACT offline-greedy stream (resumed mid-stream on the survivor, or
    deterministically re-run), zero failures.  The drain is released
    afterwards so the fixture fleet is unchanged for later tests."""
    cfg, offline = tiny_offline
    prompts = _e2e_prompts(cfg, 6, seed=17)
    # Long decodes (but still within the 64-position budget for the
    # longest prompt): after a warm module run a 24-token request could
    # FINISH inside the observe->drain->migrate window, leaving the
    # migrate nothing to move — the work must comfortably outlive that
    # window for the export path to be deterministic, not a coin flip.
    wants = [36 + (i % 4) for i in range(6)]
    client = fleet.client(timeout=300.0)
    for p in prompts[:2]:                   # compiles off the hot window
        client.generate(p, 2)
    results = [None] * 6
    errors = []

    def one(i):
        try:
            results[i] = client.generate(prompts[i], wants[i],
                                         timeout=300.0)
        except Exception as e:              # collected, not raised
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    victim = None
    try:
        for t in threads:
            t.start()
        # The victim must be a replica with SEVERAL router-visible
        # in-flight requests (>= 2, not just the first to hit the
        # wire), or the migration may race their completions and have
        # nothing to move.
        assert _wait(lambda: any(
            fleet.router.outstanding(r.addr) >= 2
            for r in fleet.registry.alive()), timeout=30.0)
        victim = max(fleet.registry.alive(),
                     key=lambda r: fleet.router.outstanding(r.addr)).addr
        assert fleet.registry.begin_drain(victim, pinned=True)
        assert fleet.request_migration(victim)
    finally:
        for t in threads:
            t.join(timeout=300.0)
        if victim is not None:
            # Restore the fixture even when an assert below fails: a
            # still-pinned drain would cascade into every later test
            # in this module (they expect N_E2E_REPLICAS routable).
            fleet.registry.clear_drain(victim)
    assert not errors, errors
    assert all(not t.is_alive() for t in threads)
    for i in range(6):
        assert results[i]["tokens"] == offline(prompts[i], wants[i]), \
            f"request {i} diverged across the migration"
    c = fleet.snapshot()["counters"]
    assert c.get("migrations_requested", 0) >= 1
    # The victim actually handed work back, and nothing was failed.
    assert c.get("migration_exports", 0) >= 1
    assert c.get("migration_resumes", 0) \
        + c.get("migration_reruns", 0) >= 1
    # The drain was released in the finally; the victim's next beat
    # revives it.
    assert _wait(lambda: len(fleet.registry.alive()) == N_E2E_REPLICAS,
                 timeout=30.0)
    client.close()



def test_fleet_streaming_matches_offline_and_is_incremental(
        fleet, tiny_offline):
    """E2E per-token streaming on the real batcher: the streamed
    chunks concatenate to EXACTLY the offline-greedy completion, and
    they arrive incrementally (first chunk strictly before the final
    reply — the batcher flushes per decode block, not at the end)."""
    cfg, offline = tiny_offline
    prompt = _e2e_prompts(cfg, 1, seed=9)[0]
    want = 24
    client = fleet.client(timeout=300.0)
    chunks, stamps = [], []
    out = client.generate(
        prompt, want,
        on_tokens=lambda t: (chunks.append(list(t)),
                             stamps.append(time.monotonic())))
    t_done = time.monotonic()
    ref = offline(prompt, want)
    assert out["tokens"] == ref
    assert [t for c in chunks for t in c] == ref, \
        "streamed chunks diverged from the completion"
    assert len(chunks) >= 2, \
        f"tokens arrived in {len(chunks)} chunk(s) — not incremental"
    assert stamps[0] < t_done, "first chunk not ahead of completion"
    client.close()


def test_fleet_multi_gateway_both_doors_serve(fleet, tiny_offline):
    """Both front doors of the module fleet serve identical
    completions over the one shared registry/router view, and each
    hands out the full discovery set."""
    cfg, offline = tiny_offline
    prompt = _e2e_prompts(cfg, 1, seed=10)[0]
    assert len(fleet.addrs) == 2
    refs = offline(prompt, 4)
    for addr in fleet.addrs:
        client = FleetClient(addr, fleet.token, timeout=300.0)
        assert client.generate(prompt, 4)["tokens"] == refs
        assert sorted(client.gateways()) == sorted(fleet.addrs)
        client.close()


def test_fleet_replica_death_mid_stream_retries_on_survivor(
        fleet, tiny_offline):
    """Acceptance: SIGKILL one replica while requests are in flight —
    every request still completes correctly (retried on the survivor)
    and the retry/death counters record it.  Runs LAST in this module:
    it permanently takes one replica down."""
    import os
    import signal as _signal

    cfg, offline = tiny_offline
    prompts = _e2e_prompts(cfg, 12, seed=3)
    want = 48                           # long enough to be in flight
    client = fleet.client(timeout=300.0)
    results = [None] * 12
    errors = []

    def one(i):
        try:
            results[i] = client.generate(prompts[i], want)
        except Exception as e:
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()

    # Wait until BOTH replicas have requests in flight (router-side
    # outstanding counts), then kill one whole task process group (the
    # Mode-B wrapper AND the replica under it) — whichever dies has
    # work mid-stream, so the failover path must fire.
    def both_busy():
        addrs = [r.addr for r in fleet.registry.alive()]
        return len(addrs) == 2 and all(
            fleet.router.outstanding(a) > 0 for a in addrs)

    assert _wait(both_busy, timeout=60.0), "work never spread over both"
    procs = fleet.scheduler.backend._procs
    victim = next(p for p in procs.values() if p.poll() is None)
    os.killpg(victim.pid, _signal.SIGKILL)
    for t in threads:
        t.join(timeout=300.0)
    assert all(not t.is_alive() for t in threads)
    assert not errors, errors
    for i in range(12):
        assert results[i]["tokens"] == offline(prompts[i], want), \
            f"request {i} diverged after failover"
    # The death was observed and at least one request failed over.
    assert fleet.metrics.get("retries") >= 1
    assert _wait(lambda: len(fleet.registry.alive()) == 1, timeout=10.0)
    snap = fleet.snapshot()
    c = snap["counters"]
    assert c["admitted"] == c["completed"] + c.get("failed", 0)
    assert c.get("replicas_died", 0) >= 1
    client.close()


def test_fleet_rejects_unservable_request(fleet):
    """A prompt that can never fit max_len comes back as an explicit
    bad_request error from the replica, not a hang or a dead loop."""
    from tfmesos_tpu.fleet.client import RequestFailed

    client = fleet.client(timeout=60.0)
    with pytest.raises(RequestFailed) as e:
        client.generate(list(range(1, 60)), max_new_tokens=40)
    assert e.value.kind == "bad_request"
    client.close()


def test_fleet_gateway_requires_token(fleet):
    """The front door speaks only the authenticated protocol."""
    sock = wire.connect(fleet.addr, timeout=5.0)
    wire.send_msg(sock, {"op": "generate", "prompt": [1],
                         "max_new_tokens": 1}, "not-the-token")
    sock.settimeout(2.0)
    with pytest.raises((OSError, wire.WireError)):
        wire.recv_msg(sock, "not-the-token")
    sock.close()


@pytest.mark.slow
def test_fleet_warmup_relaunch_rewarms_before_traffic(tiny_offline):
    """End to end on the local backend: a --warmup fleet's replica
    boots through warming -> alive before the gateway opens for it, and
    a Mode-B RELAUNCH (the exact replica cmd the scheduler runs) goes
    through the same warming window — never routed while compiling,
    correct completions the moment it flips alive."""
    import os
    import shlex
    import signal as _signal
    import subprocess

    from tfmesos_tpu.fleet.launcher import FleetServer
    from tfmesos_tpu.fleet.registry import ALIVE, WARMING

    cfg, offline = tiny_offline
    fs = FleetServer(replicas=1, rows=2, tiny=True, max_len=64,
                     page_size=16, prefill_bucket=16, warmup=True,
                     request_timeout=300.0, start_timeout=300.0)
    states = []                 # (addr, state) transitions, in order

    def watch():
        while fs.registry is None:
            time.sleep(0.01)
        while not done.is_set():
            for r in fs.registry.snapshot():
                key = (r["addr"], r["state"])
                if key not in states:
                    states.append(key)
            time.sleep(0.01)

    done = threading.Event()
    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    proc = None
    try:
        fs.start()      # returns only once the replica is ALIVE (warmed)
        assert "--warmup" in fs._replica_cmd().split()
        boot_addr = fs.registry.alive()[0].addr
        # Boot went through the warming state before alive.  (The
        # watcher polls on its own cadence — give it a beat to record
        # the flip start() already observed.)
        assert _wait(lambda: (boot_addr, ALIVE) in states, timeout=10.0)
        assert states.index((boot_addr, WARMING)) \
            < states.index((boot_addr, ALIVE))
        client = fs.client(timeout=300.0)
        prompt = _e2e_prompts(cfg, 1, seed=9)[0]
        assert client.generate(prompt, 4)["tokens"] == offline(prompt, 4)

        # Kill the replica task (process group: wrapper + replica).
        victim = next(p for p in fs.scheduler.backend._procs.values()
                      if p.poll() is None)
        os.killpg(victim.pid, _signal.SIGKILL)
        assert _wait(lambda: not fs.registry.alive(), timeout=30.0)

        # Mode-B relaunch: the scheduler's own cmd line, re-run as-is.
        env = dict(os.environ, TPUMESOS_TOKEN=fs.token,
                   JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            shlex.split(fs._replica_cmd()), env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            start_new_session=True)
        # The relaunch appears as WARMING — and while it warms, no tier
        # can pick it (the fleet has no alive replica at all now).
        assert _wait(lambda: fs.registry.warming(), timeout=120.0)
        new_addr = fs.registry.warming()[0].addr
        assert new_addr != boot_addr
        assert fs.router.pick() is None
        assert fs.router.pick_prefill() is None
        assert fs.router.pick_decode() is None
        # It flips alive when warmup returns, and serves correctly.
        assert _wait(lambda: any(r.addr == new_addr
                                 for r in fs.registry.alive()),
                     timeout=120.0)
        out = client.generate(prompt, 4, timeout=300.0)
        assert out["tokens"] == offline(prompt, 4)
        assert _wait(lambda: (new_addr, ALIVE) in states, timeout=10.0)
        assert states.index((new_addr, WARMING)) \
            < states.index((new_addr, ALIVE))
        client.close()
    finally:
        done.set()
        watcher.join(timeout=5.0)
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(proc.pid, _signal.SIGKILL)
            except OSError:
                pass
        fs.stop()


# -- drain migration: suspended replies re-placed by the router -------------
# (stub replicas, no JAX — the re-placement policy is model-agnostic)


def _suspended_meta(gen=0, version="", step=3, tokens=(4, 9, 2)):
    """A suspended-export meta header shaped like the replica's (the
    router treats everything but op/id/gen/weights_version as opaque
    artifact state to forward)."""
    return {"op": "suspended", "gen": gen, "weights_version": version,
            "version": 1, "page_size": 16, "prefix_len": 0,
            "shared_len": 0, "pos": 5, "prompt_len": 3,
            "first_token": tokens[0], "step": step,
            "tokens": list(tokens), "rid": 0, "quantized": False,
            "arrays": []}


def _stub_suspending_replica(token, registry_addr, meta, body=None,
                             version=None, prefix_summary=None):
    """A drain-migration victim: answers every generate with a
    ``suspended`` reply — a raw artifact frame when ``body`` is given,
    else the plain requeue marker.  ``prefix_summary`` lets a test
    steer the router's FIRST pick here deterministically (affinity
    beats p2c) when more than two replicas are alive."""

    def handler(msg, reply):
        mid = (msg.meta if isinstance(msg, wire.RawFrame) else msg).get("id")
        if body is not None:
            reply(wire.RawFrame(dict(meta, id=mid), body))
        else:
            reply(dict(meta, id=mid, requeue=True))

    def extra():
        beat = {}
        if version:
            beat["weights_version"] = version
        if prefix_summary is not None:
            beat["prefix_cache"] = prefix_summary
        return beat

    return ReplicaServer(handler, token=token, capacity=4,
                         registry_addr=registry_addr,
                         heartbeat_interval=0.05, extra_info=extra).start()


def _stub_resume_replica(token, registry_addr, version=None, got=None):
    """A migration target: resumes raw generate imports (completion =
    the artifact's tokens + one more) and serves plain generates with
    canned tokens (the rerun path)."""
    got = got if got is not None else []

    def handler(msg, reply):
        if isinstance(msg, wire.RawFrame):
            got.append(msg)
            reply({"op": "completion", "id": msg.meta.get("id"),
                   "tokens": list(msg.meta.get("tokens") or ()) + [5],
                   "ttft_ms": 0.5, "total_ms": 2.0})
            return
        reply({"op": "completion", "id": msg.get("id"), "tokens": [9],
               "ttft_ms": 1.0, "total_ms": 2.0})

    extra = (lambda: {"weights_version": version}) if version else None
    server = ReplicaServer(handler, token=token, capacity=4,
                           registry_addr=registry_addr,
                           heartbeat_interval=0.05,
                           extra_info=extra).start()
    return server, got


def test_router_resumes_suspended_export_on_survivor(stub_fleet):
    """The tox-lint migration smoke: a victim's suspended KV export is
    re-placed on a same-version survivor as one raw frame (artifact
    state forwarded verbatim, transport fields rebuilt), and the caller
    sees one completion continuing the suspended stream."""
    token, reg, servers = stub_fleet
    body = b"\xbb" * 512
    servers.append(_stub_suspending_replica(
        token, reg.addr, _suspended_meta(version="v1"), body=body,
        version="v1"))
    assert _wait(lambda: len(reg.alive()) == 1)
    dec, got = _stub_resume_replica(token, reg.addr, version="v1")
    servers.append(dec)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [1, 2, 3],
                            "max_new_tokens": 8})
        assert out["tokens"] == [4, 9, 2, 5]    # resumed, not re-run
        assert len(got) == 1
        meta = got[0].meta
        assert meta["op"] == "generate"
        assert meta["prompt"] == [1, 2, 3]
        assert meta["max_new_tokens"] == 8
        assert meta["step"] == 3 and meta["tokens"] == [4, 9, 2]
        assert "gen" not in meta and "weights_version" not in meta
        assert got[0].body == body
        assert metrics.get("migration_exports") == 1
        assert metrics.get("migration_resumes") == 1
        assert metrics.get("migration_reruns") == 0
    finally:
        router.close()


def test_router_requeue_marker_reruns_elsewhere(stub_fleet):
    """A suspended reply WITHOUT an artifact (nothing resumable) makes
    the router re-run the whole request on a survivor — lossless via
    determinism, never an error to the client."""
    token, reg, servers = stub_fleet
    servers.append(_stub_suspending_replica(
        token, reg.addr, {"op": "suspended", "gen": 0}))
    assert _wait(lambda: len(reg.alive()) == 1)
    dec, got = _stub_resume_replica(token, reg.addr)
    servers.append(dec)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [7],
                            "max_new_tokens": 2})
        assert out["tokens"] == [9]             # re-run, plain path
        assert not got                          # no raw resume attempted
        assert metrics.get("migration_exports") == 1
        assert metrics.get("migration_reruns") == 1
    finally:
        router.close()


def test_router_fences_stale_suspended_export(stub_fleet):
    """A suspended export stamped with a reaped (fenced) generation is
    NEVER re-imported — the zombie's stale-weights KV cannot land; the
    request re-runs on a survivor instead."""
    token, reg, servers = stub_fleet
    reg.fence_generation(5)
    servers.append(_stub_suspending_replica(
        token, reg.addr, _suspended_meta(gen=3, version="v1"),
        body=b"\xcc" * 64, version="v1"))
    assert _wait(lambda: len(reg.alive()) == 1)
    dec, got = _stub_resume_replica(token, reg.addr, version="v1")
    servers.append(dec)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [7],
                            "max_new_tokens": 2})
        assert out["tokens"] == [9]             # re-run, never resumed
        assert not got
        assert metrics.get("migration_fenced") == 1
        assert metrics.get("migration_resumes") == 0
    finally:
        router.close()


def test_router_resume_requires_matching_weights_version(stub_fleet):
    """KV pages computed under one weights_version must never feed a
    decode under another: with no same-version survivor the router
    re-runs the request instead of resuming onto mismatched weights."""
    token, reg, servers = stub_fleet
    servers.append(_stub_suspending_replica(
        token, reg.addr, _suspended_meta(version="v1"),
        body=b"\xdd" * 64, version="v1"))
    assert _wait(lambda: len(reg.alive()) == 1)
    dec, got = _stub_resume_replica(token, reg.addr, version="v2")
    servers.append(dec)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        out = router.route({"op": "generate", "prompt": [7],
                            "max_new_tokens": 2})
        assert out["tokens"] == [9]             # re-run on the v2 tier
        assert not got
        assert metrics.get("migration_reruns") == 1
    finally:
        router.close()


def test_gateway_priority_classes_rank_and_metrics(stub_fleet):
    """The gateway maps the request's class label to the class table:
    the class RANK rides to the replica (batcher preemption), the shed
    and queue-wait metrics split per class, and unlabeled requests take
    the first-listed class."""
    from tfmesos_tpu.fleet.admission import PriorityClass

    token, reg, servers = stub_fleet
    seen = []

    def handler(msg, reply):
        seen.append(msg.get("priority"))
        reply({"op": "completion", "id": msg.get("id"), "tokens": [1],
               "ttft_ms": 1.0, "total_ms": 2.0})

    servers.append(ReplicaServer(handler, token=token, capacity=4,
                                 registry_addr=reg.addr,
                                 heartbeat_interval=0.05).start())
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    adm = AdmissionController(
        max_queue=8,
        classes=[PriorityClass("interactive", weight=4.0, rank=1),
                 PriorityClass("background", weight=1.0, rank=0)])
    gw = Gateway(router, adm, metrics, token=token, workers=2).start()
    try:
        client = FleetClient(gw.addr, token)
        client.generate([1], 1)                         # unlabeled
        client.generate([1], 1, priority="background")
        client.generate([1], 1, priority="interactive")
        client.generate([1], 1, priority="no-such-class")
        assert seen.count(1) == 3 and seen.count(0) == 1
        snap = client.metrics()
        hists = snap["histograms"]
        assert hists["queue_wait_ms"]["count"] == 4
        assert hists["queue_wait_ms_interactive"]["count"] == 3
        assert hists["queue_wait_ms_background"]["count"] == 1
        assert snap["gauges"]["queue_depths"] == {
            "interactive": 0, "background": 0}
        client.close()
    finally:
        gw.stop()


# -- front-door scaling: streaming, multi-gateway, failover (no JAX) --------
#
# docs/SERVING.md "Front-door scaling": the event-loop gateway, per-
# token incremental replies, the `gateways` discovery op, and the
# FleetClient failover that replays idempotent in-flight requests when
# its gateway dies mid-stream.


def _stub_streaming_replica(token, registry_addr, chunks, tokens,
                            delay=0.05):
    """Replies `chunks` as op:tokens partial frames (with their stream
    offsets), `delay` apart, then the final completion with the full
    `tokens` list — the replica-side shape of per-token streaming."""

    def handler(msg, reply):
        def work():
            mid = msg.get("id")
            if msg.get("stream"):
                off = 0
                for c in chunks:
                    reply.partial({"op": "tokens", "id": mid,
                                   "off": off, "tokens": list(c)})
                    off += len(c)
                    time.sleep(delay)
            else:
                time.sleep(delay * len(chunks))
            reply({"op": "completion", "id": mid,
                   "tokens": list(tokens), "ttft_ms": 1.0,
                   "total_ms": 2.0})

        threading.Thread(target=work, daemon=True).start()

    return ReplicaServer(handler, token=token, capacity=8,
                         registry_addr=registry_addr,
                         heartbeat_interval=0.05).start()


def test_streaming_tokens_arrive_before_completion(stub_fleet):
    """op:tokens partials flow replica -> router -> gateway -> client
    in order, BEFORE the final completion — and concatenate to exactly
    the completion's full token list."""
    token, reg, servers = stub_fleet
    servers.append(_stub_streaming_replica(
        token, reg.addr, chunks=[(4,), (2, 9)], tokens=(4, 2, 9)))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2, registry=reg).start()
    try:
        client = FleetClient(gw.addr, token)
        got, stamps = [], []
        out = client.generate(
            [1, 2], max_new_tokens=3,
            on_tokens=lambda t: (got.append(list(t)),
                                 stamps.append(time.monotonic())))
        t_done = time.monotonic()
        assert out["tokens"] == [4, 2, 9]
        assert got == [[4], [2, 9]]
        # The first chunk landed a real delay ahead of the completion:
        # streaming, not a post-hoc replay of the final reply.
        assert stamps[0] < t_done - 0.03
        assert metrics.get("stream_chunks") == 2
        client.close()
    finally:
        gw.stop()


def test_streaming_offset_dedup_across_retry(stub_fleet):
    """A replica that streams a prefix then DIES mid-request: the
    retry re-streams from offset 0 on the survivor, and the gateway's
    offset de-dup hands the client each token exactly once."""
    token, reg, servers = stub_fleet

    # Dies after streaming its first chunk — the router retries on the
    # healthy streaming replica, which re-streams from 0.
    def dying_handler(msg, reply):
        def work():
            if msg.get("stream"):
                reply.partial({"op": "tokens", "id": msg.get("id"),
                               "off": 0, "tokens": [4]})
            time.sleep(0.05)
            # Slam every connection: mid-request EOF.
            dying.stop()

        threading.Thread(target=work, daemon=True).start()

    dying = ReplicaServer(dying_handler, token=token, capacity=8,
                          registry_addr=reg.addr,
                          heartbeat_interval=0.05).start()
    assert reg.wait_for(1, timeout=5.0)
    survivor = _stub_streaming_replica(
        token, reg.addr, chunks=[(4,), (2, 9)], tokens=(4, 2, 9),
        delay=0.02)
    servers.append(survivor)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2, registry=reg).start()
    try:
        client = FleetClient(gw.addr, token)
        got = []
        # Drive until the dying replica actually took one (it may take
        # a few requests for p2c to pick it first).
        for _ in range(8):
            got.clear()
            out = client.generate([1], max_new_tokens=3, timeout=30.0,
                                  on_tokens=lambda t: got.extend(t))
            assert out["tokens"] == [4, 2, 9]
            assert got == [4, 2, 9], \
                f"streamed tokens duplicated or lost: {got}"
            if metrics.get("retries") >= 1:
                break
        assert metrics.get("retries") >= 1, \
            "the dying replica never took a request; test proved nothing"
        client.close()
    finally:
        gw.stop()


def test_gateways_discovery_op_and_registry(stub_fleet):
    """N gateways register with the shared registry; the `gateways` op
    on ANY of them returns the full set; a graceful stop deregisters,
    a kill does not (stale entries are the client's to skip)."""
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(1,)))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    adm = AdmissionController(max_queue=8)
    gws = [Gateway(router, adm, metrics, token=token, workers=1,
                   registry=reg, close_router=False).start()
           for _ in range(3)]
    try:
        client = FleetClient(gws[1].addr, token)
        assert sorted(client.gateways()) == sorted(g.addr for g in gws)
        assert sorted(reg.gateway_addrs()) == sorted(g.addr
                                                     for g in gws)
        client.close()
        gws[2].stop()                   # graceful: deregisters
        assert sorted(reg.gateway_addrs()) == sorted(
            g.addr for g in gws[:2])
        gws[1].kill()                   # SIGKILL shape: stays listed
        assert sorted(reg.gateway_addrs()) == sorted(
            g.addr for g in gws[:2])
    finally:
        for g in gws:
            if not g.killed and g._threads:
                g.stop()
        router.close()


def test_client_failover_replays_inflight_request(stub_fleet):
    """The acceptance failure mode: a client's gateway is hard-killed
    with a request IN FLIGHT — the FleetClient re-resolves and replays
    it on the survivor; the caller sees one completion, streamed
    tokens exactly-once."""
    token, reg, servers = stub_fleet
    servers.append(_stub_streaming_replica(
        token, reg.addr, chunks=[(5,), (6,)], tokens=(5, 6),
        delay=0.25))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    adm = AdmissionController(max_queue=16)
    gws = [Gateway(router, adm, metrics, token=token, workers=2,
                   registry=reg, close_router=False).start()
           for _ in range(2)]
    try:
        client = FleetClient([g.addr for g in gws], token)
        res: dict = {"toks": []}

        def call():
            try:
                res["out"] = client.generate(
                    [3], max_new_tokens=2, timeout=30.0,
                    on_tokens=lambda t: res["toks"].extend(t))
            except Exception as e:      # surfaced in the main thread
                res["err"] = e

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.1)                 # request is mid-stream now
        victim = next(g for g in gws if g.addr == client.addr)
        victim.kill()
        t.join(timeout=30.0)
        assert "err" not in res, res.get("err")
        assert res["out"]["tokens"] == [5, 6]
        assert res["toks"] == [5, 6], \
            f"failover replay duplicated/lost streamed tokens: " \
            f"{res['toks']}"
        assert client.addr != victim.addr   # moved to the survivor
        client.close()
    finally:
        for g in gws:
            if not g.killed:
                g.stop()
        router.close()


def test_client_all_gateways_dead_fails_explicitly(stub_fleet):
    """Failover is bounded: with every gateway gone the client raises
    ConnectionLost — never a hang, never an unbounded retry loop."""
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(1,)))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=1, registry=reg,
                 close_router=False).start()
    client = FleetClient(gw.addr, token)
    assert client.generate([1], 1)["tokens"] == [1]
    gw.kill()
    try:
        with pytest.raises(ConnectionLost):
            client.generate([1], 1, timeout=5.0)
    finally:
        client.close()
        router.close()


def test_gateway_processes_discovery_and_sigkill_failover(stub_fleet):
    """Tentpole acceptance at the OS-PROCESS level: two real gateway
    processes (``python -m tfmesos_tpu.fleet.gateway``) lease into the
    shared registry (one lease PER PROCESS, keyed by each process's
    private scrape addr), the client discovers both public doors, and
    a SIGKILL of the serving process mid-stream replays the in-flight
    request on the survivor — one completion, tokens exactly-once."""
    import os
    import signal
    import subprocess
    import sys

    token, reg, servers = stub_fleet
    servers.append(_stub_streaming_replica(
        token, reg.addr, chunks=[(5,), (6,)], tokens=(5, 6),
        delay=0.25))
    assert reg.wait_for(1, timeout=5.0)
    env = dict(os.environ, TPUMESOS_TOKEN=token)
    env.pop("TPUMESOS_TOKEN_FILE", None)

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-m", "tfmesos_tpu.fleet.gateway",
             "--registry", reg.addr, "--host", "127.0.0.1",
             "--port", "0", "--workers", "2"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    procs = []
    try:
        # Spawn one at a time so the public-addr -> pid mapping is
        # known (the deterministic-kill handle below).
        procs.append(spawn())
        assert _wait(lambda: len(reg.gateway_addrs()) == 1,
                     timeout=30.0), "first gateway never leased"
        addr_a = reg.gateway_addrs()[0]
        procs.append(spawn())
        assert _wait(lambda: len(reg.gateway_addrs()) == 2,
                     timeout=30.0), "second gateway never leased"
        addrs = reg.gateway_addrs()
        addr_b = next(a for a in addrs if a != addr_a)
        assert len(reg.gateway_leases()) == 2   # one lease per process
        client = FleetClient([addr_a, addr_b], token)
        # The answering process serves `gateways` from its SIDECAR's
        # mirrored view — give its poll loop a beat to converge.
        assert _wait(lambda: sorted(client.gateways()) == sorted(addrs),
                     timeout=30.0), client.gateways()
        res: dict = {"toks": []}

        def call():
            try:
                res["out"] = client.generate(
                    [3], max_new_tokens=2, timeout=60.0,
                    on_tokens=lambda t: res["toks"].extend(t))
            except Exception as e:
                res["err"] = e

        t = threading.Thread(target=call)
        t.start()
        assert _wait(lambda: bool(res["toks"]) or "out" in res,
                     timeout=30.0)       # request is mid-stream now
        os.kill(procs[0].pid, signal.SIGKILL)   # the serving process
        t.join(timeout=60.0)
        assert "err" not in res, res.get("err")
        assert res["out"]["tokens"] == [5, 6]
        assert res["toks"] == [5, 6], \
            f"process kill duplicated/lost streamed tokens: " \
            f"{res['toks']}"
        assert client.addr == addr_b    # moved to the survivor process
        client.close()
    finally:
        for p in procs:
            p.terminate()
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_mux_reader_death_fails_calls_promptly(stub_fleet):
    """Satellite: a reader-thread DEATH (a bug, not a clean EOF) fails
    every outstanding call immediately with the distinguishable
    ReaderDied — callers must not ride their full per-call timeout."""
    from tfmesos_tpu.fleet.client import ReaderDied

    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(7,),
                                 delay=30.0))   # generate never replies
    mux = MuxConnection(servers[0].addr, token)
    results: dict = {}

    def call():
        t0 = time.monotonic()
        try:
            mux.call({"op": "generate", "prompt": [1]}, timeout=60.0)
            results["outcome"] = "reply"
        except ReaderDied:
            results["outcome"] = "reader_died"
        except ConnectionLost:
            results["outcome"] = "connection_lost"
        results["waited_s"] = time.monotonic() - t0

    t = threading.Thread(target=call)
    t.start()
    assert _wait(lambda: mux.outstanding == 1)   # call in flight
    # Inject the reader bug: the reader pops the reply slot from
    # _slots under the lock — swap the dict for one whose pop raises.
    # The next reply it processes (a pong, answered instantly by
    # ReplicaServer itself) then kills the reader thread with an
    # exception outside its (OSError, WireError) arms.
    class _Boom(dict):
        def pop(self, *a, **kw):
            raise RuntimeError("injected reader bug")

    with mux._lock:
        mux._slots = _Boom(mux._slots)
    with pytest.raises((ReaderDied, CallTimeout)):
        mux.call({"op": "ping"}, timeout=5.0)
    t.join(timeout=10.0)
    assert results.get("outcome") == "reader_died", results
    assert results["waited_s"] < 10.0, \
        f"caller rode {results['waited_s']:.1f}s instead of failing fast"
    # A fresh call on the dead mux fails distinguishably too.
    with pytest.raises(ReaderDied):
        mux.call({"op": "ping"}, timeout=1.0)
    mux.close()


def test_client_close_cancels_never_replays(stub_fleet):
    """close() racing an in-flight generate is a CANCELLATION, not a
    gateway death: the call fails with ConnectionLost, is never
    replayed, and the closed client refuses later calls instead of
    silently re-dialing."""
    token, reg, servers = stub_fleet
    served = []

    def handler(msg, reply):
        def work():
            served.append(msg.get("id"))
            time.sleep(0.4)
            reply({"op": "completion", "id": msg.get("id"),
                   "tokens": [1], "ttft_ms": 1.0, "total_ms": 2.0})

        threading.Thread(target=work, daemon=True).start()

    servers.append(ReplicaServer(handler, token=token, capacity=8,
                                 registry_addr=reg.addr,
                                 heartbeat_interval=0.05).start())
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2, registry=reg).start()
    try:
        client = FleetClient(gw.addr, token)
        res: dict = {}

        def call():
            try:
                client.generate([1], 1, timeout=30.0)
                res["outcome"] = "reply"
            except ConnectionLost:
                res["outcome"] = "connection_lost"

        t = threading.Thread(target=call)
        t.start()
        assert _wait(lambda: len(served) == 1)  # in flight
        client.close()
        t.join(timeout=10.0)
        assert res.get("outcome") == "connection_lost", res
        assert len(served) == 1, "cancelled call was replayed"
        with pytest.raises(ConnectionLost):
            client.generate([1], 1, timeout=1.0)
    finally:
        gw.stop()


# -- KV tiering & sessions (PR 13; store/router units in test_kvtier) --------


def test_session_label_rides_the_wire_to_the_parker(stub_fleet):
    """client.generate(session=) → gateway forward → router session-
    affinity pick → replica head: the label crosses every hop intact,
    and the turn lands on the replica advertising the parked session
    in its heartbeat kv_tier summary."""
    token, reg, servers = stub_fleet
    seen = []

    def handler(msg, reply):
        seen.append(dict(msg))
        reply({"op": "completion", "id": msg.get("id"),
               "tokens": [7], "ttft_ms": 1.0, "total_ms": 2.0})

    parker = ReplicaServer(
        handler, token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"kv_tier": {"sessions": ["conv-1"],
                                        "counters": {"park": 1}}}
    ).start()
    servers.append(parker)
    servers.append(_stub_replica(token, reg.addr, tokens=(9,)))
    assert reg.wait_for(2, timeout=5.0)
    assert _wait(lambda: any(
        isinstance(r.kv_tier, dict) for r in reg.members()))
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2).start()
    try:
        client = FleetClient(gw.addr, token)
        for _ in range(4):
            out = client.generate([1, 2, 3], max_new_tokens=2,
                                  session="conv-1")
            assert out["tokens"] == [7]     # the parker, every time
        assert all(m.get("session") == "conv-1" for m in seen)
        assert len(seen) == 4
        assert metrics.get("session_affinity_hits") == 4
        # The fleet aggregate rides the metrics snapshot (and from
        # there the Prometheus exposition).
        snap = client.metrics()
        assert snap["gauges"]["kv_tier"]["replicas"] == 1
        assert snap["gauges"]["kv_tier"]["park"] == 1
        client.close()
    finally:
        gw.stop()


def test_session_request_survives_parker_death(stub_fleet):
    """Chaos mid-resume: the parker dies before the turn lands — the
    router's session pick must fall back to a survivor (cold
    re-prefill, deterministic) instead of wedging on the dead
    favorite."""
    token, reg, servers = stub_fleet
    parker = ReplicaServer(
        lambda m, r: r({"op": "completion", "id": m.get("id"),
                        "tokens": [7], "ttft_ms": 1.0, "total_ms": 2.0}),
        token=token, capacity=4, registry_addr=reg.addr,
        heartbeat_interval=0.05,
        extra_info=lambda: {"kv_tier": {"sessions": ["conv-1"]}}).start()
    servers.append(parker)
    survivor = _stub_replica(token, reg.addr, tokens=(9,))
    servers.append(survivor)
    assert reg.wait_for(2, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    try:
        assert router.pick(session="conv-1") == parker.addr
        parker.stop()           # SIGKILL shape: the session is gone
        assert _wait(lambda: len(reg.alive()) == 1)
        reply = router.route({"op": "generate", "prompt": [1],
                              "session": "conv-1"})
        assert reply["tokens"] == [9]       # served cold elsewhere
    finally:
        router.close()
