"""Fleet-wide failure containment (tfmesos_tpu/fleet/containment.py and
its router/admission/gateway wiring): circuit-breaker trip/half-open/
recovery, the fleet retry budget, end-to-end deadline sheds, the chaos
``slow_task`` gray-failure fault, and a short seeded stub-fleet soak —
all jax-free (fake clocks where time matters, stub replicas where a
fleet does)."""

import random
import threading
import time

import pytest

from tfmesos_tpu import wire
from tfmesos_tpu.chaos import Fault, FaultPlan
from tfmesos_tpu.fleet.admission import (AdmissionController,
                                         DeadlineExceeded)
from tfmesos_tpu.fleet.client import FleetClient, RequestFailed
from tfmesos_tpu.fleet.containment import (CLOSED, HALF_OPEN, OPEN,
                                           BreakerBoard, BreakerConfig,
                                           RetryBudget)
from tfmesos_tpu.fleet.gateway import Gateway
from tfmesos_tpu.fleet.metrics import FleetMetrics
from tfmesos_tpu.fleet.registry import ReplicaRegistry
from tfmesos_tpu.fleet.replica import ReplicaServer
from tfmesos_tpu.fleet.router import Router, RoutingError


def _wait(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- retry budget (pure units) ----------------------------------------------


def test_retry_budget_debits_and_refills():
    """gRPC-throttling semantics: retries allowed only while the
    balance is above half of max; every consult debits one token
    (sustained failures drain it even while it still says yes), every
    success refills token_ratio — throughput-proportional recovery."""
    b = RetryBudget(max_tokens=4.0, token_ratio=1.0)
    assert b.level() == 1.0
    assert b.try_retry()        # 4 -> 3
    assert b.try_retry()        # 3 -> 2
    assert not b.try_retry()    # 2 is not > 2: exhausted
    assert not b.try_retry()    # and it stays exhausted...
    for _ in range(3):
        b.on_success()          # ...until successes refill it
    assert b.try_retry()
    with pytest.raises(ValueError):
        RetryBudget(max_tokens=0)


def test_retry_budget_degrades_to_one_attempt_under_brownout():
    """With nothing completing, the budget caps TOTAL retries at about
    max_tokens/2 — the fleet converges to ~1 attempt per request
    instead of multiplying a brown-out's load by max_retries."""
    b = RetryBudget(max_tokens=10.0, token_ratio=0.1)
    granted = sum(1 for _ in range(100) if b.try_retry())
    assert granted == 5


# -- circuit breakers (fake clock) ------------------------------------------


def _board(clock, **kw):
    return BreakerBoard(BreakerConfig(**kw), clock=clock)


def test_breaker_trips_on_consecutive_failures_then_probe_recovers():
    t = [0.0]
    board = _board(lambda: t[0], failures=3, cooldown_s=2.0)
    a = "10.0.0.1:7000"
    board.record_failure(a)
    board.record_failure(a)
    assert board.state_of(a) == CLOSED and board.eligible(a)
    board.record_failure(a)                 # third consecutive: trip
    assert board.state_of(a) == OPEN
    assert not board.eligible(a)
    assert board.describe()[a]["reason"] == "consecutive_failures"
    t[0] = 2.1                              # cooldown over
    assert board.eligible(a)
    probe = board.on_dispatch(a)            # THIS request is the probe
    assert probe is True
    assert board.state_of(a) == HALF_OPEN
    assert not board.eligible(a)            # single probe: nobody else
    assert board.on_dispatch(a) is False    # a racer is NOT the probe
    # A pre-trip straggler landing mid-probe must not close the gate
    # the probe is still testing...
    board.record_success(a, 10.0, probe=False)
    assert board.state_of(a) == HALF_OPEN
    # ...only the sanctioned probe's outcome does.
    board.record_success(a, 10.0, probe=probe)
    assert board.state_of(a) == CLOSED
    assert board.summary()["recoveries"] == 1
    assert board.summary()["trips"] == 1


def test_breaker_failed_probe_reopens_with_exponential_backoff():
    t = [0.0]
    board = _board(lambda: t[0], failures=1, cooldown_s=1.0,
                   max_cooldown_s=8.0)
    a = "addr"
    board.record_failure(a)                 # trip; cooldown 1.0
    t[0] = 1.5
    probe = board.on_dispatch(a)
    assert probe is True
    board.record_failure(a, probe=probe)    # probe failed: reopen x2
    assert board.state_of(a) == OPEN
    t[0] = 2.6                              # 1.1s later: still < 2.0
    assert not board.eligible(a)
    t[0] = 3.6                              # 2.1s later: probe allowed
    assert board.eligible(a)


def test_breaker_latency_outlier_trips_gray_replica():
    """The gray-failure detector: a replica that FAILS nothing but
    serves far above the peer-median latency trips on its successes —
    nothing else in the fleet can catch a slow-but-alive replica."""
    board = BreakerBoard(BreakerConfig(min_samples=5,
                                       latency_factor=4.0,
                                       latency_floor_ms=50.0))
    for _ in range(6):
        board.record_success("fast1", 10.0)
        board.record_success("fast2", 12.0)
    assert board.state_of("slow") == CLOSED
    for _ in range(6):
        board.record_success("slow", 500.0)
    assert board.state_of("slow") == OPEN
    assert board.describe()["slow"]["reason"] == "latency_outlier"
    assert board.state_of("fast1") == CLOSED    # peers untouched
    assert board.summary()["latency_trips"] == 1


def test_breaker_floor_and_missing_peers_never_trip():
    # Sub-floor EWMAs (microsecond jitter) must not trip no matter the
    # ratio, and a lone replica has no peer median to be an outlier of.
    board = BreakerBoard(BreakerConfig(min_samples=2,
                                       latency_floor_ms=50.0))
    for _ in range(5):
        board.record_success("a", 1.0)
        board.record_success("b", 40.0)     # 40x, but under the floor
    assert board.state_of("b") == CLOSED
    lone = BreakerBoard(BreakerConfig(min_samples=2))
    for _ in range(5):
        lone.record_success("only", 10000.0)
    assert lone.state_of("only") == CLOSED


def test_breaker_straggler_success_while_open_does_not_close():
    t = [0.0]
    board = _board(lambda: t[0], failures=1, cooldown_s=5.0)
    a = "addr"
    board.record_failure(a)                 # trip
    board.record_success(a, 5.0)            # pre-trip dispatch lands
    assert board.state_of(a) == OPEN, \
        "only the cooldown-gated probe may close a breaker"


# -- deadline sheds in the admission controller -----------------------------


def test_admission_deadline_shed_before_token_burn():
    """An already-expired arrival sheds FIRST — before capacity and
    before the token bucket, which must not be debited for dead work
    (the PR 7 no-token-burn discipline extended to deadlines)."""
    t = [0.0]
    adm = AdmissionController(max_queue=4, rate=10.0, burst=1.0,
                              clock=lambda: t[0])
    with pytest.raises(DeadlineExceeded):
        adm.admit("late", deadline=-1.0)
    adm.admit("ok")     # the single burst token was NOT burned
    assert adm.shed_counts()["default"] == (0, 0, 1)
    assert adm.get(timeout=0) == "ok"


def test_admission_deadline_shed_at_dispatch():
    """An item that expires while queued is shed by get() BEFORE any
    router worker touches it: per-class shed_deadline counts it and
    the on_expired callback still owes the client its answer."""
    t = [0.0]
    adm = AdmissionController(max_queue=8, clock=lambda: t[0])
    swept = []
    adm.on_expired = swept.append
    adm.admit("a", deadline=1.0)
    adm.admit("b", deadline=5.0)
    adm.admit("c")                          # no deadline: never expires
    t[0] = 2.0
    assert adm.get(timeout=0) == "b"        # 'a' expired while queued
    assert swept == ["a"]
    assert adm.get(timeout=0) == "c"
    assert adm.shed_counts()["default"] == (0, 0, 1)


# -- chaos slow_task (seeded gray-failure generator) ------------------------


def test_chaos_slow_task_deterministic_per_seed_and_persistent():
    def plan(seed):
        return FaultPlan([Fault("slow_task", "wire.send", nth=2,
                                target="victim", delay_s=None)],
                         seed=seed)

    p1, p2, p3 = plan(7), plan(7), plan(8)
    # The injected delay is drawn ONCE from the seeded RNG: same seed,
    # same delay — the whole point of a reproducible gray failure.
    assert p1.faults[0].delay_s == p2.faults[0].delay_s
    assert p1.faults[0].delay_s != p3.faults[0].delay_s
    assert p1.event("wire.send", key="victim:1") == []      # 1st: arming
    assert p1.event("wire.send", key="other") == []         # filtered
    assert len(p1.event("wire.send", key="victim:1")) == 1  # 2nd: armed
    assert len(p1.event("wire.send", key="victim:2")) == 1  # stays live
    assert len(p1.event("wire.send", key="victim:1")) == 1  # forever
    # fired records the arming exactly once — a soak cannot bloat it.
    assert [f[2] for f in p1.fired] == ["slow_task"]


def test_chaos_slow_task_sleeps_per_matching_event():
    p = FaultPlan([Fault("slow_task", "wire.send", nth=1,
                         target="v", delay_s=0.05)], seed=0)
    t0 = time.perf_counter()
    p.event("wire.send", key="v:1")
    p.event("wire.send", key="v:1")
    assert time.perf_counter() - t0 >= 0.1      # slept both events
    t0 = time.perf_counter()
    p.event("wire.send", key="other")
    assert time.perf_counter() - t0 < 0.04      # non-matching: free


# -- stub replicas ----------------------------------------------------------


def _stub_replica(token, registry_addr, tokens, delay=0.0):
    def handler(msg, reply):
        def work():
            if delay:
                time.sleep(delay)
            reply({"op": "completion", "id": msg.get("id"),
                   "tokens": list(tokens), "ttft_ms": 1.0,
                   "total_ms": 2.0})

        threading.Thread(target=work, daemon=True).start()

    return ReplicaServer(handler, token=token, capacity=32,
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


def _run_waves(router, n_waves, width, out):
    for _ in range(n_waves):
        threads = []
        for _ in range(width):
            def one():
                out.append(router.route({"op": "generate",
                                         "prompt": [1, 2]}))

            th = threading.Thread(target=one)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=30.0)


def test_router_breaker_isolates_slow_replica(stub_fleet):
    """THE gray-failure acceptance at stub scale: a replica that
    heartbeats perfectly but serves ~100x slow is breaker-isolated by
    the latency-outlier trip — while the registry still reports it
    ALIVE — and traffic stops landing on it."""
    token, reg, servers = stub_fleet
    slow = _stub_replica(token, reg.addr, tokens=(9,), delay=0.4)
    servers.append(slow)
    servers.append(_stub_replica(token, reg.addr, tokens=(1,)))
    assert reg.wait_for(2, timeout=5.0)
    router = Router(reg, FleetMetrics(), token=token,
                    rng=random.Random(0),
                    breaker_config=BreakerConfig(
                        min_samples=3, latency_factor=3.0,
                        latency_floor_ms=50.0, cooldown_s=60.0,
                        max_cooldown_s=120.0))
    try:
        out = []
        # Concurrent waves spread load over both replicas (p2c on
        # outstanding), feeding both EWMAs until the outlier trips.
        _run_waves(router, n_waves=4, width=4, out=out)
        assert router.breakers.state_of(slow.addr) == OPEN
        assert router.breakers.describe()[slow.addr]["reason"] \
            == "latency_outlier"
        # The heartbeat registry still swears the victim is healthy —
        # this containment exists precisely because liveness cannot
        # see a gray failure.
        assert slow.addr in [r.addr for r in reg.alive()]
        # With the breaker open, every new request lands elsewhere.
        for _ in range(4):
            assert router.route({"op": "generate",
                                 "prompt": [3]})["tokens"] == [1]
    finally:
        router.close()


def test_router_breaker_disabled_control_keeps_routing_to_slow(
        stub_fleet):
    """The control arm the soak bench leans on: with breakers=False the
    same traffic keeps landing on the slow replica (its completions
    still arrive — just late), proving isolation is the breaker's doing
    and not the workload's."""
    token, reg, servers = stub_fleet
    slow = _stub_replica(token, reg.addr, tokens=(9,), delay=0.2)
    servers.append(slow)
    servers.append(_stub_replica(token, reg.addr, tokens=(1,)))
    assert reg.wait_for(2, timeout=5.0)
    router = Router(reg, FleetMetrics(), token=token,
                    rng=random.Random(0), breakers=False)
    try:
        out = []
        _run_waves(router, n_waves=4, width=4, out=out)
        assert router.breakers is None
        assert any(r["tokens"] == [9] for r in out[-8:]), \
            "control arm should keep using the slow replica"
    finally:
        router.close()


def test_router_retry_budget_converts_failures_to_fast_failure(
        stub_fleet):
    """Brown-out: every replica is a dead port.  With the budget
    exhausted, the router stops failing over and raises fast —
    retry_budget_exhausted counts it."""
    token, reg, servers = stub_fleet
    feeders = []
    # Exactly as many dead ports as the first route can consume: the
    # budget (2 tokens) grants one failover, denies the second, and no
    # dead straggler is left alive to steal the healthy route below.
    for _ in range(2):
        s = wire.bind_ephemeral("127.0.0.1")
        dead_addr = wire.sock_addr(s, advertise_host="127.0.0.1")
        s.close()
        f = wire.connect(reg.addr)
        wire.send_msg(f, {"op": "hello", "addr": dead_addr}, token)
        feeders.append(f)
    assert _wait(lambda: len(reg.alive()) == 2)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01,
                    max_retries=2,
                    retry_budget=RetryBudget(max_tokens=2.0,
                                             token_ratio=0.1))
    try:
        with pytest.raises(RoutingError):
            router.route({"op": "generate", "prompt": [1]})
        assert metrics.get("retry_budget_exhausted") >= 1
        # The budget gates RETRIES only: first attempts always run, so
        # a healthy replica still serves at budget zero.
        servers.append(_stub_replica(token, reg.addr, tokens=(5,)))
        assert _wait(lambda: any(r.addr == servers[-1].addr
                                 for r in reg.alive()))
        assert not router.budget.try_retry()    # provably exhausted
        reply = router.route({"op": "generate", "prompt": [2]})
        assert reply["tokens"] == [5]
    finally:
        router.close()
        for f in feeders:
            f.close()


def test_router_deadline_fails_fast_and_rewrites_wire_field(stub_fleet):
    token, reg, servers = stub_fleet
    seen = []

    def capture(msg, reply):
        seen.append(dict(msg))
        reply({"op": "completion", "id": msg.get("id"), "tokens": [3],
               "ttft_ms": 1.0, "total_ms": 2.0})

    servers.append(ReplicaServer(capture, token=token, capacity=4,
                                 registry_addr=reg.addr,
                                 heartbeat_interval=0.05).start())
    assert reg.wait_for(1, timeout=5.0)
    router = Router(reg, FleetMetrics(), token=token)
    try:
        # Expired before the first pick: no replica is ever dialed.
        reply = router.route({"op": "generate", "prompt": [1],
                              "deadline": time.monotonic() - 1.0})
        assert reply["kind"] == "deadline_exceeded"
        assert not seen
        # Live deadline: the absolute stamp never crosses the wire —
        # the replica sees only the REMAINING budget in ms.
        reply = router.route({"op": "generate", "prompt": [1],
                              "deadline": time.monotonic() + 30.0})
        assert reply["tokens"] == [3]
        assert "deadline" not in seen[0]
        assert 0 < seen[0]["deadline_ms"] <= 30000.0
    finally:
        router.close()


def test_gateway_deadline_exceeded_end_to_end(stub_fleet):
    """Client -> gateway -> router with a deadline shorter than the
    (stub-slow) replica: the client gets an explicit deadline_exceeded
    error in about the deadline — never the late completion, never a
    hang — and the counters record it."""
    token, reg, servers = stub_fleet
    servers.append(_stub_replica(token, reg.addr, tokens=(7,),
                                 delay=0.6))
    assert reg.wait_for(1, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01)
    gw = Gateway(router, AdmissionController(max_queue=8), metrics,
                 token=token, workers=2).start()
    try:
        client = FleetClient(gw.addr, token)
        out = client.generate([1, 2], max_new_tokens=2,
                              deadline_ms=5000.0)
        assert out["tokens"] == [7]         # generous deadline: served
        t0 = time.perf_counter()
        with pytest.raises(RequestFailed) as e:
            client.generate([1, 2], max_new_tokens=2, deadline_ms=120.0)
        assert e.value.kind == "deadline_exceeded"
        assert time.perf_counter() - t0 < 0.55, \
            "deadline error must arrive ~at the deadline, not after " \
            "the slow replica finishes"
        assert metrics.get("deadline_exceeded") >= 1
        snap = metrics.snapshot()
        assert "retry_budget" in snap["gauges"]
        assert "breakers" in snap["gauges"]
        client.close()
    finally:
        gw.stop()


# -- the short seeded soak smoke (the tier-1 slice of scenario_soak) -----


def test_stub_fleet_soak_smoke(stub_fleet):
    """A compressed stub-scale soak: continuous traffic through a
    3-replica fleet with one gray-slow member and one mid-soak death.
    Asserts the scenario_soak invariants at unit cost: zero lost
    requests, the slow replica breaker-isolated while heartbeat-alive,
    and bounded retry amplification."""
    token, reg, servers = stub_fleet
    slow = _stub_replica(token, reg.addr, tokens=(9,), delay=0.3)
    doomed = _stub_replica(token, reg.addr, tokens=(2,))
    servers.extend([slow, doomed])
    servers.append(_stub_replica(token, reg.addr, tokens=(1,)))
    assert reg.wait_for(3, timeout=5.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, backoff_s=0.01,
                    rng=random.Random(0),
                    breaker_config=BreakerConfig(
                        min_samples=3, latency_factor=3.0,
                        latency_floor_ms=50.0, cooldown_s=60.0,
                        max_cooldown_s=120.0))
    gw = Gateway(router, AdmissionController(max_queue=64), metrics,
                 token=token, workers=4).start()
    lost, done = [], []
    lock = threading.Lock()

    def feeder(k, n):
        client = FleetClient(gw.addr, token, timeout=60.0)
        for i in range(n):
            try:
                out = client.generate([k, i], max_new_tokens=2,
                                      deadline_ms=30000.0)
                with lock:
                    done.append(out["tokens"])
            except Exception as e:  # noqa: BLE001 - every loss recorded
                with lock:
                    lost.append(e)
        client.close()

    try:
        threads = [threading.Thread(target=feeder, args=(k, 12))
                   for k in range(4)]
        for t in threads:
            t.start()
        # Mid-soak hard death: stop() closes the heartbeat link, the
        # registry marks it dead, in-flight work retries elsewhere.
        time.sleep(0.5)
        doomed.stop()
        for t in threads:
            t.join(timeout=120.0)
        assert not lost, f"lost {len(lost)}: {lost[0]!r}"
        assert len(done) == 48
        # Gray containment: breaker open, heartbeat still alive.
        assert router.breakers.state_of(slow.addr) == OPEN
        assert slow.addr in [r.addr for r in reg.alive()]
        # Retry amplification: attempts per completed request.
        completed = metrics.get("completed")
        amplification = (completed + metrics.get("retries")) \
            / max(1, completed)
        assert amplification <= 1.5, amplification
    finally:
        gw.stop()
