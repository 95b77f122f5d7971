"""Trace-driven fleet simulator (tfmesos_tpu/fleet/sim.py + workload.py):
jax-free.  The centerpiece is the FIDELITY GATE — the ``soak-replay``
scenario replays scenario_soak's seeded chaos timeline (gray-slow
replica, SIGKILL + autoscaler self-heal, link sever, blue-green
rollout) through the REAL admission/router/containment/registry code on
the virtual clock and must reproduce the soak's qualitative outcomes
(breaker isolation while heartbeat-alive, zero lost requests, retry
amplification <= 1.5, conformant deadline probes) with ZERO real
sleeping — asserted via the sleep-trap fixture, so a policy regression
or a clock-injection regression fails CI deterministically in seconds.
Plus: engine/virtual-clock units, workload synthesis determinism, trace
replay + latency-model fitting, sweep-path overrides, a disaggregated
two-tier sim run, and a slow-marked 1000-replica scale test."""

import json
import random
import time

import pytest

from tfmesos_tpu.fleet.registry import UNIFIED
from tfmesos_tpu.fleet.sim import (FleetSim, ReplicaModel, SimConfig,
                                   SimEngine, VirtualClock,
                                   apply_override, parse_sweep,
                                   run_scenario, run_sweep)
from tfmesos_tpu.fleet.workload import (Request, SyntheticWorkload,
                                        fit_replica_model,
                                        load_trace_export,
                                        replay_from_traces)


@pytest.fixture
def sleep_trap(monkeypatch):
    """Fail the test if ANY real time.sleep executes while a sim runs —
    the no-real-sleeping contract of the virtual clock (a missed clock
    injection would land here)."""
    calls = []

    def trap(seconds):
        calls.append(seconds)
        raise AssertionError(
            f"real time.sleep({seconds}) during a simulation — some "
            f"component is not running on the virtual clock")

    monkeypatch.setattr(time, "sleep", trap)
    return calls


# -- engine units ------------------------------------------------------------


def test_virtual_clock_and_event_order():
    eng = SimEngine(seed=0)
    seen = []
    eng.at(2.0, lambda: seen.append(("b", eng.clock.now)))
    eng.at(1.0, lambda: seen.append(("a", eng.clock.now)))
    eng.at(1.0, lambda: seen.append(("a2", eng.clock.now)))
    eng.run()
    assert seen == [("a", 1.0), ("a2", 1.0), ("b", 2.0)]
    assert eng.clock() == 2.0


def test_engine_fiber_sleep_is_virtual(sleep_trap):
    eng = SimEngine(seed=0)
    out = []

    def body():
        eng.sleep(5.0)
        out.append(eng.clock.now)

    eng.spawn(body, name="t")
    eng.run()
    eng.stop_fibers()
    assert out == [5.0]


def test_engine_run_until_and_stop():
    eng = SimEngine(seed=0)
    ticks = []

    def tick():
        ticks.append(eng.clock.now)
        eng.after(1.0, tick)

    eng.after(1.0, tick)
    eng.run(until=3.5)
    assert ticks == [1.0, 2.0, 3.0]
    assert eng.clock() == 3.5
    eng.run(stop=lambda: len(ticks) >= 5)
    assert len(ticks) == 5


def test_engine_fast_forward_only_when_clear():
    eng = SimEngine(seed=0)
    eng.at(10.0, lambda: None)
    assert not eng.fast_forward(11.0)    # an earlier event exists
    assert eng.fast_forward(10.0)        # heap[0] is not earlier
    assert eng.clock() == 10.0


def test_engine_fiber_crash_surfaces():
    eng = SimEngine(seed=0)

    def body():
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        eng.spawn(body, name="crash")


# -- workload synthesis & replay ---------------------------------------------


def test_synthetic_workload_deterministic_per_seed():
    mk = lambda seed: list(SyntheticWorkload(  # noqa: E731
        n_requests=50, rate=100.0, seed=seed,
        class_mix={"a": 1.0, "b": 3.0}, deadline_ms=500.0))
    one, two, other = mk(7), mk(7), mk(8)
    assert one == two
    assert one != other
    assert len(one) == 50
    assert all(r.deadline_ms == 500.0 for r in one)
    assert all(one[i].at < one[i + 1].at for i in range(49))
    assert {r.cls for r in one} == {"a", "b"}
    # tenant skew: the 3x class dominates
    assert sum(r.cls == "b" for r in one) > sum(r.cls == "a" for r in one)


def test_synthetic_workload_validation():
    with pytest.raises(ValueError):
        SyntheticWorkload(n_requests=0, rate=1.0)
    with pytest.raises(ValueError):
        SyntheticWorkload(n_requests=1, rate=0.0)
    with pytest.raises(ValueError):
        SyntheticWorkload(n_requests=1, rate=1.0, class_mix={"a": 0.0})


def _fake_trace_records():
    return [
        {"trace_id": "t1", "status": "completed", "total_ms": 120.0,
         "ts": 1000.0, "summary": {"cls": "interactive", "tokens": 10,
                                   "ttft_ms": 20.0},
         "spans": [{"component": "gateway", "name": "recv",
                    "prompt_len": 96}]},
        {"trace_id": "t2", "status": "completed", "total_ms": 220.0,
         "ts": 1000.5, "summary": {"cls": "background", "tokens": 20,
                                   "ttft_ms": 20.0}},
        {"trace_id": "t3", "status": "deadline_exceeded",
         "total_ms": 60.0, "ts": 1000.2, "summary": {"cls": "interactive"}},
    ]


def test_replay_from_traces_orders_and_classes():
    reqs = replay_from_traces(_fake_trace_records())
    assert len(reqs) == 3
    assert reqs[0].at == 0.0                    # re-anchored at t=0
    assert [r.cls for r in reqs] == ["interactive", "interactive",
                                     "background"]
    assert reqs[0].prompt_len == 96             # from the recv span
    assert reqs[0].new_tokens == 10
    assert abs(reqs[2].at - 0.5) < 1e-9
    # speedup compresses the arrival timeline
    fast = replay_from_traces(_fake_trace_records(), speedup=5.0)
    assert abs(fast[2].at - 0.1) < 1e-9


def test_fit_replica_model_from_traces():
    fit = fit_replica_model(_fake_trace_records())
    # medians over the two completed records: ttft 20ms; per-token
    # (120-20)/10=10 and (220-20)/20=10.
    assert fit["prefill_base_ms"] == 20.0
    assert fit["decode_ms_per_token"] == 10.0
    assert fit_replica_model([]) == {}


def test_load_trace_export_array_and_jsonl(tmp_path):
    recs = _fake_trace_records()
    arr = tmp_path / "arr.json"
    arr.write_text(json.dumps(recs))
    jl = tmp_path / "lines.json"
    jl.write_text("\n".join(json.dumps(r) for r in recs))
    assert load_trace_export(str(arr)) == recs
    assert load_trace_export(str(jl)) == recs


# -- sweep-path overrides ----------------------------------------------------


def test_apply_override_paths():
    cfg = SimConfig()
    apply_override(cfg, "breaker.latency_factor", "8")
    assert cfg.breaker.latency_factor == 8.0
    apply_override(cfg, "autoscaler.queue_wait_hi_ms", "200")
    assert cfg.autoscaler.queue_wait_hi_ms == 200.0
    apply_override(cfg, "admission.max_queue", "256")
    assert cfg.max_queue == 256
    apply_override(cfg, "budget.token_ratio", "0.5")
    assert cfg.budget_token_ratio == 0.5
    apply_override(cfg, "router.max_retries", "4")
    assert cfg.max_retries == 4
    apply_override(cfg, "model.decode_ms_per_token", "7.5")
    assert cfg.model.decode_ms_per_token == 7.5
    apply_override(cfg, "replicas", "9")
    assert cfg.replicas == 9
    for bad in ("nope.nope", "breaker.nope", "breaker.a.b", "zzz"):
        with pytest.raises(ValueError):
            apply_override(cfg, bad, "1")


def test_parse_sweep():
    assert parse_sweep("breaker.latency_factor=2,4,8") == \
        ("breaker.latency_factor", ["2", "4", "8"])
    for bad in ("x", "=1,2", "a="):
        with pytest.raises(ValueError):
            parse_sweep(bad)


# -- scenarios ---------------------------------------------------------------


def test_steady_scenario_completes_and_is_deterministic(sleep_trap):
    one = run_scenario("steady", n_requests=600, replicas=3, seed=11)
    two = run_scenario("steady", n_requests=600, replicas=3, seed=11)
    assert one["requests"] == 600
    assert one["lost"] == 0
    assert one["completed"] + sum(
        sum(v) for v in one["shed"].values()) == 600
    # Same seed, same virtual timeline: wall-clock keys aside, the
    # results are identical — what makes every scenario a regression
    # gate.
    for k in ("completed", "failed", "retries", "sim_seconds",
              "classes", "shed", "deadline_errors"):
        assert one[k] == two[k], k
    assert one["classes"]["interactive"]["count"] > 0


def test_sim_runs_real_wfq_admission(sleep_trap):
    # A 10x background flood against the weight-8 interactive class:
    # the REAL WFQ keeps interactive p99 well under background p99.
    wl = SyntheticWorkload(
        n_requests=1200, rate=600.0, seed=5,
        class_mix={"interactive": 1.0, "background": 10.0},
        prompt_len=32, new_tokens=16)
    out = run_scenario("steady", replicas=2, seed=5, workload=wl)
    classes = out["classes"]
    assert classes["interactive"]["p99_ms"] <= classes["background"]["p99_ms"]


def test_sweep_rows_share_seed_and_differ_by_knob(sleep_trap):
    rows = run_sweep("steady", "model.decode_ms_per_token", ["2", "20"],
                     n_requests=300, replicas=2, seed=3)
    assert [v for v, _ in rows] == ["2", "20"]
    fast, slow = rows[0][1], rows[1][1]
    assert fast["requests"] == slow["requests"] == 300
    assert fast["classes"]["background"]["p99_ms"] \
        < slow["classes"]["background"]["p99_ms"]


def test_surge_scenario_scales_up_with_real_autoscaler(sleep_trap):
    out = run_scenario("surge", n_requests=2400, replicas=2, seed=4)
    assert out["lost"] == 0
    assert out["autoscaled_to"] > 2, \
        "4x surge never grew the tier through the real autoscaler"
    traj = out["autoscaler_trajectory"]
    assert traj[0]["unified"]["actual"] == 2
    assert traj[-1]["unified"]["actual"] == out["autoscaled_to"]


def test_disagg_two_phase_routing_in_sim(sleep_trap):
    # A prefill tier + decode tier and no unified replicas: the REAL
    # router's disaggregated orchestration (prefill -> raw-frame KV
    # handoff -> decode) must serve every request in the sim too.
    cfg = SimConfig(replicas=0, prefill_replicas=2, decode_replicas=2,
                    seed=9)
    wl = SyntheticWorkload(n_requests=200, rate=200.0, seed=9,
                           class_mix={"interactive": 1.0})
    out = run_scenario("steady", cfg=cfg, workload=wl, seed=9)
    assert out["lost"] == 0
    assert out["completed"] + sum(
        sum(v) for v in out["shed"].values()) == 200


def test_replay_workload_drives_sim(sleep_trap):
    reqs = replay_from_traces(_fake_trace_records() * 40)
    fit = fit_replica_model(_fake_trace_records())
    out = run_scenario("steady", replicas=2, seed=1, workload=reqs,
                       model_fit=fit)
    assert out["requests"] == len(reqs)
    assert out["lost"] == 0


# -- THE FIDELITY GATE -------------------------------------------------------


def test_soak_replay_fidelity_gate(sleep_trap):
    """scenario_soak's seeded chaos timeline through the real
    control plane on the virtual clock: the simulator must reproduce
    the soak's qualitative outcomes, with zero real sleeping."""
    out = run_scenario("soak-replay", seed=20)
    # Gray containment: breaker open on the latency outlier while the
    # registry still reports the victim ALIVE.
    assert out["victim_isolated"], "slow replica never breaker-isolated"
    assert out["victim_alive_while_isolated"], \
        "victim must be heartbeat-alive while breaker-open (that is " \
        "what makes the failure gray)"
    assert out["victim_trip_reason"] == "latency_outlier", \
        out["victim_trip_reason"]
    # Lossless across SIGKILL + self-heal + sever + rollout.
    assert out["lost"] == 0, f"lost {out['lost']} requests"
    assert out["healed"], "autoscaler never relaunched the killed replica"
    # Bounded retry amplification (the retry budget's job).
    assert out["retry_amplification"] <= 1.5, out["retry_amplification"]
    # Deadline probes: explicit deadline_exceeded at ~the deadline.
    assert out["probes_conformant"], out["probe_outcomes"]
    assert out["conformance_violations"] == 0
    # The rollout's drain-migration actually moved in-flight work.
    assert out["migration_reruns"] >= 1


def test_soak_replay_deterministic(sleep_trap):
    one = run_scenario("soak-replay", seed=20)
    two = run_scenario("soak-replay", seed=20)
    for k in ("completed", "retries", "retry_amplification",
              "sim_seconds", "victim", "probe_outcomes"):
        assert one[k] == two[k], k


def test_soak_replay_control_arm_no_breakers(sleep_trap):
    """The control arm of the bench: same seed, same gray fault,
    breakers disabled — the victim is never isolated and interactive
    latency degrades toward the injected delay (proving the mechanism,
    not the workload)."""
    on = run_scenario("soak-replay", seed=20)
    off = run_scenario("soak-replay", seed=20,
                       overrides=[("breakers", "false")])
    assert off["breakers"] is None
    assert not off["victim_isolated"]
    assert off["lost"] == 0             # slow is not lost
    assert off["interactive_p99_ms"] > on["interactive_p99_ms"], \
        (off["interactive_p99_ms"], on["interactive_p99_ms"])


# -- direct FleetSim drive ---------------------------------------------------


def test_fleet_sim_kill_marks_dead_and_retries(sleep_trap):
    cfg = SimConfig(replicas=2, seed=2, workers=2)
    sim = FleetSim(cfg)
    a = sim.add_replica(UNIFIED)
    b = sim.add_replica(UNIFIED)
    sim.start_workers()
    wl = [Request(at=0.01 * i, cls=None, prompt_len=8, new_tokens=4)
          for i in range(40)]
    sim.feed(wl)
    # Kill one replica mid-run: in-flight calls fail over, the
    # registry learns through mark_dead/sweep, nothing is lost.
    sim.engine.at(0.15, lambda: sim.kill(a))
    sim.engine.run(stop=sim.drained)
    assert sim.lost == []
    assert sim.completed == 40
    dead = [r for r in sim.registry.members() if r.addr == a.addr]
    assert not dead or dead[0].state in ("dead",)
    assert b.served > 0
    sim.stop()


def test_fleet_sim_deadline_shed_in_queue(sleep_trap):
    # One slow replica, deadlines far shorter than the backlog: some
    # requests expire IN the WFQ queue and take the explicit
    # deadline_exceeded path (admission's dispatch-time shed).
    cfg = SimConfig(replicas=1, capacity=1, seed=3, workers=1,
                    model=ReplicaModel(decode_ms_per_token=20.0))
    sim = FleetSim(cfg)
    sim.add_replica(UNIFIED)
    sim.start_workers()
    wl = [Request(at=0.001 * i, cls=None, prompt_len=4, new_tokens=16,
                  deadline_ms=100.0) for i in range(30)]
    sim.feed(wl)
    sim.engine.run(stop=sim.drained)
    assert sim.expired_in_queue + sim.deadline_errors > 0
    assert sim.conformance_violations == 0
    assert sim.lost == []
    sim.stop()


def test_virtual_clock_threads_through_every_component(sleep_trap):
    """The multi-layer clock refactor, asserted end-to-end: after a
    sim run, every latency the control plane recorded is VIRTUAL
    (seconds of wall time would show up as tiny millisecond readings;
    virtual service times are tens of ms)."""
    clock = VirtualClock(100.0)
    assert clock() == 100.0
    out = run_scenario("steady", n_requests=400, replicas=2, seed=6)
    lat = out["classes"]["background"]
    assert lat["p50_ms"] and lat["p50_ms"] >= 10.0, \
        "latencies not measured on the virtual clock"
    assert out["sim_seconds"] > 1.0


def test_multi_gateway_scenario_failover_lossless(sleep_trap):
    """The multi-gateway topology (`tfserve --gateways N` at sim
    scale): N gateway fronts over the ONE registry/router view, one
    hard-killed mid-traffic — its queued work fails over to survivors
    and every planned request gets an answer (zero lost), with the
    failover count recorded."""
    out = run_scenario("multi-gateway", n_requests=1500, seed=3)
    assert out["gateways"] == 3
    assert out["lost"] == 0
    assert out["gateway_killed_at"] is not None
    assert out["gateway_failovers"] > 0, \
        "kill landed on an empty queue; the scenario proved nothing"
    # Every planned request was answered: completions + explicit sheds
    # across ALL fronts reconcile with the arrivals.
    shed_total = sum(sum(v) for d in out["per_front_shed"]
                     for v in d.values())
    assert out["completed"] + out["failed"] + shed_total \
        >= out["requests"]


def test_multi_gateway_deterministic(sleep_trap):
    one = run_scenario("multi-gateway", n_requests=900, seed=7)
    two = run_scenario("multi-gateway", n_requests=900, seed=7)
    for k in ("completed", "failed", "gateway_failovers",
              "sim_seconds"):
        assert one[k] == two[k], (k, one[k], two[k])


@pytest.mark.slow
def test_scale_1000_replicas(sleep_trap):
    """The scale claim at CI-affordable size: 1000 simulated replicas,
    50k requests through the real control plane, zero lost, at a
    throughput floor that catches per-request cost regressions."""
    t0 = time.perf_counter()
    out = run_scenario("scale", n_requests=50_000, replicas=1000, seed=0)
    wall = time.perf_counter() - t0
    assert out["lost"] == 0
    assert out["completed"] + sum(
        sum(v) for v in out["shed"].values()) == 50_000
    assert len(random.sample(range(1000), 2)) == 2   # sanity: stdlib rng
    assert out["sim_events_per_sec"] > 5000, out["sim_events_per_sec"]
    assert wall < 30.0, f"50k-request scale smoke took {wall:.1f}s"


# -- KV tiering & sessions (PR 13) -------------------------------------------


def test_sessions_scenario_park_resume_at_scale(sleep_trap):
    """The ``sessions`` scenario: multi-turn conversations resume from
    the host-shared tier (later turns prefill only their tails), a
    mid-run replica kill loses nothing (the tier is host-shared), and
    resumed turns are strictly cheaper than cold full-history
    prefills.  Deterministic per seed."""
    out = run_scenario("sessions", n_requests=800, replicas=3,
                       turns=4, seed=7)
    assert out["lost"] == 0
    assert out["completed"] == out["requests"]
    # 4 turns -> at most 3/4 of turns can resume; most of them must.
    assert 0.5 < out["kv_tier_hit_rate"] <= 0.75
    assert out["resumed_ttft_mean_ms"] < out["cold_ttft_mean_ms"]
    assert out["sessions_parked"] == 200
    two = run_scenario("sessions", n_requests=800, replicas=3,
                       turns=4, seed=7)
    for k in ("completed", "kv_tier_hit_rate", "resumed_ttft_mean_ms",
              "sim_seconds"):
        assert two[k] == out[k], k


def test_sessions_cross_host_placement_replication_survives_the_kill(
        sleep_trap):
    """The fabric fidelity contract: with per-host tiers and K-way
    rendezvous placement (``kv_replication`` — the REAL fabric's
    placement function), replication=2 rides out the scenario's
    mid-run hard kill with ZERO host-loss misses (surviving copies
    forward, at a wire cost, not a recompute), while replication=1
    loses every session parked only on the dead host."""
    r2 = run_scenario("sessions", [("kv_replication", "2")],
                      n_requests=800, replicas=3, turns=4, seed=7)
    assert r2["kv_replication"] == 2
    assert r2["lost"] == 0
    st2 = r2["session_tier"]
    assert st2["host_loss_miss"] == 0
    assert st2["forwarded"] > 0         # resumes landed off-parker
    # Forwarded resumes pay the wire, not a re-prefill: still strictly
    # cheaper than cold full-history turns.
    assert r2["resumed_ttft_mean_ms"] < r2["cold_ttft_mean_ms"]
    r1 = run_scenario("sessions", [("kv_replication", "1")],
                      n_requests=800, replicas=3, turns=4, seed=7)
    st1 = r1["session_tier"]
    assert st1["host_loss_miss"] > 0    # sole copy died with its host
    assert r1["lost"] == 0              # lossy tier, never lost work
    assert r1["kv_tier_hit_rate"] < r2["kv_tier_hit_rate"]
    # Deterministic per seed, like every scenario.
    again = run_scenario("sessions", [("kv_replication", "2")],
                         n_requests=800, replicas=3, turns=4, seed=7)
    assert again["kv_tier_hit_rate"] == r2["kv_tier_hit_rate"]
    assert again["session_tier"] == st2


def test_sessions_kv_replication_sweep(sleep_trap):
    """``--sweep kv_replication=1,3`` prices the placement policy on
    the virtual clock: more copies, fewer host-loss misses."""
    rows = run_sweep("sessions", "kv_replication", ["1", "3"],
                     n_requests=400, replicas=3, turns=4, seed=7)
    assert len(rows) == 2
    for val, res in rows:
        assert res["kv_replication"] == int(val)
        assert res["lost"] == 0
    assert rows[1][1]["session_tier"]["host_loss_miss"] \
        <= rows[0][1]["session_tier"]["host_loss_miss"]
    assert rows[1][1]["kv_tier_hit_rate"] \
        >= rows[0][1]["kv_tier_hit_rate"]


def test_sessions_version_fence_in_sim(sleep_trap):
    """A session parked under v1 must NOT resume on a v2 replica: the
    sim tier's version check mirrors the store's stamp fence."""
    cfg = SimConfig(replicas=1, workers=4, seed=3)
    sim = FleetSim(cfg)
    sim.add_replica(UNIFIED, weights_version="v1")
    sim.start_workers()
    sim.feed([Request(at=0.0, cls=None, prompt_len=32, new_tokens=8,
                      session="c")])
    sim.engine.run(stop=sim.drained)
    assert sim.transport.session_stats["park"] == 1
    # Roll the fleet: v2 replica takes over, the parked v1 entry must
    # read as a version miss (cold re-prefill, never stale KV).
    v2 = sim.add_replica(UNIFIED, weights_version="v2")
    sim.router.set_preferred_version("v2")
    sim.feed([Request(at=sim.engine.clock.now + 0.1, cls=None,
                      prompt_len=96, new_tokens=8, session="c")])
    sim.engine.run(stop=sim.drained)
    st = sim.transport.session_stats
    assert st["version_miss"] == 1 and st["resume"] == 0
    assert sim.lost == []
    assert v2.served >= 1
    sim.stop()


def test_sim_migration_carries_artifact_bytes(sleep_trap):
    """Drain migration in the sim now answers with a RAW-FRAME KV
    artifact (sized from the replica model) that the router's real
    ``_resume_elsewhere`` re-places on a same-version survivor —
    counted ``migration_resumes``, not the requeue-marker re-run path
    PR 11 stopped at — and the resumed call decodes only its
    remaining tokens."""
    cfg = SimConfig(replicas=2, workers=0, seed=5)
    sim = FleetSim(cfg)
    victim = sim.add_replica(UNIFIED)
    survivor = sim.add_replica(UNIFIED)
    eng = sim.engine
    results = []

    def body():
        sink = []
        f = sim.submit(Request(at=0.0, cls=None, prompt_len=64,
                               new_tokens=200, deadline_ms=None),
                       sink=sink)
        assert f
        item = sim.admission.get(timeout=0)
        results.append(sim.dispatch(item))

    # Pin the first pick onto the victim by making the survivor look
    # loaded at dispatch time, then migrate the victim mid-request.
    eng.spawn(body, name="caller")
    eng.at(0.001, lambda: sim.request_migration(victim.addr))
    eng.run(stop=lambda: len(results) == 1)
    reply = results[0]
    assert isinstance(reply, dict) and reply.get("op") == "completion"
    resumes = sim.metrics.get("migration_resumes")
    reruns = sim.metrics.get("migration_reruns")
    assert resumes >= 1, (resumes, reruns)
    assert reruns == 0
    assert sim.metrics.get("migration_exports") >= 1
    sim.stop()


def test_sessions_scenario_rejects_nothing_and_sweeps():
    """The scenario is addressable from the sweep surface like every
    other (``tfserve simulate sessions --sweep model...``)."""
    rows = run_sweep("sessions", "model.prefill_ms_per_token",
                     ["0.05", "0.4"], n_requests=200, replicas=2,
                     turns=2, seed=1)
    assert len(rows) == 2
    for _, res in rows:
        assert res["lost"] == 0


# -- the model catalog at sim scale (PR 15) ----------------------------------


def test_multi_model_scenario_trades_without_thrash(sleep_trap):
    """The ``multi-model`` scenario: skewed two-model traffic flips
    hotness mid-run against a FIXED replica budget and the REAL
    ModelTrader must converge — the heated model ends with more
    replicas than it booted, the idle model scales to zero, a late
    request for it cold-starts through the warm pool, trades stay
    BOUNDED (no thrash), and nothing is lost.  Deterministic per
    seed."""
    out = run_scenario("multi-model", n_requests=6000, seed=7)
    assert out["failed"] == 0 and out["lost"] == 0
    # The post-flip hot model booted 1 replica; trading must have
    # grown it within the fixed budget.
    assert out["post_flip_hot_actual"] > 1
    assert out["trades"] >= 1
    # Convergence, not thrash: a flapping trader would churn a trade
    # per cooldown window for the whole run (dozens at this length).
    assert out["trades"] <= 6
    assert out["scale_to_zero"] >= 1
    # The scaled-to-zero model's late request completed through the
    # warm-pool demand path — never an error.
    assert out["cold_start"]["completed"]
    assert out["cold_starts"] >= 1
    two = run_scenario("multi-model", n_requests=6000, seed=7)
    for k in ("completed", "trades", "post_flip_hot_actual",
              "scale_to_zero", "sim_seconds"):
        assert two[k] == out[k], k


def test_multi_model_sweep_reaches_trader_constants(sleep_trap):
    """``--sweep trader.zero_after_ticks=...`` (and every other
    catalog/trader constant) resolves by dotted path — the promoted-
    constant discipline of PR 11 extended to the new knobs."""
    rows = run_sweep("multi-model", "trader.zero_after_ticks",
                     ["4", "1000000"], n_requests=1500, seed=3)
    assert len(rows) == 2
    for _, res in rows:
        assert res["failed"] == 0 and res["lost"] == 0
    # The knob is live: an effectively-infinite idle threshold never
    # scales the idle model to zero, the small one does.
    assert rows[0][1]["scale_to_zero"] >= 1
    assert rows[1][1]["scale_to_zero"] == 0


def test_apply_override_trader_and_catalog_paths():
    cfg = SimConfig()
    apply_override(cfg, "trader.trade_cooldown_s", "9.5")
    assert cfg.trader.trade_cooldown_s == 9.5
    apply_override(cfg, "trader.zero_after_ticks", "4")
    assert cfg.trader.zero_after_ticks == 4
    apply_override(cfg, "catalog.warm_pool", "2")
    assert cfg.warm_pool == 2
    apply_override(cfg, "catalog.budget", "7")
    assert cfg.model_budget == 7
    with pytest.raises(ValueError):
        apply_override(cfg, "trader.nope", "1")


def test_gang_scenario_member_kill_reforms_lossless(sleep_trap):
    """The ``gang`` scenario: a unified tier of pod-slice gangs, one
    member hard-killed mid-run — the gang dies WHOLE (never a smaller
    gang), its in-flight work replays on the survivors with zero lost
    requests, and after ``gang_reform_s`` the fleet ends with the
    booted gang count again.  Deterministic per seed."""
    out = run_scenario("gang", n_requests=400, replicas=3, seed=7)
    assert out["lost"] == 0 and out["failed"] == 0
    assert out["completed"] == out["requests"]
    assert out["gang_size"] == 4
    assert out["gang_deaths"] == 1
    assert out["gang_reforms"] == 1
    assert out["gangs_actual"] == 3             # whole again
    gs = out["gang_summary"]
    assert gs["gangs"] == 3 and gs["members"] == 12 and gs["live"] == 12
    two = run_scenario("gang", n_requests=400, replicas=3, seed=7)
    for k in ("completed", "gang_deaths", "gang_reforms",
              "sim_seconds"):
        assert two[k] == out[k], k


def test_gang_model_divides_per_token_costs_only():
    from tfmesos_tpu.fleet.sim import gang_model

    base = ReplicaModel(prefill_ms_per_token=10.0,
                        decode_ms_per_token=4.0)
    g = gang_model(base, 4, 0.85)
    assert g.prefill_ms_per_token == pytest.approx(10.0 / 3.4)
    assert g.decode_ms_per_token == pytest.approx(4.0 / 3.4)
    # The per-request base and the whole-artifact KV bytes do NOT
    # shrink — the slice speeds up compute, not the fixed costs.
    assert g.prefill_base_ms == base.prefill_base_ms
    assert g.kv_bytes_per_token == base.kv_bytes_per_token
    # A 1-gang is the single-process model, and efficiency never makes
    # a gang SLOWER than one process.
    assert gang_model(base, 1, 0.85) is base
    assert gang_model(base, 2, 0.1).decode_ms_per_token \
        == base.decode_ms_per_token


def test_gang_sweep_and_cross_host_knob(sleep_trap):
    """``--sweep gang_size=...`` flows through apply_override into the
    gang scenario, and the sessions scenario's cross_host_resume knob
    models gang-parked sharded sessions landing on a different host
    (1.0 = today's host-shared tier, exactly the pre-knob behavior)."""
    rows = run_sweep("gang", "gang_size", ["2", "8"],
                     n_requests=300, replicas=2, seed=3)
    assert len(rows) == 2
    for val, res in rows:
        assert res["lost"] == 0
        assert res["gang_size"] == int(val)
    # The bigger slice decodes faster under the same offered load.
    assert rows[1][1]["classes"]["interactive"]["p50_ms"] \
        <= rows[0][1]["classes"]["interactive"]["p50_ms"]

    full = run_scenario("sessions", [("cross_host_resume", "1.0")],
                        n_requests=400, replicas=3, turns=4, seed=7)
    assert full["session_tier"]["cross_host_miss"] == 0
    lossy = run_scenario("sessions", [("cross_host_resume", "0.5")],
                         n_requests=400, replicas=3, turns=4, seed=7)
    assert lossy["cross_host_resume"] == 0.5
    assert lossy["session_tier"]["cross_host_miss"] > 0
    assert lossy["kv_tier_hit_rate"] < full["kv_tier_hit_rate"]
    assert lossy["lost"] == 0


# -- diurnal workload + 10k-scale scenario ----------------------------------


def test_diurnal_workload_deterministic_and_shaped():
    """Same seed -> byte-identical arrival stream; the sinusoidal
    envelope actually shapes it (the peak half-period carries more
    arrivals than the trough half); bursts densify their windows."""
    from tfmesos_tpu.fleet.workload import DiurnalWorkload

    def draw():
        return list(DiurnalWorkload(
            2000, base_rate=50.0, seed=11, period_s=200.0,
            peak_ratio=4.0, phase=0.0, bursts=2, burst_ratio=3.0,
            burst_duration_s=5.0,
            class_mix={"interactive": 3.0, "background": 1.0}))

    a, b = draw(), draw()
    assert [(r.at, r.cls, r.prompt_len, r.new_tokens) for r in a] \
        == [(r.at, r.cls, r.prompt_len, r.new_tokens) for r in b]
    assert all(a[i].at <= a[i + 1].at for i in range(len(a) - 1))
    assert {r.cls for r in a} == {"interactive", "background"}
    n_int = sum(1 for r in a if r.cls == "interactive")
    assert 0.6 < n_int / len(a) < 0.9       # ~3:1 mix
    # envelope(t) peaks over [0, period/2) with phase 0 and troughs
    # over [period/2, period): the first full period must be lopsided.
    wl = DiurnalWorkload(4000, base_rate=50.0, seed=3, period_s=100.0,
                         peak_ratio=8.0, phase=0.0)
    arr = [r.at for r in wl]
    peak_half = sum(1 for t in arr if t % 100.0 < 50.0)
    trough_half = sum(1 for t in arr if t % 100.0 >= 50.0)
    assert peak_half > 1.5 * trough_half, (peak_half, trough_half)


def test_diurnal_workload_burst_majorant_exact():
    """The piecewise-constant thinning majorant is EXACT: the realized
    in-burst arrival rate tracks burst_ratio x the out-of-burst rate
    (a leaky bound here would under-sample bursts), and rate_at
    agrees with the declared envelope algebra."""
    from tfmesos_tpu.fleet.workload import DiurnalWorkload

    wl = DiurnalWorkload(20000, base_rate=100.0, seed=5,
                         period_s=1e9,      # flat envelope: sin ~ 0
                         peak_ratio=1.0, bursts=3, burst_ratio=5.0,
                         burst_duration_s=10.0)
    rng = random.Random(5)
    windows = wl._burst_windows(rng, 20000 / 100.0)
    assert wl.rate_at(windows[0][0], windows) == \
        pytest.approx(5.0 * wl.rate_at(windows[0][1] + 1e-6, windows),
                      rel=1e-6)
    arr = [r.at for r in wl]
    span = arr[-1]
    in_w = sum(1 for t in arr
               if any(lo <= t < hi for lo, hi in windows))
    w_len = sum(min(hi, span) - min(lo, span) for lo, hi in windows)
    out_rate = (len(arr) - in_w) / max(1e-9, span - w_len)
    in_rate = in_w / max(1e-9, w_len)
    assert 3.5 < in_rate / out_rate < 6.5, (in_rate, out_rate)


def test_fit_diurnal_recovers_envelope():
    """fit_diurnal round-trips a synthetic diurnal trace: the fitted
    peak_ratio and phase land near the generating constants."""
    from tfmesos_tpu.fleet.workload import DiurnalWorkload, fit_diurnal

    # base 40/s, mean envelope 2.5x -> ~100/s: 20k arrivals span
    # ~200s, i.e. one full cycle (what the fitter assumes it caught).
    wl = DiurnalWorkload(20000, base_rate=40.0, seed=9,
                         period_s=200.0, peak_ratio=4.0, phase=0.0)
    records = [{"ts": r.at} for r in wl]
    # The export caught one full cycle; tell the fitter the period.
    fit = fit_diurnal(records, period_s=200.0)
    assert fit["period_s"] == 200.0
    assert 2.0 < fit["peak_ratio"] < 8.0
    # phase 0 peaks at t = period/4 = 50; the fitted phase must put
    # the crest within a bin or two of that.
    import math
    crest = (math.pi / 2 - fit["phase"]) * 200.0 / (2 * math.pi)
    assert abs(crest % 200.0 - 50.0) < 20.0, fit
    assert fit_diurnal([]) == {}
    assert fit_diurnal([{"ts": 1.0}]) == {}


def test_hb_shards_same_outcome_as_per_replica_beats(sleep_trap):
    """Sharded heartbeats are an EVENT-COUNT optimization, not a
    behavior change: same completions, zero lost, and a replica that
    stops beating inside a shard still goes dead and gets evicted."""
    plain = run_scenario("steady", n_requests=400, replicas=4, seed=21)
    sharded = run_scenario("steady", [("hb_shards", "2")],
                           n_requests=400, replicas=4, seed=21)
    assert sharded["lost"] == 0
    assert sharded["completed"] == plain["completed"] == 400
    # Liveness detection through a shard: a silenced member is marked
    # dead by the same suspect/dead sweep cadence.
    cfg = SimConfig(replicas=3, seed=4, workers=2, hb_shards=2)
    sim = FleetSim(cfg)
    reps = [sim.add_replica(UNIFIED) for _ in range(3)]
    sim.start_workers()
    sim.feed([Request(at=0.01 * i, cls=None, prompt_len=8,
                      new_tokens=4) for i in range(30)])
    sim.engine.at(0.2, lambda: sim.kill(reps[0]))
    sim.engine.run(stop=sim.drained)
    assert sim.lost == []
    assert sim.completed == 30
    dead = [r for r in sim.registry.members()
            if r.addr == reps[0].addr]
    assert not dead or dead[0].state == "dead"
    sim.stop()


def test_sim_kv_placement_loaded_diverts_from_hot_tiers(sleep_trap):
    """The placement=loaded knob mirrors KVFabric._order's occupancy
    buckets: on a balanced fleet it matches rendezvous exactly (stable
    sort on equal buckets), and under skew it diverts the peer copy
    off the loaded tier rendezvous would have picked."""
    cfg = SimConfig(replicas=5, seed=6, workers=2, kv_replication=2)
    sim = FleetSim(cfg)
    reps = [sim.add_replica(UNIFIED) for _ in range(5)]
    tr = sim.transport
    tr.kv_replication = 2       # scenarios wire this from cfg
    sid = "sess-42"
    balanced = tr._place(sid, reps[0].addr)
    tr.kv_placement = "loaded"
    assert tr._place(sid, reps[0].addr) == balanced, \
        "loaded placement must equal rendezvous on a balanced fleet"
    # Skew: rendezvous's pick is nearly full, everyone else is empty.
    tr._tier_load[balanced[1]] = reps[1].kv_pages
    skewed = tr._place(sid, reps[0].addr)
    assert skewed[0] == balanced[0] == reps[0].addr   # parker pinned
    assert skewed[1] != balanced[1], \
        "a full tier still won the peer copy under placement=loaded"
    sim.stop()


def test_sessions_kv_placement_sweep(sleep_trap):
    """`--sweep kv_placement=rendezvous,loaded` flows through the
    sessions scenario: both arms run lossless, record their knob, and
    publish the copy-occupancy telemetry the sweep compares."""
    rows = run_sweep("sessions", "kv_placement",
                     ["rendezvous", "loaded"],
                     [("kv_replication", "2")],
                     n_requests=300, replicas=3, turns=3, seed=8)
    assert len(rows) == 2
    for val, res in rows:
        assert res["lost"] == 0
        assert res["kv_placement"] == val
        assert res["kv_copy_load_max"] >= res["kv_copy_load_mean"] > 0


def test_scenario_diurnal_smoke_deterministic(sleep_trap):
    """The 10k-replica scenario, scaled down to CI size: a diurnal
    workload over sharded heartbeats and the slower 10k cadence runs
    lossless, publishes the floor key, and is deterministic per seed."""
    out = run_scenario("diurnal", n_requests=600, replicas=40, seed=17)
    again = run_scenario("diurnal", n_requests=600, replicas=40,
                         seed=17)
    assert out["lost"] == 0
    assert out["completed"] > 0
    assert out["completed"] == again["completed"]
    assert out["shed"] == again["shed"]
    assert out["sim_events_per_sec_10k"] == out["sim_events_per_sec"]
    assert out["hb_shards"] == 64
    # The slow 10k cadence holds unless overridden per knob.
    slow = run_scenario("diurnal", [("hb_interval", "1.0")],
                        n_requests=200, replicas=10, seed=17)
    assert slow["lost"] == 0


def _total_shed(res):
    return sum(sum(t) for t in res["shed"].values())


def test_diurnal_sweep_rows_differ_in_expected_direction(sleep_trap):
    """Sweeps over the diurnal scenario's front-door knobs actually
    bite (regression: the raw override-path scan used to clobber an
    ``admission.max_queue`` sweep row back to the scenario default —
    the alias-aware ``swept()`` guard keeps it): a tighter admission
    bound sheds MORE of the crest, and more gateway processes spread
    the same crest over more queues and shed LESS."""
    rows = dict(run_sweep("diurnal", "admission.max_queue",
                          ["8", "4096"],
                          n_requests=600, replicas=40, seed=17))
    assert _total_shed(rows["8"]) > _total_shed(rows["4096"]) == 0
    assert rows["8"]["completed"] < rows["4096"]["completed"]
    # Both arms still lossless — shed is an explicit answer, not loss.
    assert rows["8"]["lost"] == rows["4096"]["lost"] == 0
    rows = dict(run_sweep("diurnal", "gateways", ["1", "4"],
                          [("admission.max_queue", "8")],
                          n_requests=600, replicas=40, seed=17))
    assert _total_shed(rows["4"]) < _total_shed(rows["1"])
    assert rows["4"]["completed"] > rows["1"]["completed"]


def test_scenario_offline_lane_harvests_idle_capacity(sleep_trap):
    """The offline lane's acceptance at sim scale: with the batch lane
    ON, fleet utilization is STRICTLY higher (the backlog harvests the
    diurnal trough), interactive p99 holds, nothing is lost, and the
    whole batch backlog completes; batch_slot_frac prices the split —
    a bigger batch share harvests more without moving interactive
    p99."""
    rows = dict(run_sweep("offline-lane", "batch_lane",
                          ["false", "true"],
                          n_requests=600, replicas=3, seed=13))
    off, on = rows["false"], rows["true"]
    assert on["utilization"] > off["utilization"]
    assert on["classes"]["interactive"]["p99_ms"] \
        <= off["classes"]["interactive"]["p99_ms"]
    assert on["lost"] == off["lost"] == 0
    assert on["batch_planned"] == 300 and off["batch_planned"] == 0
    assert on["completed"] == off["completed"] + on["batch_planned"]
    # The lane yielded under the crest: the slot cap deferred batch
    # dispatches instead of letting them dilute interactive service.
    assert on["batch_deferrals"] > 0
    assert on["classes"]["batch"]["count"] == 300
    # The split knob: more batch share -> strictly more utilization,
    # interactive p99 unmoved (the lane only ever takes leftovers).
    fr = dict(run_sweep("offline-lane", "batch_slot_frac",
                        ["0.25", "0.75"],
                        n_requests=600, replicas=3, seed=13))
    assert fr["0.75"]["utilization"] > fr["0.25"]["utilization"]
    assert fr["0.75"]["classes"]["interactive"]["p99_ms"] \
        == fr["0.25"]["classes"]["interactive"]["p99_ms"]
    # Determinism per seed (the sweep's comparison contract).
    again = run_scenario("offline-lane", [("batch_lane", "true")],
                         n_requests=600, replicas=3, seed=13)
    assert again["completed"] == on["completed"]
    assert again["utilization"] == on["utilization"]
