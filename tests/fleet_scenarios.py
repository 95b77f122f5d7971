"""Fleet scenarios: end-to-end protocols over local CPU replicas, the
simulator or a loopback socket that ASSERT the fleet's contracts (zero
lost requests under a kill, breaker isolation, exactly-once replay).

Each returns the figures its smoke in ``tests/test_fleet_scenarios.py``
pins shapes and directions on.  They are counts and timings of CPU
replicas: tests, not measurements of anything a chip runs (the
benchmark is ``benchmark/run.py``).
"""

import json
import time

import numpy as np


def _p99(vals):
    """Rank-index p99 shared by the fleet scenarios (priority, soak,
    trace overhead) — ONE estimator, so the scenarios cannot silently
    disagree about rounding."""
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(0.99 * len(vals)))]


def scenario_prefix_affinity(n_requests=24, replicas=2, rows=4,
                                n_prefixes=2, max_new_tokens=6,
                                workers=8):
    """Prefix-affinity routing through the full fleet front door:
    replicas run cross-request prefix caches and advertise them on
    heartbeats; the gateway steers each shared system prompt to the
    replica already holding it.  Reports the affinity hit rate (routing
    decisions that found a cached favorite) and warm requests/s."""
    import threading

    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    rng = np.random.default_rng(3)
    page = 16
    systems = [rng.integers(0, 97, size=(2 * page,)).astype(np.int32)
               for _ in range(n_prefixes)]
    fleet = FleetServer(replicas=replicas, rows=rows, tiny=True,
                        max_len=64, page_size=page, prefill_bucket=page,
                        prefix_cache_pages=32, workers=workers,
                        max_queue=max(64, 2 * n_requests),
                        start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)

        def run_batch(prompts):
            results = [None] * len(prompts)

            def one(i):
                results[i] = client.generate(prompts[i], max_new_tokens)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return results

        def prompts(n, seed):
            r2 = np.random.default_rng(seed)
            return [np.concatenate(
                        [systems[i % n_prefixes],
                         r2.integers(0, 97, size=(4,)).astype(np.int32)])
                    for i in range(n)]

        # Prime: compiles + seeds every replica's cache, then give the
        # heartbeats a beat to advertise the summaries.
        run_batch(prompts(2 * replicas, seed=5))
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        t0 = time.perf_counter()
        results = run_batch(prompts(n_requests, seed=6))
        dt = time.perf_counter() - t0
        assert all(r is not None for r in results)
        snap = fleet.snapshot()["counters"]
        hits = snap.get("affinity_hits", 0)
        misses = snap.get("affinity_misses", 0)
        hit_rate = hits / max(1, hits + misses)
        client.close()
        return hit_rate, n_requests / dt
    finally:
        fleet.stop()


def scenario_sessions(replicas=2, rows=4, turns=4, n_shared=8,
                         workers=8, max_new_tokens=8):
    """The fleet-wide KV economy (docs/SERVING.md "KV tiering &
    sessions"), both halves asserted in the scenario:

    * SESSIONS — a multi-turn conversation on a KV-tiered fleet: each
      turn's full-history prompt is served twice, once cold (no
      session label — the whole history prefills) and once resumed
      (``session=`` — the parked turn's KV imports and only the new
      tail prefills, routed to the parker by session affinity).
      Resumed TTFT must be STRICTLY below cold, and the streams
      TOKEN-IDENTICAL (the uninterrupted-reference equivalence bar).
    * SHARED PREFIXES as a CLUSTER resource — a common system prompt
      on a prefix-cached fleet must be prefilled ONCE PER FLEET
      (router-directed seeding: affinity steers every later request to
      the replica already holding the pages), asserted by summing
      per-replica prefix-cache misses off the heartbeat summaries.

    Reports (resumed_ttft_ms, cold_ttft_ms, kv_tier_hit_rate,
    shared_prefix_prefills, shared_affinity_hit_rate)."""
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    from tfmesos_tpu.fleet.kvtier import KVTierStore
    from tfmesos_tpu.serving import ContinuousBatcher, Request

    page = 16
    rng = np.random.default_rng(11)

    # -- Part A: session resume vs cold full-history prefill, on the
    # FLAGSHIP shape (the win IS skipped prefill compute — the tiny
    # model's prefill is too cheap to measure; fleet costs are covered
    # by part A2 below) in FLOAT32: the equivalence bar is exact token
    # equality, and bfloat16 argmax ties can flip between the fused
    # cold prefill and the resume path's tail chunk writer (the same
    # documented caveat chunked prefill carries).  One batcher serves
    # both arms: unlabeled requests prefill the whole history,
    # session-labeled ones resume from the tier; a priming
    # conversation of the same turn lengths warms every compile first,
    # so neither arm's TTFT carries a trace.
    import jax
    import jax.numpy as jnp
    from tfmesos_tpu.models import transformer as _tfm

    max_len = 1024
    cfg = _tfm.TransformerConfig(
        vocab_size=8192, d_model=512, n_layers=8, n_heads=8, d_ff=1408,
        max_seq_len=max_len, dtype=jnp.float32)
    params = _tfm.init_params(cfg, jax.random.PRNGKey(0))
    fpage, sys_len, user_len, new = 64, 448, 64, 32
    tier = KVTierStore(ram_bytes=256 << 20, token="bench")
    b = ContinuousBatcher(cfg, params, rows=2, max_len=max_len,
                          page_size=fpage, prefill_bucket=fpage,
                          kv_tier=tier)

    def conversation(sid, seed, measure):
        r2 = np.random.default_rng(seed)
        hist = [int(t) for t in r2.integers(0, cfg.vocab_size,
                                            size=(sys_len,))]
        (c,) = list(b.run([Request(np.asarray(hist, np.int32), new,
                                   session_id=sid)]))
        res_t, cold_t = [], []
        for _ in range(turns):
            hist += [int(t) for t in c.tokens]
            hist += [int(t) for t in r2.integers(0, cfg.vocab_size,
                                                 size=(user_len,))]
            prompt = np.asarray(hist, np.int32)
            (cold,) = list(b.run([Request(prompt, new)]))
            (c,) = list(b.run([Request(prompt, new, session_id=sid)]))
            if measure:
                assert c.tokens == cold.tokens, \
                    "resumed stream diverged from the cold reference"
                cold_t.append(1000.0 * cold.ttft_s)
                res_t.append(1000.0 * c.ttft_s)
        return res_t, cold_t

    conversation("prime", seed=98, measure=False)   # compiles only
    resumed_ttfts, cold_ttfts = conversation("bench", seed=99,
                                             measure=True)
    assert tier.stats()["resume"] >= 2 * turns, tier.stats()
    resumed_med = sorted(resumed_ttfts)[len(resumed_ttfts) // 2]
    cold_med = sorted(cold_ttfts)[len(cold_ttfts) // 2]
    assert resumed_med < cold_med, \
        (f"session resume-from-tier TTFT ({resumed_med:.2f}ms) not "
         f"below cold full-history prefill ({cold_med:.2f}ms)")

    # -- Part A2: the same contract through the FLEET front door on
    # the tiny CI model — resumed streams token-identical over the
    # wire, the tier counters aggregated off heartbeats into the
    # gateway's kv_tier gauge, and session affinity routing the turn
    # to the parker.  (Latency is asserted in part A where prefill
    # compute is measurable; fleet hops would drown a tiny model's.)
    fleet = FleetServer(replicas=replicas, rows=rows, tiny=True,
                        max_len=128, page_size=page, prefill_bucket=page,
                        kv_tier_mb=64, warmup=True, workers=workers,
                        max_queue=128, start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        hist = [int(t) for t in rng.integers(0, 97, size=(40,))]
        out = client.generate(np.asarray(hist, np.int32),
                              max_new_tokens, session="bench")
        for _ in range(turns):
            hist += [int(t) for t in out["tokens"]]
            hist += [int(t) for t in rng.integers(0, 97, size=(8,))]
            prompt = np.asarray(hist, np.int32)
            cold = client.generate(prompt, max_new_tokens)
            out = client.generate(prompt, max_new_tokens,
                                  session="bench")
            assert out["tokens"] == cold["tokens"], \
                "fleet resumed stream diverged from the cold reference"
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        kt = fleet.snapshot()["gauges"].get("kv_tier") or {}
        hits = kt.get("hits", 0)
        misses = kt.get("misses", 0)
        hit_rate = hits / max(1, hits + misses)
        assert kt.get("resume", 0) >= turns, \
            f"the fleet tier never resumed: {kt}"
        client.close()
    finally:
        fleet.stop()

    # -- Part B: the shared prefix as a fleet resource.
    system = rng.integers(0, 97, size=(2 * page,)).astype(np.int32)
    fleet = FleetServer(replicas=replicas, rows=rows, tiny=True,
                        max_len=96, page_size=page, prefill_bucket=page,
                        prefix_cache_pages=32, kv_tier_mb=64,
                        warmup=True, workers=workers, max_queue=128,
                        start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)

        def shared_prompt():
            return np.concatenate(
                [system, rng.integers(0, 97, size=(4,)).astype(np.int32)])

        # ONE priming request publishes the prefix somewhere; the next
        # heartbeat advertises it, and affinity steers everything else
        # there — the fleet prefills the common prompt exactly once.
        client.generate(shared_prompt(), max_new_tokens)
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        for _ in range(n_shared):
            client.generate(shared_prompt(), max_new_tokens)
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        stats = [(r.prefix or {}).get("stats") or {}
                 for r in fleet.registry.members()]
        prefills = sum(s.get("misses", 0) for s in stats)
        total_hits = sum(s.get("hits", 0) for s in stats)
        assert prefills == 1, \
            (f"the shared prefix must prefill ONCE per fleet "
             f"(router-directed seeding), saw {prefills} cold "
             f"prefills across {replicas} replicas: {stats}")
        assert total_hits >= n_shared, stats
        snap = fleet.snapshot()["counters"]
        ah = snap.get("affinity_hits", 0)
        am = snap.get("affinity_misses", 0)
        aff_rate = ah / max(1, ah + am)
        client.close()
    finally:
        fleet.stop()
    return resumed_med, cold_med, hit_rate, prefills, aff_rate


def scenario_fabric(replicas=3, rows=2, workers=8, n_sessions=6,
                       max_new_tokens=4, n_transfers=24,
                       artifact_mb=1.0, seed=21):
    """The cross-host KV fabric (docs/SERVING.md "Cross-host KV
    fabric"), both halves asserted in the scenario:

    * DIRECT vs RELAY streaming — the same artifact workload (seeded
      ~1 MB session blobs over raw HMAC frames) pushed straight to a
      peer's fabric surface versus through an intermediary hop (what
      the router-relay fallback costs: the body crosses the wire
      twice).  ``fleet_kv_transfer_mb_per_sec`` is the direct rate,
      asserted STRICTLY above ``fleet_kv_relay_mb_per_sec`` on the
      same workload.
    * HOST-LOSS-PROOF RESUME — a tiny fleet with ``--kv-replication
      2`` plus one dedicated ``--role kv`` holder: every park lands a
      replicated copy on the holder (kv-role peers are the preferred
      replica targets), the serving replica with the most parked
      primaries is SIGKILLed whole, and every session's next turn
      resumes on a survivor — the victim's primaries through a DIRECT
      fabric fetch of the holder's copy (the holder serves no
      generates, so affinity cannot shortcut the wire path) — with
      streams token-identical to a cold reference: ZERO lost sessions
      and at least one forwarded fetch hit, asserted in the scenario.

    Reports (direct_mb_s, relay_mb_s, resumed_sessions,
    fabric_fetch_hits)."""
    from tfmesos_tpu.backends.local import LocalBackend
    from tfmesos_tpu.chaos import FaultPlan
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.kvtier import KVFabric, KVTierStore, fabric_rpc
    from tfmesos_tpu.fleet.launcher import FleetServer
    from tfmesos_tpu.fleet.replica import ReplicaServer, fabric_handler
    from tfmesos_tpu import wire

    rng = np.random.default_rng(seed)

    # -- Part A: direct peer streaming vs the relay fallback, on the
    # real wire stack.  The holder serves the fabric's kv_put/kv_fetch
    # surface; the relay re-ships every frame to it (one extra hop —
    # exactly the router-relay fallback's cost shape).
    token = "bench-fabric"
    body = rng.integers(0, 256, size=(int(artifact_mb * (1 << 20)),),
                        dtype=np.uint8).tobytes()
    store = KVTierStore(ram_bytes=max(4, 2 * n_transfers)
                        * len(body) + (64 << 20), token=token)
    holder = KVFabric(store, token=token, replication=1)
    hsrv = ReplicaServer(fabric_handler(holder), token=token).start()

    def relay(msg, reply):
        raw = isinstance(msg, wire.RawFrame)
        head = msg.meta if raw else msg
        reply(fabric_rpc(hsrv.addr, dict(head),
                         msg.body if raw else None, token=token,
                         timeout=60.0))

    rsrv = ReplicaServer(relay, token=token).start()

    def push_rate(addr, tag):
        fabric_rpc(addr, {"op": "kv_put", "kind": "session",
                          "key": f"{tag}-warm", "meta": {}}, body,
                   token=token, timeout=60.0)      # connection warmup
        t0 = time.perf_counter()
        for i in range(n_transfers):
            out = fabric_rpc(addr, {"op": "kv_put", "kind": "session",
                                    "key": f"{tag}-{i}", "meta": {}},
                             body, token=token, timeout=60.0)
            assert isinstance(out, dict) and out.get("op") == "kv_put_ok", \
                f"fabric push via {tag} failed: {out!r}"
        wall = time.perf_counter() - t0
        return n_transfers * len(body) / max(1e-9, wall) / (1 << 20)

    try:
        direct_mb_s = push_rate(hsrv.addr, "direct")
        relay_mb_s = push_rate(rsrv.addr, "relay")
    finally:
        rsrv.stop()
        hsrv.stop()
    assert direct_mb_s > relay_mb_s, \
        (f"direct peer streaming ({direct_mb_s:.1f} MB/s) not above "
         f"the relay fallback ({relay_mb_s:.1f} MB/s) on the same "
         f"workload — the extra hop must cost something")

    # -- Part B: replicated parking rides out a parker SIGKILL.
    plan = FaultPlan([], seed=seed)
    fleet = FleetServer(replicas=replicas, rows=rows, tiny=True,
                        max_len=128, page_size=16, prefill_bucket=16,
                        kv_tier_mb=64, kv_replication=2, kv_replicas=1,
                        warmup=True, workers=workers, max_queue=128,
                        request_timeout=300.0, start_timeout=300.0,
                        backend=LocalBackend(chaos=plan))
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        hists = {}
        for i in range(n_sessions):
            hist = [int(t) for t in rng.integers(0, 97, size=(24,))]
            out = client.generate(np.asarray(hist, np.int32),
                                  max_new_tokens, session=f"s{i}")
            hists[i] = hist + [int(t) for t in out["tokens"]]
        # Let the placement map fill: heartbeats advertise each tier's
        # parked sessions, and the replicated peer copies have landed
        # (the park ack waited for them).
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        # The victim is a SERVING replica (the kv holder carries every
        # replicated copy — killing it would test the wrong failure).
        serving = [r for r in fleet.registry.members()
                   if (r.role or "unified") != "kv"]
        victim = max(serving,
                     key=lambda r: len(((r.kv_tier or {})
                                        .get("sessions")) or []))
        n_primaries = len((victim.kv_tier or {}).get("sessions") or [])
        assert n_primaries >= 1, "no replica parked a session primary"
        assert plan.kill(victim.node), f"no pid for {victim.node}"
        deadline = time.perf_counter() + 300.0
        while victim.addr in [r.addr for r in fleet.registry.alive()]:
            assert time.perf_counter() < deadline, \
                "SIGKILLed parker never observed dead"
            time.sleep(0.05)
        # Every session's next turn must resume on a survivor — the
        # victim's primaries through a fabric fetch of the replicated
        # copy — and stream token-identical to a cold reference.
        lost = 0
        for i in range(n_sessions):
            hist = hists[i]
            hist += [int(t) for t in rng.integers(0, 97, size=(8,))]
            prompt = np.asarray(hist, np.int32)
            cold = client.generate(prompt, max_new_tokens)
            res = client.generate(prompt, max_new_tokens,
                                  session=f"s{i}")
            if res["tokens"] != cold["tokens"]:
                lost += 1
        time.sleep(3.0 * fleet.heartbeat_interval + 0.2)
        kt = fleet.snapshot()["gauges"].get("kv_tier") or {}
        resumed = kt.get("resume", 0)
        fetch_hits = kt.get("fabric_fetch_hit", 0)
        # The survivors served every post-kill turn, so their resume
        # counters alone must cover all n_sessions — a session whose
        # artifact died with its host would cold-prefill instead and
        # never count here.
        lost += max(0, n_sessions - resumed)
        assert lost == 0, \
            (f"{lost} of {n_sessions} sessions lost across the parker "
             f"SIGKILL (resumed={resumed}, tier={kt})")
        assert fetch_hits >= 1, \
            (f"no fabric fetch served a forwarded resume — the "
             f"victim held {n_primaries} primaries: {kt}")
        client.close()
    finally:
        fleet.stop()
    return direct_mb_s, relay_mb_s, n_sessions, fetch_hits


def scenario_serving(n_requests=32, replicas=2, rows=4, tiny=True,
                        max_new_tokens=8, workers=16):
    """Online fleet serving: requests/s and mean TTFT through the full
    front door — gateway + admission + router + N ``LocalBackend``
    CPU replicas (co-located replicas cannot share one TPU, so the
    multi-replica path is measured on CPU; what this metric tracks is
    the FLEET overhead trajectory — wire hops, routing, admission —
    on top of the per-replica serving numbers above).  The model is the
    tiny CI config by default: fleet costs are model-independent, and a
    flagship-on-CPU replica would measure XLA CPU, not the gateway."""
    import threading

    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    rng = np.random.default_rng(0)
    fleet = FleetServer(replicas=replicas, rows=rows, tiny=tiny,
                        max_len=64 if tiny else None,
                        page_size=16 if tiny else None,
                        prefill_bucket=16 if tiny else None,
                        workers=workers,
                        max_queue=max(64, 2 * n_requests),
                        start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)

        def run_batch(n):
            # Prompts come from the main thread: numpy Generators are
            # not thread-safe, and the workers only send/wait.
            prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
                       for _ in range(n)]
            results = [None] * n

            def one(i):
                results[i] = client.generate(prompts[i], max_new_tokens)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return results

        # Warm every replica's compile outside the timed region: with
        # least-outstanding routing, 2*replicas concurrent requests
        # land on every replica.
        run_batch(2 * replicas)
        t0 = time.perf_counter()
        results = run_batch(n_requests)
        dt = time.perf_counter() - t0
        done = [r for r in results if r is not None]
        assert len(done) == n_requests
        ttft = sum(r["ttft_ms"] for r in done) / len(done)
        # Admission-queue wait is its OWN histogram (never folded into
        # TTFT): report its p50 AND p99 — the autoscaler keys off the
        # p99 tail, so the signal scaling reacts to must be a
        # first-class observable, not a median that hides the stalls.
        qw = fleet.snapshot()["histograms"].get("queue_wait_ms", {})
        client.close()
        return (n_requests / dt, ttft, qw.get("p50", 0.0),
                qw.get("p99", 0.0))
    finally:
        fleet.stop()


def scenario_disagg(n_decode=8, decode_new=24, prompt_len=96,
                       rows=4, workers=8, feeders=2):
    """Disaggregated prefill/decode serving vs a unified fleet of the
    SAME size on a mixed workload: long-prompt requests stream in
    continuously (the feeder threads) while long-decode requests
    measure inter-token latency.  In a unified replica every admitted
    long prefill stalls the co-resident decode ticks for its whole
    prompt; with dedicated tiers the decode replica only ever imports
    KV pages (one scatter) and decodes — the p50 inter-token gap of the
    decode-heavy requests is the headline, and must be strictly better
    disaggregated.  Also reports end-to-end TTFT per mode and the
    KV-transfer throughput of the prefill→decode handoff."""
    import threading

    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    page = 16
    rng = np.random.default_rng(2)
    decode_prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
                      for _ in range(n_decode)]
    long_pool = [rng.integers(0, 97, size=(prompt_len,)).astype(np.int32)
                 for _ in range(32)]

    def run_fleet(**kw):
        fleet = FleetServer(rows=rows, tiny=True, max_len=128,
                            page_size=page, prefill_bucket=page,
                            workers=workers, max_queue=256,
                            request_timeout=300.0,
                            start_timeout=300.0, **kw)
        fleet.start()
        try:
            client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
            # Warm both request shapes' compiles outside the timed
            # region (prefill bucket of the long prompts, and decode).
            client.generate(long_pool[0], 2)
            client.generate(decode_prompts[0], 2)
            stop = threading.Event()
            feed_errors = []

            def feeder(k):
                i = 0
                streak = 0
                while not stop.is_set():
                    try:
                        client.generate(
                            long_pool[(k * 13 + i) % len(long_pool)], 2,
                            timeout=300.0)
                        streak = 0
                    except Exception as e:
                        if stop.is_set():
                            return
                        # A transient shed or heartbeat flap must not
                        # silently remove the interference load — the
                        # headline dis_itl < uni_itl comparison is only
                        # meaningful while BOTH runs see continuous long
                        # prefills.  Keep feeding; only a persistent
                        # streak aborts the scenario loudly (asserted after
                        # join, not swallowed in a daemon thread).
                        streak += 1
                        if streak >= 8:
                            feed_errors.append(e)
                            return
                        time.sleep(0.05)
                    i += 1

            results = [None] * n_decode

            def one(i):
                results[i] = client.generate(decode_prompts[i],
                                             decode_new, timeout=300.0)

            fthreads = [threading.Thread(target=feeder, args=(k,),
                                         daemon=True)
                        for k in range(feeders)]
            t0 = time.perf_counter()
            for f in fthreads:
                f.start()
            time.sleep(0.05)    # let long prefills be in flight first
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n_decode)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            stop.set()
            for f in fthreads:
                f.join(timeout=300.0)
            snap = fleet.snapshot()
            client.close()
            assert not feed_errors, \
                f"interference feeder died mid-run: {feed_errors[0]!r}"
            assert all(r is not None for r in results)
            return results, snap, wall
        finally:
            fleet.stop()

    uni_res, _, _ = run_fleet(replicas=2)
    dis_res, dis_snap, dis_wall = run_fleet(replicas=0,
                                            prefill_replicas=1,
                                            decode_replicas=1)

    def itl_p50(rs, disagg):
        vals = sorted(
            (r["decode_ms"] if disagg else r["total_ms"] - r["ttft_ms"])
            / max(1, decode_new - 1) for r in rs)
        return vals[len(vals) // 2]

    uni_itl = itl_p50(uni_res, False)
    dis_itl = itl_p50(dis_res, True)
    uni_ttft = sum(r["ttft_ms"] for r in uni_res) / len(uni_res)
    dis_ttft = sum(r["ttft_ms"] for r in dis_res) / len(dis_res)
    c = dis_snap["counters"]
    # Both tiers must actually have served: every request crossed
    # prefill → transfer → decode (the roles gauge shows the tiers).
    assert c.get("disagg_prefills", 0) > 0, "prefill tier never served"
    assert c.get("disagg_decodes", 0) > 0, "decode tier never served"
    roles = dis_snap["gauges"].get("roles") or {}
    assert roles.get("prefill", {}).get("alive"), roles
    assert roles.get("decode", {}).get("alive"), roles
    assert dis_itl < uni_itl, \
        (f"disaggregated decode inter-token p50 {dis_itl:.2f}ms not "
         f"better than unified {uni_itl:.2f}ms — prefill stalls leaked "
         f"into the decode tier")
    kv_mb_s = c.get("kv_transfer_bytes", 0) / 1e6 / dis_wall
    return dis_ttft, dis_itl, uni_ttft, uni_itl, kv_mb_s


def scenario_gang(n_requests=6, gang_size=2, rows=4, decode_new=24,
                     workers=8):
    """Gang replicas (docs/SERVING.md "Gang replicas") behind the same
    gateway: each replica is ``gang_size`` member tasks forming one
    leader-coordinated mesh, routed as ONE ``ReplicaInfo``.  Three
    phases on LocalBackend CPU gangs:

    * token identity + inter-token p50 — the SAME greedy-decode prompts
      stream through a ``gang_size``-member gang fleet and a
      single-process fleet; every stream is asserted token-identical
      (the leader owns sampling; members mirror-execute and digest-ack),
      and ``fleet_gang_itl_p50_ms`` vs ``fleet_single_itl_p50_ms``
      tracks the leader's dispatch fan-out overhead (on CPU the members
      add no compute — real slices flip the comparison).
    * ``fleet_gang_reform_s`` — SIGKILL one MEMBER task mid-decode: the
      gang dies whole (member death = gang death), in-flight work fails
      over to the surviving gang via router replay (zero lost requests
      asserted, streams still token-identical), and the launcher
      re-forms the gang under a fresh generation; the number is
      kill -> both replicas routable again.
    * gang drain-migration — a pinned drain + migrate of a busy gang
      mid-decode must move its in-flight work losslessly (zero lost,
      token-identical), exactly like a single-process replica's drain.
    """
    import threading

    from tfmesos_tpu.chaos import FaultPlan
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer
    from tfmesos_tpu.backends.local import LocalBackend

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
               for _ in range(n_requests)]

    def itl_p50(rs):
        vals = sorted((r["total_ms"] - r["ttft_ms"])
                      / max(1, decode_new - 1) for r in rs)
        return vals[len(vals) // 2]

    def run_single():
        fleet = FleetServer(replicas=1, rows=rows, tiny=True, max_len=64,
                            page_size=16, prefill_bucket=16,
                            workers=workers, max_queue=256,
                            request_timeout=300.0, start_timeout=300.0)
        fleet.start()
        try:
            client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
            client.generate(prompts[0], 2)      # warm the compile
            res = [client.generate(p, decode_new, timeout=300.0)
                   for p in prompts]
            client.close()
            return res
        finally:
            fleet.stop()

    single_res = run_single()
    single_itl = itl_p50(single_res)

    plan = FaultPlan([], seed=5)
    fleet = FleetServer(replicas=2, gang_size=gang_size, rows=rows,
                        tiny=True, max_len=64, page_size=16,
                        prefill_bucket=16, workers=workers, max_queue=256,
                        request_timeout=300.0, start_timeout=300.0,
                        backend=LocalBackend(chaos=plan))
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)

        def run_batch(reqs, results, errors):
            def one(i):
                try:
                    results[i] = client.generate(reqs[i], decode_new,
                                                 timeout=300.0)
                except Exception as e:
                    errors.append((i, e))
            threads = [threading.Thread(target=one, args=(i,),
                                        daemon=True)
                       for i in range(len(reqs))]
            for t in threads:
                t.start()
            return threads

        # Warm BOTH gangs' compiles: with least-outstanding routing,
        # 2*replicas concurrent requests land on every gang.
        warm = [None] * 4
        for t in run_batch([prompts[0]] * 4, warm, []):
            t.join(timeout=300.0)

        gang_res = [client.generate(p, decode_new, timeout=300.0)
                    for p in prompts]
        for i, (g, s) in enumerate(zip(gang_res, single_res)):
            assert g["tokens"] == s["tokens"], \
                (f"gang stream {i} diverged from the single-host "
                 f"reference: {g['tokens']} vs {s['tokens']}")
        gang_itl = itl_p50(gang_res)

        # --- phase 2: SIGKILL one gang MEMBER mid-decode -------------
        with fleet._gang_lock:
            gangs = dict(fleet._gangs)
        assert len(gangs) == 2, f"expected 2 gangs, got {list(gangs)}"
        gid, info = sorted(gangs.items())[0]
        member_node = None
        for t in fleet.scheduler.tasks_of("replica"):
            node = f"{t.job_name}:{t.task_index}"
            if getattr(t, "gang", None) == gid \
                    and node != info["leader_node"]:
                member_node = node
        assert member_node is not None, f"gang {gid} has no member task"

        old_addrs = {r.addr for r in fleet.registry.alive()}
        results = [None] * n_requests
        errors = []
        threads = run_batch(prompts, results, errors)
        deadline = time.perf_counter() + 120.0
        while time.perf_counter() < deadline:
            if any(r.outstanding > 0 for r in fleet.registry.alive()):
                break
            time.sleep(0.01)
        t_kill = time.perf_counter()
        plan.kill(member_node)
        # Re-formed means a FRESH leader addr is routable again — the
        # dead gang's leader lingers in alive() until the registry sees
        # its heartbeat drop, so counting addrs alone would read the
        # pre-kill fleet as already re-formed.
        reform_s = None
        deadline = time.perf_counter() + 300.0
        while time.perf_counter() < deadline:
            addrs = {r.addr for r in fleet.registry.alive()}
            if len(addrs) == 2 and addrs - old_addrs:
                reform_s = time.perf_counter() - t_kill
                break
            time.sleep(0.05)
        assert reform_s is not None, "gang never re-formed after the kill"
        for t in threads:
            t.join(timeout=300.0)
        assert not errors, \
            f"request lost across gang-member kill: {errors[0]!r}"
        for i, r in enumerate(results):
            assert r is not None, f"request {i} never completed"
            assert r["tokens"] == single_res[i]["tokens"], \
                f"stream {i} diverged across gang failover"
        c = fleet.snapshot()["counters"]
        assert c.get("gang_reforms", 0) >= 1, \
            f"launcher never re-formed the gang: {c}"

        # --- phase 3: drain-migrate a busy gang ----------------------
        # Warm the re-formed gang's compile first (least-outstanding
        # routing lands concurrent requests on it), so the drain's
        # suspended work has a live, warm candidate to resume on.
        warm2 = [None] * 4
        for t in run_batch([prompts[0]] * 4, warm2, []):
            t.join(timeout=300.0)
        results2 = [None] * n_requests
        errors2 = []
        threads2 = run_batch(prompts, results2, errors2)
        victim = None
        deadline = time.perf_counter() + 120.0
        while victim is None and time.perf_counter() < deadline:
            busy = [r for r in fleet.registry.alive()
                    if r.outstanding > 0]
            victim = busy[0].addr if busy else None
            time.sleep(0.01)
        assert victim is not None, "no gang ever reported work"
        assert fleet.registry.begin_drain(victim, pinned=True)
        fleet.request_migration(victim)
        for t in threads2:
            t.join(timeout=300.0)
        assert not errors2, \
            f"request lost in gang drain-migration: {errors2[0]!r}"
        for i, r in enumerate(results2):
            assert r is not None, f"drained request {i} never completed"
            assert r["tokens"] == single_res[i]["tokens"], \
                f"stream {i} diverged across gang drain-migration"
        client.close()
    finally:
        fleet.stop()
    return gang_itl, single_itl, reform_s


def scenario_autoscale(rows=2, max_new_tokens=4, workers=8):
    """Control-plane reaction scenarios on a live LocalBackend fleet:

    * ``fleet_scaleup_reaction_s`` — surge start → a NEW replica task
      launched by the autoscaler is registered and ROUTABLE.  The surge
      is an injected signal (the chaos.py discipline: the scenario
      measures the fleet's launch→register→alive pipeline, not signal
      plumbing) and the loop is stepped by hand, so the number is the
      actuation cost, deterministically triggered.
    * ``fleet_rollout_downtime_ms`` — a blue-green rollout to a new
      weights_version runs under CONTINUOUS traffic; every request must
      succeed (zero Overloaded, zero RoutingError — asserted), so the
      recorded downtime is 0 by contract and the scenario fails loudly the
      day it is not.
    """
    import threading

    from tfmesos_tpu.fleet.autoscaler import (AutoscalerConfig,
                                              FleetAutoscaler)
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
               for _ in range(16)]
    fleet = FleetServer(replicas=1, rows=rows, tiny=True, max_len=64,
                        page_size=16, prefill_bucket=16, workers=workers,
                        max_queue=256, min_replicas=1, max_replicas=2,
                        request_timeout=300.0, start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        client.generate(prompts[0], 2)      # warm the compile

        def alive():
            return fleet.registry.role_summary().get(
                "unified", {}).get("alive", 0)

        # Hand-stepped control loop over an injected signal source.
        surge = {"queue_wait_p99_ms": 10_000.0, "util": 1.0,
                 "kv_headroom": None}
        calm = {"queue_wait_p99_ms": 0.0, "util": 0.0,
                "kv_headroom": None}
        sig = {"unified": surge}
        auto = FleetAutoscaler(
            fleet, AutoscalerConfig(scale_up_cooldown=0.0,
                                    scale_down_cooldown=0.0,
                                    drain_grace=0.2),
            signals=lambda: dict(sig))
        t0 = time.perf_counter()
        deadline = t0 + 300.0
        while alive() < 2:
            if time.perf_counter() > deadline:
                raise RuntimeError("autoscaled replica never routable")
            auto.step()
            time.sleep(0.05)
        reaction_s = time.perf_counter() - t0
        # Decay: the loop drains the least-loaded replica and kills it
        # only after its outstanding work flushed.
        sig["unified"] = calm
        while fleet.tier_actual("unified") > 1:
            if time.perf_counter() > deadline:
                raise RuntimeError("scale-down drain never completed")
            auto.step()
            time.sleep(0.05)

        # Blue-green rollout under continuous traffic.
        stop = threading.Event()
        failures = []

        def feeder():
            i = 0
            while not stop.is_set():
                try:
                    client.generate(prompts[i % len(prompts)],
                                    max_new_tokens, timeout=300.0)
                except Exception as e:
                    failures.append(e)
                    return
                i += 1

        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        time.sleep(0.2)                 # traffic in flight first
        fleet.rollout("v2", bake_s=0.5)
        stop.set()
        th.join(timeout=300.0)
        client.close()
        assert not failures, \
            f"rollout failed/shed a request: {failures[0]!r}"
        versions = fleet.registry.role_summary().get(
            "unified", {}).get("versions", {})
        assert list(versions) == ["v2"], versions
        return reaction_s, 0.0
    finally:
        fleet.stop()


def scenario_multimodel(rows=2, max_new_tokens=4, workers=8):
    """Many models, one fleet (docs/SERVING.md "Model catalog") on a
    live LocalBackend fleet, every contract asserted in the scenario:

    * ``fleet_multimodel_trade_reaction_s`` — a two-model hotness flip
      on a FIXED replica budget: the hand-stepped ModelTrader (injected
      signals, the chaos.py discipline — the scenario measures the
      drain→launch→register→alive pipeline, not signal plumbing) must
      TRADE a cold model's replica away and stand the hot model's
      second replica up; continuous two-tenant traffic rides through
      the whole trade with ZERO failed/shed requests (asserted).
    * ``fleet_multimodel_pool_cold_start_ttft_ms`` vs ``..._relaunch_
      cold_start_ttft_ms`` — a scale-to-zero model's FIRST request:
      warm-pool adoption (a weight install on a pre-warmed,
      pre-compiled process) vs the pool-exhausted path (trade a slot +
      cold process launch + compile); pool STRICTLY below relaunch
      asserted.
    * ``fleet_multimodel_swap_ms`` — ``swap_adapter`` under continuous
      traffic: every request during the swap is SERVED (zero
      downtime), every stream equals exactly ONE delta version's
      reference (token-identical per version — never a mix), and
      every request submitted after the fleet-wide ack streams the NEW
      version.
    * billing-grade metering: ``metering_{prompt,decode}_tokens_
      <tenant>_<model>`` counters present for every pair that carried
      traffic (they ride the snapshot AND the Prometheus exposition).
    """
    import threading

    from tfmesos_tpu.fleet.admission import PriorityClass
    from tfmesos_tpu.fleet.autoscaler import AutoscalerConfig
    from tfmesos_tpu.fleet.catalog import (ModelSpec, ModelTrader,
                                           TraderConfig, model_key)
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer
    from tfmesos_tpu.fleet.replica import tiny_model

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, size=(6,)).astype(np.int32)
               for _ in range(8)]
    fleet = FleetServer(
        models=[ModelSpec("alpha", replicas=2, seed=0),
                ModelSpec("beta", replicas=1, seed=1),
                ModelSpec("gamma", replicas=0, seed=2),
                ModelSpec("delta", replicas=0, seed=3)],
        warm_pool=1, tiny=True, rows=rows, workers=workers,
        max_queue=256,
        priority_classes=[PriorityClass("tenantA", weight=2.0, rank=1),
                          PriorityClass("tenantB", weight=1.0, rank=0)],
        request_timeout=300.0, start_timeout=300.0)
    fleet.start()
    out = {}
    try:
        # The built-in trader thread would race the hand-stepped one
        # below; its demand hook (the router's cold-start path) stays
        # live — stopping the thread stops the TICKS, not the surface.
        fleet.trader.stop()
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        for model, tenant in (("alpha", "tenantA"), ("beta", "tenantB")):
            client.generate(prompts[0], 2, model=model,
                            priority=tenant)     # warm the compiles

        def alive(model):
            return [r for r in fleet.registry.members(model=model)
                    if r.state == "alive"]

        # -- cold start #1: through the warm pool (a weight install).
        # The pool slot is the budget's only slack, so this must come
        # FIRST — once it is consumed, every later reallocation is a
        # genuine trade.
        t0 = time.perf_counter()
        client.generate(prompts[1], max_new_tokens, model="gamma",
                        priority="tenantA")
        pool_ttft_ms = 1000.0 * (time.perf_counter() - t0)
        assert fleet.metrics.get("model_adoptions") == 1
        # Wait out the adopter's identity flip (heartbeat-lagged) so
        # a later demand cannot see a stale pool member.
        deadline = time.perf_counter() + 60.0
        while fleet.registry.has_pool():
            if time.perf_counter() > deadline:
                raise RuntimeError("adopted replica still advertises "
                                   "warm_pool")
            time.sleep(0.05)

        # -- the hotness flip, under continuous two-tenant traffic.
        # Dead-band signals on alpha, beta HOT, budget full, pool
        # gone: the ONLY way beta can grow is a TRADE — alpha (the
        # sole model above its live bound) drain-MIGRATES one replica
        # away mid-traffic and beta's second one launches in its slot.
        DEAD = {"queue_wait_p99_ms": 100.0, "util": 0.4, "samples": 5}
        sig = {model_key("alpha"): dict(DEAD),
               model_key("beta"): dict(DEAD)}
        trader = ModelTrader(
            fleet, fleet.catalog,
            AutoscalerConfig(scale_up_cooldown=0.0,
                             scale_down_cooldown=0.0, drain_grace=0.2),
            trader_config=TraderConfig(trade_cooldown_s=0.2,
                                       zero_after_ticks=10 ** 6),
            signals=lambda: {k: dict(v) for k, v in sig.items()})
        stop = threading.Event()
        failures = []

        def feeder(model, tenant):
            i = 0
            while not stop.is_set():
                try:
                    client.generate(prompts[i % len(prompts)],
                                    max_new_tokens, model=model,
                                    priority=tenant, timeout=300.0)
                except Exception as e:
                    failures.append(e)
                    return
                i += 1

        threads = [threading.Thread(target=feeder, args=a, daemon=True)
                   for a in (("alpha", "tenantA"), ("beta", "tenantB"))]
        for th in threads:
            th.start()
        time.sleep(0.4)                  # traffic in flight first
        sig[model_key("beta")] = {"queue_wait_p99_ms": 10_000.0,
                                  "util": 1.0, "samples": 50}
        t0 = time.perf_counter()
        deadline = t0 + 300.0
        while len(alive("beta")) < 2:
            if time.perf_counter() > deadline:
                raise RuntimeError("traded beta replica never routable")
            trader.step()
            time.sleep(0.05)
        reaction_s = time.perf_counter() - t0
        assert fleet.metrics.get("model_trades") >= 1
        # Converge the trade's victim side: alpha's drained replica
        # migrates its in-flight rows (the feeder keeps hammering it)
        # and is reaped — lossless, per the feeder assertion below.
        sig[model_key("beta")] = dict(DEAD)
        while fleet.tier_actual(model_key("alpha")) > 1:
            if time.perf_counter() > deadline:
                raise RuntimeError("traded-away replica never reaped")
            trader.step()
            time.sleep(0.05)

        # -- cold start #2: pool exhausted — the demand must TRADE a
        # slot from a cold model (beta, the only one above its live
        # bound now) and cold-LAUNCH a process (fork + jax import +
        # compile): the expensive path the warm pool exists to avoid.
        t0 = time.perf_counter()
        client.generate(prompts[2], max_new_tokens, model="delta",
                        priority="tenantB")
        relaunch_ttft_ms = 1000.0 * (time.perf_counter() - t0)
        assert pool_ttft_ms < relaunch_ttft_ms, \
            (f"warm-pool cold start ({pool_ttft_ms:.0f}ms) not below "
             f"cold relaunch ({relaunch_ttft_ms:.0f}ms)")

        # -- adapter hot-swap under the same continuous traffic.
        cfg_t, params_t = tiny_model(1)      # beta's preset (seed 1)
        embed = np.asarray(params_t["embed"])
        delta = {"embed": (0.5 * np.random.default_rng(9)
                           .standard_normal(embed.shape)
                           ).astype(embed.dtype)}
        probe = prompts[3]
        ref_old = client.generate(probe, max_new_tokens, model="beta",
                                  priority="tenantB")["tokens"]
        swap_records = []
        swap_stop = threading.Event()

        def swap_feeder():
            while not swap_stop.is_set():
                t_submit = time.perf_counter()
                try:
                    r = client.generate(probe, max_new_tokens,
                                        model="beta",
                                        priority="tenantB",
                                        timeout=300.0)
                except Exception as e:
                    failures.append(e)
                    return
                swap_records.append((t_submit, r["tokens"]))

        th_swap = threading.Thread(target=swap_feeder, daemon=True)
        th_swap.start()
        time.sleep(0.3)
        t0 = time.perf_counter()
        client.swap_adapter("beta", "lora1", delta)
        t_ack = time.perf_counter()
        swap_ms = 1000.0 * (t_ack - t0)
        time.sleep(0.5)                 # post-ack traffic
        swap_stop.set()
        stop.set()
        th_swap.join(timeout=300.0)
        for th in threads:
            th.join(timeout=300.0)
        assert not failures, \
            f"lost/shed a request across trade or swap: {failures[0]!r}"
        ref_new = client.generate(probe, max_new_tokens, model="beta",
                                  priority="tenantB")["tokens"]
        assert ref_new != ref_old, \
            "adapter delta did not change the stream (delta too small)"
        for t_submit, toks in swap_records:
            assert toks in (ref_old, ref_new), \
                f"stream matches NEITHER delta version: {toks}"
            if t_submit > t_ack:
                assert toks == ref_new, \
                    "request submitted after the swap ack streamed the "\
                    "OLD delta version"
        assert any(r.adapter_version == "lora1"
                   for r in alive("beta")), "adapter_version never "\
            "rode a heartbeat into the registry"

        # -- billing-grade per-tenant x model metering.
        counters = client.metrics()["counters"]
        for tenant, model in (("tenantA", "alpha"), ("tenantB", "beta"),
                              ("tenantA", "gamma"),
                              ("tenantB", "delta")):
            for kind in ("prompt", "decode"):
                key = f"metering_{kind}_tokens_{tenant}_{model}"
                assert counters.get(key, 0) > 0, f"no meter {key}"
        client.close()
        out = {
            "fleet_multimodel_trade_reaction_s": round(reaction_s, 2),
            "fleet_multimodel_pool_cold_start_ttft_ms":
                round(pool_ttft_ms, 1),
            "fleet_multimodel_relaunch_cold_start_ttft_ms":
                round(relaunch_ttft_ms, 1),
            "fleet_multimodel_swap_ms": round(swap_ms, 1),
            "fleet_multimodel_lost_requests": len(failures),
            "fleet_multimodel_metered_pairs": sum(
                1 for k in counters
                if k.startswith("metering_prompt_tokens_")),
        }
        return out
    finally:
        fleet.stop()


def scenario_priority(n_interactive=16, rows=3, workers=8,
                         flood_threads=3, interactive_new=2,
                         background_new=24):
    """SLO isolation + lossless migration under churn, on a live
    two-replica CPU fleet with priority classes and drain migration:

    * ``fleet_priority_p99_ttft_ms`` vs ``fleet_background_p99_ttft_ms``
      — client-observed completion latency p99 of short (TTFT-
      dominated) interactive requests while ``flood_threads`` background
      feeders saturate the fleet with long decodes, vs the flooding
      tenant's own p99.  WFQ admission + in-batcher preemption are what
      hold the first flat: asserted within 1.5x of its UNLOADED value
      (with a small absolute epsilon — at the CPU smoke scale the whole
      latency is tens of ms, where one scheduler hiccup outweighs any
      real queueing effect), and strictly below the background p99.
    * ``fleet_migration_lost_requests`` — failed requests across an
      autoscaler-style scale-down (pinned drain → migrate → kill) AND a
      blue-green rollout, both under continuous two-class traffic with
      drain migration on.  Asserted ZERO: suspended rows resume
      elsewhere mid-stream, requeued work re-runs deterministically.
    """
    import threading

    from tfmesos_tpu.fleet.admission import PriorityClass
    from tfmesos_tpu.fleet.autoscaler import (AutoscalerConfig,
                                              FleetAutoscaler)
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.launcher import FleetServer

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
               for _ in range(16)]
    classes = [PriorityClass("interactive", weight=8.0, rank=1),
               PriorityClass("background", weight=1.0, rank=0,
                             max_queue=2 * flood_threads)]
    fleet = FleetServer(replicas=2, rows=rows, tiny=True, max_len=64,
                        page_size=16, prefill_bucket=16, workers=workers,
                        max_queue=256, priority_classes=classes,
                        min_replicas=1, max_replicas=2,
                        request_timeout=300.0, start_timeout=300.0)
    fleet.start()
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        client.generate(prompts[0], 2)          # warm the compiles
        client.generate(prompts[1], background_new)

        p99 = _p99

        def timed_batch(n, priority):
            walls = []
            for i in range(n):
                t0 = time.perf_counter()
                client.generate(prompts[i % len(prompts)],
                                interactive_new, priority=priority,
                                timeout=300.0)
                walls.append((time.perf_counter() - t0) * 1000.0)
            return walls

        # Phase 1: unloaded interactive latency (sequential, warm).
        unloaded_p99 = p99(timed_batch(n_interactive, "interactive"))

        # Phase 2: the background tenant floods every row with long
        # decodes while the interactive tenant keeps its cadence.
        stop = threading.Event()
        bg_walls, bg_errors = [], []
        bg_lock = threading.Lock()

        def flood(k):
            i = 0
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    client.generate(prompts[(k * 7 + i) % len(prompts)],
                                    background_new,
                                    priority="background",
                                    timeout=300.0)
                    with bg_lock:
                        bg_walls.append(
                            (time.perf_counter() - t0) * 1000.0)
                except Exception as e:
                    # Background sheds are the DESIGN under flood (its
                    # class queue is bounded); anything else is a bug.
                    if "overloaded" not in repr(e).lower() \
                            and not stop.is_set():
                        bg_errors.append(e)
                        return
                    time.sleep(0.01)
                i += 1

        floods = [threading.Thread(target=flood, args=(k,), daemon=True)
                  for k in range(flood_threads)]
        for f in floods:
            f.start()
        time.sleep(0.3)             # flood in flight first
        loaded = timed_batch(n_interactive, "interactive")
        loaded_p99 = p99(loaded)
        stop.set()
        for f in floods:
            f.join(timeout=300.0)
        assert not bg_errors, \
            f"background feeder failed mid-flood: {bg_errors[0]!r}"
        assert bg_walls, "flood never completed a request"
        bg_p99 = p99(bg_walls)
        assert loaded_p99 <= max(1.5 * unloaded_p99,
                                 unloaded_p99 + 150.0), \
            (f"interactive p99 {loaded_p99:.1f}ms not held within 1.5x "
             f"of unloaded {unloaded_p99:.1f}ms under background flood")
        assert loaded_p99 < bg_p99, \
            (f"class isolation failed: interactive p99 {loaded_p99:.1f}"
             f"ms >= background p99 {bg_p99:.1f}ms")

        # Phase 3: zero lost requests across scale-down + rollout with
        # drain migration on, under continuous gentle two-class traffic.
        stop = threading.Event()
        failures = []

        def feeder(priority):
            i = 0
            while not stop.is_set():
                try:
                    client.generate(prompts[i % len(prompts)],
                                    background_new, priority=priority,
                                    timeout=300.0)
                except Exception as e:
                    if not stop.is_set():
                        failures.append(e)
                    return
                i += 1

        feeders = [threading.Thread(target=feeder, args=(p,), daemon=True)
                   for p in ("interactive", "background")]
        for f in feeders:
            f.start()
        time.sleep(0.2)             # traffic in flight first
        calm = {"queue_wait_p99_ms": 0.0, "util": 0.0,
                "kv_headroom": None}
        auto = FleetAutoscaler(
            fleet, AutoscalerConfig(scale_up_cooldown=0.0,
                                    scale_down_cooldown=0.0,
                                    drain_grace=0.2),
            signals=lambda: {"unified": dict(calm)})
        deadline = time.perf_counter() + 300.0
        while fleet.tier_actual("unified") > 1:   # drain-migrate-kill
            if time.perf_counter() > deadline:
                raise RuntimeError("scale-down drain never completed")
            auto.step()
            time.sleep(0.05)
        fleet.rollout("v2", bake_s=0.5)           # under the same traffic
        stop.set()
        for f in feeders:
            f.join(timeout=300.0)
        assert not failures, \
            f"request lost across scale-down/rollout: {failures[0]!r}"
        c = fleet.snapshot()["counters"]
        assert c.get("migrations_requested", 0) >= 1, c
        client.close()
        return unloaded_p99, loaded_p99, bg_p99, 0
    finally:
        fleet.stop()


def scenario_soak(rows=2, workers=8, slow_delay_s=0.25,
                     n_timed=16, soak_probe_deadline_ms=60.0,
                     seed=20):
    """Seeded chaos soak: a live 3-replica CPU fleet driven through a
    GRAY failure (one replica alive-per-heartbeat but slow on every
    dispatch — chaos ``slow_task``), a SIGKILL + autoscaler-tick
    self-heal, a link sever, and a blue-green rollout, under continuous
    two-class deadline-carrying traffic.  Asserts (the PR's
    acceptance criteria):

    * ``fleet_soak_lost_requests`` == 0 — every feeder request
      completes (failover, migration, and the rollout are lossless);
    * deadline conformance — every deadline-carrying reply (completion
      OR deadline_exceeded error) lands within deadline + epsilon,
      and the short-deadline probes against long decodes come back as
      explicit ``deadline_exceeded`` about at their deadline (the
      in-batcher cancel), never as a late completion;
    * ``fleet_soak_retry_amplification`` <= 1.5 — attempts per
      completed request stay bounded through all of the above (the
      retry budget's job);
    * the slow replica is breaker-isolated (state OPEN, latency
      outlier) while the registry still reports it ALIVE — and the
      CONTROL arm (same seed, same fault, breakers disabled) shows the
      interactive p99 degrading toward the injected delay, proving the
      mechanism and not the workload.
    """
    import threading

    from tfmesos_tpu.backends.local import LocalBackend
    from tfmesos_tpu.chaos import Fault, FaultPlan
    from tfmesos_tpu.fleet.admission import PriorityClass
    from tfmesos_tpu.fleet.autoscaler import (AutoscalerConfig,
                                              FleetAutoscaler)
    from tfmesos_tpu.fleet.client import FleetClient, RequestFailed
    from tfmesos_tpu.fleet.launcher import FleetServer

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 97, size=(8,)).astype(np.int32)
               for _ in range(16)]
    classes = [PriorityClass("interactive", weight=8.0, rank=1),
               PriorityClass("background", weight=1.0, rank=0)]
    eps_s = 2.0                     # CPU-scale scheduling epsilon

    p99 = _p99

    def build(breakers):
        plan = FaultPlan([], seed=seed)
        fleet = FleetServer(
            replicas=3, rows=rows, tiny=True, max_len=64, page_size=16,
            prefill_bucket=16, workers=workers, max_queue=256,
            priority_classes=classes, breakers=breakers,
            min_replicas=1, max_replicas=3,
            # Pure tail-based retention: no head sampling, the slow
            # threshold well under the injected delay — the gray
            # failure's traces must retain themselves.
            trace_sample=0.0,
            trace_slow_ms=slow_delay_s * 1000.0 * 0.6,
            request_timeout=300.0, start_timeout=300.0,
            backend=LocalBackend(chaos=plan))
        fleet.start()
        # The gray victim is chosen deterministically; its fault is
        # appended post-start (addresses exist only now) with an
        # explicit delay so the plan stays seed-reproducible.
        victim = sorted(r.addr for r in fleet.registry.alive())[0]
        plan.faults.append(Fault("slow_task", "wire.send", nth=1,
                                 target=victim, delay_s=slow_delay_s))
        plan.install()
        return plan, fleet, victim

    def timed_interactive(client, n):
        walls = []
        for i in range(n):
            t0 = time.perf_counter()
            client.generate(prompts[i % len(prompts)], 2,
                            priority="interactive", timeout=300.0,
                            deadline_ms=120000.0)
            walls.append((time.perf_counter() - t0) * 1000.0)
        return walls

    # ---- main arm: breakers ON, the full chaos timeline ----
    plan, fleet, victim = build(breakers=True)
    lost, completions = [], []
    stop = threading.Event()
    lock = threading.Lock()

    def feeder(priority, new_tokens):
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                client.generate(prompts[i % len(prompts)], new_tokens,
                                priority=priority, timeout=300.0,
                                deadline_ms=120000.0)
                with lock:
                    completions.append(
                        (time.perf_counter() - t0, 120.0))
            except Exception as e:  # noqa: BLE001 - every loss recorded
                if not stop.is_set():
                    with lock:
                        lost.append(e)
                    return
            i += 1
        client.close()

    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        client.generate(prompts[0], 2)              # warm the compiles
        feeders = [
            threading.Thread(target=feeder, args=("interactive", 2),
                             daemon=True),
            threading.Thread(target=feeder, args=("interactive", 2),
                             daemon=True),
            threading.Thread(target=feeder, args=("background", 8),
                             daemon=True),
        ]
        for f in feeders:
            f.start()

        # Phase A — gray failure: traffic feeds the latency EWMAs until
        # the victim's breaker trips on the outlier, while its
        # heartbeats keep it ALIVE in the registry the whole time.
        deadline = time.perf_counter() + 300.0
        while victim not in fleet.router.breakers.open_addrs():
            assert time.perf_counter() < deadline, \
                "slow replica never breaker-isolated"
            assert not lost, f"request lost in gray phase: {lost[0]!r}"
            time.sleep(0.05)
        assert victim in [r.addr for r in fleet.registry.alive()], \
            "victim must be heartbeat-alive while breaker-open " \
            "(that is what makes the failure gray)"
        on_p99 = p99(timed_interactive(client, n_timed))

        # Deadline probes: long decodes against a deadline far shorter
        # than they need — the reply must be an explicit
        # deadline_exceeded about AT the deadline (in-batcher cancel /
        # router fail-fast), and any completion must beat it.
        probe_violations = 0
        for i in range(4):
            t0 = time.perf_counter()
            try:
                client.generate(prompts[i], 48,
                                deadline_ms=soak_probe_deadline_ms,
                                timeout=300.0)
                wall_s = time.perf_counter() - t0
                if wall_s > soak_probe_deadline_ms / 1000.0 + eps_s:
                    probe_violations += 1    # post-deadline completion
            except RequestFailed as e:
                wall_s = time.perf_counter() - t0
                if e.kind != "deadline_exceeded" \
                        or wall_s > soak_probe_deadline_ms / 1000.0 \
                        + eps_s:
                    probe_violations += 1
        assert probe_violations == 0, \
            f"{probe_violations} deadline probes violated conformance"

        # Phase B — hard churn: SIGKILL a healthy (non-victim) replica
        # whole (process group — a real death, in-flight work fails
        # over); hand-stepped autoscaler ticks relaunch it (crash
        # self-heal).  The death must be OBSERVED (task table or
        # registry) before convergence is waited on, or the wait would
        # trivially pass against the pre-kill state.
        members = {r.addr: r for r in fleet.registry.members()}
        dead_node = next(r.node for a, r in sorted(members.items())
                         if a != victim and r.node)
        assert plan.kill(dead_node), f"no pid for {dead_node}"
        deadline = time.perf_counter() + 300.0
        while fleet.tier_actual("unified") >= 3 \
                and len(fleet.registry.alive()) >= 3:
            assert time.perf_counter() < deadline, \
                "SIGKILLed replica never observed dead"
            time.sleep(0.05)
        calm = {"queue_wait_p99_ms": 0.0, "util": 0.5,
                "kv_headroom": None}
        auto = FleetAutoscaler(
            fleet, AutoscalerConfig(scale_up_cooldown=0.0,
                                    scale_down_cooldown=0.0),
            signals=lambda: {"unified": dict(calm)})
        deadline = time.perf_counter() + 300.0
        while fleet.tier_actual("unified") < 3 \
                or len(fleet.registry.alive()) < 3:
            assert time.perf_counter() < deadline, \
                "autoscaler never relaunched the killed replica"
            auto.step()
            time.sleep(0.1)

        # A one-shot link sever against a healthy replica: the router
        # drops the link, retries elsewhere, the heartbeat revives it.
        other = next(a for a in sorted(
            r.addr for r in fleet.registry.alive()) if a != victim)
        plan.faults.append(Fault("sever", "wire.send", nth=1,
                                 target=other, delay_s=0.0))

        # Phase C — blue-green rollout under the same traffic.
        fleet.rollout("v2", bake_s=0.3)
        stop.set()
        for f in feeders:
            f.join(timeout=300.0)
        assert not lost, f"request lost in soak: {lost[0]!r}"
        # Deadline conformance over the whole soak: no completion came
        # back after its (generous) deadline + epsilon.
        late = [w for w, dl in completions if w > dl + eps_s]
        assert not late, f"{len(late)} completions beat their deadline"

        # Tracing attribution (PR 10 acceptance): the injected
        # slow_task delay is VISIBLE in the slow replica's traced
        # spans — a retained trace holds a router attempt toward the
        # victim carrying (at least) the injected delay, with the
        # chaos firing recorded on the same trace.  The gray failure
        # becomes attributable, not just breaker-detected.
        slow_attempt_ms = 0.0
        traced_fault = False
        for rec in fleet.tracebook.slowest(100):
            spans = rec.get("spans") or ()
            has_fault = any(s.get("component") == "chaos"
                            and s.get("action") == "slow_task"
                            and victim in str(s.get("key", ""))
                            for s in spans)
            for s in spans:
                if s.get("component") == "router" \
                        and s.get("addr") == victim \
                        and s.get("dur", 0.0) >= slow_delay_s * 900.0:
                    slow_attempt_ms = max(slow_attempt_ms,
                                          float(s["dur"]))
                    traced_fault = traced_fault or has_fault
        assert slow_attempt_ms > 0.0, \
            "injected slow_task delay not visible in any traced span " \
            "toward the slow replica"
        assert traced_fault, \
            "chaos slow_task firing not attributed inside the trace"
        traces_detailed = fleet.tracebook.describe()["detailed"]

        c = fleet.snapshot()["counters"]
        completed = c.get("completed", 1)
        amplification = (completed + c.get("retries", 0)) \
            / max(1, completed)
        assert amplification <= 1.5, \
            f"retry amplification {amplification:.3f} > 1.5"
        n_requests = len(completions)
        client.close()
    finally:
        stop.set()
        plan.uninstall()
        fleet.stop()

    # ---- control arm: breakers OFF, same seed, same gray fault ----
    plan, fleet, victim = build(breakers=False)
    try:
        client = FleetClient(fleet.addr, fleet.token, timeout=300.0)
        client.generate(prompts[0], 2)              # warm the compiles
        # Background pressure so p2c spreads the timed requests over
        # the whole tier (idle fleets always pick the least-loaded).
        stop = threading.Event()

        def pressure():
            i = 0
            while not stop.is_set():
                try:
                    client.generate(prompts[i % len(prompts)], 8,
                                    priority="background",
                                    timeout=300.0)
                except Exception:   # noqa: BLE001 - ambient load only
                    return
                i += 1

        bg = threading.Thread(target=pressure, daemon=True)
        bg.start()
        control_walls = timed_interactive(client, 3 * n_timed)
        stop.set()
        bg.join(timeout=300.0)
        control_p99 = p99(control_walls)
        client.close()
    finally:
        stop.set()
        plan.uninstall()
        fleet.stop()
    assert control_p99 > on_p99, \
        (f"control (no breakers) p99 {control_p99:.1f}ms not above "
         f"breakered p99 {on_p99:.1f}ms — isolation unproven")
    assert max(control_walls) >= slow_delay_s * 1000.0, \
        "control arm never even touched the slow replica"
    return (0, amplification, on_p99, control_p99, n_requests,
            slow_attempt_ms, traces_detailed)


def scenario_sim(replicas=1000, n_requests=1_000_000, seed=0):
    """Fleet-simulator scale + fidelity scenario (docs/SIMULATOR.md).

    Two asserts:

    * SCALE — the ``scale`` scenario (the REAL admission/router/
      containment/registry code on the virtual clock, 1000 simulated
      replicas, >= 1M requests, zero lost) completes in under 60s of
      CPU, recording ``sim_events_per_sec`` and
      ``sim_replicas_per_wallclock_sec`` (simulated replica-seconds
      per wall second) so per-request control-plane cost regressions
      surface as a throughput drop.
    * FIDELITY — the ``soak-replay`` scenario replays the seeded
      ``scenario_soak`` chaos timeline and must reproduce its
      qualitative outcomes: the gray-slow replica breaker-isolated
      (latency outlier) while heartbeat-alive, zero lost requests,
      retry amplification <= 1.5, conformant deadline probes.
    """
    from tfmesos_tpu.fleet.sim import run_scenario

    w0 = time.perf_counter()
    c0 = time.process_time()
    out = run_scenario("scale", n_requests=n_requests,
                       replicas=replicas, seed=seed)
    wall_s = time.perf_counter() - w0
    cpu_s = time.process_time() - c0
    assert out["requests"] >= n_requests, out["requests"]
    assert out["lost"] == 0, f"{out['lost']} requests lost in the sim"
    assert min(wall_s, cpu_s) < 60.0, \
        (f"{replicas}-replica / {n_requests}-request scenario took "
         f"{wall_s:.1f}s wall / {cpu_s:.1f}s CPU (budget: 60s)")

    fid = run_scenario("soak-replay", seed=20)
    assert fid["victim_isolated"], "gray replica never breaker-isolated"
    assert fid["victim_alive_while_isolated"], \
        "victim not heartbeat-alive while isolated (not a gray failure)"
    assert fid["victim_trip_reason"] == "latency_outlier", \
        fid["victim_trip_reason"]
    assert fid["lost"] == 0, f"{fid['lost']} requests lost in soak replay"
    assert fid["retry_amplification"] <= 1.5, fid["retry_amplification"]
    assert fid["probes_conformant"], fid["probe_outcomes"]

    # DIURNAL 10x — the ``diurnal`` scenario at 10,000 simulated
    # replicas under a sinusoidal day/night arrival envelope with
    # seeded flash crowds (sharded heartbeats, stretched liveness
    # cadence): the hot path must HOLD the scale scenario's events/s
    # within 2x at 10x the replica count, zero lost.  Recorded as
    # ``sim_events_per_sec_10k`` next to ``sim_events_per_sec``.
    diu = run_scenario("diurnal", n_requests=max(200_000, n_requests // 4),
                       replicas=10 * replicas, seed=seed)
    assert diu["lost"] == 0, f"{diu['lost']} requests lost (diurnal)"
    eps_10k = diu["sim_events_per_sec_10k"]
    assert eps_10k >= 0.5 * out["sim_events_per_sec"], \
        (f"10k-replica diurnal hot path fell below half the "
         f"{replicas}-replica floor: {eps_10k:.0f} vs "
         f"{out['sim_events_per_sec']:.0f} events/s")
    return (out["sim_events_per_sec"],
            out["sim_replicas_per_wallclock_sec"], wall_s,
            out["requests"], out["sim_seconds"],
            fid["retry_amplification"], eps_10k)


def scenario_offline_lane(n_requests=1200, replicas=3, seed=13):
    """The OFFLINE lane (ROADMAP 6b): the ``offline-lane`` scenario's
    lane-on arm vs the lane-off baseline on the same seed — a diurnal
    interactive envelope whose trough leaves slots idle, plus a
    deadline-less batch backlog submitted through the strict-priority
    ``batch`` class.  Asserts: fleet utilization STRICTLY
    higher with the lane on, interactive p99 held within the PR 7
    epsilon convention (1.5x + a small absolute floor), ZERO requests
    lost in either arm, and the whole batch backlog completes."""
    from tfmesos_tpu.fleet.sim import run_sweep

    rows = dict(run_sweep("offline-lane", "batch_lane",
                          ["false", "true"],
                          n_requests=n_requests, replicas=replicas,
                          seed=seed))
    off, on = rows["false"], rows["true"]
    assert on["lost"] == 0 and off["lost"] == 0, \
        f"offline-lane arms lost requests: on={on['lost']} " \
        f"off={off['lost']}"
    assert on["utilization"] > off["utilization"], \
        (f"batch lane did not raise fleet utilization: "
         f"{on['utilization']:.4f} (on) vs {off['utilization']:.4f} "
         f"(off)")
    on_p99 = on["classes"]["interactive"]["p99_ms"]
    off_p99 = off["classes"]["interactive"]["p99_ms"]
    assert on_p99 <= max(1.5 * off_p99, off_p99 + 150.0), \
        (f"interactive p99 not held with the batch lane on: "
         f"{on_p99:.1f}ms vs {off_p99:.1f}ms baseline")
    n_batch = on["batch_planned"]
    assert n_batch > 0 and on["classes"]["batch"]["count"] == n_batch, \
        "the batch backlog did not complete through the lane"
    return (on["utilization"], off["utilization"], on_p99, off_p99,
            on.get("batch_deferrals", 0), n_batch)


def scenario_http_keepalive(n_requests=200):
    """HTTP ingress connection reuse, before/after: requests/s for
    ``n_requests`` sequential POST /v1/completions over ONE kept-alive
    connection vs a fresh connection per request (the pre-keep-alive
    behavior — every request paid connect + teardown).  Echo gateway,
    no fleet, no jax: the delta is pure connection-lifecycle cost."""
    import json as json_mod
    import socket as socket_mod
    import threading

    from tfmesos_tpu import wire
    from tfmesos_tpu.fleet.http import HttpIngress

    class _Echo:
        def handle_ingress(self, reply, msg):
            toks = list(msg.get("prompt", []))
            threading.Thread(
                target=lambda: reply.send(
                    {"op": "completion", "id": msg.get("id"),
                     "tokens": toks, "ttft_ms": 1.0, "total_ms": 2.0}),
                daemon=True).start()

    body = json_mod.dumps({"prompt": [1, 2, 3],
                           "max_tokens": 4}).encode()
    raw = (b"POST /v1/completions HTTP/1.1\r\n"
           b"Content-Type: application/json\r\n"
           + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)

    def read_response(s, buf):
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        head, _, rest = buf.partition(b"\r\n\r\n")
        clen = 0
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                clen = int(v.strip())
        while len(rest) < clen:
            rest += s.recv(65536)
        return rest[clen:]

    srv = wire.WireServer(lambda conn, msg: None, token="bench",
                          name="http-bench")
    srv.add_ingress(HttpIngress(_Echo()))
    srv.start()
    try:
        host, _, port = srv.ingress_addrs[0].rpartition(":")
        addr = (host, int(port))
        # AFTER: one connection, n_requests ride it back to back.
        with socket_mod.create_connection(addr, timeout=30.0) as s:
            s.settimeout(30.0)
            buf = b""
            read_response(s, s.sendall(raw) or buf)   # warm
            t0 = time.perf_counter()
            buf = b""
            for _ in range(n_requests):
                s.sendall(raw)
                buf = read_response(s, buf)
            keep_rps = n_requests / (time.perf_counter() - t0)
        # BEFORE: a fresh connection (connect + close) per request.
        t0 = time.perf_counter()
        for _ in range(n_requests):
            with socket_mod.create_connection(addr, timeout=30.0) as s:
                s.settimeout(30.0)
                s.sendall(raw)
                read_response(s, b"")
        close_rps = n_requests / (time.perf_counter() - t0)
    finally:
        srv.stop()
    return keep_rps, close_rps


def _gateway_flood(addr, token, n_conns, prompt, max_new_tokens=4,
                   timeout_s=180.0):
    """Selector-driven N-connection client harness: open ``n_conns``
    sockets to one gateway, send one STREAMED generate on each, and
    drive every reply with ONE loop (the client-side mirror of the
    event-loop server — a thread per connection on the client would
    measure client thread scheduling, not the front door).  Returns
    ``(ttfts_ms, completed, failed)`` where TTFT is send-to-first-
    token-frame per connection, measured while ALL connections are in
    flight."""
    import selectors
    import socket as socket_mod

    from tfmesos_tpu import wire

    class _Conn:
        __slots__ = ("sock", "framer", "t0", "ttft_ms", "done", "ok")

    sel = selectors.DefaultSelector()
    host, port = addr.rsplit(":", 1)
    conns = []
    for i in range(n_conns):
        s = socket_mod.create_connection((host, int(port)), timeout=30.0)
        s.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        st = _Conn()
        st.sock, st.framer = s, wire.Framer(token)
        st.t0 = st.ttft_ms = None
        st.done = st.ok = False
        conns.append(st)
    # Every link is OPEN before the first request goes out: the claim
    # is concurrent connections, not sequential reuse.
    for i, st in enumerate(conns):
        frame = wire.encode(
            {"op": "generate", "id": i, "prompt": prompt,
             "max_new_tokens": max_new_tokens, "stream": True}, token)
        st.sock.sendall(frame)
        st.t0 = time.perf_counter()
        st.sock.setblocking(False)
        sel.register(st.sock, selectors.EVENT_READ, st)
    remaining = n_conns
    deadline = time.monotonic() + timeout_s

    def finish(st, ok):
        nonlocal remaining
        if st.done:
            return
        st.done, st.ok = True, ok
        remaining -= 1
        try:
            sel.unregister(st.sock)
        except (KeyError, ValueError):
            pass
        try:
            st.sock.close()
        except OSError:
            pass

    while remaining and time.monotonic() < deadline:
        for key, _ in sel.select(timeout=1.0):
            st = key.data
            try:
                data = st.sock.recv(65536)
            except BlockingIOError:
                continue
            except OSError:
                data = b""
            if not data:
                finish(st, False)
                continue
            try:
                msgs = st.framer.feed(data)
            except wire.WireError:
                finish(st, False)
                continue
            for msg in msgs:
                op = msg.get("op") if isinstance(msg, dict) else None
                if st.ttft_ms is None and op in ("tokens", "completion",
                                                 "error"):
                    st.ttft_ms = (time.perf_counter() - st.t0) * 1000.0
                if op in ("completion", "error"):
                    finish(st, op == "completion")
                    break
    for st in conns:
        if not st.done:
            finish(st, False)
    sel.close()
    ttfts = [st.ttft_ms for st in conns if st.ttft_ms is not None]
    completed = sum(1 for st in conns if st.ok)
    return ttfts, completed, n_conns - completed


def scenario_gateway_concurrency(n_conns=1100, kill_threads=8,
                                    kill_requests=30, workers=32,
                                    seed=11):
    """Front-door scale scenario (ROADMAP item 2 acceptance;
    docs/SERVING.md "Front-door scaling").  jax-free — the event-loop
    gateway/registry/router/mux machinery IS the system under test;
    replicas are stub handlers replying streamed canned tokens.

    Two phases, both asserted in the scenario:

    * CONCURRENCY — ``n_conns`` (>= 1000) simultaneous client
      connections against ONE gateway (one selector thread server-side)
      each issue a streamed generate; every one must complete and the
      p99 send-to-first-token TTFT must stay bounded (< 10s) with all
      links in flight — the thread-per-connection front door could not
      hold 1000 links at all.  Records
      ``fleet_gateway_concurrent_connections`` (= connections that
      completed) and ``fleet_gateway_flood_p99_ttft_ms``.
    * KILL SOAK — continuous traffic from ``kill_threads`` clients
      across TWO gateways sharing the one registry/router view; one
      gateway is hard-killed mid-traffic (sockets slam shut, no
      deregistration — the SIGKILL shape).  Clients fail over and
      REPLAY idempotent in-flight requests on the survivor: zero lost
      requests asserted, and the post-kill p99 TTFT must hold within
      2x of the pre-kill p99 (+500ms CPU-scheduler epsilon).  Records
      ``fleet_gateway_prekill_p99_ttft_ms`` /
      ``fleet_gateway_kill_p99_ttft_ms`` /
      ``fleet_gateway_lost_requests``.
    """
    import threading

    from tfmesos_tpu import wire
    from tfmesos_tpu.fleet.admission import AdmissionController
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.gateway import Gateway
    from tfmesos_tpu.fleet.metrics import FleetMetrics
    from tfmesos_tpu.fleet.registry import ReplicaRegistry
    from tfmesos_tpu.fleet.replica import ReplicaServer
    from tfmesos_tpu.fleet.router import Router

    try:                            # headroom for ~2x n_conns fds
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        want = 4 * n_conns + 512
        if soft < want:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(want, hard), hard))
            soft = min(want, hard)
        n_conns = min(n_conns, max(64, (soft - 512) // 4))
    except (ImportError, ValueError, OSError):
        pass

    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=2.0, dead_after=5.0,
                          evict_after=20.0, sweep_interval=0.2).start()

    def stub(tokens):
        # Synchronous streamed replies (no thread per request): a
        # `tokens` partial first — the TTFT marker — then the final
        # completion.  The front door, not replica compute, is what
        # this scenario loads.
        def handler(msg, reply):
            mid = msg.get("id")
            if msg.get("stream"):
                reply.partial({"op": "tokens", "id": mid, "off": 0,
                               "tokens": list(tokens)})
            reply({"op": "completion", "id": mid,
                   "tokens": list(tokens), "ttft_ms": 1.0,
                   "total_ms": 2.0})

        return ReplicaServer(handler, token=token, capacity=4096,
                             registry_addr=reg.addr,
                             heartbeat_interval=0.2).start()

    reps = [stub((7, 3)) for _ in range(3)]
    assert reg.wait_for(3, timeout=10.0)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, request_timeout=120.0)
    admission = AdmissionController(max_queue=max(4096, 2 * n_conns))
    gws = [Gateway(router, admission, metrics, token=token,
                   workers=workers, registry=reg,
                   close_router=False).start() for _ in range(2)]
    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(0, 97, size=(8,))]
    p99 = _p99
    try:
        # ---- phase 1: the connection flood against ONE gateway ----
        ttfts, completed, failed = _gateway_flood(
            gws[0].addr, token, n_conns, prompt)
        flood_p99 = p99(ttfts) if ttfts else float("inf")
        assert completed == n_conns, \
            (f"only {completed}/{n_conns} concurrent connections "
             f"served ({failed} failed)")
        assert flood_p99 < 10_000.0, \
            (f"p99 TTFT {flood_p99:.0f}ms unbounded at {n_conns} "
             f"concurrent connections")

        # ---- phase 2: SIGKILL one of two gateways mid-traffic ----
        addrs = [g.addr for g in gws]
        kill_at = [None]
        lost = [0]
        pre_walls, post_walls = [], []
        wlock = threading.Lock()
        start_evt = threading.Event()

        end_at = [None]

        def client_body(k):
            # Alternate initial gateway per client so both front doors
            # carry traffic when the kill lands.
            order = addrs if k % 2 == 0 else addrs[::-1]
            client = FleetClient(order, token, timeout=60.0)
            try:
                start_evt.wait(10.0)
                done = 0
                while done < kill_requests * 20:
                    with wlock:
                        if end_at[0] is not None \
                                and time.perf_counter() >= end_at[0]:
                            break
                    done += 1
                    first = [None]
                    t0 = time.perf_counter()
                    try:
                        client.generate(
                            prompt, 4, timeout=60.0,
                            on_tokens=lambda t: first.__setitem__(
                                0, first[0] or time.perf_counter()))
                    except Exception:
                        with wlock:
                            lost[0] += 1
                        continue
                    tf = first[0] or time.perf_counter()
                    wall = (tf - t0) * 1000.0
                    with wlock:
                        ka = kill_at[0]
                        if ka is None or tf < ka:
                            pre_walls.append(wall)      # finished pre-kill
                        elif t0 >= ka:
                            post_walls.append(wall)     # started post-kill
                        # requests SPANNING the kill (in flight when the
                        # gateway died — the failover-replayed ones) are
                        # counted for losslessness but excluded from both
                        # steady-state percentiles.
            finally:
                client.close()

        threads = [threading.Thread(target=client_body, args=(k,),
                                    daemon=True)
                   for k in range(kill_threads)]
        for t in threads:
            t.start()
        start_evt.set()
        # Let pre-kill traffic accumulate, then slam gateway 0 shut,
        # then keep the traffic running for the post-kill window.
        time.sleep(1.2)
        with wlock:
            kill_at[0] = time.perf_counter()
        gws[0].kill()
        with wlock:
            end_at[0] = time.perf_counter() + 1.5
        for t in threads:
            t.join(timeout=120.0)
        assert lost[0] == 0, \
            f"{lost[0]} idempotent requests lost across the gateway kill"
        assert pre_walls and post_walls, \
            (f"kill landed outside the traffic window "
             f"({len(pre_walls)} pre / {len(post_walls)} post)")
        pre_p99, post_p99 = p99(pre_walls), p99(post_walls)
        assert post_p99 <= max(2.0 * pre_p99, pre_p99 + 500.0), \
            (f"p99 TTFT did not hold across the gateway kill: "
             f"{post_p99:.0f}ms post vs {pre_p99:.0f}ms pre")
        return (completed, flood_p99, pre_p99, post_p99, lost[0])
    finally:
        for g in gws:
            if not g.killed:
                g.stop()
        router.close()
        for r in reps:
            r.stop()
        reg.stop()


def scenario_gateway_procs(n_procs=4, threads=12, window_s=2.0,
                              workers=16, seed=13):
    """Multi-process front door scenario (docs/SERVING.md "Multi-process
    gateways").  jax-free — REAL gateway OS processes (``python -m
    tfmesos_tpu.fleet.gateway``, the ``tfserve --gateway-processes N``
    unit) routed over stub replicas; one CPython event loop per
    process, so N processes are the only way past one GIL.

    Phases, all asserted in the scenario:

    * SATURATION — a closed-loop flood from ``threads`` wire clients
      for ``window_s`` against ONE gateway process, then against
      ``n_procs`` processes sharing ONE public port via SO_REUSEPORT
      (per-process ports behind the registry's discovery op where
      REUSEPORT is unavailable): with >1 CPU core the N-process
      completed-requests/s must STRICTLY beat the single process
      (``fleet_gateway_procs_rps_n`` vs ``fleet_gateway_procs_rps_1``);
      on a single core N processes cannot beat one by physics (there
      is no second core to run on), so the assert becomes a bounded
      oversubscription cost (>= 0.25x) and the recorded mode says so.
    * KILL SOAK — mid-window in the N-process run, one process is
      SIGKILLED.  Clients reconnect (the kernel steers new
      connections to surviving REUSEPORT listeners) and REPLAY
      idempotent in-flight requests — the PR 12 failover contract,
      verbatim, across an OS-process death: zero lost asserted,
      post-kill p99 TTFT recorded next to pre-kill.
    """
    import os
    import signal
    import subprocess
    import sys
    import threading

    from tfmesos_tpu import wire
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.registry import ReplicaRegistry
    from tfmesos_tpu.fleet.replica import ReplicaServer

    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=2.0, dead_after=5.0,
                          evict_after=20.0, sweep_interval=0.2).start()

    def stub():
        def handler(msg, reply):
            mid = msg.get("id")
            if msg.get("stream"):
                reply.partial({"op": "tokens", "id": mid, "off": 0,
                               "tokens": [7, 3]})
            reply({"op": "completion", "id": mid, "tokens": [7, 3],
                   "ttft_ms": 1.0, "total_ms": 2.0})

        return ReplicaServer(handler, token=token, capacity=4096,
                             registry_addr=reg.addr,
                             heartbeat_interval=0.2).start()

    reps = [stub() for _ in range(3)]
    assert reg.wait_for(3, timeout=10.0)
    env = dict(os.environ, TPUMESOS_TOKEN=token)
    env.pop("TPUMESOS_TOKEN_FILE", None)
    reuseport = wire.reuseport_available()
    procs = []
    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(0, 97, size=(8,))]
    p99 = _p99

    def spawn(port, reuse):
        cmd = [sys.executable, "-m", "tfmesos_tpu.fleet.gateway",
               "--registry", reg.addr, "--host", "127.0.0.1",
               "--port", str(port), "--workers", str(workers)]
        if reuse:
            cmd.append("--reuseport")
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    def wait_gateways(n, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(reg.gateway_leases()) >= n:
                return
            for p in procs:
                if p.poll() is not None:
                    raise AssertionError(
                        f"gateway process exited rc={p.returncode} "
                        f"during bring-up")
            time.sleep(0.05)
        raise AssertionError(
            f"only {len(reg.gateway_leases())}/{n} gateway "
            f"process(es) registered within {timeout:.0f}s")

    def wait_mirrors(want, timeout=15.0):
        # Each process's sidecar mirror must route to every alive stub
        # before traffic starts (the launcher's bring-up barrier).
        pending = set(reg.gateway_leases())
        deadline = time.monotonic() + timeout
        while pending and time.monotonic() < deadline:
            for addr in sorted(pending):
                try:
                    sock = wire.connect(addr, timeout=2.0)
                    try:
                        sock.settimeout(2.0)
                        wire.send_msg(sock, {"op": "status"}, token)
                        reply = wire.recv_msg(sock, token)
                    finally:
                        sock.close()
                except (OSError, wire.WireError):
                    continue
                alive = reply.get("alive") if isinstance(reply, dict) \
                    else None
                if isinstance(alive, int) and alive >= want:
                    pending.discard(addr)
            if pending:
                time.sleep(0.05)
        assert not pending, \
            f"{len(pending)} gateway mirror(s) never converged"

    def reap():
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5.0)
        procs.clear()
        for a in list(reg.gateway_addrs()):
            reg.unregister_gateway(a)

    def pump(addrs, window, kill_proc=None):
        """Closed-loop flood: ``threads`` clients, each measuring
        send-to-first-token per request.  Returns (rps, ttft recs,
        lost, kill timestamp)."""
        recs = []                   # (t0, t_first, wall_ms)
        lost = [0]
        kill_at = [None]
        tl = threading.Lock()
        start_evt = threading.Event()
        end_at = [None]

        def body(k):
            rot = k % len(addrs)
            order = addrs[rot:] + addrs[:rot]
            if len(order) == 1:
                # One shared REUSEPORT addr: failover = reconnect to
                # the same public door (the kernel re-picks a live
                # listener process).
                order = order * 2
            client = FleetClient(order, token, timeout=30.0)
            try:
                start_evt.wait(10.0)
                while time.perf_counter() < end_at[0]:
                    first = [None]
                    t0 = time.perf_counter()
                    try:
                        client.generate(
                            prompt, 4, timeout=30.0,
                            on_tokens=lambda t: first.__setitem__(
                                0, first[0] or time.perf_counter()))
                    except Exception:
                        with tl:
                            lost[0] += 1
                        continue
                    tf = first[0] or time.perf_counter()
                    with tl:
                        recs.append((t0, tf, (tf - t0) * 1000.0))
            finally:
                client.close()

        tls = [threading.Thread(target=body, args=(k,), daemon=True)
               for k in range(threads)]
        for t in tls:
            t.start()
        end_at[0] = time.perf_counter() + window
        start_evt.set()
        if kill_proc is not None:
            time.sleep(window / 2.0)
            with tl:
                kill_at[0] = time.perf_counter()
            os.kill(kill_proc.pid, signal.SIGKILL)
        for t in tls:
            t.join(timeout=60.0)
        rps = len(recs) / window
        return rps, recs, lost[0], kill_at[0]

    try:
        # ---- phase 1: one gateway process ----
        spawn(0, False)
        wait_gateways(1)
        wait_mirrors(3)
        addrs1 = sorted(reg.gateway_addrs())
        rps_1, recs_1, lost_1, _ = pump(addrs1, window_s)
        assert lost_1 == 0, f"{lost_1} requests lost against 1 process"
        assert recs_1, "no requests completed against 1 process"
        p99_1 = p99([r[2] for r in recs_1])
        reap()

        # ---- phase 2: N processes + mid-window SIGKILL ----
        if reuseport:
            probe = wire.bind_ephemeral("127.0.0.1", 0, reuseport=True)
            shared_port = probe.getsockname()[1]
            probe.close()
            for _ in range(n_procs):
                spawn(shared_port, True)
        else:
            for _ in range(n_procs):
                spawn(0, False)
        wait_gateways(n_procs)
        wait_mirrors(3)
        addrs_n = sorted(reg.gateway_addrs())
        if reuseport:
            assert len(addrs_n) == 1, addrs_n   # ONE public door
        # 2a: clean saturation window (no kill) — the rps comparison.
        rps_n, recs_n, lost_n, _ = pump(addrs_n, window_s)
        assert lost_n == 0, \
            f"{lost_n} requests lost against {n_procs} processes"
        cores = os.cpu_count() or 1
        if cores > 1:
            assert rps_n > rps_1, \
                (f"{n_procs} gateway processes did not beat 1 on "
                 f"{cores} cores: {rps_n:.0f} vs {rps_1:.0f} rps")
        else:
            # One core: no parallel win is possible — the contract
            # shrinks to bounded oversubscription cost.
            assert rps_n >= 0.25 * rps_1, \
                (f"{n_procs} gateway processes collapsed on one core: "
                 f"{rps_n:.0f} vs {rps_1:.0f} rps")
        # 2b: kill soak — SIGKILL one process mid-window; clients
        # replay in-flight idempotent requests on reconnect.
        rps_k, recs_k, lost_k, ka = pump(
            addrs_n, window_s, kill_proc=procs[-1])
        assert lost_k == 0, \
            (f"{lost_k} idempotent requests lost across the "
             f"gateway-process SIGKILL")
        pre = [r[2] for r in recs_k if r[1] < ka]
        post = [r[2] for r in recs_k if r[0] >= ka]
        assert pre and post, \
            (f"kill landed outside the traffic window "
             f"({len(pre)} pre / {len(post)} post)")
        mode = ("reuseport" if reuseport else "discovery") \
            + ("-1core" if cores == 1 else "")
        return (rps_1, rps_n, p99_1, p99(pre), p99(post),
                lost_k, mode)
    finally:
        reap()
        for r in reps:
            r.stop()
        reg.stop()


def scenario_trace_overhead(n_requests=240, workers=4, threads=2,
                               handler_delay_s=0.01, best_of=3):
    """Tracing overhead bound (PR 10 acceptance): the same seeded stub
    workload — jax-free; the gateway/router/tracing machinery IS the
    system under test — run with tracing at summary-only vs FULL span
    detail on every request; the detailed arm's p99 must land within
    5% of summary-only (+1ms absolute epsilon absorbing CPU scheduler
    noise at these few-ms latencies).  Arms alternate order and each
    takes its best-of-``best_of`` p99 — at this scale the scheduler's
    tail jitter is bigger than any real software cost, and only the
    min is a stable estimator of it.  Records
    ``fleet_trace_overhead_pct``."""
    import threading as _threading

    from tfmesos_tpu import wire as _wire
    from tfmesos_tpu.fleet.admission import AdmissionController
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.gateway import Gateway
    from tfmesos_tpu.fleet.metrics import FleetMetrics
    from tfmesos_tpu.fleet.registry import ReplicaRegistry
    from tfmesos_tpu.fleet.replica import ReplicaServer
    from tfmesos_tpu.fleet.router import Router
    from tfmesos_tpu.fleet.tracing import TraceBook

    p99 = _p99

    def arm(sample, detail):
        token = _wire.new_token()
        reg = ReplicaRegistry(token=token, suspect_after=1.0,
                              dead_after=2.0, evict_after=10.0).start()
        servers = []

        def handler(msg, reply):
            def work():
                time.sleep(handler_delay_s)
                reply({"op": "completion", "id": msg.get("id"),
                       "tokens": [1, 2], "ttft_ms": 1.0,
                       "total_ms": 2.0})

            _threading.Thread(target=work, daemon=True).start()

        for _ in range(2):
            servers.append(ReplicaServer(
                handler, token=token, capacity=64,
                registry_addr=reg.addr,
                heartbeat_interval=0.1).start())
        deadline = time.perf_counter() + 30.0
        while len(reg.alive()) < 2 and time.perf_counter() < deadline:
            time.sleep(0.02)
        metrics = FleetMetrics()
        router = Router(reg, metrics, token=token)
        book = TraceBook(sample=sample, slow_ms=1e9)
        gw = Gateway(router, AdmissionController(max_queue=1024),
                     metrics, token=token, workers=workers,
                     tracebook=book).start()
        walls = []
        lock = _threading.Lock()

        def feeder():
            client = FleetClient(gw.addr, token, timeout=60.0)
            for _ in range(n_requests // threads):
                t0 = time.perf_counter()
                client.generate([1, 2, 3, 4], 2,
                                trace=(True if detail else None),
                                timeout=60.0)
                dt = (time.perf_counter() - t0) * 1000.0
                with lock:
                    walls.append(dt)
            client.close()

        try:
            ts = [_threading.Thread(target=feeder)
                  for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120.0)
            assert len(walls) == (n_requests // threads) * threads
            if detail:
                # The detailed arm must actually have traced in detail.
                assert book.describe()["detailed"] == len(walls)
        finally:
            gw.stop()
            for s in servers:
                s.stop()
            reg.stop()
        return p99(walls)

    # Best-of-N per arm, orders alternating so host drift (page cache,
    # cpu governor, background load) cannot masquerade as tracing
    # cost: at few-ms stub latencies one unlucky scheduler stall is
    # bigger than the entire software path under test.
    summaries, details = [], []
    for i in range(best_of):
        if i % 2 == 0:
            summaries.append(arm(0.0, False))
            details.append(arm(1.0, True))
        else:
            details.append(arm(1.0, True))
            summaries.append(arm(0.0, False))
    p99_summary = min(summaries)
    p99_detail = min(details)
    overhead_pct = (p99_detail - p99_summary) / p99_summary * 100.0
    assert p99_detail <= p99_summary * 1.05 + 1.0, \
        (f"tracing overhead unbounded: detailed p99 {p99_detail:.2f}ms "
         f"vs summary-only p99 {p99_summary:.2f}ms "
         f"({overhead_pct:+.1f}%)")
    return overhead_pct, p99_summary, p99_detail
