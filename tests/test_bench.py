"""Smoke the benchmark's code paths on the virtual CPU mesh.

The driver runs ``bench.py`` unattended at the end of every round; a crash
there silently loses the round's benchmark, so the cheap-to-compile paths
(flops formulas, bandwidth sweep, decode loop, mnist trainer) get
tiny-shape CI runs.  The two big transformer benches share
``_bench_transformer_config`` with nothing CI-affordable to add — their
compile alone outweighs this whole file.  Numbers on CPU are meaningless —
only "runs and returns finite values" is asserted.
"""

import numpy as np
import pytest

import bench


def test_flops_formulas():
    from tfmesos_tpu.models import mlp, transformer

    cfg = transformer.TransformerConfig(
        vocab_size=8192, d_model=512, n_layers=8, n_heads=8, d_ff=1408,
        max_seq_len=2048)
    per_tok = bench.transformer_flops_per_token(cfg, 2048)
    # ~3x forward of ~2*params-ish: sanity band, not an exact constant.
    assert 1e8 < per_tok < 1e9
    assert bench.mlp_flops_per_step(mlp.MLPConfig(hidden=100), 100) == \
        6 * (784 * 100 + 100 * 10) * 100


def test_unknown_device_kind_is_an_error():
    """No default peak: the CPU is not in the table, so an MFU against
    it must raise instead of assuming the v5e figure."""
    with pytest.raises(RuntimeError, match="no published bf16 peak"):
        bench._peak_flops()


def test_bandwidth_multi_device_path():
    out = bench.bench_bandwidth(sizes=[1 << 18])
    assert out["allreduce_gbps"] is not None and out["allreduce_gbps"] > 0
    assert out["hbm_gbps"] is None  # n>1: the psum branch ran
    assert all(v > 0 for v in out["allreduce_sweep"].values())


def test_decode_bench_smoke():
    toks = bench.bench_decode(batch=1, prompt_len=8, new_tokens=4)
    assert np.isfinite(toks) and toks > 0


def test_mnist_bench_smoke():
    """Runs in a clean subprocess on the 8-device virtual CPU mesh.  The
    CPU has no published peak and ``_peak_flops`` refuses to guess one, so
    the test names a stand-in for it: only "runs and returns finite
    values" is asserted, never the MFU's size."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import json\n"
        "from tfmesos_tpu.utils.platform import force_platform\n"
        "force_platform('cpu', min_host_devices=8)\n"
        "import bench\n"
        "bench.PEAK_BF16['cpu'] = 1e12\n"
        "s, l, m = bench.bench_mnist_replica(steps=40, warmup=20)\n"
        "print(json.dumps({'steps': s, 'loss': l, 'mfu': m}))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, timeout=240)
    assert proc.returncode == 0, proc.stderr.decode()
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert np.isfinite(out["steps"]) and out["steps"] > 0
    assert np.isfinite(out["loss"])
    assert 0 <= out["mfu"] < 1


def test_decode_bench_int8_smoke():
    toks = bench.bench_decode(batch=1, prompt_len=8, new_tokens=4,
                              quantized=True)
    assert np.isfinite(toks) and toks > 0


def test_decode_bench_int8_kv_smoke():
    toks = bench.bench_decode(batch=1, prompt_len=8, new_tokens=4,
                              quantized=True, quantized_cache=True)
    assert np.isfinite(toks) and toks > 0


def test_attention_bench_smoke():
    flash_ms, xla_ms = bench.bench_attention(b=1, t=128, h=2, d=32, reps=2)
    assert np.isfinite(flash_ms) and flash_ms > 0
    assert np.isfinite(xla_ms) and xla_ms > 0


def test_decode_long_context_bench_smoke():
    kern, einsum = bench.bench_decode_long_context(
        batch=1, max_len=512, prompt_len=32, new_tokens=4)
    assert np.isfinite(kern) and kern > 0
    assert np.isfinite(einsum) and einsum > 0


def test_serving_bench_smoke():
    rps, ttft_ms, ms_rps, itl_p50 = \
        bench.bench_serving_continuous(n_requests=3, rows=2, tiny=True)
    assert rps > 0 and ttft_ms > 0 and ms_rps > 0
    assert np.isfinite(itl_p50) and itl_p50 >= 0


def test_decode_paged_call_bench_smoke():
    """The paged-call floor microbench end to end at tiny size: finite
    per-call latencies for the sync (t=1) and fused (t=8) launches,
    and the launches-per-block keys — fused <= 2 is asserted INSIDE
    the bench (the acceptance bar), sync stays the 1-launch-per-token
    analytic 16."""
    call_ms, fused_ms, sync_lpb, fused_lpb = \
        bench.bench_decode_paged_call(tiny=True, reps=3)
    assert call_ms > 0 and fused_ms > 0
    assert sync_lpb == 16
    assert fused_lpb == 2


def test_serving_pipeline_bench_smoke():
    """The pipelined-vs-synchronous protocol runs end to end at tiny
    size; token identity is asserted inside the bench.  The strict
    inter-token improvement is asserted there too — meaningful on the
    flagship config, noisy at toy sizes, so a tiny-shape inversion only
    skips (the equivalence matrix in test_serving is the correctness
    gate; the flagship assert runs in the real bench)."""
    try:
        pipe_itl, base_itl, pipe_rps = bench.bench_serving_pipeline(
            n_requests=4, rows=2, tiny=True)
    except AssertionError as e:
        if "not strictly better" in str(e):
            pytest.skip(f"tiny-shape timing inversion: {e}")
        raise
    assert pipe_itl > 0 and base_itl > 0 and pipe_rps > 0


@pytest.mark.slow
def test_serving_fused_prefill_bench_smoke():
    """The fused-vs-phase-split protocol (``serving_fused_*`` keys)
    end to end at tiny size: token identity and the fused-tick
    counters are asserted inside the bench; the strict inter-token p99
    win holds at this shape too (per-token stream timestamps make the
    stalled tick the p99's population, not an outlier), but a timing
    inversion only skips — test_serving's fused matrix is the
    correctness gate, the flagship assert runs in the real bench."""
    try:
        fused_p99, split_p99, fused_rps = \
            bench.bench_serving_fused_prefill(tiny=True)
    except AssertionError as e:
        if "not strictly better" in str(e):
            pytest.skip(f"tiny-shape timing inversion: {e}")
        raise
    assert 0 < fused_p99 < split_p99 and fused_rps > 0


@pytest.mark.slow
def test_fleet_offline_lane_bench_smoke():
    """The offline-lane bench (``fleet_offline_*`` keys) end to end at
    CI size: utilization strictly higher with the batch lane on,
    interactive p99 held, zero lost, backlog complete — all asserted
    inside the bench; the smoke pins shapes and directions."""
    on_util, off_util, on_p99, off_p99, deferrals, n_batch = \
        bench.bench_fleet_offline_lane(n_requests=600, replicas=3,
                                       seed=13)
    assert 0 < off_util < on_util <= 1.0
    assert on_p99 > 0 and off_p99 > 0
    assert n_batch == 300 and deferrals >= 0


def test_http_keepalive_bench_smoke():
    """Connection-reuse before/after rps: both arms finite, jax-free."""
    keep_rps, close_rps = bench.bench_http_keepalive(n_requests=20)
    assert keep_rps > 0 and close_rps > 0


@pytest.mark.slow
def test_serving_spec_compose_bench_smoke():
    """The spec-composition protocol end to end at tiny size,
    ``strict=False``: every CORRECTNESS assert stays hard (warm spec
    streams equal cold, perfect-draft acceptance ~1.0, zero lost
    requests and reference-exact streams through the mid-decode fleet
    migration), while the strict TIMING win (spec+prefix warm TTFT <
    cold) is asserted only at flagship scale — toy shapes invert
    timings."""
    warm_ttft, cold_ttft, spec_itl, base_itl, accept, resumes = \
        bench.bench_serving_spec_compose(
            n_requests=4, rows=2, tiny=True, decode_new=24,
            migrate_requests=4, strict=False)
    assert warm_ttft > 0 and cold_ttft > 0
    assert spec_itl > 0 and base_itl > 0
    assert 0.0 <= accept <= 1.0
    assert resumes >= 0
    # The fused-spec path's launch economics hold at this tiny shape
    # too: a 16-step block through the multi-step verify costs <= 2
    # paged launches, against the synchronous analytic 16 — the same
    # keys bench_decode_paged_call promotes to first-class metrics.
    import jax
    import jax.numpy as jnp

    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.serving import ContinuousBatcher

    cfg, params, _, max_len, _ = bench._serving_bench_setup(True)
    dcfg = transformer.TransformerConfig(
        vocab_size=cfg.vocab_size, d_model=16, n_layers=1, n_heads=2,
        d_ff=32, max_seq_len=max_len + 8, dtype=jnp.float32)
    dparams = transformer.init_params(dcfg, jax.random.PRNGKey(1))
    spec = ContinuousBatcher(cfg, params, rows=2, max_len=max_len,
                             draft_cfg=dcfg, draft_params=dparams,
                             n_draft=7)
    assert spec.paged_launches_per_block(16) <= 2
    sync = ContinuousBatcher(cfg, params, rows=2, max_len=max_len)
    assert sync.paged_launches_per_block(16) == 16


def test_serving_warmup_bench_smoke():
    warm_ttft, cold_ttft, warm_s = bench.bench_serving_warmup(
        rows=2, tiny=True)
    assert 0 < warm_ttft < cold_ttft    # also asserted in-bench
    assert warm_s >= 0


def test_bandwidth_single_device_records_skip_reason(monkeypatch):
    """With one visible device the bench must say WHY allreduce_gbps is
    absent (r05 recorded a bare null) and fall through to the HBM
    triad."""
    import jax

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    out = bench.bench_bandwidth(sizes=[1 << 16])
    assert out["allreduce_gbps"] is None
    assert "no ICI" in out["allreduce_skip_reason"]
    assert out["hbm_gbps"] is not None and out["hbm_gbps"] > 0


def test_serving_longctx_bench_smoke():
    # Same call path as the TPU long-context section (bucketed tables,
    # deferred commits, multi_step + pipeline_depth=1) at toy sizes.
    tok_s, ttft_ms = bench.bench_serving_longctx(
        n_requests=3, rows=2, tiny=True)
    assert tok_s > 0 and ttft_ms > 0


def test_serving_mesh_bench_smoke():
    rps = bench.bench_serving_continuous_mesh(n_requests=3, rows=2,
                                              tiny=True)
    assert rps is not None and rps > 0   # 8 virtual devices: dp x tp ran


def test_ring_window_bench_smoke():
    out = bench.bench_ring_window(t=64, window=16, reps=1, interpret=True,
                                  h=2, d=16)
    assert out is not None
    flash_ms, xla_ms = out
    assert flash_ms > 0 and xla_ms > 0


def test_pipeline_bubble_stats_static():
    # Bubble-bound regime (deep pipe, few microbatches): interleaving
    # must strictly beat v=1 wall-clock at equal work.
    out = bench.pipeline_bubble_stats(pp=8, m=8)
    assert 0.0 < out["pipeline_bubble_v2"] < out["pipeline_bubble_v1"]
    assert out["pipeline_interleave_speedup"] > 1.1
    # Amortized regime: the ratio honestly collapses toward 1.
    flat = bench.pipeline_bubble_stats(pp=4, m=16)
    assert 0.95 < flat["pipeline_interleave_speedup"] < 1.1


@pytest.mark.slow
def test_fleet_bench_smoke():
    """The fleet serving bench (gateway + 2 LocalBackend CPU replicas)
    runs end to end and returns finite numbers.  Marked slow: it pays a
    full fleet bring-up that tests/test_fleet.py already exercises in
    tier-1; this guards the driver's unattended bench.py run."""
    rps, ttft_ms, queue_wait_p50, queue_wait_p99 = bench.bench_fleet_serving(
        n_requests=4, replicas=2, rows=2, tiny=True, workers=4)
    assert np.isfinite(rps) and rps > 0
    assert np.isfinite(ttft_ms) and ttft_ms > 0
    assert np.isfinite(queue_wait_p50) and queue_wait_p50 >= 0
    assert np.isfinite(queue_wait_p99) and queue_wait_p99 >= queue_wait_p50


@pytest.mark.slow
def test_fleet_disagg_bench_smoke():
    """The disaggregated-vs-unified mixed-workload protocol runs end to
    end (4 fleet bring-ups worth of subprocesses — slow) and asserts
    internally that the decode tier beat the unified baseline's
    inter-token p50 and that both tiers served traffic."""
    dis_ttft, dis_itl, uni_ttft, uni_itl, kv_mb_s = \
        bench.bench_fleet_disagg(n_decode=4, decode_new=16, rows=2,
                                 workers=4)
    assert all(np.isfinite(v) and v > 0
               for v in (dis_ttft, dis_itl, uni_ttft, uni_itl))
    assert dis_itl < uni_itl
    assert np.isfinite(kv_mb_s) and kv_mb_s > 0


def test_serving_prefix_cache_bench_smoke():
    """Warm-vs-cold shared-prefix protocol runs end to end at tiny size
    and asserts warm == cold completions internally."""
    warm_ttft, cold_ttft, rps, hit_rate = bench.bench_serving_prefix_cache(
        n_requests=3, rows=2, tiny=True)
    assert warm_ttft > 0 and cold_ttft > 0 and rps > 0
    assert 0.0 < hit_rate <= 1.0


@pytest.mark.slow
def test_fleet_autoscale_bench_smoke():
    """The autoscale/rollout control-plane bench: injected surge →
    autoscaled replica routable, then a zero-downtime rollout under
    continuous traffic (zero failures asserted in-bench)."""
    reaction_s, downtime_ms = bench.bench_fleet_autoscale(rows=2,
                                                          workers=4)
    assert np.isfinite(reaction_s) and reaction_s > 0
    assert downtime_ms == 0.0


@pytest.mark.slow
def test_fleet_prefix_affinity_bench_smoke():
    """Fleet prefix-affinity protocol over 2 local CPU replicas."""
    hit_rate, rps = bench.bench_fleet_prefix_affinity(
        n_requests=6, replicas=2, rows=2, workers=4)
    assert 0.0 <= hit_rate <= 1.0 and rps > 0


@pytest.mark.slow
def test_fleet_priority_bench_smoke():
    """The priority/migration bench protocol end to end at small size:
    records the fleet_priority_* / fleet_migration_lost_requests keys,
    asserting class isolation and zero lost requests internally.  The
    SLO-hold assert compares tens-of-ms latencies on CPU, so a tiny-
    shape timing inversion only skips (the jax-free WFQ suite and the
    migration tests are the correctness gates)."""
    try:
        unloaded_p99, pri_p99, bg_p99, lost = bench.bench_fleet_priority(
            n_interactive=8, rows=2, workers=4, flood_threads=2)
    except AssertionError as e:
        if "not held within" in str(e) or "isolation failed" in str(e):
            pytest.skip(f"tiny-shape timing inversion: {e}")
        raise
    assert all(np.isfinite(v) and v > 0
               for v in (unloaded_p99, pri_p99, bg_p99))
    assert pri_p99 < bg_p99
    assert lost == 0


@pytest.mark.slow
def test_fleet_sim_bench_smoke():
    """bench_fleet_sim's protocol at small size: the scale scenario
    (real control plane, virtual clock) completes losslessly and the
    soak-replay fidelity gate holds — all asserted inside the bench."""
    (events_ps, replica_s_ps, wall_s, n, sim_s, fid_amp) = \
        bench.bench_fleet_sim(replicas=100, n_requests=20_000)
    assert n == 20_000
    assert events_ps > 0 and replica_s_ps > 0
    assert sim_s > 0
    assert fid_amp <= 1.5
    assert wall_s < 60.0


@pytest.mark.slow
def test_fleet_gateway_concurrency_bench_smoke():
    """bench_fleet_gateway_concurrency's protocol at reduced scale
    (jax-free stubs; the event-loop gateway is the system under test):
    every concurrent connection served with bounded p99, and the
    two-gateway kill soak loses zero idempotent requests — asserted
    inside the bench.  The full >= 1000-connection figure is the
    bench run's."""
    (conns, flood_p99, pre_p99, post_p99, lost) = \
        bench.bench_fleet_gateway_concurrency(
            n_conns=220, kill_threads=4, workers=8)
    assert conns == 220
    assert np.isfinite(flood_p99) and flood_p99 > 0
    assert np.isfinite(pre_p99) and np.isfinite(post_p99)
    assert lost == 0


@pytest.mark.slow
def test_fleet_soak_bench_smoke():
    """The chaos-soak protocol end to end at small size: gray-slow
    replica breaker-isolated while heartbeat-alive, SIGKILL +
    autoscaler self-heal, link sever, rollout — zero lost requests,
    deadline conformance, and bounded retry amplification asserted
    inside the bench.  The breakers-off control arm compares
    tens-of-ms CPU latencies, so a timing inversion only skips (the
    jax-free tests/test_containment.py suite is the correctness
    gate)."""
    try:
        (lost, amplification, on_p99, control_p99, n,
         slow_attempt_ms, traces_detailed) = \
            bench.bench_fleet_soak(rows=2, workers=4, n_timed=8)
    except AssertionError as e:
        if "isolation unproven" in str(e) \
                or "never even touched" in str(e):
            pytest.skip(f"tiny-shape timing inversion: {e}")
        raise
    assert lost == 0
    assert amplification <= 1.5
    assert n > 0
    assert all(np.isfinite(v) and v > 0 for v in (on_p99, control_p99))
    # PR 10: the injected gray delay is attributable inside a retained
    # trace, not just breaker-detected — the span must carry (at least)
    # the injected delay, not merely exist.
    assert slow_attempt_ms >= 0.25 * 900.0
    assert traces_detailed > 0


@pytest.mark.slow
def test_fleet_trace_overhead_bench_smoke():
    """Tracing overhead bound at small size (jax-free stub fleet):
    detailed-on-every-request p99 within 5% (+1ms) of summary-only —
    asserted inside the bench; a pure timing inversion on a loaded CI
    host only skips."""
    try:
        overhead_pct, p99_sum, p99_det = \
            bench.bench_fleet_trace_overhead(n_requests=160, threads=4)
    except AssertionError as e:
        if "tracing overhead unbounded" in str(e):
            pytest.skip(f"loaded-host timing inversion: {e}")
        raise
    assert np.isfinite(overhead_pct)
    assert p99_sum > 0 and p99_det > 0


@pytest.mark.slow
def test_fleet_sessions_bench_smoke():
    """The KV-tier sessions bench protocol at small size: flagship
    resume-vs-cold (streams asserted token-identical + resumed TTFT
    strictly below cold inside the bench), the tiny-fleet wire round
    trip, and the shared-prefix prefilled-once-per-fleet assert.  A
    pure CPU timing inversion on a loaded host only skips."""
    try:
        resumed, cold, hit_rate, prefills, aff = \
            bench.bench_fleet_sessions(replicas=2, rows=2, turns=2,
                                       n_shared=4, workers=4)
    except AssertionError as e:
        if "not below cold" in str(e):
            pytest.skip(f"loaded-host timing inversion: {e}")
        raise
    assert resumed > 0 and cold > 0
    assert 0.0 <= hit_rate <= 1.0
    assert prefills == 1
    assert 0.0 <= aff <= 1.0


@pytest.mark.slow
def test_fleet_fabric_bench_smoke():
    """The KV-fabric bench protocol at small size: direct peer
    streaming vs the relay fallback on the real wire stack (strictly
    faster asserted inside the bench), and a kv_replication=2 fleet
    riding out a parker SIGKILL with every session resuming
    token-identical on a survivor — zero lost, at least one forwarded
    fabric fetch.  A pure CPU timing inversion on a loaded host only
    skips."""
    try:
        direct_mb_s, relay_mb_s, resumed, fetch_hits = \
            bench.bench_fleet_fabric(replicas=3, rows=2, workers=4,
                                     n_sessions=4, n_transfers=8,
                                     artifact_mb=0.5)
    except AssertionError as e:
        if "not above the relay fallback" in str(e):
            pytest.skip(f"loaded-host timing inversion: {e}")
        raise
    assert direct_mb_s > relay_mb_s > 0
    assert resumed == 4
    assert fetch_hits >= 1


@pytest.mark.slow
def test_fleet_multimodel_bench_smoke():
    """The model-catalog bench protocol end to end: warm-pool cold
    start strictly below cold relaunch, a budget-tight trade under
    continuous two-tenant traffic with zero lost requests, adapter
    hot-swap token-identical per delta version, and the per-tenant x
    model meters — all asserted inside the bench itself."""
    out = bench.bench_fleet_multimodel(rows=2, workers=4)
    assert out["fleet_multimodel_lost_requests"] == 0
    assert out["fleet_multimodel_trade_reaction_s"] > 0
    assert out["fleet_multimodel_pool_cold_start_ttft_ms"] < \
        out["fleet_multimodel_relaunch_cold_start_ttft_ms"]
    assert out["fleet_multimodel_metered_pairs"] >= 4


@pytest.mark.slow
def test_fleet_gang_bench_smoke():
    """The gang-replica bench protocol at small size: a 2-member gang
    behind the gateway streams token-identical to a single-process
    fleet, a mid-decode gang-member SIGKILL loses nothing (the gang
    dies whole, re-forms, in-flight work replays on the survivor), and
    a gang drain-migration loses nothing — all asserted inside the
    bench itself."""
    gang_itl, single_itl, reform_s = bench.bench_fleet_gang(
        n_requests=4, gang_size=2, rows=2, decode_new=16, workers=4)
    assert gang_itl > 0 and single_itl > 0
    assert reform_s > 0
