"""Disaggregated prefill/decode serving (serving.export_kv /
submit(prefilled=...) + the fleet's role-aware handoff): greedy
completions through prefill-export → wire pack/unpack → decode-import
must equal the unified ``ContinuousBatcher`` token-for-token — including
chunked-prefill and int8-pool configurations — and imported pages must
interact with the cross-request prefix cache exactly like locally
prefilled ones (seed the trie, or bypass explicitly)."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tfmesos_tpu import wire
from tfmesos_tpu.models import transformer
from tfmesos_tpu.serving import (ContinuousBatcher, Prefilled, Request,
                                 pack_prefilled, unpack_prefilled)


@pytest.fixture(scope="module")
def setup():
    cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _reqs(cfg, n, seed=0, stop_every=None, max_new=7):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        stop = (int(rng.randint(0, cfg.vocab_size))
                if stop_every and i % stop_every == 0 else None)
        out.append(Request(
            prompt=rng.randint(0, cfg.vocab_size,
                               size=rng.randint(3, 20)).astype(np.int32),
            max_new_tokens=1 + (i % max_new), stop_token=stop))
    return out


def _through_wire(art):
    """Round-trip an artifact through the raw wire framing — what the
    fleet's prefill→decode handoff actually ships."""
    meta, body = pack_prefilled(art)
    frame = wire.encode_raw(dict(meta, op="generate", id=1), body, "tok")
    decoded = wire.Framer("tok", allow_raw=True).feed(frame)[0]
    return unpack_prefilled(decoded.meta, decoded.body)


def _run_disagg(pre_b, dec_b, reqs):
    """Export every request on ``pre_b``, import on ``dec_b``; returns
    completions keyed by request index."""
    items = [Prefilled(r, _through_wire(pre_b.export_kv(r)))
             for r in reqs]
    by_req = {id(r): i for i, r in enumerate(reqs)}
    out = {}
    for c in dec_b.run(items):
        out[by_req[id(c.request)]] = c.tokens
    return [out[i] for i in range(len(reqs))]


def _mk(cfg, params, **kw):
    kw.setdefault("rows", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_bucket", 16)
    return ContinuousBatcher(cfg, params, **kw)


# -- exact equivalence vs the unified batcher --------------------------------


def test_disagg_matches_unified_greedy(setup):
    """The acceptance bar: prefill replica → exported KV (through the
    raw wire framing) → decode replica equals the unified batcher
    token-for-token, stop tokens and instant completions included."""
    cfg, params = setup
    reqs = _reqs(cfg, 8, seed=1, stop_every=3)
    reqs.append(Request(prompt=reqs[0].prompt.copy(), max_new_tokens=1))
    unified = _mk(cfg, params)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    got = _run_disagg(_mk(cfg, params, rows=2), _mk(cfg, params), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged from unified"


def test_disagg_chunked_prefill_matches_unified_chunked(setup):
    """A chunked-prefill EXPORTER (the long-prompt prefill tier's
    config) against the unified chunked batcher: the tail of every
    chunk lands in the artifact exactly as the unified path wrote it."""
    cfg, params = setup
    reqs = _reqs(cfg, 6, seed=2)
    unified = ContinuousBatcher(cfg, params, rows=3, max_len=64,
                                page_size=16, prefill_chunk=16)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    pre = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                            page_size=16, prefill_chunk=16)
    got = _run_disagg(pre, _mk(cfg, params), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (chunked)"


def test_disagg_int8_pool_matches_unified_int8(setup):
    """int8 paged pools export values AND scales bit-exactly: the
    disaggregated path equals the unified quantized-cache batcher."""
    cfg, params = setup
    reqs = _reqs(cfg, 6, seed=3)
    unified = _mk(cfg, params, quantized_cache=True)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    got = _run_disagg(_mk(cfg, params, rows=2, quantized_cache=True),
                      _mk(cfg, params, quantized_cache=True), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (int8 pool)"


def test_disagg_sampled_stream_exact_with_shared_rng(setup):
    """Sampled streams stay exact too when the batchers share an rng:
    the artifact carries the sampler's rid, so the importer's in-graph
    (rid, step) folds continue the exact stream the unified batcher
    would have drawn."""
    cfg, params = setup
    reqs = _reqs(cfg, 5, seed=4)
    kw = dict(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(7))
    unified = _mk(cfg, params, **kw)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    got = _run_disagg(_mk(cfg, params, rows=2, **kw),
                      _mk(cfg, params, **kw), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (sampled)"


# -- imported KV x prefix cache ---------------------------------------------


def test_import_seeds_prefix_cache_and_later_requests_hit(setup):
    """Imported full prompt pages publish into the importer's trie like
    a local prefill's: a later request sharing the prefix maps them
    read-only and completions still equal the unified batcher's."""
    cfg, params = setup
    rng = np.random.RandomState(5)
    system = rng.randint(0, cfg.vocab_size, size=32).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
                [system, rng.randint(0, cfg.vocab_size,
                                     size=3 + i).astype(np.int32)]),
                max_new_tokens=5) for i in range(3)]
    unified = _mk(cfg, params)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    pre = _mk(cfg, params, rows=2)
    dec = _mk(cfg, params, prefix_cache_pages=16)
    # Import request 0: its two full prompt pages must seed the trie.
    art = _through_wire(pre.export_kv(reqs[0]))
    out0 = list(dec.run([Prefilled(reqs[0], art)]))
    st = dec.prefix_cache_stats()
    assert st["inserted"] == 2 and st["cached_pages"] == 2
    assert out0[0].tokens == ref[0]
    # Later LOCAL requests with the shared system prefix hit the
    # imported pages.
    done = sorted((c.rid, c.tokens) for c in dec.run(reqs[1:]))
    st = dec.prefix_cache_stats()
    assert st["hits"] >= 1 and st["hit_pages"] >= 2
    assert [t for _, t in done] == [ref[1], ref[2]]


def test_import_twin_never_double_owns_pages(setup):
    """Importing the SAME prompt twice: the second import's pages stay
    its own (insert_row refuses chunks a twin already published) and
    everything releases cleanly — no page is owned twice."""
    cfg, params = setup
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, cfg.vocab_size, size=36).astype(np.int32)
    r1 = Request(prompt=prompt.copy(), max_new_tokens=4)
    r2 = Request(prompt=prompt.copy(), max_new_tokens=4)
    unified = _mk(cfg, params)
    ref = [c.tokens for c in unified.run(
        [Request(prompt=prompt.copy(), max_new_tokens=4)])][0]
    pre = _mk(cfg, params, rows=2)
    dec = _mk(cfg, params, prefix_cache_pages=16)
    arts = [_through_wire(pre.export_kv(r)) for r in (r1, r2)]
    done = list(dec.run([Prefilled(r1, arts[0]), Prefilled(r2, arts[1])]))
    assert [c.tokens for c in done] == [ref, ref]
    st = dec.prefix_cache_stats()
    assert st["cached_pages"] == 2      # one owner for the 2 full chunks
    # Every page is accounted for exactly once: free + cached + sink.
    assert (dec.t_side.alloc.free_count() + st["cached_pages"] + 1
            == dec.n_pages)


def test_import_bypasses_prefix_cache_explicitly_when_quantized(setup):
    """An int8-pool importer cannot share pages bitwise-safely: the
    bypass must be EXPLICIT (prefix_cache_bypass_reason) and imports
    still serve correctly."""
    cfg, params = setup
    reqs = _reqs(cfg, 2, seed=7)
    unified = _mk(cfg, params, quantized_cache=True)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    pre = _mk(cfg, params, rows=2, quantized_cache=True)
    dec = _mk(cfg, params, quantized_cache=True, prefix_cache_pages=16)
    assert dec.prefix_cache_bypass_reason == "quantized kv cache"
    assert dec.prefix_cache_stats() is None
    got = _run_disagg(pre, dec, reqs)
    assert got[0] == ref[0] and got[1] == ref[1]


# -- gates and validation ----------------------------------------------------


def _draft(max_len=64, n_draft=2, seed=1):
    draft_cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=max_len + n_draft + 8, dtype=jnp.float32)
    return dict(draft_cfg=draft_cfg,
                draft_params=transformer.init_params(
                    draft_cfg, jax.random.PRNGKey(seed)),
                n_draft=n_draft)


def test_export_mode_gates(setup):
    """export_kv cannot race a live serve loop (speculative batchers
    now COMPOSE with export/import — the paired draft payload — so the
    old spec gate is gone; the bypass-registry audit enforces it stays
    gone)."""
    cfg, params = setup
    req = _reqs(cfg, 1)[0]
    b = _mk(cfg, params)
    b.submit(Request(prompt=req.prompt, max_new_tokens=2))
    it = b.serve()
    next(it)                    # loop parked mid-stream, rows live
    with pytest.raises(RuntimeError, match="serve loop"):
        b.export_kv(req)
    b.close()
    list(it)
    assert not b._loop_active   # drained: exports are legal again
    b.export_kv(req)


# -- speculative decoding x disaggregation (the bypass burn-down) ------------


def test_disagg_spec_matches_unified_spec(setup):
    """Spec exporter → raw wire → spec importer equals the unified
    SPECULATIVE batcher token-for-token: the artifact's paired draft
    payload (dk/dv + the draft header) restores the draft cache
    bit-exactly, so every later round proposes and commits
    identically."""
    cfg, params = setup
    kw = _draft()
    reqs = _reqs(cfg, 6, seed=11, stop_every=3)
    unified = _mk(cfg, params, **kw)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    pre = _mk(cfg, params, rows=2, **kw)
    art0 = pre.export_kv(_reqs(cfg, 1, seed=11)[0])
    assert isinstance(art0.get("dk"), np.ndarray) \
        and art0["draft"]["n_draft"] == 2
    got = _run_disagg(pre, _mk(cfg, params, **kw), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (spec)"


def test_disagg_spec_int8_target_pool(setup):
    """Spec + int8 TARGET pool export/import: quantized target pages
    (values + scales) and the f32 draft payload both move bit-exactly."""
    cfg, params = setup
    kw = _draft(seed=2)
    reqs = _reqs(cfg, 4, seed=12)
    unified = _mk(cfg, params, quantized_cache=True, **kw)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    got = _run_disagg(
        _mk(cfg, params, rows=2, quantized_cache=True, **kw),
        _mk(cfg, params, quantized_cache=True, **kw), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (spec int8)"


def test_draftless_prefill_feeds_spec_decode_tier(setup):
    """A DRAFT-LESS prefill tier feeding draft-equipped decode
    replicas: a fresh (step-1) artifact without a draft payload imports
    by rebuilding the draft's prompt KV with exactly the chunk write a
    local spec admission dispatches — completions equal the unified
    speculative batcher's."""
    cfg, params = setup
    kw = _draft(seed=3)
    reqs = _reqs(cfg, 5, seed=13)
    unified = _mk(cfg, params, **kw)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}
    got = _run_disagg(_mk(cfg, params, rows=2),   # no draft on prefill
                      _mk(cfg, params, **kw), reqs)
    for i in range(len(reqs)):
        assert got[i] == ref[i], f"request {i} diverged (draftless pre)"


def test_spec_artifact_validation(setup):
    """Mismatches are loud: a spec artifact is rejected by a draft-less
    importer, a MID-STREAM artifact without draft state is rejected by
    a spec importer, and draft-geometry mismatches (n_draft) reject."""
    cfg, params = setup
    kw = _draft(seed=4)
    # Fixed 10-token prompt: the tampered pos below stays inside the
    # same page, so the draft check (not a shape check) is what fires.
    req = Request(prompt=(np.arange(1, 11, dtype=np.int32) % 97),
                  max_new_tokens=4)
    spec = _mk(cfg, params, **kw)
    art = spec.export_kv(req)
    plain = _mk(cfg, params)
    with pytest.raises(ValueError, match="draft"):
        plain.validate(Prefilled(req, art))
    other = _mk(cfg, params, **dict(_draft(seed=4), n_draft=3))
    with pytest.raises(ValueError, match="n_draft"):
        other.validate(Prefilled(req, art))
    # A mid-stream (suspended-shaped) artifact with the draft payload
    # stripped: a spec importer cannot rebuild mid-stream draft state.
    bad = {k: v for k, v in art.items()
           if k not in ("dk", "dv", "draft")}
    bad["step"], bad["tokens"] = 2, [art["first_token"], 3]
    bad["pos"] = art["pos"] + 1
    req2 = Request(prompt=req.prompt.copy(), max_new_tokens=9)
    with pytest.raises(ValueError, match="draft"):
        spec.validate(Prefilled(req2, bad))


def test_artifact_validation_rejects_mismatches(setup):
    """Every geometry/dtype mismatch is a loud ValueError at ingress —
    never a silently wrong decode."""
    cfg, params = setup
    req = _reqs(cfg, 1, seed=8)[0]
    pre = _mk(cfg, params, rows=2)
    art = pre.export_kv(req)
    # Wrong page size.
    with pytest.raises(ValueError, match="page_size"):
        _mk(cfg, params, page_size=32,
            prefill_bucket=32).validate(Prefilled(req, art))
    # Quantization mismatch, both directions.
    with pytest.raises(ValueError, match="quantized"):
        _mk(cfg, params, quantized_cache=True).validate(
            Prefilled(req, art))
    # Artifact for a different prompt.
    other = Request(prompt=np.concatenate([req.prompt, [1]]),
                    max_new_tokens=2)
    with pytest.raises(ValueError, match="positions"):
        _mk(cfg, params).validate(Prefilled(other, art))
    # Truncated body fails at unpack, not at decode.
    meta, body = pack_prefilled(art)
    with pytest.raises(ValueError, match="shorter"):
        unpack_prefilled(meta, body[:-8])
    with pytest.raises(ValueError, match="trailing"):
        unpack_prefilled(meta, body + b"\x00" * 8)
    # A bad item on the run loop drains in-flight work, then raises.
    dec = _mk(cfg, params)
    bad = Prefilled(req, dict(art, page_size=32))
    with pytest.raises(ValueError, match="page_size"):
        list(dec.run([bad]))


def test_prefill_side_prefix_cache_warms_exports(setup):
    """A prefill-tier batcher with a prefix cache: the second export of
    a shared-prefix prompt maps cached pages (hit counted) and its
    artifact still decodes to the same completion."""
    cfg, params = setup
    rng = np.random.RandomState(9)
    system = rng.randint(0, cfg.vocab_size, size=32).astype(np.int32)
    mk_req = lambda i: Request(prompt=np.concatenate(
        [system, rng.randint(0, cfg.vocab_size,
                             size=4 + i).astype(np.int32)]),
        max_new_tokens=4)
    r1, r2 = mk_req(0), mk_req(1)
    unified = _mk(cfg, params)
    ref = {c.rid: c.tokens for c in unified.run(
        [Request(prompt=r1.prompt, max_new_tokens=4),
         Request(prompt=r2.prompt, max_new_tokens=4)])}
    pre = _mk(cfg, params, rows=2, prefix_cache_pages=16)
    art1 = pre.export_kv(r1)
    st = pre.prefix_cache_stats()
    assert st["inserted"] >= 2          # the export published its pages
    art2 = pre.export_kv(r2)
    st = pre.prefix_cache_stats()
    assert st["hits"] >= 1 and st["hit_pages"] >= 2
    dec = _mk(cfg, params)
    done = list(dec.run([Prefilled(r1, art1), Prefilled(r2, art2)]))
    got = {(0 if c.request is r1 else 1): c.tokens for c in done}
    assert got[0] == ref[0] and got[1] == ref[1]


# -- in-process fleet round trip (real model, real wire) ---------------------


def test_fleet_disagg_round_trip_real_model(setup):
    """End to end IN PROCESS: registry + a prefill-role ReplicaServer
    (prefill_handler → export_kv) + a decode-role ReplicaServer
    (batcher_handler → KV import) + gateway; completions through the
    full wire path equal offline generation, and the role/transfer
    metrics record the handoff."""
    from tfmesos_tpu.fleet.admission import AdmissionController
    from tfmesos_tpu.fleet.client import FleetClient
    from tfmesos_tpu.fleet.gateway import Gateway
    from tfmesos_tpu.fleet.metrics import FleetMetrics
    from tfmesos_tpu.fleet.registry import ReplicaRegistry
    from tfmesos_tpu.fleet.replica import (BatcherServing, ReplicaServer,
                                           batcher_handler,
                                           prefill_handler)
    from tfmesos_tpu.fleet.router import Router

    cfg, params = setup
    reqs = _reqs(cfg, 6, seed=10, max_new=5)
    unified = _mk(cfg, params)
    ref = {c.rid: c.tokens for c in unified.run(reqs)}

    token = wire.new_token()
    reg = ReplicaRegistry(token=token, suspect_after=5.0, dead_after=10.0,
                          sweep_interval=0.05).start()
    pre_b = _mk(cfg, params, rows=2)
    dec_b = _mk(cfg, params, rows=4)
    serving = BatcherServing(dec_b).start()
    pre_srv = ReplicaServer(
        prefill_handler(pre_b), token=token, capacity=2,
        registry_addr=reg.addr, heartbeat_interval=0.05,
        extra_info=lambda: {"role": "prefill",
                            "kv_headroom": pre_b.kv_headroom()})
    dec_srv = ReplicaServer(
        batcher_handler(serving), token=token, capacity=4,
        registry_addr=reg.addr, heartbeat_interval=0.05,
        extra_info=lambda: {"role": "decode",
                            "kv_headroom": dec_b.kv_headroom()})
    pre_srv.start()
    dec_srv.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and sorted(
            r.role for r in reg.alive()) != ["decode", "prefill"]:
        time.sleep(0.02)
    metrics = FleetMetrics()
    router = Router(reg, metrics, token=token, request_timeout=300.0)
    gw = Gateway(router, AdmissionController(max_queue=32), metrics,
                 token=token, workers=4).start()
    try:
        client = FleetClient(gw.addr, token, timeout=300.0)
        results = [None] * len(reqs)
        errors = []

        def one(i):
            try:
                results[i] = client.generate(
                    reqs[i].prompt.tolist(), reqs[i].max_new_tokens,
                    stop_token=reqs[i].stop_token)
            except Exception as e:
                errors.append((i, e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        assert not errors, errors
        for i in range(len(reqs)):
            assert results[i]["tokens"] == ref[i], \
                f"request {i} diverged through the disagg fleet"
            assert results[i]["total_ms"] >= results[i]["ttft_ms"] >= 0
            assert "decode_ms" in results[i]
            # the decode replica's own queue, beside and not inside ttft_ms
            assert results[i]["queue_ms"] >= 0
        c = metrics.snapshot()["counters"]
        assert c["disagg_prefills"] >= len(reqs)
        assert c["disagg_decodes"] >= len(reqs)
        assert c["disagg_requests"] == len(reqs)
        assert c["kv_transfer_bytes"] > 0
        summary = reg.role_summary()
        assert summary["prefill"]["alive"] == 1
        assert summary["decode"]["alive"] == 1
        client.close()
    finally:
        gw.stop()
        pre_srv.stop()
        dec_srv.stop()
        dec_b.close()
        reg.stop()


def test_prefill_handler_bounded_queue_sheds_overload():
    """The prefill-role handler admits work into a bounded FIFO queue
    drained by ONE worker thread: a full queue answers ``overloaded``
    immediately (the router treats it as transient — retry elsewhere or
    fall back) instead of stacking a blocked thread per request."""
    from tfmesos_tpu.fleet.replica import prefill_handler

    started = threading.Event()
    gate = threading.Event()

    class FakeBatcher:
        def validate(self, req):
            return None

        def export_kv(self, req):
            started.set()
            gate.wait(10.0)
            return {"version": 1, "pos": 4, "first_token": 1, "rid": 0,
                    "k": np.zeros((2, 1, 4, 1, 2), np.float32),
                    "v": np.zeros((2, 1, 4, 1, 2), np.float32)}

    handler = prefill_handler(FakeBatcher(), max_queue=1)
    replies = []
    done = threading.Event()

    def reply(out):
        replies.append(out)
        if sum(isinstance(r, wire.RawFrame) for r in replies) >= 2:
            done.set()

    msg = {"op": "prefill", "id": 1, "prompt": [1, 2, 3],
           "max_new_tokens": 2}
    handler(msg, reply)                 # the worker picks this one up
    assert started.wait(5.0)            # ... and blocks inside export_kv
    handler(dict(msg, id=2), reply)     # fills the 1-deep queue
    handler(dict(msg, id=3), reply)     # queue full -> shed NOW
    sheds = [r for r in replies if isinstance(r, dict)
             and r.get("kind") == "overloaded"]
    assert len(sheds) == 1 and sheds[0]["id"] == 3
    gate.set()
    assert done.wait(10.0)              # both admitted prefills finish
    frames = [r for r in replies if isinstance(r, wire.RawFrame)]
    assert sorted(f.meta["id"] for f in frames) == [1, 2]   # FIFO, both
    assert all(f.meta["op"] == "prefilled" for f in frames)
