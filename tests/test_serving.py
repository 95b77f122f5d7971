"""Continuous batching (tfmesos_tpu/serving.py): staggered admission into
a persistent paged decode must be token-identical to offline per-request
generation, keep pool occupancy bounded, and release/reuse rows and pages
across the stream.  CPU float32 tiny config: the paged reference path and
``generate``'s contiguous path run the same per-row math, so greedy
streams compare exactly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tfmesos_tpu.models import transformer
from tfmesos_tpu.serving import Completion, ContinuousBatcher, Request


@pytest.fixture(scope="module")
def setup():
    cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        size=rng.randint(3, 20)).astype(np.int32)
            for _ in range(n)]


def _behind(prefix, prompt):
    """``prompt`` behind a shared system prompt (``prefix`` None: none).
    The batcher declares nothing: with ``prefix_cache_pages`` the
    cross-request prefix cache finds the shared pages at admission."""
    return prompt if prefix is None else np.concatenate([prefix, prompt])


def _offline(cfg, params, req: Request):
    """Reference continuation: a per-request generate() call (contiguous
    cache, greedy)."""
    out = transformer.generate(
        cfg, params, jnp.asarray(req.prompt[None]), req.max_new_tokens,
        temperature=0.0, stop_token=req.stop_token)
    row = np.asarray(out)[0, req.prompt.size:].tolist()
    if req.stop_token is not None and req.stop_token in row:
        row = row[:row.index(req.stop_token) + 1]
    return row


def test_continuous_matches_offline(setup):
    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=1 + (i % 7))
            for i, p in enumerate(_prompts(cfg, 9))]
    batcher = ContinuousBatcher(cfg, params, rows=3, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = {c.rid: c for c in batcher.run(reqs)}
    assert len(done) == len(reqs)
    for rid, req in enumerate(reqs):
        assert done[rid].request is req
        assert done[rid].tokens == _offline(cfg, params, req), \
            f"request {rid} diverged from offline generation"


def test_staggered_stream_matches_offline(setup):
    """Arrivals from a generator admit into rows mid-flight; outputs must
    not depend on what else was being decoded."""
    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=4 + (i % 5))
            for i, p in enumerate(_prompts(cfg, 8, seed=3))]

    fed = []

    def stream():
        for r in reqs:
            fed.append(r)
            yield r

    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = {}
    for c in batcher.run(stream()):
        done[c.rid] = c
        # Lazy pull: the source never runs ahead of admission capacity.
        assert len(fed) <= len(done) + batcher.rows + 1
    assert len(done) == len(reqs)
    for rid, req in enumerate(reqs):
        assert done[rid].tokens == _offline(cfg, params, req)


def test_stop_token_frees_rows_early(setup):
    cfg, params = setup
    # An untrained model emits SOME argmax token quickly; find one that a
    # specific prompt emits so the stop path actually triggers.
    probe = Request(prompt=_prompts(cfg, 1, seed=5)[0], max_new_tokens=8)
    tokens = _offline(cfg, params, probe)
    stop = tokens[min(2, len(tokens) - 1)]
    reqs = [Request(prompt=probe.prompt, max_new_tokens=8, stop_token=stop),
            Request(prompt=_prompts(cfg, 1, seed=6)[0], max_new_tokens=6)]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = {c.rid: c for c in batcher.run(reqs)}
    assert done[0].tokens == _offline(cfg, params, reqs[0])
    assert done[0].tokens[-1] == stop
    assert len(done[0].tokens) <= 3            # stopped early
    assert done[1].tokens == _offline(cfg, params, reqs[1])


def test_pool_occupancy_bounded_and_recycled(setup):
    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=6)
            for p in _prompts(cfg, 12, seed=7)]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    # Default pool backs rows x max_len of LIVE data — the sink page is
    # extra, so worst-case requests on every row still run concurrently.
    assert batcher.n_pages == 2 * batcher.np_max + 1
    n_done = sum(1 for _ in batcher.run(reqs))
    assert n_done == len(reqs)
    # All pages returned to the pool (only the sink page stays reserved).
    assert len(batcher.alloc.free) == batcher.n_pages - 1
    assert batcher.alloc.rows == {}
    # Occupancy never exceeded 2 concurrent rows' worst case + sink.
    per_row_worst = -(-64 // 16)
    assert batcher.peak_pages_used <= 2 * per_row_worst + 1


def test_sampled_streams_invariant_to_batching(setup):
    """Per-(rid, step) folded keys make SAMPLED outputs independent of
    row packing: rows=1 (fully serial) and rows=4 must agree."""
    cfg, params = setup
    reqs = lambda: [Request(prompt=p, max_new_tokens=5)
                    for p in _prompts(cfg, 6, seed=9)]
    outs = []
    for rows in (1, 4):
        b = ContinuousBatcher(cfg, params, rows=rows, max_len=64,
                              page_size=16, prefill_bucket=16,
                              temperature=0.8, top_k=20,
                              rng=jax.random.PRNGKey(42))
        outs.append({c.rid: c.tokens for c in b.run(reqs())})
    assert outs[0] == outs[1]


def _assert_tokens_match_modulo_ties(cfg, params, prompt, got, want,
                                     atol=1e-4):
    """Greedy sequences from the chunked vs unchunked prefill paths are
    expected identical, EXCEPT where the two reduction orders land on a
    float tie: at the first divergence, teacher-force the agreed prefix
    and require the two candidate tokens' logits to be within ``atol``
    (a genuine tie — after which the sequences legitimately fork)."""
    if got == want:
        return
    import jax.numpy as jnp
    from tfmesos_tpu.models import transformer

    n = min(len(got), len(want))
    div = next(i for i in range(n) if got[i] != want[i])
    assert got[:div] == want[:div]
    ctx = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(want[:div], np.int32)])
    logits = np.asarray(
        transformer.forward(cfg, params, jnp.asarray(ctx[None]))[0, -1],
        np.float32)
    gap = abs(float(logits[got[div]]) - float(logits[want[div]]))
    assert gap < atol, (
        f"chunked prefill diverged at token {div} without a float tie "
        f"(logit gap {gap:.2e}): {got} vs {want}")


@pytest.mark.parametrize("with_prefix", [False, True])
def test_chunked_prefill_matches_unchunked(setup, with_prefix):
    """prefill_chunk mode (bounded admission stalls: one chunk per tick,
    interleaved with decode) must reproduce the unchunked batcher's
    outputs — prompts spanning one, several, and exactly-full chunks."""
    cfg, params = setup
    rng = np.random.RandomState(29)
    prefix = (rng.randint(0, cfg.vocab_size, size=11).astype(np.int32)
              if with_prefix else None)
    prompts = [_behind(prefix, rng.randint(
        0, cfg.vocab_size, size=n).astype(np.int32))
        for n in (3, 8, 13, 19, 16, 5)]
    mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 4))
                  for i, p in enumerate(prompts)]
    kw = dict(rows=3, max_len=96, page_size=16,
              prefix_cache_pages=8 if with_prefix else 0)
    chunked = ContinuousBatcher(cfg, params, prefill_chunk=8, **kw)
    plain = ContinuousBatcher(cfg, params, prefill_bucket=8, **kw)
    got = {c.rid: c.tokens for c in chunked.run(mk())}
    want = {c.rid: c.tokens for c in plain.run(mk())}
    for rid in want:
        _assert_tokens_match_modulo_ties(
            cfg, params, prompts[rid], got[rid], want[rid])
    assert chunked.alloc.rows == {}     # everything recycled


def test_chunked_prefill_timing_and_stop(setup):
    cfg, params = setup
    probe = Request(prompt=_prompts(cfg, 1, seed=31)[0], max_new_tokens=6)
    first = _offline(cfg, params, probe)[0]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_chunk=8)
    # stop == first token: the request completes straight out of prefill.
    done = list(batcher.run([Request(prompt=probe.prompt, max_new_tokens=6,
                                     stop_token=first)]))
    assert len(done) == 1 and done[0].tokens == [first]
    assert 0.0 < done[0].ttft_s <= done[0].total_s


@pytest.fixture(scope="module")
def draft_setup():
    cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=128, dtype=jnp.float32)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(5))


@pytest.mark.parametrize("perfect_draft", [False, True])
def test_speculative_batcher_matches_plain(setup, draft_setup,
                                           perfect_draft):
    """Speculative continuous batching (greedy): outputs equal the
    target-only batcher's for ANY draft — an unrelated weak draft and a
    perfect one (draft == target, every proposal accepted)."""
    cfg, params = setup
    dcfg, dparams = (cfg, params) if perfect_draft else draft_setup
    reqs = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 6))
                    for i, p in enumerate(_prompts(cfg, 7, seed=37))]
    kw = dict(rows=3, max_len=64, page_size=16, prefill_bucket=16)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {c.rid: c.tokens for c in plain.run(reqs())}
    spec = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                             draft_params=dparams, n_draft=3, **kw)
    rounds = {"n": 0}
    inner = spec._spec_round

    def counting(*a):
        rounds["n"] += 1
        return inner(*a)

    spec._spec_round = counting
    got = {c.rid: c.tokens for c in spec.run(reqs())}
    for rid in want:
        _assert_tokens_match_modulo_ties(
            cfg, params, reqs()[rid].prompt, got[rid], want[rid])
    assert spec.alloc.rows == {}
    rate = spec.acceptance_rate
    assert rate is not None and 0.0 <= rate <= 1.0
    if perfect_draft:
        assert rate == 1.0
    if perfect_draft:
        # Every proposal accepted: each round commits k+1 tokens per row,
        # so the whole stream needs far fewer rounds than tokens.
        total_tokens = sum(len(t) for t in want.values())
        assert rounds["n"] < total_tokens / 2


def test_speculative_perfect_draft_minimal_rounds(setup):
    """Regression for the draft-cache backfill: with draft == target,
    EVERY round must commit k+1 tokens — the pre-fix hole at pos+k made
    round 2+ propose from a corrupted context, silently inflating the
    round count.  rows=1, one request: the count is exact."""
    cfg, params = setup
    k, max_new = 3, 13
    b = ContinuousBatcher(cfg, params, rows=1, max_len=64, page_size=16,
                          prefill_bucket=16, draft_cfg=cfg,
                          draft_params=params, n_draft=k)
    rounds = {"n": 0}
    inner = b._spec_round

    def counting(*a):
        rounds["n"] += 1
        return inner(*a)

    b._spec_round = counting
    req = Request(prompt=_prompts(cfg, 1, seed=61)[0],
                  max_new_tokens=max_new)
    done = list(b.run([req]))
    assert done[0].tokens == _offline(cfg, params, req)
    # 1 token from prefill + ceil((max_new-1)/(k+1)) perfect rounds.
    assert rounds["n"] == -(-(max_new - 1) // (k + 1))
    # A perfect draft accepts EVERY proposal: rate exactly 1.0 (the
    # final round's quota truncation happens host-side, after commit).
    assert b.acceptance_rate == 1.0


def test_speculative_batcher_stop_token(setup, draft_setup):
    cfg, params = setup
    dcfg, dparams = draft_setup
    probe = Request(prompt=_prompts(cfg, 1, seed=41)[0], max_new_tokens=10)
    ref = _offline(cfg, params, probe)
    stop = ref[min(3, len(ref) - 1)]
    req = Request(prompt=probe.prompt, max_new_tokens=10, stop_token=stop)
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, draft_cfg=dcfg,
                          draft_params=dparams, n_draft=4)
    done = list(b.run([req]))
    assert done[0].tokens == _offline(cfg, params, req)
    assert done[0].tokens[-1] == stop


@pytest.mark.parametrize("prefix_len", [16, 13, 21])
def test_speculative_batcher_with_shared_prefix(setup, draft_setup,
                                                prefix_len):
    """shared system prompt x speculative: the prefix cache maps the
    prompt's pages into both pools (target and draft twins) — outputs
    still equal the (prefix-sharing) target-only batcher's.  Covers
    aligned, sub-page, and full+tail prefix page layouts (page_size
    16)."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    prefix = np.random.RandomState(43).randint(
        0, cfg.vocab_size, size=prefix_len).astype(np.int32)
    reqs = lambda: [Request(prompt=_behind(prefix, p),
                            max_new_tokens=3 + (i % 4))
                    for i, p in enumerate(_prompts(cfg, 5, seed=44))]
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16,
              prefix_cache_pages=8)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {c.rid: c.tokens for c in plain.run(reqs())}
    spec = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                             draft_params=dparams, n_draft=3, **kw)
    got = {c.rid: c.tokens for c in spec.run(reqs())}
    for rid in want:
        _assert_tokens_match_modulo_ties(
            cfg, params, reqs()[rid].prompt, got[rid], want[rid])


def test_speculative_batcher_sampled_invariance_and_prefix_equality(
        setup, draft_setup):
    """Sampled speculative rounds: every draw derives from (rid,
    token-index) key folds, so (a) outputs are invariant to row packing,
    and (b) with a PERFECT draft (pd == pt) the first 1 + n_draft tokens
    reproduce the plain sampled batcher's exactly (same proposal keys;
    the bonus token is the first salted-stream divergence)."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    k = 3
    mk = lambda: [Request(prompt=p, max_new_tokens=6)
                  for p in _prompts(cfg, 5, seed=51)]
    kw = dict(max_len=64, page_size=16, prefill_bucket=16,
              temperature=0.8, top_k=20, rng=jax.random.PRNGKey(9))
    outs = []
    for rows in (1, 4):
        b = ContinuousBatcher(cfg, params, rows=rows, draft_cfg=dcfg,
                              draft_params=dparams, n_draft=k, **kw)
        outs.append({c.rid: c.tokens for c in b.run(mk())})
    assert outs[0] == outs[1]

    plain = ContinuousBatcher(cfg, params, rows=2, **kw)
    want = {c.rid: c.tokens for c in plain.run(mk())}
    perfect = ContinuousBatcher(cfg, params, rows=2, draft_cfg=cfg,
                                draft_params=params, n_draft=k, **kw)
    got = {c.rid: c.tokens for c in perfect.run(mk())}
    for rid in want:
        assert got[rid][:1 + k] == want[rid][:1 + k], rid


def test_sampled_speculative_chunked_invariance(setup, draft_setup):
    """Sampled x speculative x chunked: the key schedule stays a pure
    function of (rid, token index), so row packing cannot change
    outputs even with chunked prefill interleaving."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    mk = lambda: [Request(prompt=p, max_new_tokens=5)
                  for p in _prompts(cfg, 5, seed=57)]
    outs = []
    for rows in (1, 3):
        b = ContinuousBatcher(cfg, params, rows=rows, max_len=64,
                              page_size=16, prefill_chunk=8,
                              temperature=0.8, top_k=20,
                              rng=jax.random.PRNGKey(13),
                              draft_cfg=dcfg, draft_params=dparams,
                              n_draft=3)
        outs.append({c.rid: c.tokens for c in b.run(mk())})
    assert outs[0] == outs[1]


@pytest.mark.parametrize("with_prefix", [False, True])
def test_speculative_with_chunked_prefill(setup, draft_setup,
                                          with_prefix):
    """The full composition: speculative rounds x chunked prefill (x
    prefix).  Greedy outputs must match the plain (unchunked,
    non-speculative) batcher's modulo float ties; still-filling rows
    sink-mask during spec rounds and the draft's chunks advance in
    lockstep."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    rng = np.random.RandomState(53)
    prefix = (rng.randint(0, cfg.vocab_size, size=11).astype(np.int32)
              if with_prefix else None)
    prompts = [_behind(prefix, rng.randint(
        0, cfg.vocab_size, size=n).astype(np.int32))
        for n in (3, 13, 19, 8, 16)]
    mk = lambda: [Request(prompt=p, max_new_tokens=3 + (i % 4))
                  for i, p in enumerate(prompts)]
    kw = dict(rows=3, max_len=96, page_size=16,
              prefix_cache_pages=8 if with_prefix else 0)
    plain = ContinuousBatcher(cfg, params, prefill_bucket=8, **kw)
    want = {c.rid: c.tokens for c in plain.run(mk())}
    combo = ContinuousBatcher(cfg, params, prefill_chunk=8,
                              draft_cfg=dcfg, draft_params=dparams,
                              n_draft=3, **kw)
    got = {c.rid: c.tokens for c in combo.run(mk())}
    for rid in want:
        _assert_tokens_match_modulo_ties(
            cfg, params, prompts[rid], got[rid], want[rid])
    assert combo.alloc.rows == {}


def test_speculative_draft_pool_tracks_live_tokens(setup, draft_setup):
    """The draft's K/V is paged like the target's: occupancy is bounded
    by in-flight rows' worst case, everything recycles at stream end,
    and what the prefix cache keeps of a shared system prompt it keeps
    once in each pool (a node's target page and its draft twin) instead
    of a per-row copy."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    prefix = np.random.RandomState(71).randint(
        0, cfg.vocab_size, size=13).astype(np.int32)
    reqs = [Request(prompt=_behind(prefix, p), max_new_tokens=4)
            for p in _prompts(cfg, 6, seed=72)]
    budget = 4
    b = ContinuousBatcher(cfg, params, rows=2, max_len=96, page_size=16,
                          prefill_bucket=16, prefix_cache_pages=budget,
                          draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    done = list(b.run(reqs))
    assert len(done) == len(reqs)
    cached = b.prefix_cache_stats()["cached_pages"]
    assert 0 < cached <= budget
    for side in (b.t_side, b.d_side):
        # All own pages recycled; the sink and the cached pages persist.
        assert side.alloc.rows == {}
        assert side.alloc.free_count() == side.n_pages - 1 - cached
        # High-water mark stayed within 2 concurrent worst cases.
        per_row_worst = -(-96 // 16)
        assert side.peak <= 2 * per_row_worst + 1 + budget


def test_speculative_batcher_validation(setup, draft_setup):
    cfg, params = setup
    dcfg, dparams = draft_setup
    base = dict(rows=1, max_len=64, page_size=16, draft_cfg=dcfg,
                draft_params=dparams)
    with pytest.raises(ValueError, match="come together"):
        ContinuousBatcher(cfg, params, rows=1, draft_cfg=dcfg)
    with pytest.raises(ValueError, match="cover max_len"):
        ContinuousBatcher(cfg, params, rows=1, max_len=128,
                          page_size=16, draft_cfg=dcfg,
                          draft_params=dparams, n_draft=4)


@pytest.fixture(scope="module")
def mesh_setup():
    """tp-divisible dims (vocab/heads/ff shard over tp=2)."""
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dcfg = transformer.TransformerConfig(
        vocab_size=128, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=128, dtype=jnp.float32)
    dparams = transformer.init_params(dcfg, jax.random.PRNGKey(5))
    return cfg, params, dcfg, dparams


def _mesh(axes):
    from tfmesos_tpu.parallel.mesh import build_mesh
    n = 1
    for v in axes.values():
        n *= v
    return build_mesh(axes, devices=jax.devices()[:n])


@pytest.mark.parametrize("axes,variant", [
    ({"dp": 2}, "base"),
    ({"dp": 2, "tp": 2}, "base"),
    ({"dp": 2, "tp": 2}, "spec_chunk_prefix"),
    ({"dp": 2, "tp": 2}, "sampled"),
    ({"dp": 2, "tp": 2}, "int8"),
])
def test_mesh_batcher_token_identical(mesh_setup, axes, variant):
    """Multi-chip serving (VERDICT r4 next #1): ContinuousBatcher(mesh=
    dp x tp) — pool pages sharded over dp with shard-local tables, heads
    over tp — must produce the SAME tokens as the single-device batcher,
    across the whole feature matrix (prefix sharing, chunked prefill,
    speculative, int8 pools, sampling)."""
    cfg, params, dcfg, dparams = mesh_setup
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 128, size=n).astype(np.int32)
               for n in (3, 8, 13, 19, 16, 5)]
    kw = dict(rows=4, max_len=96, page_size=16, prefill_bucket=16)
    if variant == "spec_chunk_prefix":
        prefix = rng.randint(0, 128, size=13).astype(np.int32)
        prompts = [_behind(prefix, p) for p in prompts]
        kw.update(prefix_cache_pages=8,
                  prefill_chunk=8, draft_cfg=dcfg, draft_params=dparams,
                  n_draft=3)
    elif variant == "sampled":
        kw.update(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(3))
    elif variant == "int8":
        kw.update(quantized_cache=True)
    mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 4))
                  for i, p in enumerate(prompts)]
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {c.rid: c.tokens for c in plain.run(mk())}
    b = ContinuousBatcher(cfg, params, mesh=_mesh(axes), **kw)
    got = {c.rid: c.tokens for c in b.run(mk())}
    for rid in want:
        _assert_tokens_match_modulo_ties(
            cfg, params, prompts[rid], got[rid], want[rid])
    # Per-shard invariants: every sub-pool recycled to its sink and what
    # the prefix cache keeps resident.
    for side in filter(None, (b.t_side, b.d_side)):
        assert side.alloc.rows == {}
        for s in range(b.n_shards):
            kept = b._pcache.reclaimable(s) if b._pcache else 0
            assert side.alloc.free_count(s) + kept == \
                side.n_pages // b.n_shards - 1


# -- pipelined device-resident decode (pipeline_depth=1) --------------------


@pytest.mark.parametrize("variant", [
    "base", "staggered", "stop", "sampled", "chunked", "multistep",
    "multistep_stop", "int8", "prefix",
])
def test_pipelined_batcher_token_identical(setup, variant):
    """pipeline_depth=1 (block N+1 dispatched from the DEVICE-resident
    carry — tokens, positions, and steps never round-trip to the host
    between blocks — with block N's tokens synced one block behind)
    must produce IDENTICAL token streams to the synchronous
    pipeline_depth=0 loop across the matrix: stops and quotas are
    detected one block late but the overshoot block's writes land
    inside the clamped reservation or on sink columns and its tokens
    fail the rid-checked ticket; sampled (rid, step) key folds are
    unchanged; chunked prefill flips and mid-stream re-admissions
    re-enter through the host-merge mask; the int8 pool pair compares
    int8-to-int8; a shared system prompt's warm admissions enter the
    carry from the prefix cache's tail prefill."""
    cfg, params = setup
    rng = np.random.RandomState(71)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 9, 14, 18, 6)]
    mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 5))
                  for i, p in enumerate(prompts)]
    kw = dict(rows=3, max_len=96, page_size=16, prefill_bucket=16)
    if variant == "sampled":
        kw.update(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(5))
    elif variant == "chunked":
        kw.update(prefill_chunk=8)
    elif variant in ("multistep", "multistep_stop"):
        kw.update(multi_step=4)
    elif variant == "int8":
        kw.update(quantized_cache=True)
    elif variant == "prefix":
        prefix = rng.randint(0, cfg.vocab_size, size=13).astype(np.int32)
        prompts = [_behind(prefix, p) for p in prompts]
        kw.update(prefix_cache_pages=8)
    if variant in ("stop", "multistep_stop"):
        # Find a token each prompt actually emits so stops trigger (and
        # land mid-block in the multistep case).
        probe = ContinuousBatcher(cfg, params, **kw)
        outs = {c.rid: c.tokens for c in probe.run(mk())}
        stops = {rid: t[min(1, len(t) - 1)] for rid, t in outs.items()}
        mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 5),
                              stop_token=stops[i])
                      for i, p in enumerate(prompts)]
    if variant == "staggered":
        # Fewer rows than requests: completions free rows mid-stream and
        # later requests re-enter the device carry as fresh admissions.
        kw["rows"] = 2

        def feed(reqs, done):
            for r in reqs:
                assert len(done) <= len(reqs)   # pull stays lazy
                yield r
    else:
        feed = lambda reqs, done: iter(reqs)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {}
    for c in plain.run(feed(mk(), want)):
        want[c.rid] = c.tokens
    pb = ContinuousBatcher(cfg, params, pipeline_depth=1, **kw)
    assert pb._pipelined and pb.pipeline_bypass_reason is None
    got = {}
    for c in pb.run(feed(mk(), got)):
        got[c.rid] = c.tokens
    assert got == want
    assert pb._inflight is None and pb._pipe_carry is None  # drained
    assert pb.alloc.rows == {}                              # no leaks


@pytest.mark.parametrize("variant", ["mesh", "pcache"])
def test_pipelined_batcher_token_identical_heavy(setup, mesh_setup,
                                                 variant):
    """The expensive corners of the pipelined equivalence matrix: the
    dp x tp mesh path (sharded pools, multi-device dispatch) and the
    cross-request prefix cache (warm admissions map cached pages and
    enter decode from a host merge)."""
    if variant == "mesh":
        cfg, params, _, _ = mesh_setup
    else:
        cfg, params = setup
    rng = np.random.RandomState(73)
    sys_p = rng.randint(0, cfg.vocab_size, size=32).astype(np.int32)
    prompts = [np.concatenate([sys_p, rng.randint(
        0, cfg.vocab_size, size=4 + i).astype(np.int32)])
        for i in range(4)]
    mk = lambda: [Request(prompt=p, max_new_tokens=3 + (i % 3))
                  for i, p in enumerate(prompts)]
    kw = dict(rows=4, max_len=96, page_size=16, prefill_bucket=16)
    if variant == "mesh":
        kw.update(mesh=_mesh({"dp": 2, "tp": 2}))
    else:
        kw.update(prefix_cache_pages=16)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = [{c.rid: c.tokens for c in plain.run(mk())} for _ in range(2)]
    pb = ContinuousBatcher(cfg, params, pipeline_depth=1, **kw)
    got = [{c.rid: c.tokens for c in pb.run(mk())} for _ in range(2)]
    assert got == want      # pass 2 serves pcache hits where enabled
    if variant == "pcache":
        assert pb.prefix_cache_stats()["hits"] > 0


def test_pipelined_spec_bypass_reason_and_validation(setup, draft_setup):
    """Speculative decoding BYPASSES pipelining explicitly — the
    recorded reason makes the bypass observable (like
    prefix_cache_bypass_reason) and the spec loop runs unchanged; a
    plain pipelined batcher is lagged, hence not suspendable; and
    depths outside {0, 1} stay rejected."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, draft_cfg=dcfg,
                          draft_params=dparams, n_draft=3,
                          pipeline_depth=1)
    assert b.pipeline_bypass_reason == "speculative decoding"
    assert not b._pipelined
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in _prompts(cfg, 3, seed=77)]
    plain = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16, prefill_bucket=16,
                              draft_cfg=dcfg, draft_params=dparams,
                              n_draft=3)
    want = {c.rid: c.tokens for c in plain.run(list(reqs))}
    got = {c.rid: c.tokens for c in b.run(list(reqs))}
    assert got == want
    pb = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                           prefill_bucket=16, pipeline_depth=1)
    assert pb._pipelined and pb.pipeline_bypass_reason is None
    # The pipelined carry still lags the host view: not suspendable.
    assert pb.suspend_bypass_reason == "lagged decode carry"
    assert not pb.preemptible
    # Greedy speculative decode is lossless, so the spec `want` doubles
    # as the plain-greedy ground truth the pipelined run must match.
    got = {c.rid: c.tokens for c in pb.run(
        [Request(prompt=p, max_new_tokens=4)
         for p in _prompts(cfg, 3, seed=77)])}
    assert got == want
    with pytest.raises(ValueError, match="pipeline_depth"):
        ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          pipeline_depth=2)


def _lag_stack(setup, stack):
    """(cfg, params, batcher keywords) of a plain stack asked for the
    carry, or of one of the two kinds that take it of themselves."""
    if stack == "plain":
        return (*setup, dict(pipeline_depth=1, page_size=16,
                             prefill_bucket=16))
    if stack == "eva":
        cfg = transformer.TransformerConfig(
            vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_seq_len=128, dtype=jnp.float32, attention="eva",
            eva_chunk=4, eva_window=32)
        kw = dict(pipeline_depth=None, page_size=8, prefill_bucket=8)
    else:
        cfg = transformer.TransformerConfig(
            vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=32, max_seq_len=128, dtype=jnp.float32,
            layer_types=("mamba", "attention"), mamba_heads=4,
            mamba_head_dim=16, mamba_state=16, mamba_chunk=16, rope=False)
        kw = dict(pipeline_depth=None, page_size=16, prefill_bucket=16)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(3)), kw


@pytest.mark.parametrize("stack", ["plain", "eva", "typed"])
def test_rows_outside_the_dispatch_do_not_ride_the_carry(setup, stack):
    """A row outside a pipelined block's dispatch (free, or parked: its
    quota dispatched, its last block not yet retired) enters the program
    from host zeros (``use_host`` set; token, position and step 0), as the
    synchronous loop's idle rows do.  Left on the device carry its position
    grew by K every block, up to ``max_len``, and the paged kernel walked a
    context of that length over sink pages for it in every layer of every
    block.  The program's arguments are spied on; the streams are the
    synchronous loop's."""
    cfg, params, kw = _lag_stack(setup, stack)
    kw = dict(kw, rows=4, max_len=96)
    rng = np.random.RandomState(41)
    # six requests through four rows, quotas that end in different blocks:
    # rows park one at a time, two are taken again at once, and at the tail
    # rows stay free while the others decode on
    plan = ((9, 4), (40, 14), (21, 9), (5, 6), (33, 12), (12, 20))
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n, _ in plan]
    mk = lambda: [Request(prompt=p, max_new_tokens=m)
                  for p, (_, m) in zip(prompts, plan)]
    b = ContinuousBatcher(cfg, params, **kw)
    assert b._pipelined and b.pipeline_bypass_reason is None
    seen, now = [], {}
    step, decode = b._step_pipelined, b._decode

    def spy_step(active, free_rows):
        # the host's view as the block is built: who is parked, who is free
        now["parked"] = {r for r, row in active.items()
                         if row.step >= row.req.max_new_tokens}
        now["free"] = set(range(b.rows)) - set(active)
        return step(active, free_rows)

    def spy_decode(params_, pool, table, use_host, toks, positions, steps,
                   carry_tok, carry_pos, carry_steps, rids):
        seen.append(dict(now, table=np.asarray(table),
                         use_host=np.asarray(use_host),
                         toks=np.asarray(toks), pos=np.asarray(positions),
                         steps=np.asarray(steps),
                         carry_pos=np.asarray(carry_pos)))
        return decode(params_, pool, table, use_host, toks, positions,
                      steps, carry_tok, carry_pos, carry_steps, rids)

    b._step_pipelined, b._decode = spy_step, spy_decode
    got = {c.rid: list(c.tokens) for c in b.run(mk())}
    want = {c.rid: list(c.tokens) for c in ContinuousBatcher(
        cfg, params, **dict(kw, pipeline_depth=0)).run(mk())}
    assert got == want and len(got) == len(plan)
    outside = 0
    for rec in seen:
        out = rec["parked"] | rec["free"]
        for r in range(b.rows):
            sink = bool((rec["table"][r] == b.t_side.sink).all())
            assert sink == (r in out), (r, rec["parked"], rec["free"])
            if r in out:
                outside += 1
                assert rec["use_host"][r]
                assert rec["toks"][r] == rec["pos"][r] == rec["steps"][r] == 0
    assert any(rec["parked"] for rec in seen)
    assert any(rec["free"] for rec in seen) and outside >= 12
    # the carry is used: rows that stay in the dispatch ride it
    assert any((~rec["use_host"]).any() for rec in seen)
    # and a position on it is a dispatched row's: bounded by its context
    assert max(int(rec["carry_pos"].max()) for rec in seen) <= 40 + 14


@pytest.mark.parametrize("stack", ["plain", "eva", "typed"])
def test_the_carry_reserves_what_the_synchronous_loop_reserves(setup, stack):
    """A stop token costs the carry no position: the block dispatched
    after a stop the host has not read yet is one the quota had let
    through, and writes where the same request without a stop writes.  So
    a request that fills ``max_len`` exactly is admitted by both loops
    under the same reservation (the carry used to ask for one position
    more and refuse it), and stops where it stops."""
    cfg, params, kw = _lag_stack(setup, stack)
    kw = dict(kw, rows=2, max_len=64)
    prompt = np.random.RandomState(7).randint(
        0, cfg.vocab_size, size=40).astype(np.int32)
    sync = ContinuousBatcher(cfg, params, **dict(kw, pipeline_depth=0))
    (probe,) = sync.run([Request(prompt=prompt, max_new_tokens=25)])
    at = next(i for i in range(3, 25)
              if probe.tokens[i] not in probe.tokens[:i])
    mk = lambda: Request(prompt=prompt, max_new_tokens=25,
                         stop_token=int(probe.tokens[at]))
    lag = ContinuousBatcher(cfg, params, **kw)
    assert lag._pipelined
    assert lag._worst_pages(mk()) == sync._worst_pages(mk())
    assert lag._worst_pages(mk())[2] == 64 == lag.max_len
    (want,), (got,) = sync.run([mk()]), lag.run([mk()])
    assert list(got.tokens) == list(want.tokens) == \
        list(probe.tokens[:at + 1])
    assert lag.alloc.rows == {} and lag._inflight is None


def test_one_lag_policy_overlap_is_gone(setup):
    """``pipeline_depth`` is the batcher's ONE lag policy: the former
    ``overlap=`` argument gets Python's own TypeError (no shim, no
    alias), the tick's modes are sync / pipelined / spec (and fused),
    and the serve loop has four tick loops."""
    import inspect

    cfg, params = setup
    with pytest.raises(TypeError, match="overlap"):
        ContinuousBatcher(cfg, params, overlap=True)
    sig = inspect.signature(ContinuousBatcher.__init__)
    assert "overlap" not in sig.parameters
    assert sorted(n for n in vars(ContinuousBatcher)
                  if n.startswith("_step")) == [
        "_step", "_step_fused", "_step_pipelined", "_step_spec"]
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16)
    assert b._mode == "sync" and not hasattr(b, "overlap")
    assert not hasattr(b, "overlap_bypass_reason")


# -- ahead-of-time warmup ---------------------------------------------------


@pytest.mark.parametrize("mode", ["plain", "pipelined", "chunked",
                                  "pcache"])
def test_warmup_outputs_bit_identical(setup, mode):
    """warmup() compiles every entry point the mode dispatches against
    all-sink dummy shapes — no live row, shared-prefix page, or cache
    state is touched, so a warmed batcher's outputs EQUAL a cold
    one's.  ``pcache`` is the tfserve DEFAULT config (--prefix-cache
    64 + --warmup compose), so it must warm and then hit normally."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    if mode == "pipelined":
        kw.update(pipeline_depth=1)
    elif mode == "chunked":
        kw.update(prefill_chunk=16)
    elif mode == "pcache":
        kw.update(prefix_cache_pages=16)
    if mode == "pcache":
        # Page-aligned shared prefix so the second pass actually hits.
        rng = np.random.RandomState(83)
        sys_p = rng.randint(0, cfg.vocab_size, size=16).astype(np.int32)
        pps = [np.concatenate([sys_p, rng.randint(
            0, cfg.vocab_size, size=3 + i).astype(np.int32)])
            for i in range(4)]
        reqs = lambda: [Request(prompt=p, max_new_tokens=4) for p in pps]
    else:
        reqs = lambda: [Request(prompt=p, max_new_tokens=4)
                        for p in _prompts(cfg, 4, seed=83)]
    cold = ContinuousBatcher(cfg, params, **kw)
    want = {c.rid: c.tokens for c in cold.run(reqs())}
    warm = ContinuousBatcher(cfg, params, **kw)
    info = warm.warmup()
    assert info["compiled"] and info["seconds"] >= 0.0
    assert any(c.startswith("decode[") for c in info["compiled"])
    got = {c.rid: c.tokens for c in warm.run(reqs())}
    assert got == want
    assert warm.alloc.rows == {}    # warmup owns no rows or pages
    if mode == "pcache":
        # Warmup left the cache consistent: a second pass HITS and
        # still equals the cold stream.
        assert warm.prefix_cache_stats()["cached_pages"] >= 0
        again = {c.rid: c.tokens for c in warm.run(reqs())}
        # rids keep counting across runs; the STREAMS must be equal.
        assert [t for _, t in sorted(again.items())] == \
            [t for _, t in sorted(want.items())]
        assert warm.prefix_cache_stats()["hits"] > 0


def test_warmup_speculative_covers_spec_round(setup, draft_setup):
    cfg, params = setup
    dcfg, dparams = draft_setup
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, draft_cfg=dcfg,
                          draft_params=dparams, n_draft=3)
    info = b.warmup()
    assert any(c.startswith("spec_round[") for c in info["compiled"])
    assert any(c.startswith("draft_chunk[") for c in info["compiled"])
    req = Request(prompt=_prompts(cfg, 1, seed=87)[0], max_new_tokens=5)
    plain = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16, prefill_bucket=16,
                              draft_cfg=dcfg, draft_params=dparams,
                              n_draft=3)
    assert [c.tokens for c in b.run([req])] == \
        [c.tokens for c in plain.run([req])]


def test_warmup_refused_while_serving(setup):
    import threading
    import time as _time

    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=1, max_len=32, page_size=16,
                          prefill_bucket=16)
    t = threading.Thread(target=lambda: list(b.serve()), daemon=True)
    t.start()
    deadline = _time.monotonic() + 30.0
    while not b._loop_active and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert b._loop_active
    with pytest.raises(RuntimeError, match="warm at boot"):
        b.warmup()
    b.close()
    t.join(timeout=60.0)


def test_warmup_covers_every_prefill_width(setup):
    """Non-chunked admission pads prompts to MULTIPLES of
    prefill_bucket (not just the base bucket), so warmup must compile
    every reachable width — a warmed replica's first long prompt must
    not pay a live XLA trace (the --warmup contract)."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    b = ContinuousBatcher(cfg, params, **kw)
    info = b.warmup()
    assert set(b._prefill_fns) == set(b._prefill_widths())
    assert [c for c in info["compiled"] if c.startswith("prefill[")] == \
        [f"prefill[{w}]" for w in b._prefill_widths()]
    assert len(b._prefill_widths()) > 1    # the matrix covers >1 width
    # A prompt longer than the base bucket (width 32 here) dispatches
    # an ALREADY-compiled trace: the fn cache must not grow.
    n = len(b._prefill_fns)
    long_p = _prompts(cfg, 1, seed=91)[0]
    long_p = np.tile(long_p, 4)[:20].astype(np.int32)   # pads to 32
    done = list(b.run([Request(prompt=long_p, max_new_tokens=4)]))
    assert len(done) == 1 and len(b._prefill_fns) == n
    cold = ContinuousBatcher(cfg, params, **kw)
    assert [c.tokens for c in cold.run(
        [Request(prompt=long_p, max_new_tokens=4)])] == \
        [c.tokens for c in done]
    # Decode widths come from the SAME formula live dispatch buckets
    # with (one source of truth, not a re-derivation).
    from tfmesos_tpu.serving import _PagedSide
    np_max = b.t_side.np_max
    assert b._decode_widths() == sorted(
        {_PagedSide.width_for(occ, np_max)
         for occ in range(1, np_max + 1)})


def test_warmup_covers_multibucket_tail_prefill(setup):
    """The prefix-cache TAIL writer retraces per padded tail width
    (multiples of prefill_bucket), so warmup must cover them all: a
    warmed replica's first warm-cache hit whose uncached tail spans
    2+ buckets must NOT pay a live XLA trace."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16,
              prefix_cache_pages=16)
    warm = ContinuousBatcher(cfg, params, **kw)
    info = warm.warmup()
    assert [c for c in info["compiled"]
            if c.startswith("chunk_prefill[")] == \
        [f"chunk_prefill[{w}]" for w in warm._prefill_widths()]
    rng = np.random.RandomState(71)
    sys_p = rng.randint(0, cfg.vocab_size, size=16).astype(np.int32)
    p_seed = np.concatenate([sys_p, rng.randint(
        0, cfg.vocab_size, size=3).astype(np.int32)])
    p_hit = np.concatenate([sys_p, rng.randint(
        0, cfg.vocab_size, size=17).astype(np.int32)])   # tail pads to 32
    list(warm.run([Request(prompt=p_seed, max_new_tokens=4)]))
    n = warm._tail_prefill._cache_size()
    done = list(warm.run([Request(prompt=p_hit, max_new_tokens=4)]))
    assert warm.prefix_cache_stats()["hits"] >= 1
    assert warm._tail_prefill._cache_size() == n    # no live retrace
    plain = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16, prefill_bucket=16)
    assert [c.tokens for c in plain.run(
        [Request(prompt=p_hit, max_new_tokens=4)])] == \
        [c.tokens for c in done]


def test_warmup_decode_false_skips_decode_blocks(setup):
    """A prefill-ROLE replica never decodes: warmup(decode=False) must
    skip the per-width decode compiles (they only lengthen the warming
    window on every relaunch) while still warming the prefill surface
    and the KV export/import scatter."""
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16)
    info = b.warmup(decode=False)
    assert not any(c.startswith(("decode[", "spec_round["))
                   for c in info["compiled"])
    assert any(c.startswith("prefill[") for c in info["compiled"])
    assert "kv_export_import[1]" in info["compiled"]
    # The mirror for decode-ROLE replicas (only ever import KV):
    # prefill=False skips the per-width prefill compiles but keeps the
    # decode blocks and the import scatter.
    b2 = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                           prefill_bucket=16)
    info2 = b2.warmup(prefill=False)
    assert not any(c.startswith(("prefill[", "chunk_prefill[",
                                 "draft_chunk[")) for c in info2["compiled"])
    assert any(c.startswith("decode[") for c in info2["compiled"])
    assert "kv_export_import[1]" in info2["compiled"]
    # The skipped compiles don't poison the export path: a real
    # prefill-only export still works on the warmed batcher.
    req = Request(prompt=_prompts(cfg, 1, seed=93)[0], max_new_tokens=4)
    art = b.export_kv(req)
    assert art["pos"] >= req.prompt.size and art["first_token"] >= 0


def test_mesh_batcher_validation(mesh_setup):
    cfg, params, _, _ = mesh_setup
    with pytest.raises(ValueError, match="divide over the mesh"):
        ContinuousBatcher(cfg, params, rows=3, max_len=64, page_size=16,
                          mesh=_mesh({"dp": 2}))
    with pytest.raises(ValueError, match="tp .* must divide"):
        ContinuousBatcher(cfg, params, rows=8, max_len=64, page_size=16,
                          mesh=_mesh({"tp": 8}))
    with pytest.raises(ValueError, match="data .* x tp|dp/fsdp"):
        ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          mesh=_mesh({"sp": 2}))


def test_completion_timing_metrics(setup):
    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    reqs = [Request(prompt=p, max_new_tokens=5)
            for p in _prompts(cfg, 3, seed=21)]
    for c in batcher.run(reqs):
        assert 0.0 < c.ttft_s <= c.total_s


def test_admission_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="non-empty"):
        Request(prompt=np.zeros((0,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt=np.array([1], np.int32), max_new_tokens=0)
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=32,
                                page_size=16, prefill_bucket=16)
    big = Request(prompt=np.arange(20, dtype=np.int32) % cfg.vocab_size,
                  max_new_tokens=30)
    with pytest.raises(ValueError, match="max_len"):
        list(batcher.run([big]))


def test_oversized_request_drains_inflight_before_raising(setup):
    """A malformed arrival mid-stream must not discard valid in-flight
    work: already-admitted requests complete and yield first, THEN the
    ValueError surfaces."""
    cfg, params = setup
    good = [Request(prompt=p, max_new_tokens=6)
            for p in _prompts(cfg, 2, seed=23)]
    huge = Request(prompt=np.arange(40, dtype=np.int32) % cfg.vocab_size,
                   max_new_tokens=60)
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = []
    with pytest.raises(ValueError, match="max_len"):
        for c in batcher.run([*good, huge,
                              Request(prompt=good[0].prompt,
                                      max_new_tokens=2)]):
            done.append(c)
    assert sorted(c.rid for c in done) == [0, 1]    # both good ones landed
    for c in done:
        assert c.tokens == _offline(cfg, params, c.request)
    assert batcher.alloc.rows == {}                 # nothing leaked


def test_pool_too_small_raises_not_hangs(setup):
    cfg, params = setup
    # 3 usable pages (4 minus sink) but the request's worst case needs 4.
    batcher = ContinuousBatcher(cfg, params, rows=1, max_len=64,
                                page_size=16, n_pages=4, prefill_bucket=16)
    req = Request(prompt=np.arange(17, dtype=np.int32), max_new_tokens=40)
    with pytest.raises(RuntimeError, match="raise n_pages"):
        list(batcher.run([req]))


#: Both settings of the batcher's one lag policy: the host reads
#: tokens in step, or one dispatch behind through the device carry.
LAG = pytest.mark.parametrize("lag", [{}, {"pipeline_depth": 1}],
                              ids=["sync", "pipelined"])


@LAG
def test_abandoned_run_releases_pages(setup, lag):
    """Breaking out of run() mid-stream must not leak in-flight rows'
    pages (nor, pipelined, the block in flight and its device carry);
    the batcher stays usable for a fresh run."""
    cfg, params = setup
    mk = lambda: [Request(prompt=p, max_new_tokens=8)
                  for p in _prompts(cfg, 6, seed=13)]
    batcher = ContinuousBatcher(cfg, params, rows=3, max_len=64,
                                page_size=16, prefill_bucket=16, **lag)
    for c in batcher.run(mk()):
        break               # abandon with rows still decoding
    assert batcher._inflight is None and batcher._pipe_carry is None
    assert batcher.alloc.rows == {}
    assert batcher.alloc.free_count() == batcher.n_pages - 1  # sink stays
    done = list(batcher.run(mk()))
    assert len(done) == 6


def test_typed_prng_key_accepted(setup):
    """rng accepts new-style typed keys (folding happens in-graph)."""
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, temperature=0.7,
                          rng=jax.random.key(7))
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in _prompts(cfg, 3, seed=15)]
    done = list(b.run(reqs))
    assert len(done) == 3


@pytest.mark.parametrize("prefix_len", [16, 11, 21])
def test_shared_prefix_matches_generate(setup, prefix_len):
    """A shared system prompt behind the prefix cache (page_size 16:
    aligned, sub-page, and full+tail cases): rows reference the cached
    prompt pages read-only — a partial last page is each row's own, its
    tail prefilled at admission — and greedy outputs are
    token-identical to generate(prefix=...)."""
    cfg, params = setup
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, cfg.vocab_size, size=prefix_len).astype(np.int32)
    prompts = _prompts(cfg, 6, seed=18)
    reqs = [Request(prompt=_behind(prefix, p), max_new_tokens=3 + (i % 4))
            for i, p in enumerate(prompts)]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=96,
                                page_size=16, prefill_bucket=16,
                                prefix_cache_pages=8)
    done = {c.rid: c for c in batcher.run(reqs)}
    assert len(done) == len(reqs)
    for rid, req in enumerate(reqs):
        out = transformer.generate(
            cfg, params, jnp.asarray(prompts[rid][None]),
            req.max_new_tokens, temperature=0.0,
            prefix=jnp.asarray(prefix))
        want = np.asarray(out)[0, req.prompt.size:].tolist()
        assert done[rid].tokens == want, f"request {rid} diverged"
    st = batcher.prefix_cache_stats()
    if prefix_len >= 16:
        # the prompt's full pages were prefilled once and then shared
        assert st["hits"] >= len(reqs) - 2
    # Cached pages survive the whole stream; own pages all recycled
    # (pool keeps sink + cached pages out of circulation).
    assert batcher.alloc.free_count() == \
        batcher.n_pages - 1 - st["cached_pages"]
    assert batcher.alloc.rows == {}


def test_static_prefix_argument_is_gone(setup):
    """The batcher-level static ``prefix=`` went where the prefix cache
    already was: Python's own TypeError, no shim."""
    cfg, params = setup
    with pytest.raises(TypeError, match="prefix"):
        ContinuousBatcher(cfg, params, rows=1, max_len=64, page_size=16,
                          prefix=np.zeros((16,), np.int32))


def test_import_refuses_artifact_with_static_prefix(setup):
    """The wire format keeps ``prefix_len`` / ``shared_len`` (an export
    writes both as 0, version 1 as before), and an artifact cut behind
    a static prefix — every position of it offset — is refused loudly,
    never decoded against."""
    from tfmesos_tpu.serving import Prefilled

    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=1, max_len=64, page_size=16,
                          prefill_bucket=16)
    req = Request(prompt=_prompts(cfg, 1, seed=19)[0], max_new_tokens=4)
    art = b.export_kv(req)
    assert art["version"] == 1
    assert art["prefix_len"] == 0 and art["shared_len"] == 0
    b.validate(Prefilled(req, art))             # the real one imports
    for key in ("prefix_len", "shared_len"):
        with pytest.raises(ValueError,
                           match=f"KV artifact {key} 16 does not match "
                                 f"this batcher's 0"):
            b.validate(Prefilled(req, dict(art, **{key: 16})))


def test_tpu_shaped_serving_geometry(setup):
    """The serving-quality matrix at TPU-SHAPED geometry (VERDICT r4 weak
    #6): page_size=64, max_len=2048 (32 pages/row), bf16, long prompts —
    a shared system prompt behind the prefix cache + chunked prefill +
    speculative TOGETHER, where the
    index-map arithmetic (block clamps, shared pages, verify-chunk
    overshoot) actually bites.  CPU, so correctness not speed; outputs
    must match the plain (unchunked, non-speculative) paged batcher's
    modulo bf16 float-tie argmax forks, and both pools must recycle."""
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=2304, dtype=jnp.bfloat16)
    params = transformer.init_params(cfg, jax.random.PRNGKey(2))
    dcfg = transformer.TransformerConfig(
        vocab_size=128, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=2304, dtype=jnp.bfloat16)
    dparams = transformer.init_params(dcfg, jax.random.PRNGKey(6))
    rng = np.random.RandomState(83)
    prefix = rng.randint(0, 128, size=100).astype(np.int32)  # page + tail
    prompts = [_behind(prefix, rng.randint(0, 128, size=n).astype(np.int32))
               for n in (700, 1150, 330)]
    mk = lambda: [Request(prompt=p, max_new_tokens=4 + i)
                  for i, p in enumerate(prompts)]
    kw = dict(rows=2, max_len=2048, page_size=64, prefix_cache_pages=32)
    plain = ContinuousBatcher(cfg, params, prefill_bucket=64, **kw)
    want = {c.rid: c.tokens for c in plain.run(mk())}
    combo = ContinuousBatcher(cfg, params, prefill_chunk=64,
                              draft_cfg=dcfg, draft_params=dparams,
                              n_draft=4, **kw)
    got = {c.rid: c.tokens for c in combo.run(mk())}
    assert combo.np_max == 32                   # 32 pages per row
    for rid in want:
        assert len(got[rid]) == len(want[rid])
        # bf16 logit spacing is coarse: allow forks only at near-ties.
        _assert_tokens_match_modulo_ties(
            cfg, params, prompts[rid], got[rid], want[rid],
            atol=0.15)
    cached = combo.prefix_cache_stats()["cached_pages"]
    assert combo.prefix_cache_stats()["hits"] >= 1
    for side in (combo.t_side, combo.d_side):
        assert side.alloc.rows == {}
        # the sink and the cached pages (twins in the draft pool) stay
        assert side.alloc.free_count() == side.n_pages - 1 - cached
        assert side.peak <= side.n_pages        # never oversubscribed


def test_int8_draft_pool_composes(setup, draft_setup):
    """draft_quantized_cache=True serves draft proposals from an int8
    page pool (halving draft HBM); outputs stay valid and the combo
    with an int8 TARGET pool also runs."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    reqs = lambda: [Request(prompt=p, max_new_tokens=4)
                    for p in _prompts(cfg, 4, seed=91)]
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, draft_cfg=dcfg,
                          draft_params=dparams, n_draft=3,
                          draft_quantized_cache=True)
    done = {c.rid: c for c in b.run(reqs())}
    assert len(done) == 4
    for c in done.values():
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)
    assert b.d_side.alloc.rows == {}
    # Full quantized stack: int8 target + int8 draft.
    b2 = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                           prefill_bucket=16, draft_cfg=dcfg,
                           draft_params=dparams, n_draft=3,
                           quantized_cache=True,
                           draft_quantized_cache=True)
    assert len(list(b2.run(reqs()))) == 4


def test_int8_kv_pool_composes(setup):
    """quantized_cache=True serves from an int8 page pool; outputs stay
    close to (not necessarily identical to) the fp path."""
    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in _prompts(cfg, 3, seed=11)]
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, quantized_cache=True)
    done = {c.rid: c for c in b.run(reqs)}
    assert len(done) == 3
    for c in done.values():
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)


@pytest.mark.parametrize("variant", [
    "base", "staggered", "stop", "sampled", "chunked", "prefix", "mesh",
    "pipelined", "pipelined_stop", "pipelined_mesh",
])
@pytest.mark.parametrize("k", [2, 4])
def test_multistep_batcher_token_identical(setup, mesh_setup, variant, k):
    """multi_step=K (K decode steps fused into one dispatch, one host
    sync per [rows, K] token block) must produce IDENTICAL token streams
    to the single-step batcher across the matrix: stops and quota
    endings mid-block discard the rest of the block, in-block overshoot
    writes stay inside the reservation clamp or land on sink columns,
    sampled keys fold per (rid, step) exactly as before, and the mesh +
    pipelined (pipeline_depth=1) paths compose."""
    if variant in ("mesh", "pipelined_mesh"):
        cfg, params, _, _ = mesh_setup
    else:
        cfg, params = setup
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 8, 13, 19, 16, 5)]
    kw = dict(rows=4, max_len=96, page_size=16, prefill_bucket=16)
    mkw = {}
    if variant == "sampled":
        kw.update(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(3))
    elif variant == "chunked":
        kw.update(prefill_chunk=8)
    elif variant == "prefix":
        prefix = rng.randint(0, cfg.vocab_size, size=13).astype(np.int32)
        prompts = [_behind(prefix, p) for p in prompts]
        kw.update(prefix_cache_pages=8)
    elif variant in ("mesh", "pipelined_mesh"):
        mkw.update(mesh=_mesh({"dp": 2, "tp": 2}))
    mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 5))
                  for i, p in enumerate(prompts)]
    if variant.startswith("pipelined"):
        mkw.update(pipeline_depth=1)
    if variant in ("stop", "pipelined_stop"):
        probe = ContinuousBatcher(cfg, params, **kw)
        outs = {c.rid: c.tokens for c in probe.run(mk())}
        stops = {rid: t[min(1, len(t) - 1)] for rid, t in outs.items()}
        mk = lambda: [Request(prompt=p, max_new_tokens=2 + (i % 5),
                              stop_token=stops[i])
                      for i, p in enumerate(prompts)]
    if variant == "staggered":
        kw["rows"] = 2

        def feed(reqs, done):
            for r in reqs:
                assert len(done) <= len(reqs)   # pull stays lazy
                yield r
    else:
        feed = lambda reqs, done: iter(reqs)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {}
    for c in plain.run(feed(mk(), want)):
        want[c.rid] = c.tokens
    mb = ContinuousBatcher(cfg, params, multi_step=k, **kw, **mkw)
    got = {}
    for c in mb.run(feed(mk(), got)):
        got[c.rid] = c.tokens
    if variant in ("mesh", "pipelined_mesh"):
        for rid in want:
            _assert_tokens_match_modulo_ties(
                cfg, params, prompts[rid], got[rid], want[rid])
    else:
        assert got == want
    assert mb._inflight is None             # loop drained
    assert mb.t_side.alloc.rows == {}       # nothing leaked
    # Reservation invariant held throughout: the pool high-water mark
    # never exceeded sink + what the prefix cache may keep + (concurrent
    # rows x the largest admission reservation) — if a multi-step block
    # ever ensured past its _Row.limit clamp, a row's allocations would
    # exceed its reservation and the high-water mark would break this
    # bound.
    worst = max(mb._worst_pages(q)[0] for q in mk())
    n_kept = kw.get("prefix_cache_pages", 0)
    assert mb.peak_pages_used <= 1 + n_kept + kw["rows"] * worst


def test_multistep_validation(setup, draft_setup):
    cfg, params = setup
    dcfg, dparams = draft_setup
    with pytest.raises(ValueError, match="multi_step"):
        ContinuousBatcher(cfg, params, multi_step=0)
    # spec+multi_step COMPOSES: R in-graph rounds per dispatch,
    # R = ceil(multi_step / (n_draft+1)) — asked for a lagged carry or
    # not (the pipelined carry has no speculative form).
    kw = dict(rows=2, max_len=64, page_size=16, draft_cfg=dcfg,
              draft_params=dparams, n_draft=3)
    for pd in (0, 1):
        b = ContinuousBatcher(cfg, params, multi_step=8,
                              pipeline_depth=pd, **kw)
        assert b._spec_rounds == 2 and not b._pipelined
        assert not hasattr(b, "multi_step_bypass_reason")


def test_spec_multistep_token_identical(setup, draft_setup):
    """spec+multi_step (R fused rounds per dispatch) streams
    token-identical to the R=1 speculative batcher — the composition
    acceptance bar, greedy and sampled."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    prompts = _prompts(cfg, 3, seed=311)
    for T in (0.0, 0.8):
        kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16,
                  draft_cfg=dcfg, draft_params=dparams, n_draft=3,
                  temperature=T)
        reqs = lambda: [Request(prompt=p, max_new_tokens=9,
                                stop_token=None) for p in prompts]
        base = ContinuousBatcher(cfg, params, **kw)
        want = {c.rid: c.tokens for c in base.run(reqs())}
        fused = ContinuousBatcher(cfg, params, multi_step=8, **kw)
        assert fused._spec_rounds == 2
        got = {c.rid: c.tokens for c in fused.run(reqs())}
        assert got == want
        assert fused.spec_committed == base.spec_committed


def test_bucket_width_invariants():
    """The decode-table bucket width is a power of two STRICTLY above the
    widest allocation (so an overrun row's clamped write lands past its
    own pages — on the sink), capped at np_max."""
    from tfmesos_tpu.serving import _PagedSide

    side = _PagedSide(n_pages=65, page_size=16, rows=4, np_max=64)
    assert side.bucket_width() == 2            # empty: strictly > 1
    side.ensure(0, 16)                         # 1 page
    assert side.bucket_width() == 2            # strictly > 1
    side.ensure(1, 64)                         # 4 pages
    assert side.bucket_width() == 8            # strictly > 4 (pow2)
    side.ensure(1, 65)                         # 5 pages
    assert side.bucket_width() == 8
    side.ensure(2, 16 * 33)                    # 33 pages -> 64 (cap hits)
    assert side.bucket_width() == 64           # min(pow2 > 33, np_max)
    side.release(2)
    assert side.bucket_width() == 8            # shrinks with the workload
    # Widths always slice within the table.
    assert side.bucket_width() <= side.np_max


def test_incremental_submission_matches_offline(setup):
    """The online front door's path: submit() from another thread while
    serve() decodes; streams must match offline generation exactly, and
    close() must drain and end the loop."""
    import threading
    import time

    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=3 + (i % 5))
            for i, p in enumerate(_prompts(cfg, 8, seed=11))]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = {}

    def consume():
        for c in batcher.serve():
            done[c.rid] = c

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for i, req in enumerate(reqs):
        batcher.submit(req)
        if i % 3 == 0:
            time.sleep(0.05)    # arrivals land mid-decode, not up front
    batcher.close()
    t.join(timeout=300.0)
    assert not t.is_alive(), "serve() failed to drain after close()"
    assert len(done) == len(reqs)
    for rid, req in enumerate(reqs):
        assert done[rid].request is req
        assert done[rid].tokens == _offline(cfg, params, req), \
            f"submitted request {rid} diverged from offline generation"
    with pytest.raises(RuntimeError):
        batcher.submit(reqs[0])     # the stream is closed


def test_submission_close_before_serve_and_validate(setup):
    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=1, max_len=32,
                                page_size=16, prefill_bucket=16)
    # validate() pre-checks what run() would raise only after draining.
    batcher.validate(Request(prompt=np.asarray([1, 2, 3], np.int32),
                             max_new_tokens=4))
    with pytest.raises(ValueError):
        batcher.validate(Request(
            prompt=(np.arange(30, dtype=np.int32) % cfg.vocab_size),
            max_new_tokens=30))
    # close() before serve(): the loop ends immediately instead of
    # blocking forever on an idle queue.
    batcher.close()
    assert list(batcher.serve()) == []


def test_submission_queue_type_checks(setup):
    from tfmesos_tpu.serving import SubmissionQueue

    sq = SubmissionQueue()
    with pytest.raises(TypeError):
        sq.submit([1, 2, 3])        # raw arrays must be wrapped first
    sq.submit(Request(prompt=np.asarray([1], np.int32), max_new_tokens=1))
    sq.close()
    assert sq.closed
    sq.close()                      # idempotent
    with pytest.raises(RuntimeError):
        sq.submit(Request(prompt=np.asarray([1], np.int32),
                          max_new_tokens=1))


# -- cross-request prefix caching (COW page sharing) ------------------------


def _shared_prefix_reqs(cfg, n, sys_len=36, tail0=5, new=4, seed=21):
    """A shared-system-prompt stream: one ``sys_len``-token system
    prompt + distinct user tails of varying length."""
    rng = np.random.RandomState(seed)
    system = rng.randint(0, cfg.vocab_size, size=sys_len).astype(np.int32)
    return [Request(prompt=np.concatenate(
                [system, np.random.RandomState(seed + 1 + i).randint(
                    0, cfg.vocab_size, size=tail0 + i).astype(np.int32)]),
                max_new_tokens=new)
            for i in range(n)]


def _tokens_in_order(batcher, reqs):
    return [t for _, t in sorted((c.rid, c.tokens)
                                 for c in batcher.run(reqs))]


def test_prefix_cache_exact_vs_cold(setup):
    """Warm (prefix-cached) completions must EQUAL cold-prefill
    completions — the exact-output-equivalence bar — and the pool
    accounting must balance after the drain."""
    cfg, params = setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16)
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    assert warm.prefix_cache_active
    want = _tokens_in_order(cold, _shared_prefix_reqs(cfg, 6))
    got = _tokens_in_order(warm, _shared_prefix_reqs(cfg, 6))
    assert got == want
    st = warm.prefix_cache_stats()
    # 36-token system prompt over 16-token pages: 2 full shared chunks;
    # request 0 publishes them, 1..5 map them read-only.
    assert st["hits"] == 5 and st["misses"] == 1
    assert st["hit_pages"] == 10 and st["inserted"] >= 2
    # A second stream hits on EVERY request (the pages stayed resident).
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 6)) == want
    st = warm.prefix_cache_stats()
    assert st["hits"] == 11
    # After the drain every reference is dropped: retained == cached,
    # and free + cached + sink accounts for the whole pool.
    assert st["retained_pages"] == st["cached_pages"]
    assert len(warm.alloc.free) + st["cached_pages"] + 1 == warm.n_pages
    assert warm.alloc.rows == {}


def test_prefix_cache_cow_on_page_aligned_full_hit(setup):
    """A page-aligned full-prompt hit must COW its deepest page (the
    one-token logits rewrite would otherwise write shared state) and
    stay exact."""
    cfg, params = setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16)
    prompt = np.random.RandomState(3).randint(
        0, cfg.vocab_size, size=48).astype(np.int32)   # exactly 3 pages
    mk = lambda: [Request(prompt=prompt, max_new_tokens=20)]
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, mk())
    assert _tokens_in_order(warm, mk()) == want     # miss, publishes
    assert _tokens_in_order(warm, mk()) == want     # full hit -> COW
    st = warm.prefix_cache_stats()
    assert st["cow_copies"] == 1
    assert st["hits"] == 1 and st["hit_tokens"] == 47


def test_prefix_cache_eviction_under_pressure_never_deadlocks(setup):
    """DISTINCT prompts past the pool's capacity: retained zero-ref
    pages must be evicted on demand (admission headroom counts them as
    free), so the stream completes instead of deadlocking, and outputs
    stay exact."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    reqs = lambda: [Request(prompt=np.random.RandomState(50 + i).randint(
                        0, cfg.vocab_size, size=33 + (i % 3)).astype(
                            np.int32), max_new_tokens=4)
                    for i in range(10)]
    cold = ContinuousBatcher(cfg, params, **kw)
    # Budget far past what the default pool can retain: eviction, not
    # the budget, must be what keeps admission alive.
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=64, **kw)
    want = _tokens_in_order(cold, reqs())
    assert _tokens_in_order(warm, reqs()) == want
    st = warm.prefix_cache_stats()
    assert st["evicted"] > 0, "pool pressure must trigger LRU eviction"
    assert len(warm.alloc.free) + st["cached_pages"] + 1 == warm.n_pages
    # Pool pages the batcher thinks are USED (incl. resident cache)
    # never exceeded the physical pool.
    assert warm.peak_pages_used <= warm.n_pages


def test_prefix_cache_budget_caps_residency(setup):
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=2, **kw)
    reqs = [Request(prompt=np.random.RandomState(80 + i).randint(
                0, cfg.vocab_size, size=36).astype(np.int32),
                max_new_tokens=3)
            for i in range(5)]
    assert len(list(warm.run(reqs))) == 5
    st = warm.prefix_cache_stats()
    assert st["cached_pages"] <= 2
    assert st["evicted"] + st["skipped"] > 0


def test_prefix_cache_bypasses_are_explicit(setup, draft_setup):
    """Quantized pools (target OR draft) don't share pages — the
    bypass must be DISCOVERABLE, and serving must stay correct.
    Speculative decoding now COMPOSES (the burn-down: its trie couples
    target pages with draft-pool twins), so a spec batcher's cache is
    ACTIVE — the audit test keeps 'speculative decoding' out of the
    reachable set for good."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    spec = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                             draft_params=dparams, n_draft=2,
                             prefix_cache_pages=8, **kw)
    assert spec.prefix_cache_active
    assert spec.prefix_cache_bypass_reason is None
    q = ContinuousBatcher(cfg, params, quantized_cache=True,
                          prefix_cache_pages=8, **kw)
    assert not q.prefix_cache_active
    assert q.prefix_cache_bypass_reason == "quantized kv cache"
    dq = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                           draft_params=dparams, n_draft=2,
                           draft_quantized_cache=True,
                           prefix_cache_pages=8, **kw)
    assert not dq.prefix_cache_active
    assert dq.prefix_cache_bypass_reason == "quantized kv cache"
    # Bypassed batchers still serve the shared-prefix stream correctly.
    reqs = _shared_prefix_reqs(cfg, 3, sys_len=20, new=3)
    assert len(list(q.run(reqs))) == 3


def test_spec_prefix_cache_exact_vs_cold(setup, draft_setup):
    """Spec + prefix cache (the burn-down's headline composition):
    warm speculative completions EQUAL cold speculative completions —
    both pools' twin pages map read-only, only the uncached tail
    prefills (target tail writer + draft chunk writer) — and BOTH
    pools' accounting balances after the drain."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16,
              draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, _shared_prefix_reqs(cfg, 5))
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 5)) == want
    st = warm.prefix_cache_stats()
    assert st["hits"] == 4 and st["misses"] == 1
    # A second stream hits on EVERY request (twin pages stay resident).
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 5)) == want
    st = warm.prefix_cache_stats()
    assert st["hits"] == 9
    # Each node holds a page on BOTH pools: free + cached + sink
    # accounts for each pool exactly.
    assert warm.alloc.rows == {} and warm.d_side.alloc.rows == {}
    assert len(warm.alloc.free) + st["cached_pages"] + 1 == warm.n_pages
    assert len(warm.d_side.alloc.free) + st["cached_pages"] + 1 \
        == warm.n_draft_pages


def test_spec_prefix_cache_cow_full_hit(setup, draft_setup):
    """A page-aligned full-prompt hit on a SPEC batcher must COW the
    deepest page on BOTH pools (the one-token rewrite and the draft
    round's scan both write E-1) and stay exact."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16,
              draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    prompt = np.random.RandomState(3).randint(
        0, cfg.vocab_size, size=48).astype(np.int32)   # exactly 3 pages
    mk = lambda: [Request(prompt=prompt, max_new_tokens=12)]
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, mk())
    assert _tokens_in_order(warm, mk()) == want     # miss, publishes
    assert _tokens_in_order(warm, mk()) == want     # full hit -> COW
    st = warm.prefix_cache_stats()
    assert st["cow_copies"] == 1 and st["hits"] == 1


def test_spec_prefix_cache_with_chunked_prefill(setup, draft_setup):
    """Spec + prefix cache + chunked prefill: a hit skips straight to
    the uncached tail on the chunk grid for BOTH pools (the draft's
    chunks advance from the tail), outputs equal the cache-off spec
    chunked batcher's."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_chunk=16,
              draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, _shared_prefix_reqs(cfg, 4))
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 4)) == want
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 4)) == want
    assert warm.prefix_cache_stats()["hits"] >= 4


def test_prefix_cache_with_chunked_prefill(setup):
    """prefill_chunk mode: a hit skips straight to the uncached tail on
    the chunk grid; outputs equal the cache-off chunked batcher's."""
    cfg, params = setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_chunk=16)
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, _shared_prefix_reqs(cfg, 5))
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 5)) == want
    st = warm.prefix_cache_stats()
    # Chunked publication waits for fill COMPLETION, so request 1 (in
    # flight alongside request 0) can also miss: >= 3 hits of 5.
    assert st["hits"] >= 3 and st["hit_pages"] >= 6
    # The second stream hits on every request.
    assert _tokens_in_order(warm, _shared_prefix_reqs(cfg, 5)) == want
    assert warm.prefix_cache_stats()["hits"] >= st["hits"] + 5


def test_prefix_cache_with_pipelined_and_multistep(setup):
    cfg, params = setup
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16,
              pipeline_depth=1, multi_step=2)
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, _shared_prefix_reqs(cfg, 5, new=6))
    assert _tokens_in_order(warm,
                            _shared_prefix_reqs(cfg, 5, new=6)) == want
    assert warm.prefix_cache_stats()["hits"] >= 4


@pytest.mark.parametrize("prefix_len", [16, 11])
def test_prefix_cache_composes_with_global_prefix(setup, prefix_len):
    """A deployment-wide prompt ahead of a shared system prompt: the
    prefix cache shares the pages of both, whole pages or not (a
    sub-page global prompt shifts every later page boundary), and
    outputs still equal the cache-off batcher's."""
    cfg, params = setup
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, cfg.vocab_size,
                         size=prefix_len).astype(np.int32)
    kw = dict(rows=2, max_len=96, page_size=16, prefill_bucket=16)
    mk = lambda: [Request(prompt=_behind(prefix, r.prompt),
                          max_new_tokens=r.max_new_tokens)
                  for r in _shared_prefix_reqs(cfg, 5, sys_len=30)]
    cold = ContinuousBatcher(cfg, params, **kw)
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    want = _tokens_in_order(cold, mk())
    assert _tokens_in_order(warm, mk()) == want
    assert warm.prefix_cache_stats()["hits"] >= 4
    assert _tokens_in_order(warm, mk()) == want


def test_prefix_cache_refcounts_protect_inflight_pages(setup):
    """While a hit row is mid-decode its mapped pages are referenced
    and must survive allocation pressure from other admissions."""
    cfg, params = setup
    warm = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                             page_size=16, prefill_bucket=16,
                             prefix_cache_pages=64)
    # Interleave one long-running shared-prefix request with churning
    # distinct prompts that force eviction; the shared rows' outputs
    # must match the cache-off reference.
    cold = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                             page_size=16, prefill_bucket=16)
    rng = np.random.RandomState(4)
    shared = _shared_prefix_reqs(cfg, 3, sys_len=32, new=12, seed=91)
    churn = [Request(prompt=np.random.RandomState(200 + i).randint(
                 0, cfg.vocab_size, size=34).astype(np.int32),
                 max_new_tokens=2)
             for i in range(6)]
    mix = lambda: [shared[0], churn[0], shared[1], churn[1], churn[2],
                   shared[2], churn[3], churn[4], churn[5]]
    want = _tokens_in_order(cold, [dataclasses_replace_req(r)
                                   for r in mix()])
    got = _tokens_in_order(warm, [dataclasses_replace_req(r)
                                  for r in mix()])
    assert got == want


def dataclasses_replace_req(r):
    """Fresh Request (run() consumes requests once; rid-keyed results
    need distinct objects per run)."""
    return Request(prompt=r.prompt.copy(),
                   max_new_tokens=r.max_new_tokens,
                   stop_token=r.stop_token)


def test_paged_side_tables_dirty_after_cow_remap():
    """Regression (stale-device-table audit): every page-mapping
    mutation — cached-prefix acquire, COW remap, release — must
    invalidate the host master table, the device table, AND the masked
    decode variants.  A stale device table after a COW remap silently
    decodes against freed pages."""
    import types

    from tfmesos_tpu.prefixhash import prompt_digests
    from tfmesos_tpu.serving import _PagedSide, _PrefixCache, _Row

    side = _PagedSide(n_pages=8, page_size=4, rows=2, np_max=4)
    pc = _PrefixCache(side, page_size=4, budget=8)
    digs = prompt_digests(np.arange(8, dtype=np.int32), 4)
    # Row 0 prefills two full pages and publishes them.
    side.ensure(0, 8)
    own0 = list(side.alloc.rows[0])
    pc.insert_row(0, 0, digs, types.SimpleNamespace(worst_pages=4))
    assert side.row_cached[0] == own0 and side.alloc.rows[0] == []
    t_before = np.asarray(side.table())
    assert list(t_before[1]) == [side.sink] * 4
    # Row 1 maps the cached pages read-only: the DEVICE table must
    # rebuild (row 1 now references row 0's published pages).
    nodes = pc.match(0, digs)
    assert [n.page for n in nodes] == own0
    pc.acquire(1, nodes)
    t_mapped = np.asarray(side.table())
    assert list(t_mapped[1][:2]) == own0
    # COW remap: drop the deepest cached page, back it with a fresh own
    # page instead — the device table must show the OWN copy, and the
    # masked decode-table variant must rebuild too.
    masked_before = np.asarray(side.decode_table(
        {0: None, 1: None}, {0: None}))       # row 1 masked to sink
    cow = pc.unmap_last(1)
    side.ensure(1, 8)
    own1 = side.alloc.rows[1][0]
    assert own1 != cow.page
    pc.release_nodes(1, [cow])
    t_cow = np.asarray(side.table())
    assert list(t_cow[1][:2]) == [own0[0], own1]
    masked_after = np.asarray(side.decode_table(
        {0: None, 1: None}, {0: None}))
    assert list(masked_after[1]) == [side.sink] * masked_after.shape[1]
    assert masked_after.shape == masked_before.shape
    # Release drops the references and invalidates again.
    side.release(1)
    assert list(np.asarray(side.table())[1]) == [side.sink] * 4
    assert all(n.ref == 1 for n in nodes[:-1])  # row 0 still holds its refs


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 2, "tp": 2}])
def test_prefix_cache_with_mesh(mesh_setup, axes):
    """Per-shard tries under a data x tp mesh: pages are shard-pinned,
    so hits only count on the shard holding them — and admission
    PREFERS that shard.  Outputs equal the single-device cache-off
    batcher's."""
    cfg, params, _, _ = mesh_setup
    kw = dict(rows=4, max_len=96, page_size=16, prefill_bucket=16)
    reqs = lambda: _shared_prefix_reqs(cfg, 6, sys_len=36, seed=61)
    plain = ContinuousBatcher(cfg, params, **kw)
    want = _tokens_in_order(plain, reqs())
    warm = ContinuousBatcher(cfg, params, mesh=_mesh(axes),
                             prefix_cache_pages=8, **kw)
    assert warm.prefix_cache_active
    got = _tokens_in_order(warm, reqs())
    for i, (g, w) in enumerate(zip(got, want)):
        _assert_tokens_match_modulo_ties(
            cfg, params, reqs()[i].prompt, g, w)
    st = warm.prefix_cache_stats()
    assert st["hits"] >= 4, st
    # Shard-affine admission: the system prompt's pages live on ONE
    # shard (each trie is per shard, and hits steer admission there).
    assert _tokens_in_order(warm, reqs()) == got
    st2 = warm.prefix_cache_stats()
    assert st2["hits"] >= st["hits"] + 5


def test_prefix_cache_warm_admission_never_overcommits(setup):
    """Regression (review): a warm plan's zero-ref cached pages were
    counted BOTH as reclaimable headroom and as the plan's page saving
    — double-counting that over-admitted and crashed the serve loop
    with 'page pool exhausted' under pool pressure.  A distinct
    pressure request racing a warm re-request must serve cleanly (or
    wait), never crash."""
    cfg, params = setup
    warm = ContinuousBatcher(cfg, params, rows=2, max_len=80,
                             page_size=16, prefill_bucket=16, n_pages=8,
                             prefix_cache_pages=8)
    cached_prompt = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=49).astype(np.int32)
    # Publish 3 pages (49 tokens -> 3 full chunks), leaving free=4.
    first = list(warm.run([Request(prompt=cached_prompt,
                                   max_new_tokens=4)]))
    assert len(first) == 1
    st = warm.prefix_cache_stats()
    assert st["cached_pages"] == 3 and st["retained_pages"] == 3
    # Pressure (distinct 60-token prompt, wt=5) + warm re-request
    # (wt=5, save=3): with the double-count both admit into a 4-free
    # pool and ensure() blows up mid-flight.
    pressure = Request(prompt=np.random.RandomState(6).randint(
        0, cfg.vocab_size, size=60).astype(np.int32), max_new_tokens=20)
    rewarm = Request(prompt=cached_prompt.copy(), max_new_tokens=20)
    done = list(warm.run([pressure, rewarm]))
    assert len(done) == 2
    cold = ContinuousBatcher(cfg, params, rows=2, max_len=80,
                             page_size=16, prefill_bucket=16)
    want = [c.tokens for _, c in
            sorted((c.rid, c) for c in cold.run(
                [Request(prompt=pressure.prompt.copy(),
                         max_new_tokens=20),
                 Request(prompt=cached_prompt.copy(),
                         max_new_tokens=20)]))]
    assert [c.tokens for _, c in sorted((c.rid, c) for c in done)] == want


def test_prefix_cache_cow_falls_back_on_tight_pool(setup):
    """Regression (review): a COW full hit needs one fresh page ON TOP
    of referencing every cached page, which on a tight pool can exceed
    headroom even though the same request fits cold — admission must
    retry a SHALLOWER plan (down to cold) instead of raising 'page
    pool exhausted' for a servable workload."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16,
              n_pages=5)
    prompt = np.random.RandomState(9).randint(
        0, cfg.vocab_size, size=48).astype(np.int32)   # exactly 3 pages
    mk = lambda: [Request(prompt=prompt.copy(), max_new_tokens=16)]
    cold = ContinuousBatcher(cfg, params, **kw)
    want = _tokens_in_order(cold, mk())
    assert _tokens_in_order(cold, mk()) == want     # pool serves it cold
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=8, **kw)
    assert _tokens_in_order(warm, mk()) == want     # publishes 3 pages
    # The full-hit COW plan (4 pages incl. the copy) cannot fit the
    # 5-page pool; the shallower 2-page plan must serve it instead.
    assert _tokens_in_order(warm, mk()) == want
    st = warm.prefix_cache_stats()
    assert st["cow_copies"] == 0 and st["hits"] == 1
    assert st["hit_pages"] == 2     # trimmed from the full 3-page match



def _wait_first_admission(b, deadline_s=120.0):
    """Block until the batcher has ADMITTED the first submission (rid
    assigned).  The class-aware admission order (PR 8) rank-orders
    everything pending at pull time — a preemption test must land its
    low-priority request BEFORE the outranking one is even submitted,
    or the batcher would simply admit them in rank order and never
    need to preempt."""
    import time as _time

    deadline = _time.monotonic() + deadline_s
    while b._next_rid == 0:
        assert _time.monotonic() < deadline, "first request never admitted"
        _time.sleep(0.005)


# -- priority preemption & suspend/resume (docs/SERVING.md "Priorities,
# preemption & migration") --------------------------------------------------


def _preempt_variant_kw(variant):
    """The equivalence-matrix configs the suspend/resume contract must
    hold across (greedy/sampled, int8 kv pool, chunked prefill, prefix
    cache, SPECULATIVE decoding incl. its int8-target composition —
    the bypass burn-down's preemption arm)."""
    import jax

    kw = dict(rows=1, max_len=64, page_size=16, prefill_bucket=16)
    if variant == "sampled":
        kw.update(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(7))
    elif variant == "int8":
        kw.update(quantized_cache=True)
    elif variant == "chunked":
        kw.update(prefill_chunk=16)
    elif variant == "pcache":
        kw.update(prefix_cache_pages=8)
    elif variant in ("spec", "spec_int8"):
        dcfg = transformer.TransformerConfig(
            vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_ff=32,
            max_seq_len=128, dtype=jnp.float32)
        kw.update(draft_cfg=dcfg,
                  draft_params=transformer.init_params(
                      dcfg, jax.random.PRNGKey(5)),
                  n_draft=3)
        if variant == "spec_int8":
            kw.update(quantized_cache=True)
    return kw


@pytest.mark.parametrize("variant",
                         ["greedy", "sampled", "int8", "chunked",
                          "pcache", "spec", "spec_int8"])
def test_preempt_resume_token_identical(setup, variant):
    """THE preemption/migration acceptance: with rows=1, a higher-
    priority arrival deterministically SUSPENDS the resident row (its
    KV exports, its pages free); preempt_all() then hands every
    in-flight request back as a Suspended artifact, which a SECOND
    batcher (the migration target) resumes — and every stream equals
    the uninterrupted same-rid reference exactly, across the matrix
    configs."""
    import threading
    import time as _time

    from tfmesos_tpu.serving import Prefilled, Suspended

    cfg, params = setup
    kw = _preempt_variant_kw(variant)
    rng = np.random.RandomState(31)
    pA, pB = (rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
              for n in (9, 7))
    # Reference: same admission order, same rids, equal priorities —
    # no preemption, rows=1 serves A to completion, then B.
    refb = ContinuousBatcher(cfg, params, **kw)
    refs = {c.rid: c.tokens for c in refb.run(
        [Request(prompt=pA.copy(), max_new_tokens=12),
         Request(prompt=pB.copy(), max_new_tokens=24)])}

    b1 = ContinuousBatcher(cfg, params, **kw)
    A = Request(prompt=pA.copy(), max_new_tokens=12, priority=0)
    B = Request(prompt=pB.copy(), max_new_tokens=24, priority=5)
    streams, susp = {}, []

    def drive():
        for c in b1.serve():
            if isinstance(c, Suspended):
                susp.append(c)
            else:
                streams[c.rid] = c.tokens

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    b1.submit(A)        # rid 0, admitted first
    _wait_first_admission(b1)   # A resident BEFORE B exists
    b1.submit(B)        # rid 1, outranks A -> suspends it mid-stream
    deadline = _time.monotonic() + 120.0
    while b1.preemptions < 1:
        assert _time.monotonic() < deadline, "preemption never happened"
        _time.sleep(0.005)
    # Drain-migration: everything still in flight (B mid-decode, A
    # parked) comes back as Suspended artifacts.
    b1.preempt_all()
    b1.close()
    t.join(timeout=300.0)
    assert not t.is_alive()
    assert b1.preemptions >= 1
    arts = {s.rid: s for s in susp}
    assert arts, "preempt_all returned nothing to migrate"
    assert all(s.artifact is not None for s in susp), susp
    # A was suspended mid-stream: its artifact carries emitted tokens.
    assert arts[0].artifact["step"] > 1
    assert arts[0].artifact["tokens"] == \
        refs[0][:arts[0].artifact["step"]]
    # The migration target: a fresh batcher importing the artifacts.
    b2 = ContinuousBatcher(cfg, params, **{**kw, "rows": 2})
    for c in b2.run([Prefilled(s.request, s.artifact)
                     for _, s in sorted(arts.items())]):
        streams[c.rid] = c.tokens
    assert streams == refs, f"{variant}: resumed streams diverged"


def test_preempt_strictness_and_parked_resume(setup):
    """Equal priorities never preempt (anti-thrash), and a preempted
    row RESUMES locally — token-identically — once the outranking work
    finishes."""
    import threading
    import time as _time

    cfg, params = setup
    kw = dict(rows=1, max_len=64, page_size=16, prefill_bucket=16)
    rng = np.random.RandomState(33)
    pA, pB = (rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
              for n in (8, 6))
    refb = ContinuousBatcher(cfg, params, **kw)
    refs = {c.rid: c.tokens for c in refb.run(
        [Request(prompt=pA.copy(), max_new_tokens=10),
         Request(prompt=pB.copy(), max_new_tokens=4)])}

    b = ContinuousBatcher(cfg, params, **kw)
    done = {}

    def drive():
        for c in b.serve():
            done[c.rid] = c

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    b.submit(Request(prompt=pA.copy(), max_new_tokens=10, priority=3))
    _wait_first_admission(b)    # pA resident BEFORE the outranker
    b.submit(Request(prompt=pB.copy(), max_new_tokens=4, priority=5))
    deadline = _time.monotonic() + 120.0
    while b.resumes < 1:
        assert _time.monotonic() < deadline, "parked row never resumed"
        _time.sleep(0.005)
    b.close()
    t.join(timeout=300.0)
    assert b.preemptions == 1 and b.resumes == 1
    assert {rid: c.tokens for rid, c in done.items()} == refs
    # Equal priorities: FIFO, no suspension.
    b3 = ContinuousBatcher(cfg, params, **kw)
    out = {c.rid: c.tokens for c in b3.run(
        [Request(prompt=pA.copy(), max_new_tokens=10, priority=5),
         Request(prompt=pB.copy(), max_new_tokens=4, priority=5)])}
    assert b3.preemptions == 0
    assert out == refs


def test_suspended_artifact_validation(setup):
    """A mid-stream artifact that does not match its request (or was
    tampered with) is rejected LOUDLY at import — never a silently
    wrong resumed stream."""
    import threading
    import time as _time

    from tfmesos_tpu.serving import Prefilled, Suspended

    cfg, params = setup
    kw = dict(rows=1, max_len=64, page_size=16, prefill_bucket=16)
    rng = np.random.RandomState(35)
    p, pB = (rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
             for n in (9, 6))
    b = ContinuousBatcher(cfg, params, **kw)
    req = Request(prompt=p, max_new_tokens=12, priority=0)
    susp = []

    def drive():
        for c in b.serve():
            if isinstance(c, Suspended):
                susp.append(c)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    b.submit(req)
    _wait_first_admission(b)    # req resident BEFORE the outranker
    # An outranking arrival suspends req deterministically mid-stream
    # (the same trigger test_preempt_resume_token_identical relies on).
    b.submit(Request(prompt=pB, max_new_tokens=24, priority=5))
    deadline = _time.monotonic() + 120.0
    while b.preemptions < 1:
        assert _time.monotonic() < deadline, "preemption never happened"
        _time.sleep(0.005)
    b.preempt_all()
    b.close()
    t.join(timeout=300.0)
    art = next(s.artifact for s in susp if s.request is req)
    assert art is not None and art["step"] > 1
    b2 = ContinuousBatcher(cfg, params, **kw)
    b2.validate(Prefilled(req, art))            # the real one imports
    bad = dict(art, tokens=list(art["tokens"][:-1]))
    with pytest.raises(ValueError):
        b2.validate(Prefilled(req, bad))        # tokens/step mismatch
    bad = dict(art, step=art["step"] + 1)
    with pytest.raises(ValueError):
        b2.validate(Prefilled(req, bad))        # pos/step mismatch
    with pytest.raises(ValueError):             # "finished" artifact
        b2.validate(Prefilled(
            Request(prompt=p, max_new_tokens=art["step"]), art))


# -- end-to-end deadlines & class-aware admission order ----------------------
# (docs/SERVING.md "Deadlines & failure containment")


def test_deadline_expired_arrival_shed_before_prefill(setup):
    """An arrival whose deadline passed while it waited is shed at the
    admission gate — an Expired in the stream, no prefill dispatched,
    and the live request behind it unaffected."""
    import time as _time

    from tfmesos_tpu.serving import Expired

    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=2)
    ps = _prompts(cfg, 2, seed=5)
    doomed = Request(prompt=ps[0], max_new_tokens=8, deadline_ms=1.0)
    live = Request(prompt=ps[1], max_new_tokens=4)
    _time.sleep(0.01)           # the 1ms budget is long gone
    out = list(b.run([doomed, live]))
    exp = [c for c in out if isinstance(c, Expired)]
    comps = [c for c in out if isinstance(c, Completion)]
    assert len(exp) == 1 and exp[0].request is doomed
    assert exp[0].rid == -1     # never admitted: no rid was burned
    assert len(comps) == 1 and comps[0].request is live
    assert comps[0].tokens == _offline(cfg, params, live)
    assert b.deadline_cancels == 1


@LAG
def test_deadline_cancels_resident_row_and_frees_slot(setup, lag):
    """THE in-batcher deadline acceptance, rows=1: a resident decoding
    row whose deadline passes is cancelled like a finished one — pages
    freed immediately, Expired yielded — and the next request admits
    into the freed slot and completes exactly (pipelined: the cancelled
    row's block in flight fails the rid-checked ticket, and the slot's
    next tenant enters the carry from host values).  The expiry is
    forced deterministically (the deadline attribute is host state the
    loop re-reads every tick), not timed."""
    import threading
    import time as _time

    from tfmesos_tpu.serving import Expired

    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=1, **lag)
    ps = _prompts(cfg, 2, seed=6)
    doomed = Request(prompt=ps[0], max_new_tokens=64,
                     deadline_ms=3_600_000.0)      # far future, for now
    live = Request(prompt=ps[1], max_new_tokens=6)
    out = []

    def drive():
        for c in b.serve():
            out.append(c)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    b.submit(doomed)
    deadline = _time.monotonic() + 120.0
    while b._next_rid == 0:     # admitted (rid assigned) ...
        assert _time.monotonic() < deadline, "never admitted"
        _time.sleep(0.005)
    doomed.deadline = 0.0       # ... then the client's budget "expires"
    b.submit(live)
    b.close()
    t.join(timeout=300.0)
    assert not t.is_alive()
    exp = [c for c in out if isinstance(c, Expired)]
    comps = [c for c in out if isinstance(c, Completion)]
    assert len(exp) == 1 and exp[0].rid == 0 \
        and exp[0].request is doomed
    assert b.deadline_cancels == 1
    # The freed slot served the live request to an exact completion.
    assert len(comps) == 1 and comps[0].request is live
    assert comps[0].tokens == _offline(cfg, params, live)
    # Stream order: the cancel surfaced before (or without) any tokens
    # of the live request — dead work did not outlive its deadline.
    assert out.index(exp[0]) < out.index(comps[0])


def test_deadline_validation(setup):
    with pytest.raises(ValueError):
        Request(prompt=np.asarray([1, 2], np.int32), max_new_tokens=2,
                deadline_ms=0.0)
    with pytest.raises(ValueError):
        Request(prompt=np.asarray([1, 2], np.int32), max_new_tokens=2,
                deadline_ms=-5.0)
    r = Request(prompt=np.asarray([1, 2], np.int32), max_new_tokens=2)
    assert r.deadline is None and not r.expired


def test_batcher_admission_orders_by_class_rank(setup):
    """Satellite (ROADMAP item 3 follow-up): pulled arrivals admit by
    priority rank — FIFO within a rank — matching the WFQ gateway's
    dispatch discipline instead of pure submission FIFO.  rid is
    assigned at admission, so the rid each request got IS the admission
    order."""
    cfg, params = setup
    b = ContinuousBatcher(cfg, params, rows=1)
    ps = _prompts(cfg, 4, seed=7)
    reqs = [Request(prompt=ps[0], max_new_tokens=2, priority=0),
            Request(prompt=ps[1], max_new_tokens=2, priority=5),
            Request(prompt=ps[2], max_new_tokens=2, priority=5),
            Request(prompt=ps[3], max_new_tokens=2, priority=0)]
    for r in reqs:
        b.submit(r)
    b.close()
    comps = [c for c in b.serve() if isinstance(c, Completion)]
    rid_of = {id(c.request): c.rid for c in comps}
    # Both rank-5 requests admit first (their own submission order
    # kept), then the rank-0 ones (theirs kept too).
    assert rid_of[id(reqs[1])] == 0
    assert rid_of[id(reqs[2])] == 1
    assert rid_of[id(reqs[0])] == 2
    assert rid_of[id(reqs[3])] == 3
    # Single-rank traffic stays exact FIFO (the degenerate case every
    # pre-priority test in this file keeps asserting implicitly).
    b2 = ContinuousBatcher(cfg, params, rows=1)
    for r in [Request(prompt=p, max_new_tokens=2) for p in ps]:
        b2.submit(r)
    b2.close()
    order = [c.rid for c in b2.serve()]
    assert order == [0, 1, 2, 3]


def test_batcher_trace_events_and_flight_recorder(setup):
    """Requests carrying a TraceContext get the batcher's per-request
    events (admit, prefill/decode phase spans); the flight recorder
    logs per-block decode timing in BOTH step modes (sync and
    pipelined) — and token streams are unchanged by tracing."""
    from tfmesos_tpu.fleet.tracing import TraceContext

    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    ps = _prompts(cfg, 3, seed=11)

    reqs, traces = [], []
    for p in ps:
        r = Request(prompt=p, max_new_tokens=4)
        tr = TraceContext(detailed=True)
        r.trace = tr
        reqs.append(r)
        traces.append(tr)
    batcher = ContinuousBatcher(cfg, params, **kw)
    done = {c.rid: c for c in batcher.run(reqs)}
    assert len(done) == len(reqs)
    for rid, (req, tr) in enumerate(zip(reqs, traces)):
        assert done[rid].tokens == _offline(cfg, params, req)
        spans = tr.export()
        names = [(s["component"], s["name"]) for s in spans]
        assert ("batcher", "admit") in names
        assert ("batcher", "prefill") in names
        assert ("batcher", "decode") in names
        dec = next(s for s in spans if s["name"] == "decode")
        assert dec["tokens"] == 4 and dec["dur"] >= 0.0
        adm = next(s for s in spans if s["name"] == "admit")
        assert adm["prompt_len"] == int(req.prompt.size)
    blocks = [e for e in batcher.flight.snapshot()
              if e["name"] == "decode.block"]
    assert blocks and all(e["mode"] == "sync" and e["dur"] >= 0.0
                          and e["k"] == 1 for e in blocks)

    # Pipelined loop: same stream, per-block entries tagged pipelined.
    piped = ContinuousBatcher(cfg, params, pipeline_depth=1, **kw)
    reqs2 = [Request(prompt=p, max_new_tokens=4) for p in ps]
    done2 = {c.rid: c for c in piped.run(reqs2)}
    assert [done2[r].tokens for r in sorted(done2)] \
        == [done[r].tokens for r in sorted(done)]
    pblocks = [e for e in piped.flight.snapshot()
               if e["name"] == "decode.block"]
    assert pblocks and all(e["mode"] == "pipelined" for e in pblocks)


# -- per-token incremental streaming (Request.on_tokens) ---------------------


@LAG
def test_streaming_callback_chunks_match_stream(setup, lag):
    """Request.on_tokens receives contiguous, correctly-offset chunks
    whose concatenation is a PREFIX of the completion (rows finishing
    inside a block keep their tail for the Completion), token streams
    byte-identical to non-streaming, and a raising callback costs its
    stream, never the request.  Pipelined, the chunks arrive one block
    late (at retire) and stay contiguous."""
    cfg, params = setup
    reqs = [Request(prompt=p, max_new_tokens=5 + (i % 6))
            for i, p in enumerate(_prompts(cfg, 6, seed=7))]
    got = {i: [] for i in range(len(reqs))}
    offs = {i: [] for i in range(len(reqs))}
    for i, r in enumerate(reqs):
        def cb(chunk, off, i=i):
            assert off == len(got[i]), \
                f"req {i}: chunk offset {off} != streamed {len(got[i])}"
            got[i].extend(chunk)
            offs[i].append(off)
        r.on_tokens = cb
    # One request's consumer is broken: its stream is disarmed, the
    # request still completes exactly.
    def boom(chunk, off):
        got[3].extend(chunk)
        raise RuntimeError("broken consumer")
    reqs[3].on_tokens = boom
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16, **lag)
    done = {c.rid: c for c in batcher.run(reqs)}
    assert len(done) == len(reqs)
    for rid, req in enumerate(reqs):
        ref = _offline(cfg, params, req)
        assert done[rid].tokens == ref, f"req {rid} diverged"
        streamed = got[rid]
        assert streamed == ref[:len(streamed)], \
            f"req {rid}: streamed {streamed} not a prefix of {ref}"
        if rid == 3:
            assert len(streamed) <= len(ref)    # disarmed after raise
        else:
            # At least the first token streamed ahead of completion.
            assert len(streamed) >= 1


def test_streaming_multi_step_and_chunked_prefill(setup):
    """Streaming composes with multi_step blocks (chunks arrive K at a
    time) and chunked prefill — streams still equal offline."""
    cfg, params = setup
    for kw in ({"multi_step": 3}, {"prefill_chunk": 8}):
        reqs = [Request(prompt=p, max_new_tokens=7)
                for p in _prompts(cfg, 3, seed=11)]
        got = {id(r): [] for r in reqs}
        for r in reqs:
            r.on_tokens = lambda c, off, r=r: got[id(r)].extend(c)
        b = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16,
                              **(dict(prefill_bucket=16, **kw)
                                 if "prefill_chunk" not in kw else kw))
        done = {id(c.request): c for c in b.run(reqs)}
        for r in reqs:
            ref = _offline(cfg, params, r)
            assert done[id(r)].tokens == ref
            assert got[id(r)] == ref[:len(got[id(r)])]
            assert len(got[id(r)]) >= 1


# -- the KV tier: prefix spill/promote + session park/resume -----------------
# (store-level and fleet-routing tests live in tests/test_kvtier.py;
# these cover the batcher halves: eviction-seam spill, admission
# promotion, and the session park/resume equivalence contract.)


def _tier(**kw):
    from tfmesos_tpu.fleet.kvtier import KVTierStore
    kw.setdefault("ram_bytes", 8 << 20)
    kw.setdefault("token", "t")
    return KVTierStore(**kw)


def test_session_park_resume_token_identical(setup):
    """A multi-turn conversation resumed from the tier must be
    TOKEN-IDENTICAL to a cold full-history prefill — the uninterrupted
    reference — turn after turn, with the pool accounting balanced
    after the drain."""
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    tier = _tier()
    warm = ContinuousBatcher(cfg, params, kv_tier=tier, **kw)
    cold = ContinuousBatcher(cfg, params, **kw)
    assert warm.kv_tier_bypass_reason is None
    rng = np.random.RandomState(3)
    hist = list(rng.randint(0, cfg.vocab_size, size=24))
    (c,) = list(warm.run([Request(np.asarray(hist, np.int32), 6,
                                  session_id="conv")]))
    for turn in range(3):
        hist += list(c.tokens) + list(rng.randint(0, cfg.vocab_size,
                                                  size=5 + turn))
        prompt = np.asarray(hist, np.int32)
        (ref,) = list(cold.run([Request(prompt, 6)]))
        (c,) = list(warm.run([Request(prompt, 6, session_id="conv")]))
        assert c.tokens == ref.tokens, f"turn {turn} diverged"
    st = tier.stats()
    assert st["park"] == 4 and st["resume"] == 3, st
    assert warm.alloc.rows == {}
    assert len(warm.alloc.free) == warm.n_pages - 1     # sink only


def test_session_miss_paths_fall_back_cold(setup):
    """Every session-miss shape — unknown id, a prompt that does not
    extend the parked history, and a version-fenced store — re-prefills
    COLD and stays exact (deterministic re-prefill, never stale KV)."""
    from tfmesos_tpu.fleet.kvtier import KVTierStore
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    cold = ContinuousBatcher(cfg, params, **kw)
    rng = np.random.RandomState(5)
    p1 = rng.randint(0, cfg.vocab_size, size=20).astype(np.int32)
    other = rng.randint(0, cfg.vocab_size, size=30).astype(np.int32)

    tier = _tier()
    warm = ContinuousBatcher(cfg, params, kv_tier=tier, **kw)
    (c1,) = list(warm.run([Request(p1, 4, session_id="conv")]))
    # A prompt that DIVERGES from the parked history: cold, correct.
    (got,) = list(warm.run([Request(other, 4, session_id="conv")]))
    (ref,) = list(cold.run([Request(other, 4)]))
    assert got.tokens == ref.tokens
    st = tier.stats()
    assert st["resume"] == 0 and st["hits"] >= 1    # hit, then rejected

    # Version fence: the rollout shape — park under v1, resume as v2
    # (same RAM dict would not survive a real relaunch; use the disk
    # tier like the deployment does).
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        t1 = KVTierStore(ram_bytes=0, disk_dir=d, disk_bytes=1 << 20,
                         token="t", stamp={"weights_version": "v1"})
        w1 = ContinuousBatcher(cfg, params, kv_tier=t1, **kw)
        (c1,) = list(w1.run([Request(p1, 4, session_id="conv")]))
        p2 = np.concatenate([p1, np.asarray(c1.tokens, np.int32),
                             rng.randint(0, cfg.vocab_size,
                                         size=4).astype(np.int32)])
        t2 = KVTierStore(ram_bytes=0, disk_dir=d, disk_bytes=1 << 20,
                         token="t", stamp={"weights_version": "v2"})
        w2 = ContinuousBatcher(cfg, params, kv_tier=t2, **kw)
        (got,) = list(w2.run([Request(p2, 4, session_id="conv")]))
        (ref,) = list(cold.run([Request(p2, 4)]))
        assert got.tokens == ref.tokens
        assert t2.stats()["version_miss"] == 1
        assert t2.stats()["resume"] == 0


def test_kv_tier_spill_promote_exact_with_reclaim_accounting(setup):
    """The eviction-callback seam under allocation pressure: evicted
    prefix pages SPILL to the tier and PROMOTE back on the next
    matching admission — outputs exact, and the reclaim accounting
    still prevents the PR 2 over-admission crash (headroom must keep
    treating zero-ref pages as reclaimable with the spill hook
    installed)."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    reqs = lambda: [Request(prompt=np.random.RandomState(50 + i).randint(
                        0, cfg.vocab_size, size=33 + (i % 3)).astype(
                            np.int32), max_new_tokens=4)
                    for i in range(10)]
    cold = ContinuousBatcher(cfg, params, **kw)
    tier = _tier()
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=64,
                             kv_tier=tier, **kw)
    want = _tokens_in_order(cold, reqs())
    assert _tokens_in_order(warm, reqs()) == want
    st = warm.prefix_cache_stats()
    ts = tier.stats()
    assert st["evicted"] > 0, "pressure must trigger LRU eviction"
    assert ts["spills"] == st["evicted"], "every eviction must spill"
    # Second pass: spilled chains promote back into the trie and the
    # stream stays exact — the spill seam never corrupted a page.
    assert _tokens_in_order(warm, reqs()) == want
    ts = tier.stats()
    st = warm.prefix_cache_stats()
    assert ts["promotions"] > 0 and st["promoted"] == ts["promotions"]
    # The over-admission regression: pool accounting balanced, peak
    # within the physical pool, every row released.
    assert len(warm.alloc.free) + st["cached_pages"] + 1 == warm.n_pages
    assert warm.peak_pages_used <= warm.n_pages
    assert warm.alloc.rows == {}


def test_kv_tier_park_rejection_explicit(setup):
    """A tier too small for the artifact REJECTS the park (counted)
    and the completion is untouched — and the next turn simply
    re-prefills cold."""
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    tier = _tier(ram_bytes=64)              # nothing real fits
    warm = ContinuousBatcher(cfg, params, kv_tier=tier, **kw)
    cold = ContinuousBatcher(cfg, params, **kw)
    rng = np.random.RandomState(9)
    p1 = rng.randint(0, cfg.vocab_size, size=30).astype(np.int32)
    (c1,) = list(warm.run([Request(p1, 5, session_id="conv")]))
    (ref1,) = list(cold.run([Request(p1, 5)]))
    assert c1.tokens == ref1.tokens
    st = tier.stats()
    assert st["park_rejected"] == 1 and st["park"] == 0
    p2 = np.concatenate([p1, np.asarray(c1.tokens, np.int32)])
    (c2,) = list(warm.run([Request(p2, 4, session_id="conv")]))
    (ref2,) = list(cold.run([Request(p2, 4)]))
    assert c2.tokens == ref2.tokens         # cold resume, still exact


def test_kv_tier_bypasses_are_explicit(setup, draft_setup):
    """Modes the single-shard export/import scatter cannot serve
    BYPASS the tier discoverably (the bypass-registry discipline) and
    serving stays correct.  Speculative decoding now COMPOSES (spec
    parks carry the paired draft payload) — only quantized pools
    (either side) still bypass."""
    cfg, params = setup
    dcfg, dparams = draft_setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    spec = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                             draft_params=dparams, kv_tier=_tier(), **kw)
    assert spec.kv_tier_bypass_reason is None
    dq = ContinuousBatcher(cfg, params, draft_cfg=dcfg,
                           draft_params=dparams,
                           draft_quantized_cache=True, kv_tier=_tier(),
                           **kw)
    assert dq.kv_tier_bypass_reason == "quantized kv cache"
    q = ContinuousBatcher(cfg, params, quantized_cache=True,
                          kv_tier=_tier(), **kw)
    assert q.kv_tier_bypass_reason == "quantized kv cache"
    # Bypassed batchers still serve session-labeled requests (cold).
    p = np.random.RandomState(2).randint(0, cfg.vocab_size,
                                         size=9).astype(np.int32)
    (c,) = list(q.run([Request(p, 3, session_id="s")]))
    assert len(c.tokens) == 3


def _spec_kw(max_len=128, n_draft=3):
    """A draft whose max_seq_len covers max_len + n_draft + 1 (the
    verify overshoot) — session tests run at max_len 128, past the
    module draft fixture's 128 cap."""
    dcfg = transformer.TransformerConfig(
        vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=max_len + n_draft + 8, dtype=jnp.float32)
    return dict(draft_cfg=dcfg,
                draft_params=transformer.init_params(
                    dcfg, jax.random.PRNGKey(5)),
                n_draft=n_draft)


def test_spec_session_park_resume_token_identical(setup):
    """A SPECULATIVE multi-turn conversation resumed from the tier —
    parked draft payload installed, draft tail written in lockstep —
    must be token-identical to the cold full-history speculative
    prefill, turn after turn, with BOTH pools balanced after the
    drain."""
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16,
              **_spec_kw())
    tier = _tier()
    warm = ContinuousBatcher(cfg, params, kv_tier=tier, **kw)
    cold = ContinuousBatcher(cfg, params, **kw)
    assert warm.kv_tier_bypass_reason is None
    rng = np.random.RandomState(3)
    hist = list(rng.randint(0, cfg.vocab_size, size=24))
    (c,) = list(warm.run([Request(np.asarray(hist, np.int32), 6,
                                  session_id="conv")]))
    for turn in range(3):
        hist += list(c.tokens) + list(rng.randint(0, cfg.vocab_size,
                                                  size=5 + turn))
        prompt = np.asarray(hist, np.int32)
        (ref,) = list(cold.run([Request(prompt, 6)]))
        (c,) = list(warm.run([Request(prompt, 6, session_id="conv")]))
        assert c.tokens == ref.tokens, f"turn {turn} diverged (spec)"
    st = tier.stats()
    assert st["park"] == 4 and st["resume"] == 3, st
    assert warm.alloc.rows == {} and warm.d_side.alloc.rows == {}


def test_session_park_resume_lagged_modes(setup):
    """PR 13 follow-up regression: the lagged decode loop
    (pipeline_depth=1, alone and with multi_step blocks) used to
    silently MISS parking — its host view overshoots at finish — so
    every next turn re-prefilled cold.  The export now clamps to the
    committed boundary (_export_row(final=True)), so parking works in
    EVERY mode and resumed turns stay token-identical to the cold
    full-history prefill."""
    cfg, params = setup
    base = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    cold = ContinuousBatcher(cfg, params, **base)
    for mode_kw in ({"pipeline_depth": 1},
                    {"pipeline_depth": 1, "multi_step": 2}):
        tier = _tier()
        warm = ContinuousBatcher(cfg, params, kv_tier=tier, **base,
                                 **mode_kw)
        rng = np.random.RandomState(5)
        hist = list(rng.randint(0, cfg.vocab_size, size=20))
        (c,) = list(warm.run([Request(np.asarray(hist, np.int32), 6,
                                      session_id="s")]))
        for turn in range(2):
            hist += list(c.tokens) + list(rng.randint(
                0, cfg.vocab_size, size=4))
            prompt = np.asarray(hist, np.int32)
            (ref,) = list(cold.run([Request(prompt, 6)]))
            (c,) = list(warm.run([Request(prompt, 6, session_id="s")]))
            assert c.tokens == ref.tokens, (mode_kw, turn)
        st = tier.stats()
        # The regression: parks/resumes were silently 0 before.
        assert st["park"] == 3 and st["resume"] == 2, (mode_kw, st)


def _fabric_trio(replication=2):
    """Three replicas on an in-process fabric mesh (test_kvfabric's
    zero-socket harness): real KVFabric + KVTierStore per node, real
    registry placement, stubbed transport."""
    from test_kvfabric import FabricNet
    net = FabricNet()
    fabs = {n: net.add(n, replication=replication, ram=8 << 20)
            for n in ("a:1", "b:1", "c:1")}
    return net, fabs


def test_fabric_host_loss_resume_token_identical(setup):
    """The seeded cross-host e2e: a conversation parked on replica A
    (replication=2 → one rendezvous-picked peer copy), host A DIES,
    and the next turn lands on the survivor WITHOUT the copy — the
    batcher's session lookup misses locally, the fabric locates the
    surviving copy through the registry and fetches it from the peer,
    and the resumed turn is TOKEN-IDENTICAL to the cold full-history
    reference.  Greedy AND sampled: with equal batcher rngs the
    (rid, step) sample folds continue the exact stream the cold
    reference draws, so host loss is invisible at the token level."""
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    for samp in ({}, dict(temperature=0.8, top_k=20)):
        net, fabs = _fabric_trio()
        skw = lambda seed: (dict(samp, rng=jax.random.PRNGKey(seed))
                            if samp else {})
        parker = ContinuousBatcher(cfg, params, kv_tier=fabs["a:1"],
                                   **kw, **skw(7))
        assert parker.kv_tier_bypass_reason is None
        rng = np.random.RandomState(11)
        hist = list(rng.randint(0, cfg.vocab_size, size=24))
        (c,) = list(parker.run([Request(np.asarray(hist, np.int32), 6,
                                        session_id="conv")]))
        assert fabs["a:1"].store.stats()["park_replicated"] == 1, samp
        net.kill("a:1")     # survivors' beats advertise the placement
        holder = ("b:1" if fabs["b:1"].store.get("session", "conv")
                  else "c:1")
        resumer_addr = "c:1" if holder == "b:1" else "b:1"
        hist += list(c.tokens) + list(rng.randint(0, cfg.vocab_size,
                                                  size=5))
        prompt = np.asarray(hist, np.int32)
        # The resumer and the cold reference are both fresh batchers
        # with the same rng: same rid (0), same sample folds.
        cold = ContinuousBatcher(cfg, params, **kw, **skw(9))
        (ref,) = list(cold.run([Request(prompt, 6)]))
        resumer = ContinuousBatcher(cfg, params,
                                    kv_tier=fabs[resumer_addr],
                                    **kw, **skw(9))
        (c2,) = list(resumer.run([Request(prompt, 6,
                                          session_id="conv")]))
        assert c2.tokens == ref.tokens, \
            f"host-loss resume diverged (sampled={bool(samp)})"
        st = fabs[resumer_addr].store.stats()
        assert st["fabric_fetch_hit"] == 1, (samp, st)
        assert st["resume"] == 1, (samp, st)


def test_fabric_gang_host_loss_resume_round_trips_whole(setup):
    """Gang-sharded host loss: each rank's parked session artifact
    folds into ONE gang artifact (pack_gang_shards) that replicates
    through the fabric; after the parker dies, a survivor fetches the
    copy (shape-checked whole — fabric_reject_torn covers the torn
    case in tests/test_kvfabric.py), splits it back into rank shards,
    and EVERY rank's resumed turn is token-identical to the cold
    full-history reference."""
    from tfmesos_tpu.fleet.kvtier import (KVTierStore, pack_gang_shards,
                                          unpack_gang_shards)
    cfg, params = setup
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    ranks = 2
    rng = np.random.RandomState(13)
    hist = list(rng.randint(0, cfg.vocab_size, size=20))
    prompt1 = np.asarray(hist, np.int32)
    # Turn 1 on the gang: each rank parks locally; the leader folds
    # the per-rank artifacts into one gang artifact and parks THAT
    # through the fabric (replication=2 → a peer copy).
    shards = []
    toks1 = None
    for r in range(ranks):
        store = KVTierStore(ram_bytes=8 << 20, token="tok")
        b = ContinuousBatcher(cfg, params, kv_tier=store, **kw)
        (c,) = list(b.run([Request(prompt1, 6, session_id="g")]))
        toks1 = c.tokens    # same math every rank in this tiny config
        meta, body = store.resume("g")
        shards.append((dict(meta, rank=r), body))
    gmeta, gbody = pack_gang_shards(shards)
    net, fabs = _fabric_trio()
    fabs["a:1"].park("g", gmeta, gbody)
    assert fabs["a:1"].store.stats()["park_replicated"] == 1
    net.kill("a:1")
    holder = "b:1" if fabs["b:1"].store.get("session", "g") else "c:1"
    resumer_addr = "c:1" if holder == "b:1" else "b:1"
    got = fabs[resumer_addr].resume("g")
    assert got is not None, "gang artifact did not survive host loss"
    assert fabs[resumer_addr].store.stats()["fabric_fetch_hit"] == 1
    back = unpack_gang_shards(dict(got[0]), got[1])
    assert [m["rank"] for m, _ in back] == list(range(ranks))
    # Turn 2: every rank resumes from its own shard of the fetched
    # copy and must match the cold full-history reference.
    hist += list(toks1) + list(rng.randint(0, cfg.vocab_size, size=4))
    prompt2 = np.asarray(hist, np.int32)
    cold = ContinuousBatcher(cfg, params, **kw)
    (ref,) = list(cold.run([Request(prompt2, 6)]))
    for r, (smeta, sbody) in enumerate(back):
        store = KVTierStore(ram_bytes=8 << 20, token="tok")
        store.park("g", dict(smeta), sbody)
        b = ContinuousBatcher(cfg, params, kv_tier=store, **kw)
        (c2,) = list(b.run([Request(prompt2, 6, session_id="g")]))
        assert c2.tokens == ref.tokens, f"rank {r} diverged"
        assert store.stats()["resume"] == 1


def test_spec_tier_spill_promote_twin_pages(setup):
    """Spec + prefix cache + KV tier under allocation pressure: an
    evicted trie node spills its TARGET page and draft TWIN as one
    entry; the next matching admission promotes both back into free
    pool pages — streams exact, both pools' accounting balanced."""
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16,
              **_spec_kw(max_len=64))
    reqs = lambda: [Request(prompt=np.random.RandomState(50 + i).randint(
                        0, cfg.vocab_size, size=33 + (i % 3)).astype(
                            np.int32), max_new_tokens=4)
                    for i in range(10)]
    cold = ContinuousBatcher(cfg, params, **kw)
    tier = _tier()
    warm = ContinuousBatcher(cfg, params, prefix_cache_pages=64,
                             kv_tier=tier, **kw)
    want = _tokens_in_order(cold, reqs())
    assert _tokens_in_order(warm, reqs()) == want
    st = warm.prefix_cache_stats()
    ts = tier.stats()
    assert st["evicted"] > 0 and ts["spills"] == st["evicted"]
    assert _tokens_in_order(warm, reqs()) == want
    ts = tier.stats()
    st = warm.prefix_cache_stats()
    assert ts["promotions"] > 0 and st["promoted"] == ts["promotions"]
    assert len(warm.alloc.free) + st["cached_pages"] + 1 == warm.n_pages
    assert len(warm.d_side.alloc.free) + st["cached_pages"] + 1 \
        == warm.n_draft_pages
    assert warm.alloc.rows == {} and warm.d_side.alloc.rows == {}


def test_spec_tier_entries_fenced_from_draftless_peers(setup):
    """A spec batcher's twin-page tier entries are geometry-fenced: a
    draft-less batcher sharing the same store reads them as misses
    (never installs half an entry), and vice versa — serving stays
    exact on both."""
    cfg, params = setup
    tier = _tier()
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    reqs = lambda: [Request(prompt=np.random.RandomState(70 + i).randint(
                        0, cfg.vocab_size, size=33).astype(np.int32),
                        max_new_tokens=3)
                    for i in range(8)]
    cold = ContinuousBatcher(cfg, params, **kw)
    want = _tokens_in_order(cold, reqs())
    spec = ContinuousBatcher(cfg, params, prefix_cache_pages=64,
                             kv_tier=tier, **dict(kw, **_spec_kw(64)))
    assert _tokens_in_order(spec, reqs()) == want
    assert tier.stats()["spills"] > 0
    plain = ContinuousBatcher(cfg, params, prefix_cache_pages=64,
                              kv_tier=tier, **kw)
    assert _tokens_in_order(plain, reqs()) == want
    # The plain batcher promoted NOTHING from the spec-cut entries.
    assert plain.prefix_cache_stats()["promoted"] == 0


# -- the bypass-registry audit (the burn-down, enforced) ---------------------


def test_bypass_registry_audit(setup):
    """Enumerate EVERY ``*_bypass_reason`` value reachable from a
    ContinuousBatcher config through the one pure helper __init__
    itself uses, and fail on any value outside the documented
    allowlist — the burn-down is enforceable, not aspirational.  Also
    pins the burn-down itself: 'speculative decoding' is no longer
    reachable in the prefix_cache or kv_tier registries, and the
    'overlap' / 'multi_step' registries are gone with the loops that
    needed them (PR 30: one lag policy, pipeline_depth)."""
    import itertools

    from tfmesos_tpu.serving import (BYPASS_ALLOWLIST,
                                     compute_bypass_reasons)

    assert set(BYPASS_ALLOWLIST) == {
        "prefix_cache", "kv_tier", "pipeline", "suspend", "fused_prefill",
        "speculative", "kv_export"}
    for gone in ("overlap", "multi_step"):
        with pytest.raises(TypeError, match=gone):
            compute_bypass_reasons(**{gone: 1})
    reachable = {k: set() for k in BYPASS_ALLOWLIST}
    eva_reach = {k: set() for k in BYPASS_ALLOWLIST}
    rec_reach = {k: set() for k in BYPASS_ALLOWLIST}
    for eva, rec, spec_on, shards, q, dq, pd in itertools.product(
            (False, True), (False, True), (False, True), (1, 2, 4),
            (False, True), (False, True), (0, 1)):
        if eva and rec:
            continue    # a typed stack runs full attention (config check)
        reasons = compute_bypass_reasons(
            speculative=spec_on, n_shards=shards, quantized_cache=q,
            draft_quantized_cache=dq, pipeline_depth=pd, eva=eva,
            recurrent=rec)
        assert set(reasons) == set(BYPASS_ALLOWLIST)
        for reg, val in reasons.items():
            if val is not None:
                (eva_reach if eva else rec_reach if rec
                 else reachable)[reg].add(val)
    for reg in BYPASS_ALLOWLIST:
        extra = (reachable[reg] | eva_reach[reg] | rec_reach[reg]) \
            - set(BYPASS_ALLOWLIST[reg])
        assert not extra, (
            f"bypass registry {reg!r} reaches undocumented reasons "
            f"{sorted(extra)} — add a burn-down plan or remove the "
            f"bypass (BYPASS_ALLOWLIST is the contract)")
    # EVA attention (pages of summaries and one window, PR 28): every
    # surface that shares, moves or snapshots pages by position is closed
    # with ONE reason; nothing of it is reachable without EVA, whose
    # registries read as before below.  The lagged carry composes (a
    # window is closed where its last position is dispatched, PR 41), so
    # the lag registry gains nothing: a draft model is refused under EVA
    # at construction, and the helper says what it would have said.
    for reg in ("prefix_cache", "kv_tier", "suspend", "speculative",
                "kv_export"):
        assert "eva summary pages" in eva_reach[reg], reg
        assert "eva summary pages" not in reachable[reg], reg
    assert eva_reach["pipeline"] == {"speculative decoding"}
    assert BYPASS_ALLOWLIST["pipeline"] == ("speculative decoding",)
    # A recurrent row state (a typed stack's mamba layers, PR 32): a row is
    # its pages AND a state no page holds, so the same five surfaces close
    # with ONE reason of their own; the pipelined carry composes (the state
    # store rides the donated pool), so the lag registry gains nothing.
    for reg in ("prefix_cache", "kv_tier", "suspend", "speculative",
                "kv_export"):
        assert "recurrent row state" in rec_reach[reg], reg
        assert "recurrent row state" not in reachable[reg], reg
        assert "recurrent row state" not in eva_reach[reg], reg
    assert rec_reach["pipeline"] == {"speculative decoding"}
    assert compute_bypass_reasons(
        recurrent=True, pipeline_depth=1)["pipeline"] is None
    assert compute_bypass_reasons(
        recurrent=True, pipeline_depth=1)["suspend"] == "recurrent row state"
    assert compute_bypass_reasons(
        eva=True, pipeline_depth=1)["pipeline"] is None
    assert compute_bypass_reasons(
        eva=True, pipeline_depth=1)["suspend"] == "eva summary pages"
    # The lag left to the batcher (pipeline_depth=None) reads THIS table:
    # the carry where the model's own cache has closed suspend already.
    assert compute_bypass_reasons()["suspend"] is None
    assert reachable["pipeline"] == {"speculative decoding"}
    assert not reachable["speculative"] and not reachable["kv_export"]
    # The burn-down, pinned: spec composes with the prefix cache and
    # the KV tier now.
    assert "speculative decoding" not in reachable["prefix_cache"]
    assert "speculative decoding" not in reachable["kv_tier"]
    # Suspend-under-lag is an enumerable mode gate, reachable with
    # exactly its documented reasons; a speculative batcher asked for
    # the pipelined carry serves synchronously, so it stays suspendable.
    assert reachable["suspend"] == {"mesh data sharding",
                                    "lagged decode carry"}
    assert compute_bypass_reasons(speculative=True,
                                  pipeline_depth=1)["suspend"] is None
    # Fused prefill+decode ticks: every documented reason reachable,
    # nothing else; int8 / multi_step / prefix-cache configs compose
    # (reason None), and the lagged + sharded + spec modes bypass.
    assert reachable["fused_prefill"] == {"mesh data sharding",
                                          "speculative decoding",
                                          "lagged decode carry"}
    assert compute_bypass_reasons(
        quantized_cache=True)["fused_prefill"] is None
    # And __init__ really uses the helper (spot-check: a live batcher's
    # attributes equal the helper's output for its config).
    cfg, params = setup
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16,
              prefix_cache_pages=8)
    b = ContinuousBatcher(cfg, params, quantized_cache=True,
                          kv_tier=_tier(), pipeline_depth=1, **kw)
    want = compute_bypass_reasons(quantized_cache=True,
                                  pipeline_depth=1)
    assert b.prefix_cache_bypass_reason == want["prefix_cache"]
    assert b.kv_tier_bypass_reason == want["kv_tier"]
    assert b.pipeline_bypass_reason == want["pipeline"]
    assert b.suspend_bypass_reason == want["suspend"]
    # The suspend gate IS the preemptible property.
    assert b.preemptible == (b.suspend_bypass_reason is None)
    # Fused spot-check: a live fused batcher records the helper's
    # fused_prefill verdict (None here — the mode is active).
    bf = ContinuousBatcher(cfg, params, fused_prefill=True,
                           prefill_chunk=16,
                           **{k: v for k, v in kw.items()
                              if k != "prefix_cache_pages"})
    assert bf.fused_prefill_bypass_reason is None
    bs = ContinuousBatcher(cfg, params, fused_prefill=True,
                           prefill_chunk=16, pipeline_depth=1,
                           rows=2, max_len=64, page_size=16,
                           prefill_bucket=16)
    want = compute_bypass_reasons(pipeline_depth=1)
    assert bs.fused_prefill_bypass_reason == want["fused_prefill"] \
        == "lagged decode carry"


# -- stall-free fused scheduling (PR 20) -------------------------------------


@pytest.mark.parametrize("variant",
                         ["greedy", "sampled", "int8", "pcache",
                          "multistep", "budget", "spec"])
def test_fused_tick_token_identical(setup, variant):
    """THE fused-tick acceptance: fused_prefill=True (one dispatch per
    tick covering the decode block PLUS budgeted prefill chunk slots)
    produces IDENTICAL token streams to the phase-split chunked
    batcher across the mode matrix — greedy/sampled, int8 kv pool,
    prefix cache, multi_step, a clipped token budget, and the
    speculative config (which takes the enforced BYPASS route, reason
    recorded, never a constructor rejection)."""
    cfg, params = setup
    rng = np.random.RandomState(41)
    # Staggered lengths: the long prompts are still chunking while the
    # short ones decode, so fused ticks genuinely mix both lanes.
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 21, 13, 34, 16, 5)]
    kw = dict(rows=4, max_len=96, page_size=16, prefill_bucket=16,
              prefill_chunk=8)
    fkw = {}
    if variant == "sampled":
        kw.update(temperature=0.8, top_k=20, rng=jax.random.PRNGKey(3))
    elif variant == "int8":
        kw.update(quantized_cache=True)
    elif variant == "pcache":
        prefix = rng.randint(0, cfg.vocab_size, size=13).astype(np.int32)
        prompts = [_behind(prefix, p) for p in prompts]
        kw.update(prefix_cache_pages=16)
    elif variant == "multistep":
        kw.update(multi_step=4)
    elif variant == "budget":
        # Clip to ONE chunk slot per tick: rows*K + one chunk.
        fkw.update(tokens_per_tick=4 + 8)
    elif variant == "spec":
        kw.update(**_spec_kw(max_len=96))
    mk = lambda: [Request(prompt=p.copy(), max_new_tokens=2 + (i % 5))
                  for i, p in enumerate(prompts)]
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {c.rid: c.tokens for c in plain.run(mk())}
    fb = ContinuousBatcher(cfg, params, fused_prefill=True, **kw, **fkw)
    got = {c.rid: c.tokens for c in fb.run(mk())}
    assert got == want, f"{variant}: fused stream diverged"
    assert fb._inflight is None
    assert fb.t_side.alloc.rows == {}
    if variant == "spec":
        # The bypass lane: recorded reason, zero fused dispatches,
        # streams still identical (the phase-split path served them).
        assert fb.fused_prefill_bypass_reason == "speculative decoding"
        assert fb.fused_ticks == 0
    else:
        assert fb.fused_prefill_bypass_reason is None
        # The analytic win was exercised: fused dispatches really
        # coalesced prefill chunk tokens alongside live decode rows.
        assert fb.fused_ticks > 0
        assert fb.fused_chunk_tokens > 0
        assert fb.fused_decode_tokens > 0
        assert fb.fused_tokens_per_tick() \
            >= kw["rows"] * kw.get("multi_step", 1)


def test_fused_prefill_requires_chunked():
    """fused_prefill without prefill_chunk is a config error (chunked
    prefill IS the lane being fused), not a silent no-op."""
    with pytest.raises(ValueError, match="fused_prefill"):
        ContinuousBatcher(None, None, fused_prefill=True)


def test_offline_lane_batch_row_preempted_within_tick(setup):
    """Offline-lane acceptance at the batcher: a ``batch``-class row
    (rank below every interactive class, forwarded as a negative
    priority) SUSPENDS within one tick of an interactive arrival via
    the existing preemption machinery, the interactive stream
    completes first, and the batch stream resumes token-identically."""
    import threading
    import time as _time

    cfg, params = setup
    kw = dict(rows=1, max_len=64, page_size=16, prefill_bucket=16)
    rng = np.random.RandomState(47)
    pBatch, pInter = (rng.randint(0, cfg.vocab_size,
                                  size=n).astype(np.int32)
                      for n in (9, 6))
    refb = ContinuousBatcher(cfg, params, **kw)
    refs = {c.rid: c.tokens for c in refb.run(
        [Request(prompt=pBatch.copy(), max_new_tokens=24),
         Request(prompt=pInter.copy(), max_new_tokens=4)])}

    b = ContinuousBatcher(cfg, params, **kw)
    order, done = [], {}

    def drive():
        for c in b.serve():
            order.append(c.rid)
            done[c.rid] = c.tokens

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    # Batch lane = rank floor(min interactive rank) - 1 → priority -1.
    b.submit(Request(prompt=pBatch.copy(), max_new_tokens=24,
                     priority=-1))
    _wait_first_admission(b)    # batch row resident and decoding
    b.submit(Request(prompt=pInter.copy(), max_new_tokens=4,
                     priority=0))
    deadline = _time.monotonic() + 120.0
    while b.resumes < 1:
        assert _time.monotonic() < deadline, "batch row never yielded"
        _time.sleep(0.005)
    b.close()
    t.join(timeout=300.0)
    assert not t.is_alive()
    # One suspend, one resume — and the interactive request finished
    # BEFORE the (earlier-admitted, longer) batch row.
    assert b.preemptions == 1 and b.resumes == 1
    assert order == [1, 0]
    assert done == refs


# -- adapter hot-swap / warm-pool adoption (PR 15) ---------------------------


def _fold(params, delta):
    """Offline reference of a LoRA-style fold: params with each
    path's delta added (dict copies along the paths, jax leaves —
    the same arithmetic _apply_weight_update performs)."""
    def clone(node):
        return ({k: clone(v) for k, v in node.items()}
                if isinstance(node, dict) else node)

    new = clone(params)
    for path, arr in delta.items():
        keys = path.split("/")
        node = new
        for k in keys[:-1]:
            node = node[k]
        leaf = node[keys[-1]]
        node[keys[-1]] = leaf + jnp.asarray(arr).astype(leaf.dtype)
    return new


def _first_2d_path(params):
    """Some real param path to perturb (+ its leaf), as 'a/b/...'."""
    flat = []

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, prefix + (k,))
        else:
            flat.append((prefix, node))

    walk(params, ())
    flat.sort(key=lambda kv: "/".join(kv[0]))
    path, leaf = flat[0]
    return "/".join(path), leaf


def test_swap_adapter_fence_streams_token_identical(setup):
    """The adapter hot-swap contract end to end at the batcher: a
    delta queued while rows are RESIDENT applies only after they
    finish (in-flight streams complete on the OLD weights), new
    admissions wait behind the fence and serve the NEW weights — every
    stream token-identical to an offline run under exactly one delta
    version."""
    cfg, params = setup
    path, leaf = _first_2d_path(params)
    rng = np.random.RandomState(5)
    delta = {path: (0.5 * rng.standard_normal(np.asarray(leaf).shape)
                    ).astype(np.asarray(leaf).dtype)}
    folded = _fold(params, delta)
    prompts = _prompts(cfg, 3, seed=11)
    req_a = Request(prompt=prompts[0], max_new_tokens=10)   # long
    req_b = Request(prompt=prompts[1], max_new_tokens=2)    # short
    req_c = Request(prompt=prompts[2], max_new_tokens=6)    # post-swap
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    gen = batcher.serve()
    batcher.submit(req_a)
    batcher.submit(req_b)
    first = next(gen)
    assert first.request is req_b       # the short one lands first
    # req_a is still mid-decode: queue the swap NOW.  It must not
    # apply (nor fire its callback) until req_a's stream finishes.
    applied = []
    batcher.swap_adapter(delta, "lora1",
                         on_applied=lambda: applied.append(
                             batcher.adapter_version))
    batcher.submit(req_c)               # waits behind the fence
    second = next(gen)
    assert second.request is req_a
    third = next(gen)
    assert third.request is req_c
    batcher.close()
    assert list(gen) == []
    # In-flight finished on the OLD delta; post-swap serves the NEW.
    assert first.tokens == _offline(cfg, params, req_b)
    assert second.tokens == _offline(cfg, params, req_a)
    assert third.tokens == _offline(cfg, folded, req_c)
    assert third.tokens != _offline(cfg, params, req_c)
    assert applied == ["lora1"]
    assert batcher.adapter_version == "lora1"
    assert batcher.weight_swaps == 1


def test_swap_adapter_validation_and_direct_apply(setup):
    cfg, params = setup
    path, leaf = _first_2d_path(params)
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    shape = np.asarray(leaf).shape
    with pytest.raises(ValueError):
        batcher.swap_adapter({}, "v")               # empty delta
    with pytest.raises(ValueError):
        batcher.swap_adapter({path: np.zeros(shape)}, "")   # no label
    with pytest.raises(ValueError):
        batcher.swap_adapter({"nope/nope": np.zeros((2, 2))}, "v")
    with pytest.raises(ValueError):                 # shape mismatch
        batcher.swap_adapter({path: np.zeros((1, 1, 7))}, "v")
    interior = path.rsplit("/", 1)[0] if "/" in path else None
    if interior:                        # interior node, not a leaf
        with pytest.raises(ValueError):
            batcher.swap_adapter({interior: np.zeros((2, 2))}, "v")
    with pytest.raises(ValueError):     # empty path
        batcher.swap_adapter({"": np.zeros((2, 2))}, "v")
    # Validation failures left the weights untouched.
    assert batcher.adapter_version == "" and batcher.weight_swaps == 0
    # No serve loop: the fold applies synchronously (the prefill-role
    # / direct-use path) and the next run serves the folded weights.
    delta = {path: np.full(shape, 0.03,
                           dtype=np.asarray(leaf).dtype)}
    batcher.swap_adapter(delta, "d1")
    assert batcher.adapter_version == "d1"
    req = Request(prompt=_prompts(cfg, 1, seed=3)[0], max_new_tokens=5)
    done = list(batcher.run([req]))
    assert done[0].tokens == _offline(cfg, _fold(params, delta), req)


def test_set_weights_installs_other_model(setup):
    """The warm-pool adoption path: set_weights replaces the FULL tree
    (same shapes — nothing recompiles) and subsequent streams equal
    the other model's offline run; the adapter label resets to base."""
    cfg, params = setup
    other = transformer.init_params(cfg, jax.random.PRNGKey(42))
    path, leaf = _first_2d_path(params)
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    batcher.swap_adapter(
        {path: np.full(np.asarray(leaf).shape, 0.02,
                       dtype=np.asarray(leaf).dtype)}, "d1")
    assert batcher.adapter_version == "d1"
    batcher.set_weights(other, version="v0@other")
    assert batcher.adapter_version == ""    # full install = base state
    req = Request(prompt=_prompts(cfg, 1, seed=7)[0], max_new_tokens=6)
    done = list(batcher.run([req]))
    assert done[0].tokens == _offline(cfg, other, req)
    assert batcher.weight_swaps == 2


def test_swap_adapter_flushes_prefix_cache(setup):
    """KV computed under the old delta is WRONG under the new one: the
    fold flushes the prefix trie, so a warm repeat after the swap
    re-prefills and equals the folded offline run (stale pages would
    silently corrupt it)."""
    cfg, params = setup
    path, leaf = _first_2d_path(params)
    shape = np.asarray(leaf).shape
    prompt = _prompts(cfg, 1, seed=13)[0]
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16,
                                prefix_cache_pages=8)
    req1 = Request(prompt=prompt, max_new_tokens=4)
    list(batcher.run([req1]))           # warms the trie
    stats = batcher.prefix_cache_stats()
    assert stats and stats["cached_pages"] > 0
    delta = {path: np.full(shape, 0.04,
                           dtype=np.asarray(leaf).dtype)}
    batcher.swap_adapter(delta, "d2")
    stats = batcher.prefix_cache_stats()
    assert stats["cached_pages"] == 0   # flushed, not spilled
    req2 = Request(prompt=prompt, max_new_tokens=4)
    done = list(batcher.run([req2]))
    assert done[0].tokens == _offline(cfg, _fold(params, delta), req2)


def test_rid_seed_gives_disjoint_rid_streams(setup):
    """Fleet regression (PR 4 caveat): two replicas seeded from different
    node ids must mint disjoint rids, so traces and KV-export keys from
    different gang members never collide at the gateway."""
    from tfmesos_tpu.fleet.replica import rid_seed_for_node
    cfg, params = setup
    seeds = [rid_seed_for_node(n) for n in ("replica:0", "replica:1")]
    assert seeds[0] != seeds[1]
    rids = []
    for seed in seeds:
        b = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16, prefill_bucket=16,
                              rid_seed=seed)
        reqs = [Request(prompt=p, max_new_tokens=2)
                for p in _prompts(cfg, 3, seed=5)]
        done = list(b.run(reqs))
        assert sorted(c.rid for c in done) == [seed, seed + 1, seed + 2]
        rids.extend(c.rid for c in done)
    assert len(set(rids)) == len(rids)      # globally disjoint
    with pytest.raises(ValueError):
        ContinuousBatcher(cfg, params, rows=2, max_len=64,
                          page_size=16, prefill_bucket=16,
                          rid_seed=2 ** 30)
    with pytest.raises(ValueError):
        ContinuousBatcher(cfg, params, rows=2, max_len=64,
                          page_size=16, prefill_bucket=16, rid_seed=-1)


# -- the tick recorder (docs/SERVING.md "Observability") ----------------------

TICK_FIELDS = {"name", "tick", "batcher", "t", "wall_ms", "kind", "mode",
               "k", "rows", "dur", "admitted", "prefill_tokens", "phases",
               "idle_ms", "compiles", "compile_s", "gc_ms", "gc_n",
               "gc_gen2", "thread_cpu_ms", "ctx_invol", "cpu_ms", "majflt",
               "cpu_span_ms", "ready"}
REQUEST_FIELDS = {"name", "rid", "batcher", "status", "t_submit", "t_admit",
                  "t_first", "t_done", "prompt_tokens", "prefill_tokens",
                  "out_tokens", "admit_tick", "first_tick", "done_tick"}
TICK_PHASES = {"batcher.pull", "batcher.admit", "batcher.prefill_sync",
               "batcher.prep", "batcher.dispatch", "batcher.readback",
               "batcher.retire", "batcher.emit"}
#: step mode -> (constructor arguments, the modes its blocks may carry,
#: the mode at least one block must carry)
TICK_MODES = {
    "sync": ({}, {"sync"}, "sync"),
    "pipelined": ({"pipeline_depth": 1}, {"pipelined"}, "pipelined"),
    "pipelined_multistep": ({"pipeline_depth": 1, "multi_step": 2},
                            {"pipelined"}, "pipelined"),
    "chunked": ({"prefill_chunk": 8}, {"sync"}, "sync"),
    "fused": ({"prefill_chunk": 8, "fused_prefill": True},
              {"sync", "fused"}, "fused"),
    "spec": ("spec", {"spec"}, "spec"),
    "import": ({}, {"sync"}, "sync"),
    "session": ("session", {"sync"}, "sync"),
}


def _check_ticks(batcher, traces, modes, must):
    """What every step mode's tick records have in common."""
    recs = batcher.flight.snapshot()
    assert recs
    for r in recs:
        assert set(r) == TICK_FIELDS
        assert set(r["phases"]) <= TICK_PHASES
        assert all(ms >= 0.0 for ms in r["phases"].values())
        assert sum(r["phases"].values()) <= r["wall_ms"] + 1e-6
        assert 0.0 <= r["idle_ms"] <= r["phases"].get("batcher.pull",
                                                      0.0) + 1e-9
        assert r["kind"] in ("decode", "prefill", "mixed", "fused", "idle")
        assert (r["name"] == "decode.block") == (
            r["kind"] in ("decode", "mixed", "fused"))
        # what held the tick: differences of counters that only grow
        assert all(r[k] >= 0 for k in ("gc_ms", "gc_n", "gc_gen2",
                                       "thread_cpu_ms", "ctx_invol"))
        assert r["gc_gen2"] <= r["gc_n"] and (r["gc_ms"] > 0) == (
            r["gc_n"] > 0)
        # the process-wide ones where the tick closed with a read of them,
        # over a span that ends with it: every tick of a quarter second
        process = [r[k] for k in ("cpu_ms", "majflt", "cpu_span_ms")]
        if r["wall_ms"] >= 250.0 or process != [None] * 3:
            assert r["cpu_ms"] >= 0 and r["majflt"] >= 0
            assert r["cpu_span_ms"] >= max(250.0, r["wall_ms"] - 1e-6)
        # the pipelined loop read a lagged block back, ready or not; a
        # synchronous loop does not ask (it has only just dispatched)
        assert r["ready"] in ((0, 1) if r["mode"] == "pipelined"
                              and "batcher.readback" in r["phases"]
                              else (None,))
    ticks = [r["tick"] for r in recs]
    assert all(b > a for a, b in zip(ticks, ticks[1:]))
    blocks = [r for r in recs if r["name"] == "decode.block"]
    assert blocks and {r["mode"] for r in blocks} <= modes
    assert any(r["mode"] == must for r in blocks)
    assert all(r["rows"] >= 1 and r["k"] >= 1 and r["dur"] >= 0.0
               for r in blocks)
    by_tick = {r["tick"]: r for r in recs}
    for tr in traces:       # the admit event names the tick that caused it
        # (an import's trace begins with the exporter's admit, made
        # outside any serve loop: tick -1)
        ev = [s for s in tr.export()
              if s["name"] in ("admit", "import", "session_resume")][-1]
        rec = by_tick[ev["tick"]]
        assert rec["admitted"] >= 1 and rec["phases"]["batcher.admit"] > 0
    return recs


@pytest.mark.parametrize("mode", sorted(TICK_MODES))
def test_tick_records_every_step_mode(setup, draft_setup, mode):
    """Every step mode records through the one helper: one record per
    pass of the serve loop, the same fields, flat phases inside the
    tick's wall time, and the request's admit event naming its tick."""
    from tfmesos_tpu.fleet.tracing import TraceContext
    from tfmesos_tpu.serving import Prefilled

    cfg, params = setup
    extra, modes, must = TICK_MODES[mode]
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    if extra == "spec":
        dcfg, dparams = draft_setup
        kw.update(draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    elif extra == "session":
        kw.update(kv_tier=_tier(), max_len=128)
    else:
        kw.update(extra)
    batcher = ContinuousBatcher(cfg, params, **kw)
    reqs, traces = [], []
    for p in _prompts(cfg, 5, seed=29):
        r = Request(prompt=p, max_new_tokens=6)
        r.trace = TraceContext(detailed=True)
        reqs.append(r)
        traces.append(r.trace)
    if mode == "import":
        pre = ContinuousBatcher(cfg, params, **kw)
        items = [Prefilled(r, pre.export_kv(r)) for r in reqs]
        assert pre.flight.snapshot() == []      # no serve loop, no tick
        done = list(batcher.run(items))
    elif mode == "session":
        hist = list(reqs[0].prompt)
        (c,) = batcher.run([Request(np.asarray(hist, np.int32), 6,
                                    session_id="conv")])
        turn = Request(np.asarray(hist + list(c.tokens) + [5, 9, 3],
                                  np.int32), 6, session_id="conv")
        turn.trace = TraceContext(detailed=True)
        traces = [turn.trace]
        done = list(batcher.run([turn]))
        assert any(s["name"] == "session_resume"
                   for s in turn.trace.export())
    else:
        done = list(batcher.run(reqs))
    assert done
    recs = _check_ticks(batcher, traces, modes, must)
    assert {r["k"] for r in recs if r["name"] == "decode.block"} == {
        batcher.n_draft + 1 if extra == "spec" else batcher.multi_step}
    assert sum(r["admitted"] for r in recs) >= len(traces)
    if mode in ("import", "session"):
        assert any(r["admitted"] for r in recs)
    else:
        assert sum(r["prefill_tokens"] for r in recs) >= sum(
            int(r.prompt.size) for r in reqs)
    # another batcher of the process leaves this one's share alone
    other = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                              page_size=16, prefill_bucket=16)
    list(other.run([Request(prompt=reqs[0].prompt, max_new_tokens=2)]))
    assert batcher.flight.snapshot() == recs
    assert {r["batcher"] for r in other.flight.snapshot()}.isdisjoint(
        r["batcher"] for r in recs)


def test_tick_ring_outlives_the_batcher_and_idles(setup):
    """The ring is the process's: a reader with no handle to the batcher
    takes it by name after the batcher is gone.  An online loop with
    nothing to do sleeps in an idle pull, which is not host time."""
    import threading
    import time as _time

    from tfmesos_tpu import serving
    from tfmesos_tpu.fleet.tracing import flight

    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    done = []
    th = threading.Thread(target=lambda: done.extend(batcher.serve()))
    th.start()
    _time.sleep(0.3)                    # idle: blocked in pull()
    batcher.submit(Request(prompt=_prompts(cfg, 1)[0], max_new_tokens=3))
    batcher.close()
    th.join(120.0)
    assert not th.is_alive() and len(done) == 1
    bid = batcher.flight.value
    del batcher
    ring = flight(serving.TICK_COMPONENT)
    assert ring.capacity >= 4096
    recs = [r for r in ring.snapshot() if r["batcher"] == bid]
    idle = [r for r in recs if r["kind"] == "idle" and r["idle_ms"] > 200.0]
    assert idle and idle[0]["wall_ms"] >= idle[0]["idle_ms"]
    assert any(r["name"] == "decode.block" for r in recs)


def test_tick_counts_a_compile_forced_inside_it(setup):
    """A jit cache miss inside a tick shows in that tick's ``compiles``
    (here forced from a token callback, which runs in batcher.emit); the
    same requests again, every shape warm, compile nothing."""
    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    prompts = _prompts(cfg, 2, seed=17)
    list(batcher.run([Request(prompt=p, max_new_tokens=4)
                      for p in prompts]))
    warm_until = batcher.flight.snapshot()[-1]["tick"]

    list(batcher.run([Request(prompt=p, max_new_tokens=4)
                      for p in prompts]))
    again = [r for r in batcher.flight.snapshot()
             if r["tick"] > warm_until]
    assert again and sum(r["compiles"] for r in again) == 0
    warm_until = again[-1]["tick"]

    fired = []

    def miss(toks, off):
        if not fired:       # a program no one has compiled yet
            fired.append(jax.jit(lambda x: x * 3 + len(prompts))(
                jnp.ones((7,))).block_until_ready())

    req = Request(prompt=prompts[0], max_new_tokens=4)
    req.on_tokens = miss
    list(batcher.run([req]))
    forced = [r for r in batcher.flight.snapshot()
              if r["tick"] > warm_until]
    assert fired and sum(r["compiles"] for r in forced) >= 1
    hit = next(r for r in forced if r["compiles"])
    assert hit["compile_s"] > 0.0 and "batcher.emit" in hit["phases"]


def test_profile_has_flat_batcher_phases_and_named_programs(setup, tmp_path):
    """Under the benchmark's profiler options a batcher served from a
    worker thread leaves ``batcher.*`` spans with a ``tick`` stat on a
    host line, none nested in another, and the host's own dispatch spans
    name the program (``PjitFunction(decode_block)``), none ``fn``."""
    import glob
    import threading

    from jax.profiler import ProfileData

    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    mk = lambda: [Request(prompt=p, max_new_tokens=5)
                  for p in _prompts(cfg, 3, seed=41)]
    list(batcher.run(mk()))                     # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    done = []
    th = threading.Thread(target=lambda: done.extend(batcher.run(mk())))
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        th.start()
        th.join(120.0)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive() and len(done) == 3
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)) for e in ln.events] for ln in host.lines]
    (line,) = [evs for evs in lines
               if any(n.startswith("batcher.") for n, *_ in evs)]
    spans = sorted((s, e, n, st) for n, s, e, st in line
                   if n.startswith("batcher."))
    assert {n for _, _, n, _ in spans} >= {
        "batcher.pull", "batcher.admit", "batcher.prefill_sync",
        "batcher.prep", "batcher.dispatch", "batcher.readback",
        "batcher.retire", "batcher.emit"}
    assert all("tick" in st for *_, st in spans)
    for (_, e0, n0, _), (s1, _, n1, _) in zip(spans, spans[1:]):
        assert s1 >= e0, f"{n1} starts inside {n0}"
    ticks = {r["tick"] for r in batcher.flight.snapshot()}
    assert {int(st["tick"]) for *_, st in spans} <= ticks
    programs = {n for n, *_ in line if n.startswith("PjitFunction(")}
    assert {"PjitFunction(decode_block)", "PjitFunction(prefill)"} <= programs
    assert "PjitFunction(fn)" not in programs


# -- the request ring and the stall rule (docs/SERVING.md "Observability") ----


def _check_requests(batcher, recs):
    """What every request record has in common: the fields, stamps in
    order on the one clock, and edges made by ticks the tick ring has."""
    ticks = {r["tick"]: r for r in batcher.flight.snapshot()}
    for r in recs:
        assert set(r) == REQUEST_FIELDS and r["name"] == "request"
        assert r["status"] in ("completed", "expired", "shed", "suspended",
                               "abandoned")
        stamps = [r[k] for k in ("t_submit", "t_admit", "t_first", "t_done")
                  if r[k] is not None]
        assert stamps == sorted(stamps) and len(stamps) >= 2
        assert (r["t_admit"] is None) == (r["admit_tick"] is None)
        assert (r["t_first"] is None) == (r["first_tick"] is None)
        assert r["t_first"] is None or r["t_admit"] is not None
        for edge, t in (("admit_tick", r["t_admit"]),
                        ("first_tick", r["t_first"]),
                        ("done_tick", r["t_done"])):
            if r[edge] is not None:
                tick = ticks[r[edge]]   # it exists, and the edge lies in it
                assert tick["t"] <= t <= tick["t"] + tick["wall_ms"] / 1e3 \
                    + 1e-6
        assert r["prompt_tokens"] >= 1 and r["prefill_tokens"] >= 0
        assert r["out_tokens"] >= (1 if r["status"] == "completed" else 0)


@pytest.mark.parametrize("mode", sorted(TICK_MODES))
def test_request_ring_has_one_record_for_every_exit(setup, draft_setup, mode):
    """In every step mode, through the one helper: a request that
    finishes, expires in a row, is shed in the queue, is given back or is
    left behind leaves exactly one record with its stamps in order;
    ``Completion.queue_s`` and the trace's ``batcher.queue`` span are the
    record's; ``run(iterable)`` stamps the submission at the pull."""
    import collections
    import time as _time

    from tfmesos_tpu.fleet.tracing import TraceContext
    from tfmesos_tpu.serving import Expired, Prefilled, Suspended

    cfg, params = setup
    extra = TICK_MODES[mode][0]
    kw = dict(rows=2, max_len=64, page_size=16, prefill_bucket=16)
    if extra == "spec":
        dcfg, dparams = draft_setup
        kw.update(draft_cfg=dcfg, draft_params=dparams, n_draft=3)
    elif extra == "session":
        kw.update(kv_tier=_tier(), max_len=128)
    else:
        kw.update(extra)
    batcher = ContinuousBatcher(cfg, params, **kw)
    pre = ContinuousBatcher(cfg, params, **kw) if mode == "import" else None
    prompts = _prompts(cfg, 12, seed=31)

    def wrap(req):
        return Prefilled(req, pre.export_kv(req)) if pre else req

    # -- run(iterable): completions, stamped at the pull -------------------
    reqs, pulled = [], {}
    for p in prompts[:4]:
        r = Request(prompt=p, max_new_tokens=5)
        r.trace = TraceContext(detailed=True)
        reqs.append(r)
    if mode == "session":       # the second turn resumes the first
        (c,) = batcher.run([Request(prompts[0], 5, session_id="conv")])
        turn = Request(np.asarray(list(prompts[0]) + list(c.tokens)
                                  + [5, 9, 3], np.int32), 5,
                       session_id="conv")
        turn.trace = TraceContext(detailed=True)
        reqs = [turn] + reqs[1:]

    def source():
        for r in reqs:
            pulled[id(r)] = _time.perf_counter()
            yield wrap(r)

    before = len(batcher.requests.snapshot())
    done = list(batcher.run(source()))
    recs = batcher.requests.snapshot()[before:]
    assert len(done) == len(recs) == len(reqs)
    assert {r["status"] for r in recs} == {"completed"}
    by_rid = {r["rid"]: r for r in recs}
    assert len(by_rid) == len(recs)
    for c in done:
        rec = by_rid[c.rid]
        assert rec["t_submit"] >= pulled[id(c.request)]
        assert c.queue_s == rec["t_admit"] - rec["t_submit"] >= 0.0
        assert c.ttft_s == rec["t_first"] - rec["t_admit"]
        assert rec["out_tokens"] == len(c.tokens)
        assert rec["prompt_tokens"] == c.request.prompt.size
        spans = [s for s in c.request.trace.export()
                 if s["name"] in ("queue", "prefill", "decode")]
        assert [s["name"] for s in spans] == ["queue", "prefill", "decode"]
        assert spans[0]["component"] == "batcher"
        assert spans[0]["dur"] == pytest.approx(c.queue_s * 1e3, abs=1e-3)
        assert spans[0]["t0"] + spans[0]["dur"] == pytest.approx(
            spans[1]["t0"], abs=2e-3)
    # the padded width dispatched: nothing for an import, the new turn's
    # tail for a session's resume, the whole prompt otherwise
    bucket = (pre or batcher).prefill_bucket
    width = lambda n: -(-n // bucket) * bucket
    for c in done:
        rec, n = by_rid[c.rid], int(c.request.prompt.size)
        if mode == "import":
            assert rec["prefill_tokens"] == 0
        elif c.request.session_id:
            assert 0 < rec["prefill_tokens"] < width(n)
        else:
            assert rec["prefill_tokens"] >= width(n)
    if pre is not None:         # an export is its batcher's to record
        exported = pre.requests.snapshot()
        assert len(exported) == len(reqs)
        assert {(r["status"], r["admit_tick"], r["done_tick"])
                for r in exported} == {("suspended", -1, -1)}
        assert all(r["t_first"] >= r["t_admit"] == r["t_submit"]
                   and r["prefill_tokens"] >= width(r["prompt_tokens"])
                   for r in exported)

    # -- serve(): shed, completed, expired, suspended ----------------------
    before = len(batcher.requests.snapshot())
    shed = Request(prompts[4], 4, deadline_ms=0.001)
    short = Request(prompts[5], 2)
    doomed = Request(prompts[6], 40, deadline_ms=3.6e6)
    rest = [Request(p, 40) for p in prompts[7:10]]
    for r in [shed, short, doomed] + rest:
        item = wrap(r)
        if pre:
            batcher.submit(item.request, prefilled=item.artifact)
        else:
            batcher.submit(r)
    t_submitted = _time.perf_counter()
    got = collections.Counter()
    it = batcher.serve()
    for item in it:
        got[type(item).__name__, item.request is shed] += 1
        if isinstance(item, Completion):
            assert item.request is short
            doomed.deadline = 0.0           # passed, while it holds a row
        elif isinstance(item, Expired) and item.request is doomed:
            batcher.preempt_all()           # two resident, one queued
        elif isinstance(item, Suspended):
            batcher.close()
    assert got == {("Expired", True): 1, ("Completion", False): 1,
                   ("Expired", False): 1, ("Suspended", False): 3}
    recs = batcher.requests.snapshot()[before:]
    assert collections.Counter(r["status"] for r in recs) == {
        "shed": 1, "completed": 1, "expired": 1, "suspended": 3}
    for r in recs:
        assert r["t_submit"] <= t_submitted     # SubmissionQueue.submit's
        if r["status"] == "shed":
            assert r["t_admit"] is None and r["t_first"] is None
        if r["status"] in ("completed", "expired"):
            # (a chunked prefill may expire before its first token)
            assert r["t_admit"] is not None
            assert r["t_first"] is not None or r["status"] == "expired"
    queued = [r for r in recs if r["status"] == "suspended"
              and r["t_admit"] is None]
    assert len(queued) == 1 and queued[0]["out_tokens"] == 0

    # -- a consumer that stops early leaves the rest behind ----------------
    before = len(batcher.requests.snapshot())
    it = batcher.run(wrap(Request(p, 30)) for p in prompts[8:12])
    it.close()                              # before its first pass: nothing
    assert batcher.requests.snapshot()[before:] == []
    it = batcher.run([wrap(Request(prompts[5], 2))]
                     + [wrap(Request(p, 30)) for p in prompts[8:12]])
    first = next(it)
    it.close()
    recs = batcher.requests.snapshot()[before:]
    assert recs[0]["status"] == "completed" and recs[0]["rid"] == first.rid
    # the row beside it, and what the loop had pulled and not admitted
    assert 1 <= len(recs[1:]) <= 2
    assert {r["status"] for r in recs[1:]} == {"abandoned"}
    assert any(r["t_admit"] is not None for r in recs[1:])
    _check_requests(batcher, batcher.requests.snapshot())
    # another batcher of the process leaves this one's share alone
    assert {r["batcher"] for r in batcher.requests.snapshot()} == {
        batcher.flight.value}


def test_tick_counts_a_collection_forced_inside_it(setup):
    """A collector pause that ends inside a tick shows in that tick's
    ``gc_ms`` (here a full collection forced from a token callback)."""
    import gc

    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    fired = []

    def collect(toks, off):
        if not fired:
            fired.append(gc.collect())

    req = Request(prompt=_prompts(cfg, 1, seed=3)[0], max_new_tokens=4)
    req.on_tokens = collect
    list(batcher.run([req]))
    recs = batcher.flight.snapshot()
    hit = [r for r in recs if r["gc_gen2"]]
    assert fired and len(hit) >= 1
    assert hit[0]["gc_ms"] > 0.0 and hit[0]["gc_n"] >= hit[0]["gc_gen2"]
    assert hit[0]["gc_ms"] <= hit[0]["phases"]["batcher.emit"] + 1e-6 \
        or hit[0]["gc_n"] > 1
    assert "batcher.emit" in hit[0]["phases"]


def test_a_held_tick_makes_one_stall_record_and_one_line(setup, caplog):
    """A tick held for 0.4 s (a token callback that sleeps) is a stall:
    the whole tick goes into the stall ring with what it was judged by
    and ONE warning names its phases and counters; a second stall within
    the second is recorded and not logged; a run that does not stall logs
    nothing, and a long admission is no stall."""
    import logging
    import time as _time

    from tfmesos_tpu import serving
    from tfmesos_tpu.fleet.tracing import flight

    cfg, params = setup
    batcher = ContinuousBatcher(cfg, params, rows=2, max_len=64,
                                page_size=16, prefill_bucket=16)
    prompts = _prompts(cfg, 2, seed=23)
    mk = lambda: [Request(prompt=p, max_new_tokens=12) for p in prompts]
    caplog.set_level(logging.WARNING, logger="tfmesos_tpu.serving")
    list(batcher.run(mk()))         # cold: ticks that compile are no stalls
    list(batcher.run(mk()))         # warm: nothing to say
    assert batcher.stalls.snapshot() == []
    assert not [r for r in caplog.records if "stalled" in r.getMessage()]

    naps = []

    def nap(toks, off):
        if off in (3, 6):           # two ticks, well inside one second
            naps.append(off)
            _time.sleep(0.4)

    reqs = mk()
    reqs[0].on_tokens = nap
    list(batcher.run(reqs))
    assert naps == [3, 6]
    stalls = batcher.stalls.snapshot()
    assert len(stalls) == 2
    ticks = {r["tick"]: r for r in batcher.flight.snapshot()}
    for st in stalls:
        assert set(st) == TICK_FIELDS | {"held_ms", "median_ms"}
        assert {k: v for k, v in st.items()
                if k not in ("held_ms", "median_ms")} == ticks[st["tick"]]
        assert st["phases"]["batcher.emit"] >= 400.0
        assert st["held_ms"] >= 400.0 > serving.STALL_MIN_MS
        assert 0.0 < st["median_ms"] * serving.STALL_FACTOR < st["held_ms"]
        # the serve thread slept: neither it nor the collector ran; a
        # tick this long closes with a read of the process's counters
        assert st["thread_cpu_ms"] < 200.0 and st["gc_ms"] < 200.0
        assert st["cpu_ms"] >= 0.0 and st["majflt"] >= 0
        assert st["wall_ms"] <= st["cpu_span_ms"] < st["wall_ms"] + 300.0
    lines = [r.getMessage() for r in caplog.records
             if "stalled" in r.getMessage()]
    assert len(lines) == 1
    for word in (f"tick {stalls[0]['tick']} ", "kind ", "rows ", "wall_ms ",
                 "median decode tick", "'emit': ", "gc_ms ",
                 "thread_cpu_ms ", "ctx_invol ", " cpu_ms ", "majflt ",
                 "(the process, over ", "ready "):
        assert word in lines[0], (word, lines[0])
    # the ring is the process's, found by name with no handle to the batcher
    assert [r for r in flight(serving.STALL_COMPONENT).snapshot()
            if r["batcher"] == batcher.flight.value] == stalls

    # honest work is not a stall: the same 0.4 s inside an admission
    real = batcher._worst_pages

    def slow_worst_pages(req):
        _time.sleep(0.4)
        return real(req)

    batcher._worst_pages = slow_worst_pages
    _time.sleep(1.1)                # a line would be allowed again
    list(batcher.run(mk()[:1]))
    slow = [r for r in batcher.flight.snapshot()
            if r["phases"].get("batcher.admit", 0.0) >= 400.0]
    assert slow and batcher.stalls.snapshot() == stalls
    assert len([r for r in caplog.records
                if "stalled" in r.getMessage()]) == 1
