"""EVA chunked attention (TransformerConfig.attention == "eva") through
``ContinuousBatcher``'s paged pool, against the plain reference
(``benchmark/models/evabyte_reference.py``: float32, one mask over
[T, T + T / chunk], no cache): prefill then decoding must give the
reference's full forward pass in LOGITS, wherever a prompt or a decode ends
relative to a chunk and a window; the pool holds ``E(T)`` entries per row,
and what EVA's pages cannot do yet is refused with a registered reason.
The lagged carry, which an EVA stack takes of itself, is held in
``tests/test_eva_carry.py``.

Small size, seeded random weights, float32, CPU: hidden 64, 4 heads of 16,
chunks of 4, windows of 32 (8 summaries a window = one page of 8), 3 layers,
contexts of up to 5 windows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import evabyte, evabyte_reference as ref
from tfmesos_tpu.models import transformer as T
from tfmesos_tpu.ops import attention as A
from tfmesos_tpu.serving import (BYPASS_ALLOWLIST, ContinuousBatcher,
                                 Prefilled, Request)

W, C, PS = 32, 4, 8
MODEL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "num_hidden_layers": 3, "vocab_size": 97, "chunk_size": C,
         "window_size": W, "rms_norm_eps": 1e-5, "rope_theta": 1e5,
         "num_pred_heads": 8, "attention_class": "eva",
         "norm_add_unit_offset": True, "fp32_skip_add": True,
         "fp32_logits": True, "torch_dtype": "float32"}
#: logits are near N(0, 1) (unit-scale activations, a head at 1/sqrt(d)), so
#: their scale is ~1..4; float32 arithmetic in another order (a paged
#: online softmax against one dense one, summaries pooled page by page)
#: leaves differences of ~1e-6.  1e-4 of the scale stands two orders above
#: that and three below the ~1e-1 that a wrong summary or mask moves them.
TOL = 1e-4


def make_cfg():
    return evabyte.program_config(MODEL, 256)


def make_params(cfg):
    p = T.init_params(cfg, jax.random.PRNGKey(7))
    # gains away from the identity, so that a forgotten unit offset shows
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(8), 3)
    p["layers"]["attn_norm"] = 0.2 * jax.random.normal(
        k1, p["layers"]["attn_norm"].shape)
    p["layers"]["mlp_norm"] = 0.2 * jax.random.normal(
        k2, p["layers"]["mlp_norm"].shape)
    p["norm_f"] = 0.2 * jax.random.normal(k3, p["norm_f"].shape)
    return p


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_params(cfg)


def batcher(cfg, params, **kw):
    kw = {"rows": 2, "max_len": 256, "page_size": PS, "prefill_bucket": 8,
          **kw}
    return ContinuousBatcher(cfg, params, **kw)


def run_logged(b, requests):
    """Serve ``requests``; returns ({rid: tokens}, {rid: [logits of step
    0, 1, ...]}): the logits every sampled token was the argmax of, taken
    where the batcher samples (``_sample`` is looked up when a program is
    traced, so wrapping the instance's catches prefill and decode)."""
    seen = {}
    inner = b._sample

    def keep(last, rids, steps):
        # step 0 is a prefill's sample: every window's chunk samples, and
        # the last one (the prompt's end) stands.  An idle row of a decode
        # block samples too, as (rid 0, step 0): by then rid 0 has decoded
        # (steps are taken highest first), and it is left out.
        order = np.argsort(-np.asarray(steps))
        for i in order:
            d = seen.setdefault(int(rids[i]), {})
            s = int(steps[i])
            if s not in d or (s == 0 and len(d) == 1):
                d[s] = np.asarray(last[i])

    def sample(last, rids, steps):
        jax.debug.callback(keep, last, rids, steps)
        return inner(last, rids, steps)

    b._sample = sample
    done = {c.rid: (c.request, list(c.tokens)) for c in b.run(requests)}
    jax.effects_barrier()
    return done, seen


def reference_logits(params, prompt, tokens):
    """Head 0 of the reference's full forward over prompt + tokens[:-1],
    at the positions whose next token was served."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return np.asarray(ref.logits_at(params, MODEL, seq, at)[:, 0])


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 97, n, dtype=np.int32)


def check_against_reference(params, done, seen):
    for rid, (req, toks) in done.items():
        got = np.stack([seen[rid][s] for s in range(len(toks))])
        # step 0 is the prefill's sample and is logged at step 0 too
        want = reference_logits(params, req.prompt, toks)
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= TOL * scale, (
            rid, len(req.prompt), np.abs(got - want).max(), scale)
        assert toks == list(np.argmax(want, axis=-1))


CASES = {
    # prompt length, new tokens: where the prompt ends / what decode crosses
    "inside_a_chunk": (70, 9),                # 70 = 2 windows + 6, 6 % 4 = 2
    "at_a_chunk_end": (72, 9),
    "at_a_window_end": (64, 9),               # decode starts a window
    "below_one_window": (19, 9),
    "crosses_one_window_end": (90, 12),       # 96
    "crosses_two_window_ends": (60, 45),      # 64, 96
    "prompt_of_one_window_to_its_end": (32, 32),   # the decode fills 64 too
    "five_windows": (150, 9),
}


@pytest.mark.parametrize("multi_step", [1, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_gives_the_reference_logits(cfg, params, case,
                                                        multi_step):
    n, new = CASES[case]
    b = batcher(cfg, params, multi_step=multi_step)
    done, seen = run_logged(b, [Request(prompt=prompt_of(n),
                                        max_new_tokens=new)])
    check_against_reference(params, done, seen)
    assert b.alloc.free_count() == b.n_pages - 1        # all but the sink


def test_two_rows_of_different_lengths_in_one_block(cfg, params):
    b = batcher(cfg, params, multi_step=4)
    reqs = [Request(prompt=prompt_of(n), max_new_tokens=new)
            for n, new in ((101, 30), (27, 40), (64, 5))]
    done, seen = run_logged(b, reqs)
    assert len(done) == 3
    check_against_reference(params, done, seen)


def test_below_one_window_the_logits_are_the_full_attention_programs(
        cfg, params):
    """For T < window EVA is plain causal attention: the same weights
    through the full-attention program give the same logits."""
    full_cfg = dataclasses.replace(cfg, attention="full")
    full = dict(params, layers={k: v for k, v in params["layers"].items()
                                if not k.startswith("eva_")})
    req = lambda: [Request(prompt=prompt_of(13), max_new_tokens=18)]
    done_e, seen_e = run_logged(batcher(cfg, params), req())
    done_f, seen_f = run_logged(batcher(full_cfg, full), req())
    (_, toks_e), = done_e.values()
    (_, toks_f), = done_f.values()
    assert toks_e == toks_f
    for s in range(len(toks_e)):
        np.testing.assert_allclose(seen_e[0][s], seen_f[0][s], atol=2e-6,
                                   rtol=0)


@pytest.mark.parametrize("multi_step", [1, 4])
def test_entries_held_follow_E_of_T_at_every_step(cfg, params, multi_step):
    """The share test's place (the cut is depth only): the cache is tied to
    the model.  At every step of a context that grows over 5 windows a row
    holds exactly E(T) entries, its pages are ceil(E(T) / page), the tick
    ring's counters say the same, and the adapter's ``cache_entries``
    agrees with the program's."""
    check_entries_follow_E_of_T(cfg, params, multi_step, lag=0)


def check_entries_follow_E_of_T(cfg, params, multi_step, lag):
    """``lag``: ``pipeline_depth`` (tests/test_eva_carry.py runs the same
    check under the carry, where the host's view is the dispatched one)."""
    b = batcher(cfg, params, rows=1, multi_step=multi_step,
                pipeline_depth=lag)
    assert b._pipelined == (lag is None)
    seen = []
    account = b._eva_account

    def spy(active):
        account(active)
        for r, row in active.items():
            seen.append((row.pos, b.alloc.allocated(r), b._eva_live))

    b._eva_account = spy
    n, new = 45, 110                            # 45 .. 154: 5 windows
    list(b.run([Request(prompt=prompt_of(n), max_new_tokens=new)]))
    assert [p for p, _, _ in seen][0] == n and seen[-1][0] >= 150
    for pos, pages, (n_sum, n_win, n_pages) in seen:
        e = pos // W * (W // C) + pos % W
        assert cfg.cache_entries(pos) == e == evabyte.cache_entries(MODEL, pos)
        assert (n_sum, n_win) == (pos // W * (W // C), pos % W)
        # the ring's page count is the allocator's, not a formula's
        assert n_pages == pages == -(-e // PS), (pos, pages, e)
    assert b.eva_rolls == 1 + 3                 # 32 in prefill; 64, 96, 128
    assert b.alloc.free_count() == b.n_pages - 1
    recs = [r for r in b.flight.snapshot() if "eva_rolls" in r]
    assert sum(r["eva_rolls"] for r in recs) == b.eva_rolls
    held = {r["eva_summary_entries"] + r["eva_window_entries"] for r in recs}
    assert all(r["eva_pages"] == -(-(r["eva_summary_entries"]
                                     + r["eva_window_entries"]) // PS)
               for r in recs)                   # one row: no page is left
    every = {evabyte.cache_entries(MODEL, p) for p, _, _ in seen}
    assert held - {0} <= every and len(held) >= (len(every) - 1) // multi_step
    # a plain-attention row of the same context would hold T, not E(T)
    assert max(held) <= 4 * (W // C) + W - 1 < 150


def test_admission_reserves_by_entries_not_positions(cfg, params):
    """A pool too small for the rows' positions holds them by entries, and
    the high-water mark stays under what positions would have taken."""
    b = batcher(cfg, params, rows=2, n_pages=21)      # 20 pages = 160 entries
    reqs = [Request(prompt=prompt_of(100, seed=s), max_new_tokens=40)
            for s in (1, 2)]
    for r in reqs:
        wt, _, need = b._worst_pages(r)
        assert need == 139 and wt == -(-(3 * 8 + 32) // PS)    # a held window
    done, seen = run_logged(b, reqs)
    assert len(done) == 2
    check_against_reference(params, done, seen)
    assert b.peak_pages_used <= 1 + 2 * 6 + 4     # sink, 2 rows, one open window
    assert b.peak_pages_used < 2 * (139 // PS)
    with pytest.raises(ValueError, match="max_len"):
        b.validate(Request(prompt=prompt_of(250), max_new_tokens=10))


@pytest.mark.parametrize("lo,hi,want", [
    (0, 31, 31), (0, 32, 32), (0, 33, 32), (0, 70, 40), (64, 70, 22),
    (64, 96, 48), (60, 63, 39), (90, 97, 48), (0, 159, 63), (128, 159, 63)])
def test_cache_entries_peak(cfg, lo, hi, want):
    assert cfg.cache_entries_peak(lo, hi) == want
    full = dataclasses.replace(cfg, attention="full")
    assert full.cache_entries_peak(lo, hi) == hi == full.cache_entries(hi)


def test_quantize_params_round_trips(cfg, params):
    """Weight-only int8 through the same path: phi, mu and the norms stay
    as they are, every matmul leaf and the 8-head matrix become QTensors,
    and the served logits stay near the float32 ones (not equal to them)."""
    from tfmesos_tpu.ops.quant import QTensor
    q = T.quantize_params(cfg, params)
    assert isinstance(q["head"], QTensor) and \
        q["head"].values.shape == (64, 8 * 97)
    for k in ("eva_phi", "eva_mu", "attn_norm"):
        assert not isinstance(q["layers"][k], QTensor)
    assert isinstance(q["layers"]["wq"], QTensor)
    req = lambda: [Request(prompt=prompt_of(70), max_new_tokens=4)]
    _, seen_q = run_logged(batcher(cfg, q), req())
    _, seen_f = run_logged(batcher(cfg, params), req())
    err = max(np.abs(seen_q[0][s] - seen_f[0][s]).max() for s in seen_f[0])
    assert 1e-5 < err < 0.5


def test_what_eva_pages_cannot_do_is_refused_with_a_registered_reason(
        cfg, params):
    reason = "eva summary pages"
    for reg in ("prefix_cache", "kv_tier", "suspend", "speculative",
                "kv_export"):
        assert reason in BYPASS_ALLOWLIST[reg]
    # the lagged carry is NOT among them: a close hangs on a position,
    # and positions advance at dispatch
    assert BYPASS_ALLOWLIST["pipeline"] == ("speculative decoding",)
    with pytest.raises(ValueError, match=f"speculative.*{reason}"):
        batcher(cfg, params, draft_cfg=cfg, draft_params=params)
    from tfmesos_tpu.fleet.kvtier import KVTierStore
    b = batcher(cfg, params, prefix_cache_pages=8, pipeline_depth=1,
                kv_tier=KVTierStore(1 << 20))
    assert b.prefix_cache_bypass_reason == reason and b._pcache is None
    assert b.kv_tier_bypass_reason == reason and not b._tier_active
    assert b.suspend_bypass_reason == reason and not b.preemptible
    assert b.pipeline_bypass_reason is None and b._pipelined
    # sessions park through the tier: a labeled request is served cold
    req = Request(prompt=prompt_of(40), max_new_tokens=3, session_id="s")
    done, seen = run_logged(b, [req])
    check_against_reference(params, done, seen)
    with pytest.raises(ValueError, match=f"export_kv is refused: {reason}"):
        b.export_kv(Request(prompt=prompt_of(9), max_new_tokens=2))
    art = {"version": 1}
    with pytest.raises(ValueError, match=reason):
        b.validate(Prefilled(req, art))
    with pytest.raises(ValueError, match=reason):
        b.submit(req, prefilled=art)
    for kw in ({"prefill_chunk": 8}, {"quantized_cache": True}):
        with pytest.raises(ValueError, match="attention='eva'"):
            batcher(cfg, params, **kw)
    with pytest.raises(ValueError, match="multiple of page_size"):
        batcher(cfg, params, page_size=16)
    with pytest.raises(ValueError, match="multiple of .*prefill_bucket"):
        batcher(cfg, params, prefill_bucket=24)
    with pytest.raises(NotImplementedError, match="serving path"):
        T.forward(cfg, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="paged cache"):
        T.init_cache(cfg, 1, 64)
    with pytest.raises(ValueError, match="eva_window"):
        dataclasses.replace(cfg, eva_chunk=5)


def test_warmup_compiles_what_the_loop_dispatches(cfg, params):
    check_warmup_compiles_what_the_loop_dispatches(cfg, params, lag=0)


def check_warmup_compiles_what_the_loop_dispatches(cfg, params, lag):
    """Either loop (``lag``: ``pipeline_depth``): K = 4 blocks, the single
    steps before a window's end (``_decode1``), every prefill width up to a
    window and the close."""
    b = batcher(cfg, params, multi_step=4, pipeline_depth=lag)
    names = b.warmup()["compiled"]
    assert "eva_roll" in names and "prefill[32]" in names
    assert "prefill[40]" not in names           # a window, then a tail
    list(b.run([Request(prompt=prompt_of(75), max_new_tokens=30),
                Request(prompt=prompt_of(9), max_new_tokens=5)]))
    recs = b.flight.snapshot()
    assert sum(r["compiles"] for r in recs) == 0
    assert {r["mode"] for r in recs if r["name"] == "decode.block"} == {
        "sync" if lag == 0 else "pipelined"}


# -- the reference itself ------------------------------------------------------

def _qkv(t, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(kk, (t, h, d)) for kk in ks[:3])
    phi, mu = (jax.random.normal(kk, (h, d)) for kk in ks[3:])
    return q, k, v, phi, mu


def test_reference_forms_agree_and_reduce_to_causal_attention():
    q, k, v, phi, mu = _qkv(5 * W)
    ks, vs = ref.summaries(k, v, phi, mu, C)
    dense = ref.attention_dense(q, k, v, ks, vs, C, W)
    by_window = ref.attention_windows(q, k, v, ks, vs, C, W)
    np.testing.assert_allclose(dense, by_window, atol=2e-6, rtol=0)
    # inside the first window: plain causal softmax attention
    from benchmark import reference as plain
    s = jnp.einsum("ihd,mhd->him", q[:W], k[:W]) / 4.0
    s = jnp.where(jnp.arange(W)[None] > jnp.arange(W)[:, None], -jnp.inf, s)
    causal = jnp.einsum("him,mhd->ihd", jax.nn.softmax(s, -1), v[:W])
    np.testing.assert_allclose(dense[:W], causal.reshape(W, -1), atol=2e-6,
                               rtol=0)
    assert plain.HI is ref.HI
    # a summary is a convex pooling of its chunk's values (and keys + mu)
    assert ks.shape == vs.shape == (5 * W // C, 4, 16)
    lo = v.reshape(-1, C, 4, 16).min(1)
    hi = v.reshape(-1, C, 4, 16).max(1)
    assert bool(jnp.all((vs >= lo - 1e-6) & (vs <= hi + 1e-6)))
    # position i of window 2 sees 2 windows of chunks and its window so far
    i = 2 * W + 5
    one = ref.attention_dense(q.at[i].multiply(1.0), k, v, ks, vs, C, W)[i]
    keys = jnp.concatenate([k[2 * W:i + 1], ks[:2 * W // C]])
    vals = jnp.concatenate([v[2 * W:i + 1], vs[:2 * W // C]])
    p = jax.nn.softmax(jnp.einsum("hd,mhd->hm", q[i], keys) / 4.0, -1)
    np.testing.assert_allclose(
        one, jnp.einsum("hm,mhd->hd", p, vals).reshape(-1), atol=2e-6, rtol=0)


def test_all_eight_heads_and_the_int8_control(params):
    prompt = prompt_of(70)
    lg = ref.logits_at(params, MODEL, prompt, [10, 69])
    assert lg.shape == (2, 8, 97)
    g = ref.served_gaps(params, MODEL, prompt,
                        list(np.argmax(np.asarray(lg[1:, 0]), -1)) + [3],
                        control=True)
    assert g["gap"].shape == (2,) and g["gap"][0] == 0 and g["gap"][1] >= 0
    assert g["control_gap"].shape == (2,) and (g["control_gap"] >= 0).all()
    low = ref.logits_at(params, MODEL, prompt, [69], quantize="int8")
    assert 1e-4 < float(jnp.abs(low - lg[1:]).max()) < 1.0


# -- the pieces ---------------------------------------------------------------

@pytest.mark.parametrize("t", [64, 576])
def test_eva_prefill_attention_through_the_flash_kernel_interpreted(
        monkeypatch, t):
    """The chunk's own part through ``flash_attention_fwd`` (interpret
    mode) and the cached summaries in XLA blocks, against the dense form;
    a clamped block column lies past every row's entries.  576 is a tail
    width its tile does not divide (288 rows against K blocks of 384, the
    second mostly padding): the summaries continue from a log-sum-exp that
    must not have seen the padding."""
    h, d, ps, n_pages = 4, 16, 8, 12
    assert A._flash_tiles(576, 576, d, 4) == (288, 384)
    monkeypatch.setattr(A, "EVA_BLOCK_PAGES", 4)    # 10 pages: 3 blocks
    q, k, v, _, _ = _qkv(t, h, d, seed=3)
    pool = jax.random.normal(jax.random.PRNGKey(4), (2, 2, n_pages, h, ps, d))
    table = jnp.asarray([[5, 3, 9, 1, 7, 2, 11, 0, 4, 6]], jnp.int32)
    for n_cached in (0, 8, 24, 72):
        got = A.eva_prefill_attention(
            q[None], k[None], v[None], pool[0], pool[1], 1, table,
            jnp.asarray([n_cached], jnp.int32), interpret=True)[0]
        gather = lambda p: p[1][table[0]].transpose(0, 2, 1, 3).reshape(
            -1, h, d)[:n_cached]
        keys = jnp.concatenate([gather(pool[0]), k])
        vals = jnp.concatenate([gather(pool[1]), v])
        s = jnp.einsum("ihd,mhd->him", q, keys) / 4.0
        ok = jnp.concatenate(
            [jnp.ones((t, n_cached), bool),
             jnp.arange(t)[None] <= jnp.arange(t)[:, None]], axis=1)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
        want = jnp.einsum("him,mhd->ihd", p, vals)
        np.testing.assert_allclose(got, want, atol=3e-6, rtol=0)


def test_eva_summarize_is_the_references_pooling():
    q, k, v, phi, mu = _qkv(W, seed=5)
    tcfg = T.TransformerConfig(d_model=64, n_heads=4, attention="eva",
                               eva_chunk=C, eva_window=W)
    ks, vs = T.eva_summarize(tcfg, k[None, None], v[None, None], phi[None],
                             mu[None])
    rk, rv = ref.summaries(k, v, phi, mu, C)
    np.testing.assert_allclose(ks[0, 0], rk, atol=2e-6, rtol=0)
    np.testing.assert_allclose(vs[0, 0], rv, atol=2e-6, rtol=0)
