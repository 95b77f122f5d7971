"""The fourth model adapter, on the CPU: the plain float32 reference of
Solar-Open2's block against the PROGRAM's typed decode path (prefill through
the paged pool and the row-state store, then one-token steps), logits not
tokens; the share tests (experts and vocabulary); the configuration file
against the catalog's row; the cell's entries; the adapter's byte counts
against hand arithmetic; the new readers on a made trace; and a rehearsal of
the cell through ``drivers/serve.py``.

Tolerances.  Program and reference are both float32 here (the tiny
configuration states float32) and differ in the order of their sums only: the
chunkwise delta rule (a triangular solve a chunk) against the position-by-
position recurrence, the flash / paged attention against a plain softmax, the
sorted grouped expert matmul against every-expert-then-mask.  With ``beta`` up
to 2 the recurrence's factor ``I - beta k k^T`` does not contract, so rounding
is carried along a prompt, not damped: measured 3e-7 .. 2.3e-5 of the largest
logit (~3-4 at these weights) over every case below, the largest on the
longest prompt (130 positions, three chunks); ``RTOL`` 1e-4 of the largest
logit leaves a factor of four, and a missing term (a dropped assignment, a
state that kept a slot's last row, padding that reached the state, a gate
left out) moves a logit by 1e-2 of it or more.  The int8 control moves the
served token's logit by ~1e-2 (``control_max_gap`` 0.03 in the rehearsal): a
hundred times the tolerance."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import solar_tiny as st  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.models import solar_kda as sk  # noqa: E402
from benchmark.models import solar_kda_reference as ref  # noqa: E402

CELL = "solar2.reason_batch"
CONFIG = "solar2-l4-ep8-serve"
RTOL = 1e-4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def weights(model, seed=11):
    import jax.numpy as jnp
    return sk.make_weights(model, seed, dtype=jnp.float32)


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


# -- prefill then decode, against the reference's full forward ----------------

@pytest.mark.parametrize("kinds", [
    "akkk",         # the published period: attention first
    "kka",          # attention last
    "kak",          # attention in the middle
    "akak",         # two periods of two: the scan over periods
])
@pytest.mark.parametrize("plen", [
    37,     # ends inside a chunk of 64 AND inside the bucket's padding (48)
    64,     # ends exactly on a chunk and on a bucket
    9,      # narrower than a chunk (width 16)
    130,    # three chunks, the last of two positions
])
def test_prefill_then_decode_logits_match_the_reference(kinds, plen):
    model = st.tiny(kinds)
    w = weights(model)
    prompt = np.random.default_rng(plen).integers(0, 256, plen,
                                                  dtype=np.int32)
    # ``dirty``: pool and state hold ones, as a slot another row just left
    # may: the prefill must start from an empty state whatever is there
    got, toks, _ = st.program_logits(model, w, prompt, 5, dirty=True)
    close(got, st.reference_logits(model, w, prompt, toks))


def test_a_row_admitted_into_a_slot_another_row_left():
    """Two requests through the same row slot, one store: the second's
    logits are what it would have got in a fresh store."""
    model = st.tiny("akkk")
    w = weights(model)
    rng = np.random.default_rng(5)
    first = rng.integers(0, 256, 50, dtype=np.int32)
    second = rng.integers(0, 256, 21, dtype=np.int32)
    _, _, store = st.program_logits(model, w, first, 6)
    got, toks, _ = st.program_logits(model, w, second, 6, store=store)
    close(got, st.reference_logits(model, w, second, toks))


@pytest.mark.parametrize("bucket", [8, 16, 64])
def test_bucket_padding_is_kept_out_of_the_state(bucket):
    """The same prompt under three paddings: the state after it, and so
    every later logit, is the same."""
    model = st.tiny("kak")
    w = weights(model)
    prompt = np.random.default_rng(3).integers(0, 256, 19, dtype=np.int32)
    got, toks, _ = st.program_logits(model, w, prompt, 4, bucket=bucket)
    close(got, st.reference_logits(model, w, prompt, toks))


# -- the shares ---------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 2 s .. 2 s + 1 of 16 for the eight shares s, the shared
    expert and the mixer counted once, add up to the uncut reference's layer
    (float32, sums in another order: 1e-5 of the layer's output)."""
    import jax.numpy as jnp
    whole = st.tiny("ka", held=16)
    w = weights(whole)
    lay = w["layers"]
    dm = ref.dims(whole)
    x = np.random.default_rng(0).normal(size=(256, 48)).astype(np.float32)
    for li, (kind, ki) in enumerate((("kda", 0), ("attention", 0))):
        mixed = ref.mixer(jnp.asarray(x), lay, li, ki, dm=dm, kind=kind,
                          quantize=None)
        want = ref.expert_block(mixed, lay, li, dm=dm, quantize=None)
        h = ref.rms_norm(mixed, lay["mlp_norm"][li], dm.eps)
        routed, parts = 0, []
        for shard in range(8):
            part = st.tiny("ka", held=2, shard=shard)
            cut = {**lay, **{k: lay[k][:, 2 * shard:2 * shard + 2]
                             for k in ("e_gate", "e_up", "e_down")}}
            parts.append(ref.routed_experts(h, cut, li, ref.dims(part), None))
            routed = routed + parts[-1]
        got = mixed + routed + ref.shared_mlp(h, lay, li, None)
        assert sum(float(jnp.abs(p).max()) > 0 for p in parts) == 8
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())


@pytest.mark.parametrize("shard", [0, 3])
def test_the_program_computes_its_own_share(shard):
    """The program holding experts 4 * shard .. + 3 of 16 against the
    reference given the same share: what the others would add is left out
    of both alike."""
    model = st.tiny("kak", held=4, shard=shard)
    w = weights(model)
    assert w["layers"]["e_gate"].shape[:2] == (3, 4)
    assert w["layers"]["router"].shape == (3, 48, 16)
    assert sk.program_config(model, 256).expert_offset == 4 * shard
    prompt = np.random.default_rng(8).integers(0, 256, 30, dtype=np.int32)
    got, toks, _ = st.program_logits(model, w, prompt, 4)
    close(got, st.reference_logits(model, w, prompt, toks))


def test_the_sliced_head_is_the_same_rows_of_the_whole_one():
    """Rows 0-255 of an embedding and a head of 512: on ids of the slice,
    the sliced model's logits are the whole model's over the same rows."""
    whole = st.tiny("ak", vocab=st.VOCAB)
    w = weights(whole)
    cut = st.tiny("ak", vocab=256)
    wc = {**w, "embed": w["embed"][:256], "head": w["head"][:, :256]}
    seq = np.random.default_rng(1).integers(0, 256, 40, dtype=np.int32)
    at = np.arange(30, 40)
    full = np.asarray(ref.logits_at(w, whole, seq, at))
    part = np.asarray(ref.logits_at(wc, cut, seq, at))
    assert part.shape == (10, 256) and full.shape == (10, 512)
    np.testing.assert_allclose(part, full[:, :256], rtol=1e-6, atol=1e-6)
    # and the program over the slice agrees with the reference over it
    got, toks, _ = st.program_logits(cut, wc, seq[:30], 4)
    assert got.shape[-1] == 256 and max(toks) < 256
    close(got, st.reference_logits(cut, wc, seq[:30], toks))


def test_the_reference_is_the_recurrence():
    """The reference's KDA mixer against the delta rule written out by hand
    in numpy, position by position (float64)."""
    import jax.numpy as jnp
    model = st.tiny("k")
    w = weights(model)
    dm = ref.dims(model)
    kda = {k: np.asarray(v[0], np.float64)
           for k, v in w["layers"]["kda"].items()}
    t, nh, dk = 12, 4, 16
    hk = nh * dk
    h = np.random.default_rng(2).normal(size=(t, 48))
    got = np.asarray(ref.kda_mixer(jnp.asarray(h, jnp.float32),
                                   w["layers"]["kda"], 0, dm, None))
    qkv = h @ kda["in_proj"]
    silu = lambda v: v / (1 + np.exp(-v))
    conv = np.zeros_like(qkv)
    for i in range(t):
        for j in range(4):
            if i - 3 + j >= 0:
                conv[i] += kda["conv_w"][j] * qkv[i - 3 + j]
    act = silu(conv)
    l2 = lambda v: v / np.sqrt((v * v).sum(-1, keepdims=True) + 1e-6)
    q = l2(act[:, :hk].reshape(t, nh, dk)) * dk ** -0.5
    k = l2(act[:, hk:2 * hk].reshape(t, nh, dk))
    v = act[:, 2 * hk:].reshape(t, nh, dk)
    f = (h @ kda["f_down"]) @ kda["f_up"] + kda["dt_bias"]
    g = -np.exp(kda["A_log"])[:, None] * np.log1p(np.exp(f)).reshape(
        t, nh, dk)
    beta = 2.0 / (1 + np.exp(-(h @ kda["b_proj"])))
    s = np.zeros((nh, dk, dk))
    o = np.zeros((t, nh, dk))
    for i in range(t):
        for hd in range(nh):
            sp = np.exp(g[i, hd])[:, None] * s[hd]
            u = v[i, hd] - sp.T @ k[i, hd]
            s[hd] = sp + beta[i, hd] * np.outer(k[i, hd], u)
            o[i, hd] = s[hd].T @ q[i, hd]
    gate = 1 / (1 + np.exp(-((h @ kda["g_down"]) @ kda["g_up"])))
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * kda["norm"]
    want = (o * gate.reshape(t, nh, dk)).reshape(t, hk) @ kda["out_proj"]
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_the_references_router_against_hand_arithmetic():
    """Selection by ``s + b``, gates from ``s`` over the chosen, times the
    scaling factor; all 16 outputs though 4 experts are held."""
    import jax.numpy as jnp
    model = st.tiny("k", held=4)
    model["routed_scaling_factor"] = 2.5
    w = weights(model)
    dm = ref.dims(model)
    assert (dm.experts, dm.held, dm.top_k, dm.routed_scale) == (16, 4, 3, 2.5)
    h = np.random.default_rng(4).normal(size=(20, 48)).astype(np.float32)
    gates, idx = ref.routing(jnp.asarray(h), w["layers"], 0, dm)
    router = np.asarray(w["layers"]["router"][0], np.float64)
    bias = np.asarray(w["layers"]["router_bias"][0], np.float64)
    assert np.abs(bias).max() > 0          # small and not zero
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ router)))
    for row in range(20):
        chosen = np.argsort(-(s[row] + bias))[:3]
        assert sorted(chosen) == sorted(np.asarray(idx[row]).tolist())
        want = s[row, np.asarray(idx[row])]
        np.testing.assert_allclose(np.asarray(gates[row]),
                                   2.5 * want / want.sum(), rtol=1e-5)


# -- the configuration file and the cell --------------------------------------

def config_file():
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def test_the_configuration_is_the_catalogs_row_but_the_four_cuts():
    entry, config = config_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Solar-Open2-250B")
    assert entry["source"] == config["source"] == row["source_url"]
    cut = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
           "vocab_size": 24576}
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(cut)
    for k, v in row["config"].items():
        want = cut.get(k, v)
        assert config[k] == want and type(config[k]) is type(want), k
        if k in cut:
            assert config["published"][k] == v
    # one whole period of the published pattern, in its published ratio
    assert row["config"]["gqa_layers"][:2] == [0, 4]
    kinds = ref.layer_kinds(config)
    assert kinds == ["attention", "kda", "kda", "kda"]
    # the floors: a whole period of four, 8 experts, an eighth of the rows
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for what in ("kda layer", "kda low rank", "kda chunk", "state dtype",
                 "conv tail layout", "gqa gate", "router", "shared expert",
                 "intermediate_size", "torch_dtype", "weights", "routing",
                 "depth"):
        assert what in config["assumed"], what
    dep = config["deployment"]
    assert (dep["rows"], dep["max_len"], dep["page_size"], dep["n_pages"],
            dep["expert_parallel"], dep["expert_shard"],
            dep["vocab_rows"]) == (192, 8192, 64, 9216, 8, 0, [0, 24575])
    assert config["driver"] == "serve" and config["model"] == "solar_kda"
    dm = ref.dims(config)
    assert (dm.experts, dm.held, dm.offset, dm.top_k) == (320, 40, 0, 8)
    limits = config["correct"]["limits"]
    assert config["correct"]["sample_requests"] == 4
    assert set(limits) == {"mean_gap", "max_gap"}


#: the accepted ``tok_s`` lists the cell joined (a suffixed name is read by
#: its base name's file), and the two entries of its own
JOINED = ("gen_late_p99_ms", "decode_rows_mean", "pool_fill",
          "prefill_p50_ms", "decode_block_ms_p50", "attn_kernel_share",
          "pool_copy_share", "tick_host_ms_p50", "host_gap_share",
          "prefill_stall_share", "compiles_in_window")
OWN = ("kda_state_roofline", "kda_share")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_the_cell_and_its_entries(spec):
    cell = harness.find_cell(spec, CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "reason_batch",
                    "chips": 1}
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    mine = {m["name"]: m
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    assert set(mine) >= {n + ".docqa" for n in JOINED} | set(OWN)
    for m in mine.values():
        assert m["moves"] == "tok_s"
    for n in JOINED:
        assert {"mistral7b.docqa_batch", CELL} <= set(
            mine[n + ".docqa"]["workloads"])
    # its own two: each lists this cell, has a reader and names a layer the
    # benchmark has
    by = {m["name"]: m for m in spec["per_layer"]}
    layers = {m["layer"] for m in spec["per_layer"] if m["name"] not in OWN}
    for n in OWN:
        assert CELL in mine[n]["workloads"] and mine[n]["layer"] in layers
        assert set(mine[n]) == set(by["pool_fill.docqa"])
        assert mine[n]["unit"] == "%" and mine[n]["source"] == "device_trace"
        assert harness.load_reader(n) is not None
    # the readers keyed to another configuration's keys are not its
    assert not {"ssm_state_roofline", "ssm_share",
                "moe_load_max_over_mean"} & set(mine)


def test_reason_batch_offers_nineteen_lengths_in_a_fixed_order():
    from benchmark import traffic_gen
    traffic = traffic_gen.load_traffic("reason_batch")
    assert traffic["schedule_seed"] == 37 and traffic["block"] == 64
    assert (traffic["ramp_s"], traffic["grace_s"]) == (30, 6)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.9, "min": 320, "max": 4000,
                                 "quantum": 160}
    assert traffic["output"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 256, "max": 2048}
    a = traffic_gen.make_schedule(traffic, 1, 51, 24576)
    b = traffic_gen.make_schedule(traffic, 2 ** 31 + 5, 51, 24576)
    assert a.kind == "backlog" and len(a.requests) == 2048
    assert [len(r.prompt) for r in a.requests] == [
        len(r.prompt) for r in b.requests]
    assert [r.max_new_tokens for r in a.requests] == [
        r.max_new_tokens for r in b.requests]
    # ids are drawn from the slice
    assert max(int(r.prompt.max()) for r in b.requests[:200]) < 24576
    lens = [len(r.prompt) for r in a.requests]
    assert min(lens) == 320 and max(lens) == 4000
    assert all(n % 160 == 0 for n in lens) and len(set(lens)) <= 24
    # the odd multiples of 160 end 32 positions into a chunk of 64 and a
    # page, and are padded up to the 64-token bucket
    inside = sum(1 for n in lens if n % 64) / len(lens)
    assert 0.3 <= inside <= 0.6
    assert all(n % 64 in (0, 32) for n in lens)
    outs = [r.max_new_tokens for r in a.requests]
    assert min(outs) >= 256 and max(outs) <= 2048
    assert 950 <= np.mean(lens) <= 1250 and 1000 <= np.mean(outs) <= 1250
    assert max(n + o for n, o in zip(lens, outs)) + 64 <= 8192


# -- the adapter's arithmetic -------------------------------------------------

def test_adapter_functions_and_bytes_against_hand_arithmetic():
    _, config = config_file()
    for fn in ("program_config", "make_weights", "int8_program_weights",
               "served_gaps", "kv_bytes_per_context_token",
               "pool_leaf_shapes", "paged_kernel_shape", "token_slots"):
        assert callable(getattr(sk, fn)), fn
    counters = {"rows": 192, "n_pages": 9216, "page_size": 64}
    # ONE attention layer of four keeps K/V: 2 x 8 heads x 128 x 2 B: 4 KB
    assert sk.kv_bytes_per_context_token(config) == 4096
    assert sk.pool_leaf_shapes(config, counters) == [
        [1, 9216, 8, 64, 128], [9216, 8, 64, 128]]
    assert sk.paged_kernel_shape(config, 192) == [192, 8, 8, 128]
    assert sk.token_slots(config, counters) == 589824
    # a row's state: 3 layers x (64 x 128 x 128 float32 + 3 x 24576 bf16):
    # 13.03 MB
    assert sk.state_bytes_per_row(config) == 3 * (4194304 + 147456) \
        == 13025280
    assert sk.kda_store_shape(config, 192) == [3, 192, 8192, 128]
    assert sk.kda_carry_shape(config) == [1, 64, 128, 128]
    # one step of 192 rows: 192 x 3 x 4 MiB, once in and once out: 4.8 GB
    assert sk.kda_step_bytes(config, 192) == 2 * 192 * 3 * 4194304
    assert sk.kda_step_bytes(config, 1) * 192 == sk.kda_step_bytes(config,
                                                                    192)
    # an expert matrix is 4096 x 1280 bf16 = 10,485,760 B: gate and up for
    # the first kernel, down for the second: 31.46 MB an expert touched
    per = sk.expert_step_bytes(config, 40)
    assert per == {"moe_grouped_swiglu": 2 * 40 * 10485760,
                   "moe_grouped_matmul": 40 * 10485760}
    assert sum(sk.expert_step_bytes(config, 1).values()) == 31457280
    # the kernels' rows: 1536 assignments in tiles of 16, 40 tiles of padding
    assert sk.expert_kernel_rows(config, 192) == 1536 + 40 * 16 == 2176
    # 3,308 M parameters held here, 6.62 GB in bfloat16
    n = sk.parameters(config)
    assert 3.305e9 <= n <= 3.311e9 and 6.61e9 <= 2 * n <= 6.63e9
    outside = 126.09e6 + 3 * 154.76e6
    assert abs(n - (outside + 4 * 40 * 15.7286e6 + 2 * 24576 * 4096)) < 2e6


def test_the_adapters_kernel_rows_are_the_programs():
    from tfmesos_tpu.ops import moe
    _, config = config_file()
    for tokens in (1, 192, 320, 1024, 4032):
        tile = moe.pick_tile(tokens * 8, 320)
        rows = -(-tokens * 8 // tile) * tile + 40 * tile
        assert sk.expert_kernel_rows(config, tokens) == rows


def test_the_made_weights_are_the_programs_tree():
    """``make_weights`` gives the tree ``init_params`` gives (names, shapes,
    the float32 selection bias), so the program takes it as it is."""
    import jax
    import jax.numpy as jnp
    from tfmesos_tpu.models.transformer import init_params
    model = st.tiny("akkk")
    w = weights(model)
    cfg = sk.program_config(model, 256)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert shapes(w) == shapes(want)
    assert w["layers"]["router_bias"].dtype == jnp.float32
    # the draw: a step's log-decay within about -1e-3 .. -0.5, beta over
    # (0, 2), the router's choice near uniform
    kda = w["layers"]["kda"]
    h = jax.random.normal(jax.random.PRNGKey(1), (512, 48))
    f = (h @ kda["f_down"][0]) @ kda["f_up"][0] + kda["dt_bias"][0]
    g = -jnp.exp(kda["A_log"][0])[:, None] * jax.nn.softplus(f).reshape(
        512, 4, 16)
    lo, hi = np.quantile(np.asarray(-g), [0.01, 0.99])
    assert 5e-4 <= lo and hi <= 0.7, (lo, hi)
    beta = 2 * jax.nn.sigmoid(h @ kda["b_proj"][0])
    assert float(beta.min()) < 0.3 and float(beta.max()) > 1.7
    _, idx = ref.routing(h, w["layers"], 0, ref.dims(model))
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=16)
    assert counts.min() > 0 and counts.max() <= 3 * counts.mean()


def test_the_draw_keeps_what_every_token_shares_out_of_the_residual_stream():
    """SiLU leaves v a positive mean, so part of a KDA layer's gated output
    is the same for every token; a random ``out_proj`` turns it into one
    direction every row's hidden state shares, an offset on every expert's
    router logit (on the chip: the fullest held expert 4-9 times the mean,
    PERF.md section 6, PR 37).  ``make_weights`` draws conv taps of unit
    power a channel (that part is then constant within a head) and an
    ``out_proj`` with zero sum over a head's channels (which adds none of
    it).  Measured here, the share of the mixer's output power that is its
    mean over 500 random tokens, mean of the three layers: 0.007 as drawn
    (1 / 500 is the floor), 0.044 with a plain ``out_proj`` of the same
    scale; the limits leave a factor of 1.7 and of 2."""
    import jax
    import jax.numpy as jnp
    model = st.tiny("akkk")
    dm = ref.dims(model)
    kda = weights(model)["layers"]["kda"]
    op = np.asarray(kda["out_proj"]).reshape(3, dm.k_heads, dm.k_hd, -1)
    assert np.abs(op.sum(axis=2)).max() < 1e-5
    taps = np.asarray(kda["conv_w"])
    np.testing.assert_allclose((taps * taps).sum(axis=1), 1.0, rtol=1e-5)
    h = jax.random.normal(jax.random.PRNGKey(11), (600, dm.d))

    def shared(leaves):
        out = [np.asarray(ref.kda_mixer(h, leaves, ki, dm, None))[100:]
               for ki in range(3)]
        return np.mean([(o.mean(0) ** 2).sum() / (o * o).sum(1).mean()
                        for o in out])

    plain = dict(kda, out_proj=jax.random.normal(
        jax.random.PRNGKey(5), kda["out_proj"].shape)
        * jnp.std(kda["out_proj"]))
    drawn = shared(kda)
    assert drawn < 0.012 and shared(plain) > 3 * drawn, (drawn, shared(plain))


def test_program_config_states_the_published_equations():
    import jax.numpy as jnp
    _, config = config_file()
    cfg = sk.program_config(config, 8192)
    assert cfg.layer_types == ("attention", "kda", "kda", "kda")
    assert (cfg.n_attn_layers, cfg.n_kda_layers, cfg.layer_period) == (
        1, 3, 4)
    assert cfg.layer_runs == (("attention", 0, 1, 0), ("kda", 1, 3, 0))
    assert cfg.keeps_row_state
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_conv,
            cfg.kda_chunk) == (64, 128, 8192, 4, 64)
    assert cfg.kda_neg_eigval and cfg.attn_gate and not cfg.rope
    assert cfg.attn_scale is None               # head_dim ** -0.5
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset, cfg.top_k,
            cfg.shared_width, cfg.moe_impl, cfg.router_score,
            cfg.routed_scale) == (320, 40, 0, 8, 1280, "grouped", "sigmoid",
                                  1.0)
    assert cfg.vocab_size == 24576 and not cfg.tie_embeddings
    assert cfg.norm_eps == 1e-5
    assert cfg.dtype == jnp.bfloat16 and cfg.logits_dtype == jnp.float32


# -- the readers --------------------------------------------------------------

def test_readers_on_a_made_trace_against_hand_arithmetic():
    """A made trace of one decode block (the instructions XLA compiles the
    update to: the layer copied out of the store, the reduction over the
    copy, the in-place write) and one prefill with a chunk scan: the two new
    readers against arithmetic done by hand."""
    from types import SimpleNamespace

    from benchmark import trace_reduce
    _, config = config_file()
    store = "f32[3,192,8192,128]"
    store5 = "f32[3,192,64,128,128]"    # the update's view of the store
    layer = "f32[1,192,8192,128]"
    heads = "f32[192,64,128,128]"
    ops = [
        # decode block, 0.0 .. 0.1 s
        (f"%constant_dynamic-slice_fusion.1 = {layer}{{3,2,1,0}} fusion("
         f"{store}{{3,2,1,0}} %p, s32[] %i)", 0.00, 0.004),
        (f"%multiply_reduce_fusion.2 = f32[192,64,2,128]{{3,2,1,0}} fusion("
         f"f32[192,64,2,128] %a, {heads}{{3,2,1,0}} %b)", 0.01, 0.002),
        (f"%select_dynamic-update-slice_fusion.3 = {store5}{{4,3,2,1,0}} "
         f"fusion({store5}{{4,3,2,1,0}} %p, s32[] %i)", 0.02, 0.006),
        ("%moe_grouped_swiglu.4 = bf16[2176,1280]{1,0} custom-call(%a)",
         0.03, 0.008),
        ("%fusion.5 = bf16[192,4096]{1,0} fusion(%a)", 0.04, 0.06),
        # prefill, 0.2 .. 0.3 s: the scan's while spans its body
        ("%while.6 = (s32[], f32[1,64,128,128]{3,2,1,0}, f32[16,1,64,64,128]"
         "{4,3,2,1,0}) while(%t)", 0.20, 0.03),
        ("%fusion.7 = f32[1,64,128,128]{3,2,1,0} fusion(%b)", 0.20, 0.03),
        (f"%while.8 = (s32[], {store}{{3,2,1,0}}, f32[1,64,128,128]"
         "{3,2,1,0}) while(%t)", 0.20, 0.09),
        (f"%dynamic-update-slice.9 = {store}{{3,2,1,0}} fusion(%c)",
         0.27, 0.01),
        ("%fusion.10 = bf16[1,64]{1,0} fusion(%a)", 0.30, 0.01),
    ]
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 0.1),
                 ("jit_prefill(2)", 0.2, 0.1), ("jit_x(3)", 0.3, 0.01)],
        ops=ops)], host=[], t_min=0.0, t_max=0.31)
    run = {"trace": tr, "trace_window": (1000.0, 1001.0),
           "records": [SimpleNamespace(
               token_times=[1000.0] + [1000.05] * 40)],
           "config": config, "model": sk, "t0": 1000.0, "t1": 1002.0,
           "counters": {"rows": 192, "n_pages": 9216, "page_size": 64},
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}
    read = lambda name: harness.load_reader(name)(run)
    # 40 row-steps x 3 layers x 4 MiB x 2 over the three decode
    # instructions (the prefill's write of the store is not a decode step's)
    assert read("kda_state_roofline") == pytest.approx(
        100 * 40 * 3 * 4194304 * 2 / 819e9 / 0.012)
    # busy: 0.08 of the decode block, the outer loop's 0.09, the last
    # fusion's 0.01; KDA: 0.004 + 0.002 + 0.006, the scan's 0.03 and the
    # prefill's state write 0.01
    busy = 0.08 + 0.09 + 0.01
    assert read("kda_share") == pytest.approx(100 * 0.052 / busy)


def test_readers_find_nothing_where_there_is_no_kda_state():
    """On a program without the mechanism (the parent commit's: no such
    instruction), and under an adapter without the functions (any other
    configuration's), both readers return None and raise nothing."""
    from benchmark import trace_reduce
    from benchmark.models import mistral
    _, config = config_file()
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 1.0)],
        ops=[("%fusion.1 = bf16[192,4096]{1,0} fusion(bf16[192,4096] %p)",
              0.1, 0.2)])], host=[], t_min=0.0, t_max=1.0)
    run = {"trace": tr, "trace_window": (0.0, 1.0), "records": [],
           "config": config, "model": sk, "t0": 0.0, "t1": 1.0,
           "counters": {"rows": 192, "n_pages": 9216, "page_size": 64},
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}
    for name in OWN:
        assert harness.load_reader(name)(run) is None, name
        assert harness.load_reader(name)(dict(run, model=mistral)) is None
        assert harness.load_reader(name)(dict(run, trace=None)) is None


# -- the rehearsal through drivers/serve.py -----------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_solar_kda.py")],
        capture_output=True, text=True, timeout=1500, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_serves_correctly_and_the_controls_fail(rehearsal):
    """Prefill then decode through ``ContinuousBatcher`` (paged gated
    attention + row slots, slots reused, the pipelined carry) against the
    reference's full forward pass on LOGITS: every served token's reference
    logit within 1e-5 of the reference's best.  Both int8 controls fail
    that tolerance, and a broken sampler is seen."""
    sound, broken, int8 = (rehearsal[k] for k in ("sound", "broken", "int8"))
    assert sound["correct"] is True and sound["finished"] >= 64
    chk = sound["check"]
    assert chk["length_mismatches"] == 0 and chk["max_gap"] <= 1e-5
    assert any("'pipeline_depth':" in ln for ln in rehearsal["lines"])
    # the int8 reference puts another token first somewhere
    assert chk["control_off_best_share"] > 0 and chk["control_max_gap"] > 1e-4
    # the program serving from its own int8 weights is refused
    assert int8["correct"] is False and int8["check"]["max_gap"] > 1e-4
    # prompts that end inside a chunk of 64, on one, inside bucket padding
    assert any(p % 64 for p in sound["prompts"])
    assert any(p % 64 == 0 for p in sound["prompts"])
    # the runner-up sampler is seen
    assert broken["correct"] is False
    assert broken["check"]["off_best_share"] > 0.9


def test_rehearsal_reports_the_cells_entries_and_the_ring(rehearsal):
    metrics = rehearsal["sound"]["metrics"]
    assert set(rehearsal["per_layer"]) >= {n + ".docqa" for n in JOINED} | set(
        OWN)
    for name in ("gen_late_p99_ms.docqa", "decode_rows_mean.docqa",
                 "pool_fill.docqa", "tick_host_ms_p50.docqa"):
        assert name in metrics, name
    assert 0 < metrics["pool_fill.docqa"]["value"] <= 100
    assert metrics["compiles_in_window.docqa"]["value"] == 0
    assert 1.5 <= metrics["decode_rows_mean.docqa"]["value"] <= 3
    ring = rehearsal["ring"]
    # 3 rows, all live at some tick; every block's assignments on the 4
    # held experts of 4 layers: at most rows x top-3 x layers a block
    assert ring["state_rows_max"] == 3
    assert 0 < ring["assignments"] <= ring["blocks"] * 3 * 3 * 4
    assert 1 <= ring["expert_max"] <= 3
    assert ring["touched"] <= min(ring["blocks"] * 4 * 4,
                                  ring["assignments"])
