"""The sixth model adapter, on the CPU: the configuration file against the
catalog's row; the cell's entries; the schedule ``agent_batch`` offers; the
adapter's byte, flop and parameter counts against hand arithmetic; the three
new readers over a made trace and a made ring; the reference against
arithmetic done by hand; and a rehearsal of the cell through
``drivers/serve.py``.  (``tests/test_mimo.py`` holds the program's logits
against this reference, prefill then decode through pages and rings and
through ``ContinuousBatcher``, the kernels against their references, the
share test and the sink of minus infinity, each tolerance with its
reason.)"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import mimo_tiny as mt  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.models import mimo  # noqa: E402
from benchmark.models import mimo_reference as ref  # noqa: E402

CELL = "mimo.agent_batch"
CONFIG = "mimo-v2-flash-l7-ep16-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 7,
           "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
           "n_routed_experts": 16, "vocab_size": 19072}


def config_file():
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def counters(config):
    dep = config["deployment"]
    return {k: dep[k] for k in ("rows", "n_pages", "page_size")}


# -- the configuration file and the cell --------------------------------------

def test_the_configuration_is_the_catalogs_row_with_no_width_cut():
    entry, config = config_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "MiMo-V2-Flash")
    assert entry["source"] == config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        REDUCED)
    for k, v in row["config"].items():
        want = REDUCED.get(k, v)
        assert config[k] == want and type(config[k]) is type(want), k
        if k in REDUCED:
            assert config["published"][k] == v
    # the published widths, uncut
    assert (config["hidden_size"], config["num_attention_heads"],
            config["head_dim"], config["v_head_dim"],
            config["num_key_value_heads"], config["swa_num_key_value_heads"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"]) == (
        4096, 64, 192, 128, 4, 8, 16384, 2048, 8, 128)
    assert config["published"]["n_routed_experts"] == 256
    # the leading dense layer once, then five window layers and a full one
    assert ref.layer_kinds(config) == ["attention"] + ["window"] * 4 + [
        "attention", "window"]
    assert ref.n_dense(config) == 1 and ref.rotary_dim(config) == 64
    dm = ref.dims(config)
    assert (dm.experts, dm.held, dm.offset, dm.routed_scale) == (
        256, 16, 0, 1.0)
    for what in ("no q/k norm", "rope pairing", "window", "sink", "router",
                 "attention_chunk_size", "attention_value_scale", "weights",
                 "routing", "window cache", "k layout", "torch_dtype",
                 "prediction layers", "depth"):
        assert what in config["assumed"], what
    dep = config["deployment"]
    assert (dep["rows"], dep["page_size"], dep["expert_parallel"]) == (
        128, 64, 16)
    assert dep["max_len"] >= 18944 and dep["max_len"] % 64 == 0
    assert "16 chips" in config["reduced_why"]
    assert "3,429.9 M" in config["parameters"]["held_here"]
    assert "64" in config["reduced_why"] and "4 rows" in config["reduced_why"]
    assert config["driver"] == "serve" and config["model"] == "mimo"
    # the mean gap over the positions whose routing is decided for this
    # chip tells the bf16 program (at most 7.28e-5 on the chip) from the
    # program serving from its int8 weights (3.01e-4) and from the int8
    # reference (2.42e-4): the one limit between them, a factor of two over
    # the program's largest reading
    chk = config["correct"]
    assert chk["sample_requests"] >= 8 and chk["decided_margin"] == 0.001
    assert set(chk["limits"]) == {"mean_gap"}
    assert 1.99 * 7.28e-5 <= chk["limits"]["mean_gap"] <= 3.01e-4 / 2
    assert len(chk["limits_why"]) > 200


def test_the_parameter_count_is_the_published_one():
    """308.8 B at the published sizes (the published 309 B), 3,429.9 M held
    here: the hand count of ISSUE.md, leaf by leaf."""
    _, config = config_file()
    d = 4096
    full = d * 64 * 192 + d * 4 * 192 + d * 4 * 128 + 64 * 128 * d
    win = d * 64 * 192 + d * 8 * 192 + d * 8 * 128 + 64 * 128 * d
    expert, router, dense = 3 * d * 2048, d * 256, 3 * d * 16384
    assert (full, win, expert, router, dense) == (
        89128960, 94371840, 25165824, 1048576, 201326592)
    layer0 = full + dense
    sparse_w, sparse_f = (a + router + 16 * expert for a in (win, full))
    head = 2 * 19072 * d
    assert (round(layer0 / 1e6, 2), round(sparse_w / 1e6, 2),
            round(sparse_f / 1e6, 2), round(head / 1e6, 2)) == (
        290.46, 498.07, 492.83, 156.24)      # ISSUE.md's 498.08 adds rounded parts
    hand = layer0 + 5 * sparse_w + sparse_f + head
    small = 2 * 7 * d + d + 5 * 64 + 6 * 256   # norms, sinks, selection bias
    held = mimo.parameters(config)
    assert held == hand + small
    assert abs(held - 3429.9e6) < 0.1e6 and round(2 * held / 1e9, 2) == 6.86
    whole = (9 * full + 39 * win + 47 * (256 * expert + router) + dense
             + 2 * 152576 * d)
    assert round(whole / 1e9, 1) == 308.8


#: the accepted ``tok_s`` lists the cell joined (a suffixed name is read by
#: its base name's file), the accepted entries of window and expert layers
#: it joined, and the entries of its own
JOINED = ("gen_late_p99_ms", "decode_rows_mean", "pool_fill",
          "prefill_p50_ms", "decode_block_ms_p50", "attn_kernel_share",
          "pool_copy_share", "tick_host_ms_p50", "host_gap_share",
          "prefill_stall_share", "compiles_in_window",
          "admit_to_first_ms_per_ktok_p50", "stall_share", "gc_pause_share",
          "ready_on_arrival_share")
SHARED = ("swa_cache_ratio", "swa_decode_roofline",
          "paged_decode_roofline.docqa", "moe_sparse_roofline",
          "moe_share.docqa", "moe_tile_fill", "moe_sparse_load_max_over_mean")
OWN = {"attn_fwd_roofline": ("%", "higher", "device_trace", "kernels"),
       "swa_sink_share": ("%", "lower", "device_trace", "kernels"),
       "moe_held_share": ("%", "higher", "program_counter", "batcher")}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_the_cell_and_its_entries(spec):
    cell = harness.find_cell(spec, CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "agent_batch",
                    "chips": 1}
    assert len(cell["why"]) <= 200
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    mine = {m["name"]: m
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    assert set(mine) >= ({n + ".docqa" for n in JOINED} | set(SHARED)
                         | set(OWN))
    for m in mine.values():
        assert m["moves"] == "tok_s"
    for n in [j + ".docqa" for j in JOINED] + list(SHARED):
        assert CELL in mine[n]["workloads"]
        assert len(mine[n]["workloads"]) > 1
        assert harness.load_reader(n) is not None
    by = {m["name"]: m for m in spec["per_layer"]}
    for n, (unit, better, source, layer) in OWN.items():
        assert CELL in mine[n]["workloads"]
        assert set(mine[n]) == set(by["pool_fill.docqa"])
        assert (mine[n]["unit"], mine[n]["better"], mine[n]["source"],
                mine[n]["layer"]) == (unit, better, source, layer)
        assert harness.load_reader(n) is not None
    # both kinds of layer have 64 query heads: ``swa_share``'s reader would
    # count the full layers' forward as the window's
    assert "swa_share" not in mine
    assert not hasattr(mimo, "swa_prefill_heads")


def test_agent_batch_offers_long_prompts_in_a_fixed_order():
    from benchmark import traffic_gen
    _, config = config_file()
    traffic = traffic_gen.load_traffic("agent_batch")
    assert traffic["schedule_seed"] == 45 and traffic["block"] == 64
    assert (traffic["ramp_s"], traffic["grace_s"]) == (30, 6)
    assert traffic["arrivals"] == {"kind": "backlog", "requests": 1024}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 6336,
                                 "sigma": 0.5, "min": 2112, "max": 16896,
                                 "quantum": 1056}
    assert traffic["output"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.5, "min": 256, "max": 2048}
    a = traffic_gen.make_schedule(traffic, 1, 51, 19072)
    b = traffic_gen.make_schedule(traffic, 2 ** 31 + 5, 51, 19072)
    assert a.kind == "backlog" and len(a.requests) == 1024
    lens = [len(r.prompt) for r in a.requests]
    outs = [r.max_new_tokens for r in a.requests]
    assert lens == [len(r.prompt) for r in b.requests]
    assert outs == [r.max_new_tokens for r in b.requests]
    # ids are drawn from the slice of the vocabulary this chip holds
    assert max(int(r.prompt.max()) for r in b.requests[:100]) < 19072
    # multiples of 1,056 from 2,112 to 16,896: 15 lengths, 15 prefill widths
    assert min(lens) == 2112 and max(lens) == 16896
    assert all(n % 1056 == 0 for n in lens)
    assert len(set(lens)) <= 15 == len(range(2112, 16896 + 1, 1056))
    assert len({-(-n // 64) * 64 for n in lens}) == len(set(lens)) >= 12
    # the odd multiples end 32 positions into a page and into a window
    assert all(n % 64 in (0, 32) and n % 128 in (0, 32, 64, 96) for n in lens)
    assert 0.3 <= sum(1 for n in lens if n % 64) / len(lens) <= 0.7
    # about a third past 8,192, ~3% at the maximum, means ~7.1 k and ~870
    assert 0.25 <= sum(1 for n in lens if n > 8192) / len(lens) <= 0.40
    assert 0.01 <= sum(1 for n in lens if n == 16896) / len(lens) <= 0.06
    assert 6600 <= np.mean(lens) <= 7600 and 780 <= np.mean(outs) <= 960
    assert min(outs) >= 256 and max(outs) <= 2048
    assert max(n + o for n, o in zip(lens, outs)) <= config["deployment"][
        "max_len"]


# -- the adapter's arithmetic -------------------------------------------------

def test_adapter_functions_and_bytes_against_hand_arithmetic():
    _, config = config_file()
    c = counters(config)
    for fn in ("program_config", "make_weights", "int8_program_weights",
               "served_gaps", "kv_bytes_per_context_token",
               "pool_leaf_shapes", "paged_kernel_shape", "token_slots"):
        assert callable(getattr(mimo, fn)), fn
    # the TWO full layers keep pages: 2 layers x 4 heads x (192 + 128) x 2 B,
    # what a step HAS to read whatever a layout pads
    assert mimo.kv_bytes_per_context_token(config) == 5120
    # the pool's two leaves differ: two heads' keys side by side, V as ever
    n = c["n_pages"]
    assert mimo.pool_leaf_shapes(config, c) == [
        [2, n, 2, 64, 384], [n, 2, 64, 384], [2, n, 4, 64, 128],
        [n, 4, 64, 128]]
    assert mimo.paged_kernel_shape(config, 128) == [128, 4, 16, 128]
    assert mimo.swa_kernel_shape(config, 128) == [128, 8, 8, 128]
    assert mimo.token_slots(config, c) == n * 64
    # a row's rings: 5 layers x 8 heads x 128 positions x 320 channels x 2 B
    assert mimo.state_bytes_per_row(config) == 5 * 8 * 128 * 320 * 2 \
        == 3276800
    per_pos = 5 * 8 * 320 * 2
    assert mimo.swa_read_bytes(config, 0) == per_pos
    assert mimo.swa_read_bytes(config, 99) == 100 * per_pos
    assert mimo.swa_read_bytes(config, 127) == 128 * per_pos
    assert mimo.swa_read_bytes(config, 16000) == 128 * per_pos
    assert mimo.expert_layers(config) == 6 and mimo.held_experts(config) == 16
    # an expert matrix is 4096 x 2048 bf16 = 16,777,216 B: gate and up for
    # the first kernel, down for the second
    per = mimo.expert_step_bytes(config, 16)
    assert per == {"moe_grouped_swiglu": 2 * 16 * 16777216,
                   "moe_grouped_matmul": 16 * 16777216}
    # the kernels' rows at 128 tokens: 1024 assignments in tiles of 16 and
    # 16 tiles of padding
    assert mimo.expert_kernel_rows(config, 128) == 1024 + 16 * 16 == 1280
    # a prompt's attention forward: a full layer every earlier position, a
    # window layer at most 128, 64 heads x (192 + 128) x 2 a pair
    pair = 64 * 320 * 2
    assert mimo.attn_fwd_flops(config, 1) == 7 * pair
    assert mimo.attn_fwd_flops(config, 100) == 7 * 5050 * pair
    t = 6336
    band = 128 * 129 // 2 + (t - 128) * 128
    assert mimo.attn_fwd_flops(config, t) == pair * (
        2 * t * (t + 1) // 2 + 5 * band)


def test_the_adapters_rules_are_the_programs():
    from tfmesos_tpu.ops import attention, moe
    _, config = config_file()
    for tokens in (1, 128, 2112, 16896):
        tile = moe.pick_tile(tokens * 8, 256)
        rows = -(-tokens * 8 // tile) * tile + 16 * tile
        assert mimo.expert_kernel_rows(config, tokens) == rows
    assert mimo._k_pack(config, "attention") == attention.pack_k(192, 4) == 2
    assert mimo._k_pack(config, "window") == attention.pack_k(192, 8) == 2
    tiny = mt.tiny()
    assert mimo._k_pack(tiny, "window") == attention.pack_k(24, 4) == 1


def test_program_config_states_the_published_equations():
    import jax
    import jax.numpy as jnp
    from tfmesos_tpu.models import transformer
    _, config = config_file()
    dep = config["deployment"]
    cfg = mimo.program_config(config, dep["max_len"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.v_head_dim) == (4096, 64, 4, 192, 128)
    assert (cfg.kind_heads("window"), cfg.kind_kv_heads("window"),
            cfg.window, cfg.window_sink) == (64, 8, 128, True)
    assert cfg.attn_value_scale == 0.707 and cfg.k_pack() == 2
    assert cfg.attn_rope.kwargs(192)["rotary_dim"] == 64
    assert (cfg.attn_rope.theta, cfg.window_rope.theta) == (5e6, 1e4)
    assert cfg.ffn_types == ("dense",) + ("sparse",) * 6
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.top_k,
            cfg.router_score, cfg.routed_scale, cfg.shared_width) == (
        256, 16, 0, 8, "sigmoid", 1.0, 0)
    assert cfg.logits_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    # the pool's bytes a position as laid out are the 5,120 it has to cost
    pool = jax.eval_shape(lambda: transformer.init_paged_cache(
        cfg, dep["n_pages"], dep["page_size"]))
    nbytes = sum(np.prod(leaf.shape) * 2 for leaf in pool.values())
    assert nbytes == dep["n_pages"] * 64 * 5120
    assert pool["k"].shape[-1] % 128 == 0 and pool["v"].shape[-1] % 128 == 0
    state = jax.eval_shape(lambda: transformer.init_row_state(cfg, 128))
    assert sum(np.prod(leaf.shape) * 2 for leaf in state.values()) \
        == 128 * mimo.state_bytes_per_row(config)
    # the weights' tree is the program's own, leaf for leaf
    mine = jax.eval_shape(lambda: mimo.make_weights(config, 1))
    theirs = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    flat = lambda t: {jax.tree_util.keystr(k): (v.shape, v.dtype) for k, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(mine) == flat(theirs)
    # a program that lacks the fields says so at once
    import dataclasses
    real = dataclasses.fields

    def without(cls):
        return [f for f in real(cls) if f.name != "window_sink"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dataclasses, "fields", without)
        with pytest.raises(SystemExit, match="window_sink"):
            mimo.program_config(config, 128)


# -- the reference against arithmetic done by hand ------------------------------

def test_the_references_sink_window_and_router_against_hand_arithmetic():
    import jax
    import jax.numpy as jnp
    model = mt.tiny("S", dense=0, held=8)
    dm = ref.dims(model)
    rng = np.random.default_rng(0)
    w = mimo.make_weights(model, 3, jnp.float32)["layers"]
    h = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    # one window layer by hand, position 20: keys 13 .. 20, a sink a head
    att = w["window"]
    inv = ref.rope_freqs(model, "window")
    assert len(inv) == 4 and inv[0] == 1.0
    got = ref.attention_mixer(h, att, 0, dm, 4, 8, True, inv, None)
    pos = jnp.arange(128)
    q = ref.rope((h @ att["wq"][0]).reshape(128, 8, 24), pos, inv)
    k = ref.rope((h @ att["wk"][0]).reshape(128, 4, 24), pos, inv)
    v = (h @ att["wv"][0]).reshape(128, 4, 16) * 0.707
    # the last 16 channels of a head pass through unrotated
    np.testing.assert_allclose(
        q[:, :, 8:], (h @ att["wq"][0]).reshape(128, 8, 24)[:, :, 8:])
    out = []
    for head in range(8):
        s = (k[13:21, head // 2] @ q[20, head]) / np.sqrt(24.0)
        b = att["sink"][0, head]
        m = jnp.maximum(s.max(), b)
        p = jnp.exp(s - m) / (jnp.exp(s - m).sum() + jnp.exp(b - m))
        assert float(p.sum()) < 1.0         # the sink took its share
        out.append(p @ v[13:21, head // 2])
    want = jnp.concatenate(out) @ att["wo"][0]
    np.testing.assert_allclose(got[20], want, atol=2e-5)
    # the router: the 2 largest of sigmoid + bias, gates over their sum
    gates, idx, margin = ref.routing(h, w, 0, dm)
    s = jax.nn.sigmoid(h @ w["router"][0])
    top = np.argsort(-np.asarray(s + w["router_bias"][0]), axis=1)[:, :2]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(top, 1))
    np.testing.assert_allclose(gates.sum(1), 1.0, atol=1e-6)
    kept = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(gates, kept / kept.sum(1, keepdims=True),
                               atol=1e-6)
    assert np.isinf(np.asarray(margin)).all()   # every expert held: no edge
    part = ref.dims(mt.tiny("S", dense=0, held=2, shard=1))
    assert (part.held, part.offset, part.experts) == (2, 2, 8)
    _, idx, margin = ref.routing(h, w, 0, part)
    score = np.sort(np.asarray(s + w["router_bias"][0]), axis=1)[:, ::-1]
    third = np.argsort(-np.asarray(s + w["router_bias"][0]), axis=1)[:, 2]
    here = lambda e: (e >= 2) & (e < 4)
    second = np.asarray(idx)[:, 1]
    edge = here(second) != here(third)
    assert 0 < edge.sum() < 128
    np.testing.assert_allclose(np.asarray(margin)[edge],
                               (score[:, 1] - score[:, 2])[edge], rtol=1e-5)
    assert np.isinf(np.asarray(margin)[~edge]).all()


@pytest.mark.parametrize("tau", [0.0, 1e9])
def test_served_gaps_reads_the_decided_positions(tau):
    import jax.numpy as jnp
    model = mt.tiny()
    weights = mimo.make_weights(model, 9, jnp.float32)
    rng = np.random.default_rng(1)
    prompt, served = rng.integers(0, 256, 21), rng.integers(0, 256, 40)
    every = ref.served_gaps(weights, model, prompt, served, control=True)
    assert every["gap"].shape == (40,) and (every["gap"] > 0).any()
    held = dict(model, correct=dict(model["correct"], decided_margin=tau))
    got = ref.served_gaps(weights, held, prompt, served, control=True)
    keep = every["margin"] >= tau
    assert keep.sum() == (40 if tau == 0.0 else np.isinf(
        every["margin"]).sum())
    for key in ("gap", "control_gap"):
        np.testing.assert_array_equal(got[key], every[key + "_all"][keep])


# -- the readers ----------------------------------------------------------------

def made_run(config):
    from benchmark import trace_reduce
    swa, paged = "bf16[128,8,8,128]", "bf16[128,4,16,128]"
    fwd = ("%flash_attention_fwd{}.{} = (bf16[1,64,{},128]{{3,2,1,0}}, "
           "f32[1,64,{},1]{{3,2,1,0}}) custom-call(%q, %k, %v)")
    prompt = "%fusion.{} = s32[{}]{{0}} fusion(s32[1,{}]{{1,0}} %prompt.1)"
    ops = [
        # a decode block, 0.0 .. 0.1 s: the rings' kernel, the pages' kernel
        (f"%flash_decode.1 = {swa}{{3,2,1,0}} custom-call(%s, %q, %k)",
         0.00, 0.002),
        (f"%flash_decode.1 = {swa}{{3,2,1,0}} custom-call(%s, %q, %k)",
         0.01, 0.002),
        (f"%flash_decode_paged.2 = {paged}{{3,2,1,0}} custom-call(%a)",
         0.03, 0.006),
        ("%fusion.6 = bf16[128,4096]{1,0} fusion(%a)", 0.06, 0.03),
        # a whole prefill of width 2112 (a prompt of 2112), 0.2 .. 0.3 s
        (prompt.format(7, 2112, 2112), 0.20, 0.001),
        (fwd.format("", 8, 2112, 2112), 0.21, 0.004),
        (fwd.format("_sink", 9, 2112, 2112), 0.22, 0.002),
        ("%moe_grouped_swiglu.9 = bf16[17152,2048]{1,0} custom-call(%a)",
         0.25, 0.03),
        # a whole prefill of width 3200 (a prompt of 3168), 0.4 .. 0.5 s
        (prompt.format(10, 3200, 3200), 0.40, 0.001),
        (fwd.format("", 11, 3200, 3200), 0.41, 0.008),
        (fwd.format("_sink", 12, 3200, 3200), 0.42, 0.003),
        # a prefill the trace's end cuts (width 16896): in neither side
        (prompt.format(13, 16896, 16896), 0.60, 0.001),
        (fwd.format("", 14, 4608, 4608), 0.61, 0.03),
    ]
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block_pipelined(1)", 0.0, 0.1),
                 ("jit_prefill(2)", 0.2, 0.1), ("jit_prefill(3)", 0.4, 0.1),
                 ("jit_prefill(4)", 0.6, 0.1)],
        ops=ops)], host=[], t_min=0.0, t_max=0.7)
    requests = [SimpleNamespace(prompt=np.zeros(n, np.int32))
                for n in (2112, 3168, 4224, 16896)]
    return {"trace": tr, "trace_window": (1000.0, 1001.0), "records": [],
            "config": config, "model": mimo, "t0": 1000.0, "t1": 1002.0,
            "counters": counters(config),
            "schedule": SimpleNamespace(requests=requests, kind="backlog"),
            "device": {"peaks": {"hbm_bytes_per_s": 819e9,
                                 "bf16_flops": 197e12}}}


def test_trace_readers_against_hand_arithmetic():
    _, config = config_file()
    run = made_run(config)
    read = lambda name: harness.load_reader(name)(run)
    # the two whole prefills' flops at their REAL lengths (2112, and 3168
    # inside a width of 3200) over their forward kernels' 0.006 + 0.011 s;
    # the prefill the trace's end cuts counts on neither side
    flops = mimo.attn_fwd_flops(config, 2112) + mimo.attn_fwd_flops(
        config, 3168)
    assert read("attn_fwd_roofline") == pytest.approx(
        100 * flops / 197e12 / 0.017)
    # busy: the block's 0.04, the prefills' 0.037, 0.012 and 0.031
    busy = 0.04 + 0.037 + 0.012 + 0.031
    assert read("swa_sink_share") == pytest.approx(
        100 * (0.004 + 0.002 + 0.003) / busy)
    assert mimo.swa_forward_ops(run) == [(0.22, 0.002), (0.42, 0.003)]
    # the forward cannot read over 100% however short the kernels: what
    # they multiplied at the padded width covers the real length's flops
    assert mimo.attn_fwd_flops(config, 3168) < mimo.attn_fwd_flops(
        config, 3200)


def test_the_forwards_share_leaves_out_a_cut_prefill():
    """With only the cut prefill in the trace there is nothing to read; were
    its flops counted over the kernel time the trace holds of it, the share
    would read over 100%."""
    from benchmark import trace_reduce
    _, config = config_file()
    run = made_run(config)
    dev = run["trace"].devices[0]
    cut = trace_reduce.Trace(devices=[trace_reduce.Device(
        name=dev.name, modules=dev.modules[-1:], ops=dev.ops[-2:])],
        host=[], t_min=0.0, t_max=0.7)
    assert harness.load_reader("attn_fwd_roofline")(
        dict(run, trace=cut)) is None
    assert 100 * mimo.attn_fwd_flops(config, 16896) / 197e12 / 0.03 > 100


def test_ring_reader_against_hand_arithmetic(monkeypatch):
    from benchmark import tick_readers
    _, config = config_file()
    run = dict(made_run(config), trace_window=None)
    block = {"name": "decode.block", "wall_ms": 10.0, "k": 1}
    ring = [dict(block, t=1000.1, moe_assignments=400, moe_routed=6144),
            dict(block, t=1000.2, moe_assignments=368, moe_routed=6144),
            {"name": "tick", "t": 1000.3, "wall_ms": 1.0, "k": 0}]
    monkeypatch.setattr(tick_readers, "ring", lambda: ring)
    # 128 rows x top-8 x 6 expert layers are 6,144 a step; 768 fell here
    assert harness.load_reader("moe_held_share")(run) == pytest.approx(
        100 * 768 / 12288) == 6.25


def test_readers_find_nothing_on_a_program_without_the_mechanism(monkeypatch):
    """On the parent commit's program (no sink forward, no ``moe_routed``
    counter) and under an adapter without the functions (any other
    configuration's), the new readers return None and raise nothing."""
    from benchmark import tick_readers, trace_reduce
    from benchmark.models import mistral
    _, config = config_file()
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 1.0)],
        ops=[("%fusion.1 = bf16[128,4096]{1,0} fusion(bf16[128,4096] %p)",
              0.1, 0.2),
             ("%flash_decode_paged.2 = bf16[128,8,4,128]{3,2,1,0} "
              "custom-call(%a)", 0.4, 0.1)])], host=[], t_min=0.0, t_max=1.0)
    run = dict(made_run(config), trace=tr, trace_window=(0.0, 1.0), t0=0.0,
               t1=1.0)
    monkeypatch.setattr(tick_readers, "ring", lambda: [
        {"name": "decode.block", "t": 0.5, "wall_ms": 1.0, "k": 1,
         "moe_assignments": 5, "moe_experts_touched": 5}])
    for name in OWN:
        assert harness.load_reader(name)(run) is None, name
        assert harness.load_reader(name)(dict(run, model=mistral)) is None
        assert harness.load_reader(name)(dict(run, trace=None)) is None


# -- the rehearsal through drivers/serve.py -----------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_mimo.py")],
        capture_output=True, text=True, timeout=1500, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_serves_correctly_and_the_controls_fail(rehearsal):
    """Prefill then decode through ``ContinuousBatcher`` (pages for the
    full layers, rings for the window layers, slots reused, the pipelined
    carry) against the reference's full forward pass on LOGITS: every served
    token's reference logit within 2e-5 of the reference's best.  Both int8
    controls fail that tolerance, and a broken sampler is seen."""
    sound, broken, int8 = (rehearsal[k] for k in ("sound", "broken", "int8"))
    assert sound["correct"] is True and sound["finished"] >= 64
    chk = sound["check"]
    assert chk["length_mismatches"] == 0 and chk["max_gap"] <= 2e-5
    assert any("'pipeline_depth':" in ln for ln in rehearsal["lines"])
    assert chk["control_off_best_share"] > 0 and chk["control_max_gap"] > 1e-4
    assert int8["correct"] is False and int8["check"]["max_gap"] > 1e-4
    # prompts inside one window of 8, on its edge, and many windows long
    assert {4, 8} <= set(sound["prompts"]) and max(sound["prompts"]) > 64
    assert chk["longest_context"] > 64
    assert broken["correct"] is False
    assert broken["check"]["off_best_share"] > 0.9


def test_rehearsal_reports_the_cells_entries_and_the_ring(rehearsal):
    metrics = rehearsal["sound"]["metrics"]
    assert set(rehearsal["per_layer"]) >= ({n + ".docqa" for n in JOINED}
                                           | set(SHARED) | set(OWN))
    for name in ("gen_late_p99_ms.docqa", "decode_rows_mean.docqa",
                 "pool_fill.docqa", "tick_host_ms_p50.docqa",
                 "ready_on_arrival_share.docqa", "swa_cache_ratio",
                 "moe_tile_fill", "moe_sparse_load_max_over_mean",
                 "moe_held_share"):
        assert name in metrics, name
    assert 0 < metrics["pool_fill.docqa"]["value"] <= 100
    assert metrics["compiles_in_window.docqa"]["value"] == 0
    assert 1.5 <= metrics["decode_rows_mean.docqa"]["value"] <= 3
    # contexts of ~30 on a window of 8: the rings hold a fraction of them
    assert 2 < metrics["swa_cache_ratio"]["value"] < 12
    # 2 of 8 experts held: a quarter of the routers' assignments when even
    assert 15 <= metrics["moe_held_share"]["value"] <= 35
    ring = rehearsal["ring"]
    assert ring["state_rows_max"] == 3
    # every block of 3 rows (idle ones too) routes 3 x top-2 x 6 sparse
    # layers assignments a step (booked where the lagged loop reads the
    # block back: a run's last blocks are not)
    whole = ring["steps"] * 3 * 2 * 6
    assert 0.95 * whole <= ring["routed"] <= whole
    assert ring["assignments"] < ring["routed"]
    assert ring["tile_rows"] == 16 * ring["touched"] >= ring["assignments"]
    assert ring["swa"] <= 8 * 3 * ring["blocks"] and ring["ctx"] > ring["swa"]
