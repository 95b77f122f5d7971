"""The fifth model adapter, on the CPU: the configuration file against the
catalog's row; the cell's entries; the schedule ``mixed_batch`` offers; the
adapter's byte and parameter counts against hand arithmetic; the new readers
on a made trace; the reference against arithmetic done by hand; and a
rehearsal of the cell through ``drivers/serve.py``.  (``tests/test_swa.py``
holds the program's logits against this reference, prefill then decode
through the rings and through ``ContinuousBatcher``, with the tolerance and
its reason.)"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import laguna_tiny as lt  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.models import laguna  # noqa: E402
from benchmark.models import laguna_reference as ref  # noqa: E402

CELL = "laguna.mixed_batch"
CONFIG = "laguna-xs2-l5-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTERS = {"rows": 128, "n_pages": 7168, "page_size": 64}


def config_file():
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


# -- the configuration file and the cell --------------------------------------

def test_the_configuration_is_the_catalogs_row_cut_in_depth_only():
    entry, config = config_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Laguna-XS.2")
    assert entry["source"] == config["source"] == row["source_url"]
    lists = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        ("num_hidden_layers",) + lists)
    for k, v in row["config"].items():
        want = 5 if k == "num_hidden_layers" else v[:5] if k in lists else v
        assert config[k] == want and type(config[k]) is type(want), k
        if k in config["reduced"]:
            assert config["published"][k] == v
    # the leading dense layer once, then one whole period of the sparse
    # layers in the published 3:1; no width, expert or vocabulary row is cut
    assert ref.layer_kinds(config) == ["attention", "window", "window",
                                       "window", "attention"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert ref.kind_heads(config) == {"attention": 48, "window": 64}
    assert (config["num_experts"], config["vocab_size"]) == (256, 100352)
    for what in ("gating", "router", "no q/k norm", "rope pairing",
                 "shared expert", "torch_dtype", "weights", "routing",
                 "window cache", "depth"):
        assert what in config["assumed"], what
    assert "33.44 B" in config["assumed"]["gating"]
    dep = config["deployment"]
    assert (dep["rows"], dep["max_len"], dep["page_size"], dep["n_pages"]) \
        == (128, 17408, 64, COUNTERS["n_pages"])
    assert config["driver"] == "serve" and config["model"] == "laguna"
    # the mean gap over the positions whose routing is decided tells the
    # bf16 program (1.1e-4 on the chip) from its int8 control (1.0e-3):
    # enough requests for ~1,500 such positions, the limit between the two
    # with a factor of two and more on either side; no limit on the tail (a
    # single flipped token reads like the control's)
    chk = config["correct"]
    assert chk["sample_requests"] >= 12 and 0.015 <= chk["decided_margin"]
    assert set(chk["limits"]) == {"mean_gap"}
    assert 2 * 1.25e-4 < chk["limits"]["mean_gap"] < 6.56e-4 / 2


def test_the_parameter_count_is_the_published_one():
    """33.44 B at the published sizes (the published 33.4 B: the per-head
    gate's reading), 3,869.9 M held here."""
    _, config = config_file()
    held = laguna.parameters(config)
    assert abs(held - 3869.9e6) < 0.1e6 and abs(2 * held - 7.74e9) < 0.01e9
    whole = laguna.parameters({**config, **config["published"]})
    assert abs(whole - 33.44e9) < 0.01e9
    # by hand: a full layer's attention 2048 x (48 + 8 + 8) x 128 + 48 x 128
    # x 2048 + the gate 2048 x 48 + two norms; a window layer's at 64 heads
    full = 2048 * 64 * 128 + 48 * 128 * 2048 + 2048 * 48 + 2 * 2048
    win = 2048 * 80 * 128 + 64 * 128 * 2048 + 2048 * 64 + 2 * 2048
    assert (full, win) == (29462528, 37883904)
    sparse = 256 * 3 * 2048 * 512 + 2048 * 256 + 3 * 2048 * 512
    dense = 3 * 2048 * 8192
    head = 2 * 100352 * 2048 + 2048
    assert whole == 10 * full + 30 * win + 39 * sparse + dense + head
    assert held == 2 * full + 3 * win + 4 * sparse + dense + head
    # an elementwise gate would add 2048 x heads x 127 a layer: 34.07 B
    wide = whole + 2048 * 127 * (10 * 48 + 30 * 64)
    assert abs(wide - 34.07e9) < 0.01e9


#: the accepted ``tok_s`` lists the cell joined (a suffixed name is read by
#: its base name's file), and the entries of its own
JOINED = ("gen_late_p99_ms", "decode_rows_mean", "pool_fill",
          "prefill_p50_ms", "decode_block_ms_p50", "attn_kernel_share",
          "pool_copy_share", "tick_host_ms_p50", "host_gap_share",
          "prefill_stall_share", "compiles_in_window",
          "admit_to_first_ms_per_ktok_p50", "stall_share", "gc_pause_share",
          "ready_on_arrival_share")
OWN = {"swa_cache_ratio": ("x", "program_counter", "batcher"),
       "swa_decode_roofline": ("%", "device_trace", "kernels"),
       "swa_share": ("%", "device_trace", "kernels"),
       "paged_decode_roofline.docqa": ("%", "device_trace", "kernels"),
       "moe_sparse_roofline": ("%", "device_trace", "kernels"),
       "moe_share.docqa": ("%", "device_trace", "kernels"),
       "moe_tile_fill": ("%", "program_counter", "kernels"),
       "moe_sparse_load_max_over_mean": ("x", "program_counter", "batcher")}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_the_cell_and_its_entries(spec):
    cell = harness.find_cell(spec, CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "mixed_batch",
                    "chips": 1}
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    mine = {m["name"]: m
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    assert set(mine) >= {n + ".docqa" for n in JOINED} | set(OWN)
    for m in mine.values():
        assert m["moves"] == "tok_s"
    for n in JOINED:
        assert CELL in mine[n + ".docqa"]["workloads"]
        assert len(mine[n + ".docqa"]["workloads"]) > 1
    by = {m["name"]: m for m in spec["per_layer"]}
    for n, (unit, source, layer) in OWN.items():
        assert CELL in mine[n]["workloads"]
        assert set(mine[n]) == set(by["pool_fill.docqa"])
        assert (mine[n]["unit"], mine[n]["source"], mine[n]["layer"]) == (
            unit, source, layer)
        assert harness.load_reader(n) is not None
    # the readers keyed to another configuration's state are not its
    assert not {"ssm_state_roofline", "kda_state_roofline", "eva_pool_fill",
                "moe_expert_roofline"} & set(mine)


def test_mixed_batch_offers_short_and_long_prompts_in_a_fixed_order():
    from benchmark import traffic_gen
    traffic = traffic_gen.load_traffic("mixed_batch")
    assert traffic["schedule_seed"] == 42 and traffic["block"] == 64
    assert (traffic["ramp_s"], traffic["grace_s"]) == (30, 6)
    assert traffic["arrivals"] == {"kind": "backlog", "requests": 1536}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 1.1, "min": 416, "max": 16224,
                                 "quantum": 416}
    assert traffic["output"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    a = traffic_gen.make_schedule(traffic, 1, 51, 100352)
    b = traffic_gen.make_schedule(traffic, 2 ** 31 + 5, 51, 100352)
    assert a.kind == "backlog" and len(a.requests) == 1536
    lens = [len(r.prompt) for r in a.requests]
    outs = [r.max_new_tokens for r in a.requests]
    assert lens == [len(r.prompt) for r in b.requests]
    assert outs == [r.max_new_tokens for r in b.requests]
    assert max(int(r.prompt.max()) for r in b.requests[:100]) < 100352
    # multiples of 416 from 416 to 16224: 39 lengths are possible, and the
    # stratified draw (64 quantiles a block) offers some two dozen of them
    assert min(lens) == 416 and max(lens) == 16224
    assert all(n % 416 == 0 for n in lens)
    assert len(range(416, 16224 + 1, 416)) == 39
    assert 20 <= len(set(lens)) <= 39
    # the odd multiples end 32 positions into a page (and a 64-token bucket)
    assert all(n % 64 in (0, 32) for n in lens)
    assert 0.3 <= sum(1 for n in lens if n % 64) / len(lens) <= 0.7
    # ~14% inside one window of 512, ~10% over 8 k, mean ~3.5 k
    assert 0.10 <= sum(1 for n in lens if n <= 512) / len(lens) <= 0.18
    assert 0.07 <= sum(1 for n in lens if n > 8192) / len(lens) <= 0.14
    assert 3000 <= np.mean(lens) <= 4000 and 350 <= np.mean(outs) <= 520
    assert min(outs) >= 64 and max(outs) <= 1024
    assert max(n + o for n, o in zip(lens, outs)) + 64 <= 17408


# -- the adapter's arithmetic -------------------------------------------------

def test_adapter_functions_and_bytes_against_hand_arithmetic():
    _, config = config_file()
    for fn in ("program_config", "make_weights", "int8_program_weights",
               "served_gaps", "kv_bytes_per_context_token",
               "pool_leaf_shapes", "paged_kernel_shape", "token_slots"):
        assert callable(getattr(laguna, fn)), fn
    n_pages = COUNTERS["n_pages"]
    # the TWO full layers keep pages: 2 layers x (K, V) x 8 heads x 128 x 2 B
    assert laguna.kv_bytes_per_context_token(config) == 8192
    assert laguna.pool_leaf_shapes(config, COUNTERS) == [
        [2, n_pages, 8, 64, 128], [n_pages, 8, 64, 128]]
    # the paged kernel at 6 query heads a K/V head, the ring's at 8
    assert laguna.paged_kernel_shape(config, 128) == [128, 8, 6, 128]
    assert laguna.swa_kernel_shape(config, 128) == [128, 8, 8, 128]
    assert laguna.swa_prefill_heads(config) == 64
    assert laguna.token_slots(config, COUNTERS) == n_pages * 64
    # a row's rings: 3 layers x (K, V) x 8 heads x 512 x 128 x 2 B: 6 MiB,
    # whatever its context; 128 rows: 0.81 GB
    assert laguna.state_bytes_per_row(config) == 3 * 2 * 8 * 512 * 128 * 2 \
        == 6291456
    # a step at position t reads min(t + 1, 512) positions of 3 layers at
    # 4 KB a position a layer
    assert laguna.swa_read_bytes(config, 0) == 3 * 4096
    assert laguna.swa_read_bytes(config, 99) == 100 * 3 * 4096
    assert laguna.swa_read_bytes(config, 511) == 512 * 3 * 4096
    assert laguna.swa_read_bytes(config, 16000) == 512 * 3 * 4096
    # held in full the three window layers would cost 12 KB a position
    # beside the full layers' 8: the pool 2.5 times the size
    assert (3 * 4096 + 8192) / 8192 == 2.5
    assert laguna.expert_layers(config) == 4
    # an expert matrix is 2048 x 512 bf16 = 2,097,152 B: gate and up for the
    # first kernel, down for the second; all 256 touched: 1.61 GB a layer
    per = laguna.expert_step_bytes(config, 256)
    assert per == {"moe_grouped_swiglu": 2 * 256 * 2097152,
                   "moe_grouped_matmul": 256 * 2097152}
    assert sum(per.values()) == 1610612736
    # the kernels' rows at 128 tokens: 1024 assignments in tiles of 16 and
    # 256 tiles of padding
    assert laguna.expert_kernel_rows(config, 128) == 1024 + 256 * 16 == 5120


def test_the_adapters_kernel_rows_are_the_programs():
    from tfmesos_tpu.ops import moe
    _, config = config_file()
    for tokens in (1, 128, 448, 4096, 16256):
        tile = moe.pick_tile(tokens * 8, 256)
        rows = -(-tokens * 8 // tile) * tile + 256 * tile
        assert laguna.expert_kernel_rows(config, tokens) == rows


def test_program_config_states_the_published_equations():
    import jax.numpy as jnp
    _, config = config_file()
    cfg = laguna.program_config(config, 17408)
    assert (cfg.d_model, cfg.n_heads, cfg.window_heads, cfg.kv_heads,
            cfg.head_dim) == (2048, 48, 64, 8, 128)
    assert cfg.layer_kinds == ("attention", "window", "window", "window",
                               "attention")
    assert (cfg.n_lead_layers, cfg.layer_period, cfg.n_sparse_layers) \
        == (1, 4, 4)
    assert cfg.layer_runs == (("window", 0, 3, 0), ("attention", 3, 1, 0))
    assert (cfg.window, cfg.d_ff, cfg.expert_width, cfg.shared_width) == (
        512, 8192, 512, 512)
    assert (cfg.n_experts, cfg.held_experts, cfg.top_k) == (256, 256, 8)
    assert (cfg.router_score, cfg.routed_scale, cfg.attn_gate) == (
        "sigmoid", 2.5, "head")
    assert cfg.dtype == jnp.bfloat16 and cfg.logits_dtype == jnp.float32
    full = cfg.attn_rope.kwargs(128)
    assert full["rotary_dim"] == 64 and full["theta"] == 500000.0
    assert full["factor"] == 1.4158883083359672
    # the program's frequencies are the reference's own (computed apart)
    inv, factor = ref.rope_tables(config, "attention")
    np.testing.assert_allclose(full["inv_freq"], inv, rtol=1e-6)
    assert factor == full["factor"]
    assert cfg.window_rope.kwargs(128) == {"theta": 10000.0}
    inv, factor = ref.rope_tables(config, "window")
    assert len(inv) == 64 and factor == 1.0
    assert inv[1] == pytest.approx(10000.0 ** (-2 / 128))


# -- the reference against arithmetic done by hand --------------------------

def test_the_references_router_and_window_against_hand_arithmetic():
    import jax.numpy as jnp
    model = lt.tiny("FS", 1)
    dm = ref.dims(model)
    rng = np.random.default_rng(0)
    h = rng.standard_normal((5, 64)).astype(np.float32)
    router = rng.standard_normal((1, 64, 16)).astype(np.float32)
    gates, idx, margin = ref.routing(
        jnp.asarray(h), {"router": jnp.asarray(router)}, 0, dm)
    logits = h @ router[0]
    s = 1 / (1 + np.exp(-logits))
    for t in range(5):
        top = np.argsort(-s[t])[:4]
        assert sorted(np.asarray(idx[t])) == sorted(top)
        want = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(gates[t], want / want.sum() * 2.5,
                                   rtol=1e-5)
        # the margin: the fourth router logit over the fifth
        ordered = np.sort(logits[t])[::-1]
        np.testing.assert_allclose(margin[t], ordered[3] - ordered[4],
                                   rtol=1e-4, atol=1e-6)
    # a window layer attends positions max(0, t - 7) .. t: against a plain
    # softmax written out, one head a K/V head, no gate effect (w_g = 0)
    t, hd = ref.Q_BLOCK, 16
    x = rng.standard_normal((t, 64)).astype(np.float32)
    att = {k: jnp.asarray(rng.standard_normal(s_).astype(np.float32) * 0.1)
           for k, s_ in (("wq", (1, 64, 32)), ("wk", (1, 64, 32)),
                         ("wv", (1, 64, 32)), ("wo", (1, 32, 64)))}
    att["wg"] = jnp.zeros((1, 64, 2), jnp.float32)
    inv, fac = ref.rope_tables(model, "window")
    got = np.asarray(ref.attention_mixer(
        jnp.asarray(x), att, 0, dm, 2, 8, inv, fac, None))
    pos = jnp.arange(t)
    q = np.asarray(ref.rope((x @ att["wq"][0]).reshape(t, 2, hd), pos, inv,
                            fac))
    k = np.asarray(ref.rope((x @ att["wk"][0]).reshape(t, 2, hd), pos, inv,
                            fac))
    v = np.asarray(x @ att["wv"][0]).reshape(t, 2, hd)
    o = np.zeros((t, 2, hd), np.float32)
    for i in (0, 3, 7, 8, 20, t - 1):
        lo = max(0, i - 7)
        for head in range(2):
            sc = q[i, head] @ k[lo:i + 1, head].T / math.sqrt(hd)
            p = np.exp(sc - sc.max())
            o[i, head] = (p / p.sum()) @ v[lo:i + 1, head]
        want = 0.5 * o[i].reshape(-1) @ np.asarray(att["wo"][0])
        np.testing.assert_allclose(got[i], want, atol=2e-5)


@pytest.mark.parametrize("tau", [0.0, 0.1, 1e9])
def test_served_gaps_reads_the_decided_positions(tau):
    """``correct.decided_margin``: ``gap`` and ``control_gap`` hold the
    positions whose smallest routing margin over the sparse layers reaches
    it (absent: every position); ``gap_all`` and ``margin`` hold them all."""
    import jax.numpy as jnp
    model = lt.tiny("FSSF", 1)
    weights = laguna.make_weights(model, 11, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, 21, dtype=np.int32)
    served = rng.integers(0, 256, 40, dtype=np.int32)   # any tokens: gaps
    every = ref.served_gaps(weights, model, prompt, served, control=True)
    assert len(every["gap"]) == len(every["margin"]) == 40
    assert (every["gap"] > 0).any() and (every["margin"] > 0).all()
    np.testing.assert_array_equal(every["gap"], every["gap_all"])
    held = dict(model, correct=dict(model["correct"], decided_margin=tau))
    got = ref.served_gaps(weights, held, prompt, served, control=True)
    keep = every["margin"] >= tau
    assert keep.sum() == {0.0: 40, 1e9: 0}.get(tau, keep.sum())
    assert tau != 0.1 or 0 < keep.sum() < 40
    for key in ("gap", "control_gap"):
        np.testing.assert_array_equal(got[key], every[key + "_all"][keep])
        np.testing.assert_array_equal(got[key + "_all"], every[key + "_all"])
    # the margin is the smallest over the sparse layers of the position's
    # own (k-th minus (k+1)-th router logit): read again layer by layer
    seq = np.concatenate([prompt, served[:-1]])
    at = np.arange(20, 60)
    _, margin = ref.read_at(weights, model, seq, at)
    np.testing.assert_allclose(margin, every["margin"], rtol=1e-6)


# -- the readers --------------------------------------------------------------

def made_run(config):
    from types import SimpleNamespace

    from benchmark import trace_reduce
    swa = "bf16[128,8,8,128]"
    paged = "bf16[128,8,6,128]"
    ops = [
        # decode block, 0.0 .. 0.1 s: three window layers, two full ones
        (f"%flash_decode.1 = {swa}{{3,2,1,0}} custom-call(s32[3,128] %s, "
         f"{swa} %q, bf16[3,128,8,512,128]{{4,3,2,1,0}} %k)", 0.00, 0.001),
        (f"%flash_decode.1 = {swa}{{3,2,1,0}} custom-call(s32[3,128] %s, "
         f"{swa} %q, bf16[3,128,8,512,128]{{4,3,2,1,0}} %k)", 0.01, 0.001),
        (f"%flash_decode.1 = {swa}{{3,2,1,0}} custom-call(s32[3,128] %s, "
         f"{swa} %q, bf16[3,128,8,512,128]{{4,3,2,1,0}} %k)", 0.02, 0.002),
        (f"%flash_decode_paged.2 = {paged}{{3,2,1,0}} custom-call(%a)",
         0.03, 0.005),
        ("%moe_grouped_swiglu.4 = bf16[5120,512]{1,0} custom-call(%a)",
         0.04, 0.008),
        ("%moe_grouped_matmul.5 = bf16[5120,2048]{1,0} custom-call(%a)",
         0.05, 0.004),
        ("%fusion.6 = bf16[128,2048]{1,0} fusion(%a)", 0.06, 0.03),
        # prefill, 0.2 .. 0.3 s: a full layer's kernel and a window layer's
        ("%flash_attention_fwd.7 = (bf16[1,48,448,128]{3,2,1,0}, "
         "f32[1,48,448,1]{3,2,1,0}) custom-call(%q, %k, %v)", 0.20, 0.02),
        ("%flash_attention_fwd.8 = (bf16[1,64,448,128]{3,2,1,0}, "
         "f32[1,64,448,1]{3,2,1,0}) custom-call(%q, %k, %v)", 0.23, 0.01),
        ("%moe_grouped_swiglu.9 = bf16[7680,512]{1,0} custom-call(%a)",
         0.25, 0.03),
    ]
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block_pipelined(1)", 0.0, 0.1),
                 ("jit_prefill(2)", 0.2, 0.1)],
        ops=ops)], host=[], t_min=0.0, t_max=0.3)
    # two requests decode inside the traced second: a prompt of 100 with
    # tokens 1..3 (decoded from positions 100..102) and one of 9000 with 1..2
    records = [
        SimpleNamespace(prompt_len=100,
                        token_times=[999.0, 1000.1, 1000.2, 1000.3]),
        SimpleNamespace(prompt_len=9000,
                        token_times=[999.5, 1000.4, 1000.5, 1002.0])]
    return {"trace": tr, "trace_window": (1000.0, 1001.0),
            "records": records, "config": config, "model": laguna,
            "t0": 1000.0, "t1": 1002.0, "counters": COUNTERS,
            "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}


def test_readers_on_a_made_trace_against_hand_arithmetic():
    _, config = config_file()
    run = made_run(config)
    read = lambda name: harness.load_reader(name)(run)
    # rows at positions 100, 101, 102 read 101 + 102 + 103 positions, the
    # rows at 9000 and 9001 a whole window each, of 3 layers at 4 KB
    nbytes = (101 + 102 + 103 + 2 * 512) * 3 * 4096
    assert read("swa_decode_roofline") == pytest.approx(
        100 * nbytes / 819e9 / 0.004)
    # busy: 0.001 + 0.001 + 0.002 + 0.005 + 0.008 + 0.004 + 0.03 and the
    # prefill's 0.02 + 0.01 + 0.03; the window layers': 0.004 + 0.01
    busy = 0.051 + 0.06
    assert read("swa_share") == pytest.approx(100 * 0.014 / busy)
    assert read("moe_share.docqa") == pytest.approx(100 * 0.042 / busy)
    # the paged kernel is told by its 6 query heads a K/V head: 0.005 s for
    # the pages' 8 KB a position of the five decoded tokens' contexts
    ctx = 101 + 102 + 103 + 9001 + 9002
    assert read("paged_decode_roofline.docqa") == pytest.approx(
        100 * ctx * 8192 / 819e9 / 0.005, rel=0.01)


def test_ring_readers_against_hand_arithmetic(monkeypatch):
    from benchmark import tick_readers
    _, config = config_file()
    run = made_run(config)
    block = {"name": "decode.block", "wall_ms": 10.0, "k": 1}
    ring = [
        dict(block, t=1000.1, ctx_positions=9100, swa_positions=612,
             moe_assignments=1024, moe_tile_rows=4096, moe_expert_max=9,
             moe_experts_touched=1000),
        dict(block, t=1000.2, ctx_positions=9102, swa_positions=614,
             moe_assignments=1024, moe_tile_rows=4000, moe_expert_max=11,
             moe_experts_touched=1020),
        {"name": "tick", "t": 1000.3, "wall_ms": 1.0, "k": 0},
    ]
    monkeypatch.setattr(tick_readers, "ring", lambda: ring)
    # the profiler's reach is left out of the window's ticks: none here
    run = dict(run, trace_window=None)
    read = lambda name: harness.load_reader(name)(run)
    assert read("swa_cache_ratio") == pytest.approx(18202 / 1226)
    assert read("moe_tile_fill") == pytest.approx(100 * 2048 / 8096)
    # 1024 assignments over 4 sparse layers x 256 held experts are 1 each
    # in the mean; the fullest took 9 and 11: the median of the two ticks
    assert read("moe_sparse_load_max_over_mean") == pytest.approx(10.0)
    # 2 steps x 4 sparse layers touched 2020 experts: 252.5 a layer-step of
    # 6 MiB each, once a kernel run: one swiglu and one matmul in the trace
    run = dict(run, trace_window=(1000.0, 1001.0))
    per = 252.5 * 2097152
    assert harness.load_reader("moe_sparse_roofline")(run) == pytest.approx(
        100 * (2 * per + per) / 819e9 / 0.012)


def test_readers_find_nothing_where_there_is_no_window_layer(monkeypatch):
    """On a program without the mechanism (the parent commit's: no such
    kernel, no such counter), and under an adapter without the functions
    (any other configuration's), the new readers return None and raise
    nothing."""
    from benchmark import tick_readers, trace_reduce
    from benchmark.models import mistral
    _, config = config_file()
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 1.0)],
        ops=[("%fusion.1 = bf16[128,2048]{1,0} fusion(bf16[128,2048] %p)",
              0.1, 0.2),
             ("%flash_decode_paged.2 = bf16[128,8,8,128]{3,2,1,0} "
              "custom-call(%a)", 0.4, 0.1)])], host=[], t_min=0.0, t_max=1.0)
    run = {"trace": tr, "trace_window": (0.0, 1.0), "records": [],
           "config": config, "model": laguna, "t0": 0.0, "t1": 1.0,
           "counters": COUNTERS,
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}
    monkeypatch.setattr(tick_readers, "ring", lambda: [
        {"name": "decode.block", "t": 0.5, "wall_ms": 1.0, "k": 1,
         "moe_assignments": 5, "moe_experts_touched": 5}])
    for name in ("swa_cache_ratio", "swa_decode_roofline", "swa_share",
                 "moe_sparse_roofline", "moe_tile_fill",
                 "moe_sparse_load_max_over_mean"):
        if name != "moe_sparse_load_max_over_mean":     # the parent counts
            assert harness.load_reader(name)(run) is None, name     # these
        assert harness.load_reader(name)(dict(run, model=mistral)) is None
        assert harness.load_reader(name)(dict(run, trace=None)) is None


# -- the rehearsal through drivers/serve.py -----------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_laguna.py")],
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
    assert set(rehearsal["per_layer"]) >= {n + ".docqa" for n in JOINED} | set(
        OWN)
    for name in ("gen_late_p99_ms.docqa", "decode_rows_mean.docqa",
                 "pool_fill.docqa", "tick_host_ms_p50.docqa",
                 "ready_on_arrival_share.docqa", "swa_cache_ratio",
                 "moe_tile_fill", "moe_sparse_load_max_over_mean"):
        assert name in metrics, name
    assert 0 < metrics["pool_fill.docqa"]["value"] <= 100
    assert metrics["compiles_in_window.docqa"]["value"] == 0
    assert 1.5 <= metrics["decode_rows_mean.docqa"]["value"] <= 3
    # contexts of ~30 on a window of 8: the rings hold a fraction of them
    assert 2 < metrics["swa_cache_ratio"]["value"] < 12
    # 3 rows x top-4 over 16 experts: an expert's tile of 16 holds ~1 row
    assert 100 / 16 <= metrics["moe_tile_fill"]["value"] <= 30
    # 3 rows x top-4 over 16 experts: 0.75 an expert in the mean, the
    # fullest of 64 (layer, expert)s takes 2 or 3
    assert 2 <= metrics["moe_sparse_load_max_over_mean"]["value"] <= 4.5
    ring = rehearsal["ring"]
    assert ring["state_rows_max"] == 3
    # every block of 3 rows (idle ones too) gives 3 x top-4 x 4 sparse
    # layers assignments (booked where the lagged loop reads the block
    # back: a run's last blocks are not); a row holds at most the window
    whole = ring["steps"] * 3 * 4 * 4
    assert 0.95 * whole <= ring["assignments"] <= whole
    assert ring["tile_rows"] == 16 * ring["touched"] >= ring["assignments"]
    assert ring["swa"] <= 8 * 3 * ring["blocks"] and ring["ctx"] > ring["swa"]
