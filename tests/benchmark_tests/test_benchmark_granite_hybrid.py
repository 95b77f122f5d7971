"""The third model adapter, on the CPU: the plain float32 reference of
Granite-4.0-H's block against the PROGRAM's typed decode path (prefill through
the paged pool and the row-state store, then one-token steps), logits not
tokens; the share test; the configuration file against the catalog's row; the
cell's entries; the adapter's byte counts against hand arithmetic; and a
rehearsal of the cell through ``drivers/serve.py``.

Tolerances.  Program and reference are both float32 here (the tiny
configuration states float32) and differ in the order of their sums only: the
chunked SSD form against the position-by-position recurrence, the flash /
paged attention against a plain softmax, the sorted grouped expert matmul
against every-expert-then-mask.  Measured: 1e-6 .. 3e-6 of the largest logit
(~2.5e-3 at these weights) over every case below; ``RTOL`` 2e-5 of the
largest logit leaves a decade, and a missing term (a dropped assignment, a
state that kept a slot's last row, padding that reached the state) moves a
logit by 1e-2 of it or more."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import hybrid_tiny as ht  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.models import granite_hybrid as gh  # noqa: E402
from benchmark.models import granite_hybrid_reference as ref  # noqa: E402

CELL = "granite4h.gen_batch"
CONFIG = "granite4h-l10-ep2-serve"
RTOL = 2e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def weights(model, seed=11):
    import jax.numpy as jnp
    return gh.make_weights(model, seed, dtype=jnp.float32)


def close(got, want):
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= RTOL * scale, (
        np.abs(got - want).max() / scale)


# -- prefill then decode, against the reference's full forward ---------------

@pytest.mark.parametrize("kinds", [
    "amm",          # attention first in the period
    "mma",          # attention last
    "mmamm",        # attention in the middle, runs of two mamba layers
    "mamama",       # three periods of two: the scan over periods
])
@pytest.mark.parametrize("plen", [
    37,     # ends inside a chunk of 32 AND inside the bucket's padding (48)
    64,     # ends exactly on a chunk and on a bucket
    9,      # narrower than a chunk (width 16)
    33,     # one past a chunk: width 48, either side of the chunk
])
def test_prefill_then_decode_logits_match_the_reference(kinds, plen):
    model = ht.tiny(kinds)
    w = weights(model)
    prompt = np.random.default_rng(plen).integers(0, 256, plen,
                                                  dtype=np.int32)
    # ``dirty``: pool and state hold ones, as a slot another row just left
    # may: the prefill must start from an empty state whatever is there
    got, toks, _ = ht.program_logits(model, w, prompt, 5, dirty=True)
    close(got, ht.reference_logits(model, w, prompt, toks))


def test_a_row_admitted_into_a_slot_another_row_left():
    """Two requests through the same row slot, one store: the second's
    logits are what it would have got in a fresh store."""
    model = ht.tiny("mmamm")
    w = weights(model)
    rng = np.random.default_rng(5)
    first = rng.integers(0, 256, 50, dtype=np.int32)
    second = rng.integers(0, 256, 21, dtype=np.int32)
    _, _, store = ht.program_logits(model, w, first, 6)
    got, toks, _ = ht.program_logits(model, w, second, 6, store=store)
    close(got, ht.reference_logits(model, w, second, toks))


@pytest.mark.parametrize("bucket", [8, 16, 64])
def test_bucket_padding_is_kept_out_of_the_state(bucket):
    """The same prompt under three paddings: the state after it, and so
    every later logit, is the same."""
    model = ht.tiny("mam")
    w = weights(model)
    prompt = np.random.default_rng(3).integers(0, 256, 19, dtype=np.int32)
    got, toks, _ = ht.program_logits(model, w, prompt, 4, bucket=bucket)
    close(got, ht.reference_logits(model, w, prompt, toks))


# -- the share ---------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of one layer, the shared MLP and the mixer
    counted once, add up to the uncut reference's layer (float32, sums in
    another order: 1e-5 of the layer's output)."""
    import jax.numpy as jnp
    whole = ht.tiny("ma", held=8)
    w = weights(whole)
    lay = w["layers"]
    dm = ref.dims(whole)
    x = np.random.default_rng(0).normal(size=(256, 64)).astype(np.float32)
    for li, (kind, ki) in enumerate((("mamba", 0), ("attention", 0))):
        mixed = ref.mixer(jnp.asarray(x), lay, li, ki, dm=dm, kind=kind,
                          quantize=None)
        want = ref.expert_block(mixed, lay, li, dm=dm, quantize=None)
        h = ref.rms_norm(mixed, lay["mlp_norm"][li], dm.eps)
        routed = 0
        for shard in (0, 1):
            part = ht.tiny("ma", held=4, shard=shard)
            cut = {**lay, **{k: lay[k][:, 4 * shard:4 * shard + 4]
                             for k in ("e_gate", "e_up", "e_down")}}
            routed = routed + ref.routed_experts(h, cut, li, ref.dims(part),
                                                 None)
        got = mixed + dm.resid_mult * (routed + ref.shared_mlp(h, lay, li,
                                                               None))
        assert float(jnp.abs(routed).max()) > 0
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())


@pytest.mark.parametrize("shard", [0, 1])
def test_the_program_computes_its_own_share(shard):
    """The program holding experts 4 * shard .. + 3 of 8 against the
    reference given the same share: what the others would add is left out
    of both alike."""
    model = ht.tiny("mam", held=4, shard=shard)
    w = weights(model)
    assert w["layers"]["e_gate"].shape[:2] == (3, 4)
    assert w["layers"]["router"].shape == (3, 64, 8)
    prompt = np.random.default_rng(8).integers(0, 256, 30, dtype=np.int32)
    got, toks, _ = ht.program_logits(model, w, prompt, 4)
    close(got, ht.reference_logits(model, w, prompt, toks))


def test_the_reference_is_the_recurrence():
    """The reference's mamba mixer against the recurrence written out by
    hand in numpy, position by position (float64)."""
    import jax.numpy as jnp
    model = ht.tiny("m")
    w = weights(model)
    dm = ref.dims(model)
    mam = {k: np.asarray(v[0], np.float64)
           for k, v in w["layers"]["mamba"].items()}
    h = np.random.default_rng(2).normal(size=(12, 64))
    got = np.asarray(ref.mamba_mixer(jnp.asarray(h, jnp.float32),
                                     w["layers"]["mamba"], 0, dm, None))
    di, n = 128, 16
    proj = h @ mam["in_proj"]
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], proj[:, -8:]
    silu = lambda v: v / (1 + np.exp(-v))
    conv = np.zeros_like(xbc)
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                conv[t] += mam["conv_w"][j] * xbc[t - 3 + j]
    act = silu(conv + mam["conv_b"])
    dt = np.log1p(np.exp(dt + mam["dt_bias"]))
    a = -np.exp(mam["A_log"])
    s = np.zeros((8, 16, n))
    y = np.zeros((12, 8, 16))
    for t in range(12):
        xt = act[t, :di].reshape(8, 16)
        s = (np.exp(dt[t] * a)[:, None, None] * s + (dt[t][:, None] * xt)
             [:, :, None] * act[t, di:di + n][None, None])
        y[t] = s @ act[t, di + n:] + mam["D"][:, None] * xt
    y = y.reshape(12, di) * silu(z)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * mam["norm"]
    want = y @ mam["out_proj"]
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


# -- the configuration file and the cell ---------------------------------------

def config_file():
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def test_the_configuration_is_the_catalogs_row_but_the_three_cuts():
    entry, config = config_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "granite-4.0-h-small")
    assert entry["source"] == config["source"] == row["source_url"]
    cut = {"num_hidden_layers": 10, "num_local_experts": 36,
           "layer_types": row["config"]["layer_types"][:10]}
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(cut)
    for k, v in row["config"].items():
        want = cut.get(k, v)
        assert config[k] == want and type(config[k]) is type(want), k
        if k in cut:
            assert config["published"][k] == v
    # one whole period of the published pattern, in its published ratio
    kinds = config["layer_types"]
    assert kinds == row["config"]["layer_types"][10:20]
    assert (kinds.count("mamba"), kinds.count("attention")) == (9, 1)
    for what in ("head_dim", "state dtype", "conv tail layout", "weights",
                 "routing", "depth"):
        assert what in config["assumed"]
    dep = config["deployment"]
    assert (dep["rows"], dep["max_len"], dep["page_size"], dep["n_pages"],
            dep["expert_parallel"], dep["expert_shard"]) == (
        64, 8192, 64, 4096, 2, 0)
    assert config["driver"] == "serve" and config["model"] == "granite_hybrid"
    dm = ref.dims(config)
    assert (dm.experts, dm.held, dm.offset, dm.top_k) == (72, 36, 0, 10)


#: the accepted ``tok_s`` lists the cell joined (a suffixed name is read by
#: its base name's file), and the six entries of its own
JOINED = ("gen_late_p99_ms", "decode_rows_mean", "pool_fill",
          "prefill_p50_ms", "decode_block_ms_p50", "attn_kernel_share",
          "pool_copy_share", "tick_host_ms_p50", "host_gap_share",
          "prefill_stall_share", "compiles_in_window")
OWN = ("paged_decode_roofline.hybrid", "ssm_state_roofline",
       "moe_expert_roofline", "ssm_share", "moe_share",
       "moe_load_max_over_mean")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_the_cell_and_its_entries(spec):
    cell = harness.find_cell(spec, CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "gen_batch",
                    "chips": 1}
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    mine = {m["name"]: m
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    assert set(mine) >= {n + ".docqa" for n in JOINED} | set(OWN)
    for m in mine.values():
        assert m["moves"] == "tok_s"
    # the accepted lists it joined are the batch cells' (the attention
    # layer's pool holds a position a token, as Mistral's)
    for n in JOINED:
        assert {"mistral7b.docqa_batch", CELL} <= set(
            mine[n + ".docqa"]["workloads"])
    # its own six: each lists this cell, has a reader and names a layer
    # the benchmark has; the paged kernel's twin states what chat's does
    by = {m["name"]: m for m in spec["per_layer"]}
    layers = {m["layer"] for m in spec["per_layer"] if m["name"] not in OWN}
    for n in OWN:
        assert mine[n]["workloads"] == [CELL] and mine[n]["layer"] in layers
        assert set(mine[n]) == set(by["pool_fill.docqa"])
        assert harness.load_reader(n) is not None
    fields = ("unit", "better", "source", "layer")
    assert [mine["paged_decode_roofline.hybrid"][k] for k in fields] == [
        by["paged_decode_roofline"][k] for k in fields]


def test_gen_batch_offers_thirty_two_widths_in_a_fixed_order():
    from benchmark import traffic_gen
    traffic = traffic_gen.load_traffic("gen_batch")
    a = traffic_gen.make_schedule(traffic, 1, 51, 100352)
    b = traffic_gen.make_schedule(traffic, 2 ** 31 + 5, 51, 100352)
    assert a.kind == "backlog" and len(a.requests) == 1024
    assert [len(r.prompt) for r in a.requests] == [
        len(r.prompt) for r in b.requests]
    assert [r.max_new_tokens for r in a.requests] == [
        r.max_new_tokens for r in b.requests]
    lens = [len(r.prompt) for r in a.requests]
    assert min(lens) == 128 and max(lens) == 4096
    widths = {-(-n // 64) * 64 for n in lens}
    assert all(n % 128 == 0 for n in lens) and len(widths) <= 32
    # about half of them end inside a Mamba chunk of 256
    inside = sum(1 for n in lens if n % 256) / len(lens)
    assert 0.35 <= inside <= 0.65
    outs = [r.max_new_tokens for r in a.requests]
    assert min(outs) >= 128 and max(outs) <= 1024
    assert 1100 <= np.mean(lens) <= 1600 and 450 <= np.mean(outs) <= 650


# -- the adapter's arithmetic --------------------------------------------------

def test_adapter_functions_and_bytes_against_hand_arithmetic():
    _, config = config_file()
    for fn in ("program_config", "make_weights", "int8_program_weights",
               "served_gaps", "kv_bytes_per_context_token",
               "pool_leaf_shapes", "paged_kernel_shape", "token_slots"):
        assert callable(getattr(gh, fn)), fn
    counters = {"rows": 64, "n_pages": 4096, "page_size": 64}
    # ONE attention layer of ten keeps K/V: 2 x 8 heads x 128 x 2 B
    assert gh.kv_bytes_per_context_token(config) == 4096
    assert gh.pool_leaf_shapes(config, counters) == [
        [1, 4096, 8, 64, 128], [4096, 8, 64, 128]]
    assert gh.paged_kernel_shape(config, 64) == [64, 8, 4, 128]
    assert gh.token_slots(config, counters) == 262144
    # a row's state: 9 layers x (128 x 64 x 128 float32 + 3 x 8448 bf16)
    assert gh.state_bytes_per_row(config) == 9 * (4194304 + 50688)
    assert gh.ssm_state_shape(config, 64) == [64, 8192, 128]
    assert gh.ssd_carry_shape(config) == [1, 128, 64, 128]
    # one step of 64 rows: 64 x 9 x 4 MiB, once in and once out
    assert gh.ssm_step_bytes(config, 64) == 2 * 64 * 9 * 4194304
    assert gh.ssm_step_bytes(config, 1) * 64 == gh.ssm_step_bytes(config, 64)
    # an expert matrix is 4096 x 768 bf16 = 6,291,456 B: gate and up for
    # the first kernel, down for the second, of the experts touched
    per = gh.expert_step_bytes(config, 36)
    assert per == {"moe_grouped_swiglu": 2 * 36 * 6291456,
                   "moe_grouped_matmul": 36 * 6291456}
    assert gh.expert_step_bytes(config, 30.5)["moe_grouped_matmul"] == \
        30.5 * 6291456
    # the kernels' rows: 640 assignments in tiles of 16, 36 tiles of padding
    assert gh.expert_kernel_rows(config, 64) == 640 + 36 * 16 == 1216
    assert gh.expert_kernel_rows(config, 4096) == 40960 + 36 * 128


def test_the_adapters_kernel_rows_are_the_programs():
    from tfmesos_tpu.ops import moe
    _, config = config_file()
    for tokens in (1, 64, 128, 512, 1024, 4096):
        tile = moe.pick_tile(tokens * 10, 72)
        rows = -(-tokens * 10 // tile) * tile + 36 * tile
        assert gh.expert_kernel_rows(config, tokens) == rows


def test_program_config_states_the_published_equations():
    import jax.numpy as jnp
    _, config = config_file()
    cfg = gh.program_config(config, 8192)
    assert cfg.layer_types == tuple(config["layer_types"])
    assert (cfg.n_attn_layers, cfg.n_mamba_layers, cfg.layer_period) == (
        1, 9, 10)
    assert cfg.layer_runs == (("mamba", 0, 5, 0), ("attention", 5, 1, 0),
                              ("mamba", 6, 4, 5))
    assert (cfg.mamba_inner, cfg.mamba_conv_dim, cfg.head_dim) == (
        8192, 8448, 128)
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset, cfg.top_k,
            cfg.shared_width, cfg.moe_impl) == (72, 36, 0, 10, 1536,
                                                "grouped")
    assert not cfg.rope and cfg.tie_embeddings
    assert (cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
            cfg.logits_scale) == (0.0078125, 12.0, 0.22, 16.0)
    assert cfg.dtype == jnp.bfloat16 and cfg.logits_dtype == jnp.float32


# -- the rehearsal through drivers/serve.py -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_granite_hybrid.py")],
        capture_output=True, text=True, timeout=1500, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_serves_correctly_and_the_controls_fail(rehearsal):
    sound, broken = rehearsal["sound"], rehearsal["broken"]
    assert sound["correct"] is True and sound["finished"] >= 64
    chk = sound["check"]
    assert chk["length_mismatches"] == 0 and chk["max_gap"] <= 1e-5
    # the int8 control puts another token first somewhere
    assert chk["control_off_best_share"] > 0 and chk["control_max_gap"] > 0
    # prompts that end inside a chunk of 32, on one, inside bucket padding
    assert any(p % 32 for p in sound["prompts"])
    assert any(p % 32 == 0 for p in sound["prompts"])
    assert any(p % 64 for p in sound["prompts"])
    # the runner-up sampler is seen
    assert broken["correct"] is False
    assert broken["check"]["off_best_share"] > 0.9


def test_rehearsal_reports_the_cells_entries_and_the_ring(rehearsal):
    metrics = rehearsal["sound"]["metrics"]
    assert set(rehearsal["per_layer"]) >= {n + ".docqa" for n in JOINED} | set(
        OWN)
    for name in ("gen_late_p99_ms.docqa", "decode_rows_mean.docqa",
                 "moe_load_max_over_mean", "pool_fill.docqa"):
        assert name in metrics, name
    assert 0 < metrics["pool_fill.docqa"]["value"] <= 100
    assert metrics["compiles_in_window.docqa"]["value"] == 0
    assert 1.0 <= metrics["moe_load_max_over_mean"]["value"] <= 20
    assert 1.5 <= metrics["decode_rows_mean.docqa"]["value"] <= 3
    ring = rehearsal["ring"]
    # 3 rows, all live at some tick; every block's assignments on the 4
    # held experts of 5 layers: at most rows x top-3 x layers a block
    assert ring["state_rows_max"] == 3
    assert 0 < ring["assignments"] <= ring["blocks"] * 3 * 3 * 5
    assert 1 <= ring["expert_max"] <= 3
    # at least one expert a layer, at most the 4 held, and never more
    # experts than assignments
    assert ring["blocks"] * 5 <= ring["touched"] <= min(
        ring["blocks"] * 5 * 4, ring["assignments"])


def test_readers_on_a_made_trace_against_hand_arithmetic():
    """A made trace of one decode block (two layers' worth of the named
    instructions) and one prefill with an SSD scan: every new reader
    against arithmetic done by hand."""
    from types import SimpleNamespace

    from benchmark import trace_reduce
    from tfmesos_tpu.fleet.tracing import flight
    _, config = config_file()
    store = "f32[9,64,8192,128]"
    ops = [
        # decode block, 0.0 .. 0.1 s
        (f"%fusion.1 = f32[64,8192]{{1,0}} fusion({store}{{3,2,1,0}} %p)",
         0.00, 0.004),
        (f"%fusion.2 = {store}{{3,2,1,0}} fusion({store}{{3,2,1,0}} %p)",
         0.01, 0.006),
        ("%moe_grouped_swiglu.3 = bf16[1216,768]{1,0} custom-call(%a)",
         0.02, 0.008),
        ("%moe_grouped_matmul.4 = bf16[1216,4096]{1,0} custom-call(%a)",
         0.03, 0.004),
        ("%fusion.5 = bf16[64,4096]{1,0} fusion(%a)", 0.04, 0.058),
        # prefill, 0.2 .. 0.3 s: the scan's while spans its body
        ("%while.6 = (s32[], f32[1,128,64,128]{3,2,1,0}, f32[16,1,256,128,64]"
         "{4,3,2,1,0}) while(%t)", 0.20, 0.02),
        ("%fusion.7 = f32[1,128,64,128]{3,2,1,0} fusion(%b)", 0.20, 0.02),
        (f"%while.8 = (s32[], {store}{{3,2,1,0}}, f32[1,128,64,128]{{3,2,1,0}})"
         " while(%t)", 0.20, 0.09),
        ("%moe_grouped_swiglu.9 = bf16[45568,768]{1,0} custom-call(%a)",
         0.23, 0.03),
        (f"%dynamic-update-slice.10 = {store}{{3,2,1,0}} fusion(%c)",
         0.27, 0.01),
        ("%fusion.11 = bf16[1,64]{1,0} fusion(%a)", 0.30, 0.01),
    ]
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 0.1),
                 ("jit_prefill(2)", 0.2, 0.1), ("jit_x(3)", 0.3, 0.01)],
        ops=ops)], host=[], t_min=0.0, t_max=0.31)
    # 40 row-steps in the traced seconds; one block of k = 1 whose ten
    # layers touched 300 held experts between them
    rec = SimpleNamespace(token_times=[0.0] + [0.05] * 40)
    ring = flight("batcher.tick")
    ring.record({"name": "decode.block", "t": 1000.05, "k": 1,
                 "moe_experts_touched": 300, "moe_assignments": 3200,
                 "moe_expert_max": 20, "wall_ms": 30.0, "batcher": -7})
    run = {"trace": tr, "trace_window": (1000.0, 1001.0),
           "records": [SimpleNamespace(
               token_times=[1000.0 + t for t in rec.token_times])],
           "config": config, "model": gh, "t0": 1000.0, "t1": 1002.0,
           "counters": {"rows": 64, "n_pages": 4096, "page_size": 64},
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}
    read = lambda name: harness.load_reader(name)(run)
    # 40 row-steps x 9 layers x 4 MiB x 2 over the two decode instructions
    assert read("ssm_state_roofline") == pytest.approx(
        100 * 40 * 9 * 4194304 * 2 / 819e9 / 0.010)
    # 30 experts a layer-step: (2 + 1) x 30 x 6,291,456 B over 12 ms; the
    # prefill's kernel (45,568 rows) is not a decode step's
    assert read("moe_expert_roofline") == pytest.approx(
        100 * 3 * 30 * 6291456 / 819e9 / 0.012)
    # busy is the union of every instruction's span, a ``while`` over its
    # body included (as ``busy_s`` has it): 0.08 of the decode block, the
    # outer loop's 0.09, the last fusion's 0.01.  SSM: 0.004 + 0.006, the
    # scan's 0.02 and the prefill's state write 0.01
    busy = 0.08 + 0.09 + 0.01
    assert read("ssm_share") == pytest.approx(100 * 0.04 / busy)
    assert read("moe_share") == pytest.approx(100 * 0.042 / busy)
    # the tick inside the traced seconds is left to the trace's readers
    assert read("moe_load_max_over_mean") is None
    ring.record({"name": "decode.block", "t": 1001.8, "k": 1,
                 "moe_experts_touched": 360, "moe_assignments": 3240,
                 "moe_expert_max": 18, "wall_ms": 30.0, "batcher": -7})
    run["trace_window"] = (None, None)      # an untraced run: both ticks
    assert read("moe_load_max_over_mean") == pytest.approx(
        (20 * 360 / 3200 + 18 * 360 / 3240) / 2)


def test_readers_find_nothing_on_a_program_without_the_mechanisms():
    """On the parent commit's program (no row state, no grouped experts, so
    no such instruction and no such ring field) every new reader returns
    None and raises nothing."""
    from benchmark import trace_reduce
    _, config = config_file()
    tr = trace_reduce.Trace(devices=[trace_reduce.Device(
        name="/device:TPU:0",
        modules=[("jit_decode_block(1)", 0.0, 1.0)],
        ops=[("%fusion.1 = bf16[64,4096]{1,0} fusion(bf16[64,4096] %p)",
              0.1, 0.2)])], host=[], t_min=0.0, t_max=1.0)
    run = {"trace": tr, "trace_window": (0.0, 1.0), "records": [],
           "config": config, "model": gh, "t0": 0.0, "t1": 1.0,
           "counters": {"rows": 64, "n_pages": 4096, "page_size": 64},
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}}}
    for name in ("ssm_state_roofline", "moe_expert_roofline", "ssm_share",
                 "moe_share", "moe_load_max_over_mean"):
        assert harness.load_reader(name)(run) is None, name
