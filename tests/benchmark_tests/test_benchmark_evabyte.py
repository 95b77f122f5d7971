"""The EvaByte configuration, its adapter and the cell ``evabyte.
longdoc_batch``: the file against the catalog's values, the adapter's own
arithmetic (entries, not positions), a CPU rehearsal of the cell at a tiny
size through ``drivers/serve.py`` (``rehearse_evabyte.py``), and every
per-layer metric BENCHMARK.json lists for the cell, read from that run's tick
ring or from a trace made here: the accepted ``.docqa`` entries whose lists
the cell joined, and the four entries of its own (``eva_*``).  A program
without EVA's counters and names (the parent commit) leaves those four
readers nothing to read, and they say so."""

import gzip
import json
import math
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane_wire as xw            # a helper beside this file

from benchmark import harness, trace_reduce as tr
from benchmark.models import evabyte, mistral

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "evabyte.longdoc_batch"
#: the catalog's ``config`` for EvaByte (beside the model-configs guide),
#: key for key
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
NEW = ("eva_pool_fill", "eva_decode_roofline", "eva_cache_ratio",
       "eva_roll_share")
#: the accepted entries that read right under EVA; ``pool_fill.docqa``
#: multiplies positions by the adapter's bytes per ENTRY and stays out
LISTED = ("gen_late_p99_ms", "decode_rows_mean", "prefill_p50_ms",
          "decode_block_ms_p50", "attn_kernel_share", "pool_copy_share",
          "tick_host_ms_p50", "host_gap_share", "prefill_stall_share",
          "compiles_in_window")


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "evabyte-l16-serve.json")


def test_the_file_is_the_catalogs_config_but_for_its_layers(config):
    for k, v in CATALOG.items():
        if k != "num_hidden_layers":
            assert config[k] == v and type(config[k]) is type(v), k
    assert config["num_hidden_layers"] == 16
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    for what in ("chunk pooling", "summaries from rotated keys",
                 "order of the prediction heads", "weights", "depth"):
        assert what in config["assumed"]
    dep = config["deployment"]
    assert (dep["rows"], dep["max_len"], dep["page_size"]) == (16, 32768, 64)
    # a closed window leaves whole pages of summaries
    assert (config["window_size"] // config["chunk_size"]) % dep[
        "page_size"] == 0


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_the_cell_and_its_entries(spec):
    cell = harness.find_cell(spec, CELL)
    assert cell == {**cell, "config": "evabyte-l16-serve",
                    "traffic": "longdoc_batch", "chips": 1}
    e2e = {m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    mine = {m["name"]: m
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    assert set(mine) >= {n + ".docqa" for n in LISTED} | set(NEW)
    # these two multiply POSITIONS by the adapter's bytes per entry
    assert not {"pool_fill", "paged_decode_roofline"} & {
        n.split(".")[0] for n in mine}
    for m in mine.values():
        assert m["moves"] == "tok_s"
    # the accepted lists it joined are the batch cells'
    for n in LISTED:
        assert "mistral7b.docqa_batch" in mine[n + ".docqa"]["workloads"]
    # its own four: a reader each, a layer the benchmark has
    layers = {m["layer"] for m in spec["per_layer"] if m["name"] not in NEW}
    for n in NEW:
        assert mine[n]["workloads"] == [CELL] and mine[n]["layer"] in layers
        assert harness.load_reader(n) is not None


def test_every_entry_read_from_the_tick_ring_names_its_reader_and_layer(spec):
    """The cell reports all four of the ring's quantities, each under an
    entry that ``tick_readers`` reads (test_benchmark_tick_readers.py holds
    every such entry to its layer and source, wherever it stands)."""
    from benchmark import tick_readers
    ring = ("tick_host_ms_p50", "host_gap_share", "prefill_stall_share",
            "compiles_in_window")
    mine = {m["name"].split(".")[0]: m["name"]
            for m in harness.cell_metrics(spec, CELL, "per_layer")}
    for base in ring:
        assert harness.load_reader(mine[base]) is getattr(tick_readers, base)


def test_longdoc_batch_offers_sixteen_lengths_in_a_fixed_order():
    from benchmark import traffic_gen
    t = traffic_gen.load_traffic("longdoc_batch")
    a = traffic_gen.make_schedule(t, 1, 51, 320)
    b = traffic_gen.make_schedule(t, 2 ** 31 + 5, 51, 320)
    lens = [(len(r.prompt), r.max_new_tokens) for r in a.requests]
    assert lens == [(len(r.prompt), r.max_new_tokens) for r in b.requests]
    assert len(lens) == 96 and a.kind == "backlog"
    prompts = sorted({p for p, _ in lens})
    assert len(prompts) == 16 and prompts[0] == 3072 and prompts[-1] == 24576
    assert all(p % 256 == 0 for p in prompts)
    assert min(o for _, o in lens) == 256 and max(o for _, o in lens) == 2048
    assert max(int(r.prompt.max()) for r in a.requests) < 320
    assert max(p + o for p, o in lens) <= 32768


def test_the_adapter_counts_entries_not_positions(config):
    """E(T) = 128 * (T // 2048) + T % 2048; the bytes are per entry; a decode
    step reads its row's entries."""
    e = lambda t: evabyte.cache_entries(config, t)
    assert [e(t) for t in (0, 1, 2047, 2048, 2049, 8192, 8960, 32767)] == [
        0, 1, 2047, 128, 129, 512, 512 + 768, 15 * 128 + 2047]
    assert max(e(t) for t in range(0, 32768, 7)) < 3968 + 1
    per = evabyte.kv_bytes_per_context_token(config)
    assert per == 16 * 2 * 32 * 128 * 2 == mistral.kv_bytes_per_context_token(
        dict(config))
    assert evabyte.decode_read_bytes(config, [8960, 100]) == per * (1280 + 100)
    counters = {"rows": 16, "n_pages": 372, "page_size": 64}
    assert evabyte.paged_kernel_shape(config, 16) == [16, 32, 1, 128]
    assert evabyte.token_slots(config, counters) == 372 * 64
    assert evabyte.pool_leaf_shapes(config, counters) == [
        [16, 372, 32, 64, 128], [372, 32, 64, 128]]
    import numpy as np
    np.testing.assert_array_equal(
        evabyte.cache_entries(config, np.asarray([2048, 5000])), [128, 1160])


def test_the_program_config_states_what_the_file_states(config):
    import jax.numpy as jnp
    cfg = evabyte.program_config(config, 32768)
    assert (cfg.attention, cfg.eva_chunk, cfg.eva_window) == ("eva", 16, 2048)
    assert cfg.norm_eps == 1e-5 and cfg.norm_offset
    assert cfg.residual_dtype == jnp.float32 == cfg.logits_dtype
    assert cfg.dtype == jnp.bfloat16 and cfg.n_pred_heads == 8
    assert cfg.rope_theta == 1e5 and cfg.kv_heads == 32 and cfg.d_ff == 11008
    # the program's counter and the adapter's agree
    for t in (0, 5, 2048, 9000, 32767):
        assert cfg.cache_entries(t) == evabyte.cache_entries(config, t)
    sh = evabyte.shapes(config)
    assert sh["head"][0] == (4096, 8 * 320)
    assert sh["layers"]["eva_phi"] == ((16, 32, 128), 1.0)
    n = sum(math.prod(s) for s, _ in
            [sh["embed"], sh["head"], *sh["layers"].values()])
    assert 3.24e9 < n < 3.27e9


def test_weights_come_from_the_seed_in_the_programs_tree():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tfmesos_tpu.models.transformer import init_params
    import rehearse_evabyte as rh
    cfg = evabyte.program_config(rh.TINY, 512)
    w = evabyte.make_weights(rh.TINY, 2 ** 31 + 9, dtype=jnp.float32)
    again = evabyte.make_weights(rh.TINY, 2 ** 31 + 9, dtype=jnp.float32)
    other = evabyte.make_weights(rh.TINY, 2 ** 31 + 10, dtype=jnp.float32)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda a: a.shape, w) == \
        jax.tree_util.tree_map(lambda a: a.shape, want)
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), w, again)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((w["embed"] == other["embed"]).all())
    assert 0.5 < float(np.std(np.asarray(w["layers"]["eva_phi"]))) < 1.5
    assert abs(float(np.mean(np.asarray(w["norm_f"])))) < 0.1   # gains near 0


# -- the cell, rehearsed --------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_evabyte.py")],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_the_cell_is_served_and_read_by_the_eva_reference(rehearsal):
    sound = rehearsal["sound"]
    assert sound["correct"] is True
    check = sound["check"]
    assert check["sampled_requests"] == 8 and check["served_tokens"] > 100
    assert check["max_gap"] <= 1e-3 and check["length_mismatches"] == 0
    assert check["longest_context"] > 5 * 64        # five windows and more
    assert check["control_max_gap"] > 0             # the int8 control differs
    assert max(sound["prompts"]) >= 256 and sound["e2e"]["tok_s"] > 0
    assert rehearsal["ring"]["rolls"] > 100


def test_with_a_broken_sampler_the_cell_is_not_correct(rehearsal):
    broken = rehearsal["broken"]
    assert broken["correct"] is False and broken["check"]["max_gap"] > 1e-3


def test_ring_metrics_of_the_cell_are_read_from_the_run(rehearsal):
    """The readers that exist read the cell under the accepted ``.docqa``
    names; the two new counters' readers read the ring.  Device-trace
    metrics find no device in a CPU trace and leave themselves out."""
    got = rehearsal["sound"]["metrics"]
    assert set(rehearsal["per_layer"]) >= set(got)
    assert set(rehearsal["per_layer"]) >= {n + ".docqa" for n in LISTED} | set(
        NEW)
    for name in ("gen_late_p99_ms.docqa", "decode_rows_mean.docqa",
                 "tick_host_ms_p50.docqa", "host_gap_share.docqa",
                 "prefill_stall_share.docqa", "compiles_in_window.docqa",
                 "eva_pool_fill", "eva_cache_ratio"):
        assert name in got, name
    assert got["compiles_in_window.docqa"]["value"] == 0
    fill = got["eva_pool_fill"]["value"]
    ratio = got["eva_cache_ratio"]["value"]
    assert 10 < fill <= 100 and got["eva_pool_fill"]["unit"] == "%"
    # positions over entries: the same two averages, the other way round
    held = fill / 100 * 48 * 8
    assert ratio == pytest.approx(
        rehearsal["sound"]["live_tokens_mean"] / held, rel=1e-6)
    assert 1.5 < ratio < 6
    assert rehearsal["ring"]["held_max"] <= 47 * 8      # never past the pool


# -- a trace made here ------------------------------------------------------------

MS = 10 ** 9       # picoseconds


def _made_trace(tmp_path, roll="jit_eva_roll(14)"):
    """One chip: a decode block with the paged kernel at EvaByte's shape
    (2 ms of 6), a prefill, two window closes (1 ms each), and a last run
    that the trace's end cuts."""
    ops = {1: "jit_decode_block(11)", 2: "jit_prefill(12)", 3: roll,
           10: "%fusion.1 = bf16[16,1,4096]{2,1,0} fusion(bf16[16,1,4096]"
               "{2,1,0} %get-tuple-element.1), kind=kLoop",
           11: "%flash_decode_paged.1 = bf16[16,32,1,128]{3,2,1,0} "
               "custom-call(bf16[16,32,1,128]{3,2,1,0} %q), "
               "custom_call_target=\"tpu\"",
           12: "%fusion.9 = s32[2048]{0:T(128)} fusion(s32[1,2048]{1,0:"
               "T(1,128)} %prompt.1), kind=kLoop",
           13: "%fusion.7 = bf16[128,16,32,128]{3,2,1,0} fusion("
               "bf16[16,372,32,64,128]{4,3,2,1,0} %pool.1), kind=kLoop"}
    device = xw.plane("/device:TPU:0", [
        xw.line("XLA Modules", [(1, 0, 6 * MS), (2, 6 * MS, 5 * MS),
                                (3, 11 * MS, 1 * MS), (3, 12 * MS, 1 * MS),
                                (1, 13 * MS, 1 * MS)], 1),
        xw.line("XLA Ops", [(10, 0, 4 * MS), (11, 4 * MS, 2 * MS),
                            (12, 6 * MS, 5 * MS), (13, 11 * MS, 1 * MS),
                            (13, 12 * MS, 1 * MS), (10, 13 * MS, 1 * MS)],
                2)], ops)
    host = xw.plane("/host:CPU", [
        xw.line("python3", [(1, 0, 14 * MS)], 7)], {1: "batcher.readback"}, 1)
    path = os.path.join(tmp_path, "made.xplane.pb.gz")
    with gzip.open(path, "wb") as f:
        f.write(xw.space([device, host]))
    return tr.load(path)


def _run(trace, config, model=evabyte):
    from benchmark.window import Served
    rec = Served(index=0, prompt_len=8192, max_new_tokens=8, due=0.0,
                 prompt=None)
    rec.token_times = [0.5, 1.0, 1.5, 2.5]
    rec.tokens = [1, 2, 3, 4]
    return {"trace": trace, "config": config, "model": model,
            "records": [rec], "trace_window": (0.9, 2.0), "t0": 0.0,
            "t1": 3.0, "device": {"peaks": {"hbm_bytes_per_s": 819e9}},
            "counters": {"rows": 16, "n_pages": 372, "page_size": 64}}


def test_trace_metrics_of_the_cell_from_a_made_trace(tmp_path, config):
    run = _run(_made_trace(str(tmp_path)), config)
    # tokens 1 and 2 fall in the traced window: contexts 8193 and 8194
    nbytes = evabyte.kv_bytes_per_context_token(config) * (513 + 514)
    assert harness.load_reader("eva_decode_roofline")(run) == pytest.approx(
        100 * nbytes / 819e9 / 0.002, rel=1e-9)
    # 2 ms of closes in 14 ms busy; the cut run is busy time too
    assert harness.load_reader("eva_roll_share")(run) == pytest.approx(
        100 * 2 / 14)
    # the readers that exist, under the names the cell reports them by
    assert harness.load_reader("decode_block_ms_p50.docqa")(run) == \
        pytest.approx(6.0)
    assert harness.load_reader("prefill_p50_ms.docqa")(run) == \
        pytest.approx(5.0)
    assert harness.load_reader("attn_kernel_share.docqa")(run) == \
        pytest.approx(100 * 2 / 14)
    assert harness.load_reader("pool_copy_share.docqa")(run) == 0.0
    assert [r["width"] for r in tr.module_runs(run["trace"])
            if r["kind"] == "prefill"] == [2048]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_eva_leaves_the_new_readers_nothing(
        tmp_path, config, name):
    """The parent commit's side of a traced run: no ``jit_eva_roll``
    program, no ``eva_*`` fields in the tick ring, an adapter that counts
    no entries.  Each reader returns None and raises nothing."""
    from tfmesos_tpu.fleet.tracing import flight
    trace = _made_trace(str(tmp_path), roll="jit__copy_page(14)")
    run = _run(trace, harness.load_json("configs", "mistral7b-l16-serve.json"),
               model=mistral)
    run["counters"]["rows"] = 32
    ring = flight("batcher.tick")
    ring.record({"name": "tick", "t": 1.0, "wall_ms": 5.0, "phases": {},
                 "compiles": 0})
    assert harness.load_reader(name)(run) is None
    assert harness.load_reader(name)(dict(run, trace=None)) is None
