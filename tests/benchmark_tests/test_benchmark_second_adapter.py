"""The promise of benchmark/README.md, rehearsed: a configuration of another
block arrives as added files only.  ``added_files/`` (a configuration, a
model adapter with its plain reference beside it, a script) is laid over a
copy of ``benchmark/`` without replacing a file, and the copy's own
``drivers/serve.py`` serves it on the CPU through the program's batcher:
``correct`` by the adapter's reference, ``pool_fill`` on the adapter's own
count of token slots, and not correct with the sampler broken.  Also: the
seeded weights are the arrays they were before the adapter stood between
the driver and ``weights.py``.  And the rule of README.md's "How a later
PR adds a cell and its entries", held: with that PR's cell and entries in a
copy of BENCHMARK.json, no structural check of any cell's test fails."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness, tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ADDED = os.path.join(HERE, "added_files")
CELL = "tinyhybrid.batch"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of ``benchmark/`` with ``added_files/`` laid over it."""
    root = str(tmp_path_factory.mktemp("checkout"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), bench)
              for d, _, files in os.walk(bench) for f in files}
    added = {os.path.relpath(os.path.join(d, f), ADDED)
             for d, _, files in os.walk(ADDED) for f in files
             if not f.endswith(".pyc")}
    assert added and not added & before        # files added, none replaced
    shutil.copytree(ADDED, bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def run_in(root, script):
    """``script`` from the copy: its ``benchmark`` is the copy's, the
    program the checkout's.  The last line of its output, parsed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + harness.ROOT)
    p = subprocess.run([sys.executable, script], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["benchmark"] == os.path.join(root, "benchmark")
    return out


@pytest.fixture(scope="module")
def rehearsal(checkout):
    return run_in(checkout, os.path.join(checkout, "benchmark",
                                         "rehearse_tinyhybrid.py"))


def with_a_later_prs_cell(spec):
    """What a program PR may do to BENCHMARK.json, and all it may: a
    configuration and a cell added, the cell JOINED to the end-to-end metric
    it reports and to two accepted per-layer lists of that metric, one
    per-layer entry APPENDED behind the last.  Changes ``spec``."""
    with open(os.path.join(ADDED, "configs", "tinyhybrid.json")) as f:
        config = json.load(f)
    spec["configs"].append({
        "name": "tinyhybrid", "source": config["source"],
        "file": "benchmark/configs/tinyhybrid.json",
        "reduced": config["reduced"], "why": "K/V in one layer of two"})
    spec["workloads"].append({
        "name": CELL, "config": "tinyhybrid", "traffic": "tinyhybrid_batch",
        "chips": 1, "why": "a backlog of short prompts; the rehearsal's"})
    by = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in ("tok_s", "gen_late_p99_ms.docqa", "decode_rows_mean.docqa"):
        by[name]["workloads"].append(CELL)
    spec["per_layer"].append({
        "name": "pool_fill.tinyhybrid", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "batcher", "moves": "tok_s",
        "workloads": [CELL]})
    return spec


def test_a_later_prs_cell_and_entries_fail_no_structural_check(checkout):
    """README.md, "How a later PR adds a cell and its entries": a cell's
    test asserts what its own cell's entries contain, never a list's exact
    value, a count of ``per_layer`` or a position in it.  So every
    structural check (``spec_checks.py``) holds on the copy too."""
    spec = harness.load_spec()
    listed = len(spec["per_layer"])
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as f:
        json.dump(with_a_later_prs_cell(spec), f, indent=1)
    out = run_in(checkout, os.path.join(HERE, "spec_checks.py"))
    assert out["cells"][-1] == CELL
    assert out["per_layer"][-1] == "pool_fill.tinyhybrid"
    assert len(out["per_layer"]) == listed + 1
    files = {name.split("::")[0] for name in out["ran"]}
    assert files >= {"test_benchmark_spec", "test_benchmark_tick_readers",
                     "test_benchmark_evabyte", "test_benchmark_granite_hybrid"}
    assert "test_benchmark_spec::test_metrics" in out["ran"]
    assert not out["failed"], "\n".join(out["failed"].values())


def test_second_adapter_is_served_and_read_by_its_own_reference(rehearsal):
    sound = rehearsal["sound"]
    assert sound["correct"] is True
    check = sound["check"]
    assert check["sampled_requests"] == 12 and check["served_tokens"] > 200
    assert check["max_gap"] <= 1e-3 and check["length_mismatches"] == 0
    assert check["control_max_gap"] >= 0        # its int8 control was read
    assert check["compared"]["max_gap"] == {"value": check["max_gap"],
                                            "limit": 1e-3}
    assert any(ln.startswith("correct: control_max_gap = ") for ln in
               rehearsal["lines"])      # a reading without a limit


def test_second_adapter_pool_fill_is_on_its_own_count(rehearsal):
    sound = rehearsal["sound"]
    dep = tiny.TINY_CONFIG["deployment"]
    pages = dep["n_pages"] * dep["page_size"]
    fill = sound["metrics"]["pool_fill.batch"]["value"]
    assert fill == pytest.approx(100 * sound["live_tokens_mean"]
                                 / (2 * pages), rel=1e-9)
    assert 0 < fill <= 50


def test_second_adapter_with_a_broken_sampler_is_not_correct(rehearsal):
    broken = rehearsal["broken"]
    assert broken["correct"] is False
    assert broken["check"]["max_gap"] > 1e-3
    assert broken["check"]["length_mismatches"] == 0


def test_second_adapter_counts_kv_in_the_layers_that_hold_it(rehearsal):
    """One layer of two holds K and V: half the bytes a context token costs
    a dense stack of the same sizes (4 layers x K and V x 2 heads x 16 x
    bf16), and twice the token slots by its own count."""
    assert rehearsal["counts"] == {
        "kv_bytes": 2 * 2 * 2 * 16 * 2, "kv_bytes_dense": 4 * 2 * 2 * 16 * 2,
        "token_slots": 2 * 80 * 16, "token_slots_dense": 80 * 16}


# Of ``weights.make_weights(tiny.config(), seed, float32)`` at the parent of
# the PR that brought the adapters (dc816bf), on the CPU, per leaf: its
# projection on cos(0.37 i + 1) over the flattened leaf, and the sum of its
# magnitudes.  No hash of the bytes: this suite compiles at XLA's optimisation
# level 0, which moves one value in 25 by one ulp against a default compile
# (the projections then differ by at most 6e-6; another draw moves them by
# more than 1).
PARENT_WEIGHTS = {
    2 ** 31 + 77: {
        "['embed']": [73.498659, 13172.307156],
        "['head']": [-2.353221, 1663.240849],
        "['layers']['attn_norm']": [-3.195329, 128.144089],
        "['layers']['mlp_norm']": [-4.234109, 128.963365],
        "['layers']['w_down']": [-0.143765, 576.162657],
        "['layers']['w_gate']": [2.582723, 1639.474064],
        "['layers']['w_up']": [15.302158, 1632.749713],
        "['layers']['wk']": [-3.229449, 413.433612],
        "['layers']['wo']": [3.637613, 415.630444],
        "['layers']['wq']": [4.734168, 816.588074],
        "['layers']['wv']": [-1.707197, 398.133738],
        "['norm_f']": [-3.759511, 64.160933]},
    5: {
        "['embed']": [-140.883427, 12991.193033],
        "['head']": [7.302166, 1630.844257],
        "['layers']['attn_norm']": [-5.563653, 128.136786],
        "['layers']['mlp_norm']": [-4.477027, 127.702549],
        "['layers']['w_down']": [1.474918, 578.761215],
        "['layers']['w_gate']": [20.237604, 1641.988445],
        "['layers']['w_up']": [-0.430672, 1624.297614],
        "['layers']['wk']": [-7.361043, 416.12824],
        "['layers']['wo']": [4.212438, 411.976289],
        "['layers']['wq']": [-0.030352, 810.24381],
        "['layers']['wv']": [4.085556, 406.688121],
        "['norm_f']": [-3.239654, 62.877161]},
}


@pytest.mark.parametrize("seed", sorted(PARENT_WEIGHTS))
def test_seeded_weights_are_the_same_arrays_through_the_adapter(seed):
    """Every later ``level`` in the ledger refers to the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    config = tiny.config()
    w = harness.load_model(config).make_weights(config, seed,
                                                dtype=jnp.float32)
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        assert leaf.dtype == jnp.float32
        x = np.asarray(leaf, np.float64).ravel()
        got[jax.tree_util.keystr(path)] = [
            float(x @ np.cos(0.37 * np.arange(x.size) + 1.0)),
            float(np.abs(x).sum())]
    want = PARENT_WEIGHTS[seed]
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=1e-3), k
