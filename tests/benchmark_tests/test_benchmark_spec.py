"""BENCHMARK.json against the contract's static rules, and against the files
it names: every cell finds its configuration, its traffic mix, its driver,
its model adapter and the readers of its per-layer metrics.

A test whose one argument is ``spec`` is a structural check: it reads
nothing of BENCHMARK.json but through that argument, so that ``spec_checks.
py`` can hold it against a copy to which a later PR's cell and entries were
added (test_benchmark_second_adapter.py; README.md, "How a later PR adds a
cell and its entries")."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness, tiny, traffic_gen

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: what a model adapter (``models/<model>.py``) defines; README.md lists them
ADAPTER = ("program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$"
                   r"|head_dim|expand|num_experts_per_tok")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def config_files(spec):
    for c in spec["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            yield c, json.load(f)


def line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert spec["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536
    # a full check with all 24 cells has to fit 43200 s
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c, cfg in config_files(spec):
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        limits = cfg["correct"]["limits"]
        assert limits and all(isinstance(v, (int, float))
                              for v in limits.values())


def test_every_configuration_finds_its_driver_and_its_model_adapter(spec):
    """Files, not a list of names: a later PR brings ``drivers/<driver>.py``
    or ``models/<model>.py`` and edits nothing here."""
    configs = [cfg for _, cfg in config_files(spec)] + [tiny.config()]
    for cfg in configs:
        assert NAME.match(cfg["driver"]) and NAME.match(cfg["model"])
        driver = importlib.import_module("benchmark.drivers." + cfg["driver"])
        assert os.path.dirname(driver.__file__) == os.path.join(
            harness.HERE, "drivers")
        assert callable(driver.run_cell)
        model = harness.load_model(cfg)
        assert os.path.dirname(model.__file__) == os.path.join(
            harness.HERE, "models")
        for fn in ADAPTER:
            assert callable(getattr(model, fn, None)), (cfg["model"], fn)


def test_a_configuration_without_a_model_or_with_an_unknown_one_is_refused():
    cfg = tiny.config()
    del cfg["model"]
    with pytest.raises(SystemExit, match="names no"):
        harness.load_model(cfg)
    with pytest.raises(SystemExit, match="no model adapter 'no_such'"):
        harness.load_model(dict(cfg, model="no_such"))


def test_published_stands_beside_reduced(spec):
    """Every key a configuration cut states its published value, and the
    value in the file differs from it; nothing else is listed."""
    for c, cfg in config_files(spec):
        assert set(cfg["published"]) == set(c["reduced"]), c["name"]
        for k, v in cfg["published"].items():
            assert cfg[k] != v and type(cfg[k]) is type(v), (c["name"], k)


def test_published_widths_are_not_cut(spec):
    """No configuration, whichever, cuts a width: not at the top level, and
    not inside a group it lists as reduced (the published group stands
    beside it, and their widths agree)."""
    for c, cfg in config_files(spec):
        for k in list(cfg["published"]) + list(c["reduced"]):
            assert not WIDTH.search(k), (c["name"], k)
            if isinstance(cfg[k], dict):
                assert not any(WIDTH.search(kk) and cfg[k][kk] != v
                               for kk, v in cfg["published"][k].items())


def test_mistral7b_l16_serve_is_the_published_config_but_for_its_layers():
    """The first configuration's values, known by heart (it is in no
    catalog; a catalog model is held to its entry by the driver)."""
    cfg = harness.load_json("configs", "mistral7b-l16-serve.json")
    for k, v in {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32768, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
                 "max_position_embeddings": 32768}.items():
        assert cfg[k] == v, k
    assert cfg["num_hidden_layers"] == 16 and cfg["reduced"] == [
        "num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 32}


def test_workloads(spec):
    names = [w["name"] for w in spec["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        traffic = traffic_gen.load_traffic(w["traffic"])
        sched = traffic_gen.make_schedule(traffic, 1, spec["run_seconds"],
                                          32768)
        assert sched.requests and line(traffic["why"], 400)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert len(e2e) == len(spec["end_to_end"]) <= 16 and "setup_s" in e2e
    assert "workloads" not in e2e["setup_s"]
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    names = [m["name"] for m in spec["per_layer"]] + list(e2e)
    assert len(set(names)) == len(names) and 1 <= len(spec["per_layer"]) <= 128
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for c in m.get("workloads", []):
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)
        assert callable(harness.load_reader(m["name"]))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:     # setup_s, one more end-to-end metric, one per-layer
        assert len(harness.cell_metrics(spec, c, "end_to_end")) >= 2
        assert len(harness.cell_metrics(spec, c, "per_layer")) >= 1


def test_files_under_paths_are_named_from_names(spec):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in spec["paths"]:
        for root, dirs, files in os.walk(os.path.join(harness.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), harness.ROOT)
                assert ok.match(rel), rel
