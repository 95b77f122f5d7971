"""A tiny end-to-end rehearsal of the serving driver on the CPU.

It is never a measurement: with the look for a chip on, the run refuses.
With the look skipped it drives the rest of a run: ``correct`` comes out
true for the program as it is, false with the timed path broken underneath
(a token altered where it is produced) and for the program serving from its
own int8 weights, and the reference's int8 control reads outside the
limit."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.drivers import serve
from benchmark import tiny

SEED = 2 ** 31 + 77


def run(cell_index, traffic, sample_requests=None, **kw):
    spec = tiny.tiny_spec()
    config = tiny.config()
    if sample_requests:
        config["correct"]["sample_requests"] = sample_requests
    lines = []
    res = serve.run_cell(spec, spec["workloads"][cell_index], config,
                         traffic, seed=kw.pop("seed", SEED), seconds=3,
                         trace=kw.pop("trace", False), t_start=0.0,
                         require_chip=False, out=lines.append, **kw)
    return res, lines


def test_run_refuses_without_a_chip():
    spec = tiny.tiny_spec()
    with pytest.raises(SystemExit) as e:
        serve.run_cell(spec, spec["workloads"][0], tiny.config(),
                       tiny.TINY_OPEN, seed=1, seconds=1, trace=False,
                       t_start=0.0)
    assert e.value.code not in (0, None)


def test_command_fails_as_a_measurement_here():
    spec = harness.load_spec()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         spec["workloads"][0]["name"], "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "not measurable" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_open_loop_rehearsal_is_correct_and_counts_its_samples():
    res, lines = run(0, tiny.TINY_OPEN)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 10
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"} \
        or set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert any(ln.startswith("samples: due_requests=") for ln in lines)
    compared = res["check"]["compared"]
    line = json.loads(harness.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=res["device"],
        compared=compared))
    # the numbers compared, each beside its limit, come last in the line
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["compared"]["max_gap"] == {
        "value": res["check"]["max_gap"], "limit": 1e-3}
    assert set(compared) == {"length_mismatches", "token_ids_out_of_range",
                             "max_gap"}
    assert harness.compared_lines(compared)[0] == \
        "correct: length_mismatches = 0 (limit 0)"
    assert line["device"]["platform"] == "cpu"      # and so never a result


def test_backlog_rehearsal_traced_reports_host_side_layer_metrics():
    res, _ = run(1, tiny.TINY_BACKLOG, trace=True)
    assert res["correct"] is True
    # a metric split by cell is read by its base name's reader
    assert 0 < res["metrics"]["decode_rows_mean.batch"]["value"] <= 4
    assert 0 < res["metrics"]["pool_fill.batch"]["value"] <= 100
    assert res["metrics"]["gen_late_p99_ms.batch"]["value"] > 0
    # no device plane in a CPU trace: the device readers report nothing
    assert "prefill_p50_ms.batch" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_broken_timed_path_comes_out_not_correct(monkeypatch):
    """A token altered where it is produced: the batcher's sampler returns
    the runner-up instead of the best."""
    import jax.numpy as jnp
    from tfmesos_tpu import serving

    def second_best(self, last, rids, steps):
        order = jnp.argsort(last.astype(jnp.float32), axis=-1)
        return order[..., -2].astype(jnp.int32)

    monkeypatch.setattr(serving.ContinuousBatcher, "_sample", second_best)
    res, lines = run(1, tiny.TINY_BACKLOG)
    assert res["correct"] is False
    assert res["check"]["max_gap"] > tiny.TINY_CONFIG["correct"]["limits"][
        "max_gap"]


def test_a_reader_is_found_by_its_name_or_its_base_name():
    from benchmark import readers
    assert harness.load_reader("pool_fill") is readers.pool_fill
    assert harness.load_reader("pool_fill.docqa") is readers.pool_fill
    with pytest.raises(SystemExit):
        harness.load_reader("no_such_metric.docqa")


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_program_serving_from_int8_weights_comes_out_not_correct(seed):
    """The control that would tempt a later PR: the program's own
    weight-only int8 path in the timed path's place.  At this size int8
    moves about one served token in a hundred off the reference's best, and
    which requests a 3 s window finishes is a matter of timing: read enough
    of them that some moved token is always in the sample."""
    res, _ = run(1, tiny.TINY_BACKLOG, program_int8=True, seed=seed,
                 sample_requests=96)
    assert res["correct"] is False
    assert res["check"]["max_gap"] > tiny.TINY_CONFIG["correct"]["limits"][
        "max_gap"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_int8_control_reads_outside_the_limit(seed):
    res, _ = run(1, tiny.TINY_BACKLOG, control=True, seed=seed,
                 sample_requests=96)
    limit = tiny.TINY_CONFIG["correct"]["limits"]["max_gap"]
    assert res["check"]["max_gap"] <= limit          # the program passes
    assert res["check"]["control_max_gap"] > limit   # its control does not
