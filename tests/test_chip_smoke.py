"""``chip_smoke.py`` rehearsed without the chip, and the compile cache's
placement: the script's control flow runs here on the CPU at tiny sizes
(the test passes the sizes and the platform; the script has no option for
it), so a chip run is never spent on a wrong path or argument."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

sys.path.remove(REPO)

TINY = chip_smoke.Sizes(
    tiny=True, steps=20, seq_len=64, batch=4, rows=2, max_len=64,
    page_size=16, prefill_bucket=16, vocab=97, n_requests=4,
    prompt_len=(4, 24), long_prompt=20, new_tokens=8, phase_timeout=300.0)


def _python(code, **env):
    """Run ``code`` in a fresh interpreter at the repo root; returns its
    stdout lines."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()


def test_scheduler_gateway_and_smoke_never_import_jax():
    """A parent that has touched JAX holds the chip: the processes that
    launch chip tasks must stay off it."""
    out = _python(
        "import sys\n"
        "import tfmesos_tpu, tfmesos_tpu.cli, tfmesos_tpu.fleet.launcher\n"
        "import tfmesos_tpu.fleet.gateway, tfmesos_tpu.backends.local\n"
        "import chip_smoke\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'libtpu')))\n")
    assert out == ["[]"]


_CACHE_PROBE = (
    "import jax\n"
    "from tfmesos_tpu.utils.platform import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_dir_from_the_environment_is_left_to_jax(tmp_path):
    out = _python(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    # No directory set in code; JAX itself read the variable.
    assert out == ["None", str(tmp_path)]


def test_compile_cache_default_is_one_fixed_dir_in_the_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    # Two processes, started from different directories: the same place.
    a = _python(_CACHE_PROBE)
    b = _python("import os\nos.chdir(%r)\n" % str(tmp_path) + _CACHE_PROBE,
                PYTHONPATH=REPO)
    assert a == b == [want, want]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_cache_directory_is_named_anywhere():
    """The old test-only cache knobs are gone from every tracked file."""
    files = subprocess.run(["git", "ls-files"], cwd=REPO, text=True,
                           capture_output=True).stdout.split()
    old = ("tpumesos-jax" + "-test-cache", "TPUMESOS_TEST" + "_CACHE")
    for path in files or ["tests/conftest.py", "chip_smoke.py"]:
        if path == "ISSUE.md" or not os.path.isfile(os.path.join(REPO, path)):
            continue
        with open(os.path.join(REPO, path), errors="replace") as f:
            text = f.read()
        assert not any(name in text for name in old), path


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_prompts_are_seeded_sized_and_two_are_long(seed):
    prompts = chip_smoke.make_prompts(chip_smoke.FULL, seed)
    assert prompts == chip_smoke.make_prompts(chip_smoke.FULL, seed)
    assert prompts != chip_smoke.make_prompts(chip_smoke.FULL, seed + 1)
    lens = [len(p) for p in prompts]
    assert len(lens) == 8 and all(16 <= n <= 768 for n in lens)
    assert sum(n >= 600 for n in lens) >= 2
    assert all(0 <= t < 8192 for p in prompts for t in p)
    # The longest request fits the fleet's max_len with its completion.
    assert max(lens) + chip_smoke.FULL.new_tokens <= chip_smoke.FULL.max_len


def test_no_chip_means_exit_nonzero_and_no_result(capsys):
    """This sandbox has no TPU device node: the script must say so in one
    line and never print a result."""
    from tfmesos_tpu.backends.local import host_chip_nodes

    if host_chip_nodes():
        pytest.skip("this host has TPU device nodes")
    assert chip_smoke.main([]) == 2
    assert chip_smoke.main(["--chips", "4"]) == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert cap.err.count("\n") == 2 and "needs 1 TPU chip(s)" in cap.err


def _fake_tfrun(lines):
    def main(argv):
        # Where a task's output arrives: the process's descriptor 1 (under
        # pytest, sys.stdout is not that).
        os.write(1, ("\n".join(lines) + "\n").encode())
        return 0
    return main


_DEV = "device: platform=cpu kind='cpu' count=1"


@pytest.mark.parametrize("lines, why", [
    ([_DEV, "step 10: loss=nan ppl=nan", "step 20: loss=nan ppl=nan"],
     "non-finite"),
    ([_DEV, "step 10: loss=5.0000 ppl=1", "step 20: loss=5.5000 ppl=1"],
     "loss rose"),
    ([_DEV, "step 10: loss=5.0000 ppl=1"], "no loss lines"),
    (["step 10: loss=5.0 ppl=1", "step 20: loss=4.0 ppl=1"],
     "no device line"),
    (["device: platform=tpu kind='TPU v5 lite' count=1",
      "step 10: loss=5.0 ppl=1", "step 20: loss=4.0 ppl=1"],
     "ran on 'tpu'"),
])
def test_a_broken_train_phase_fails_the_smoke(monkeypatch, lines, why):
    from tfmesos_tpu import cli

    monkeypatch.setattr(cli, "main", _fake_tfrun(lines))
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.phase_train(TINY, 0, "cpu")


def test_a_good_train_phase_reports_device_and_losses(monkeypatch):
    from tfmesos_tpu import cli

    monkeypatch.setattr(cli, "main", _fake_tfrun(
        ["device: platform=tpu kind='TPU v5 lite' count=4",
         "step 10: loss=6.5000 ppl=1", "step 20: loss=6.2500 ppl=1",
         "DEBUG:x:jax._src.compiler:102: Persistent compilation cache hit "
         "for 'jit_sharded_step' with key 'k'",
         "DEBUG:x:jax._src.compiler:112: PERSISTENT COMPILATION CACHE MISS "
         "for 'jit_zeros' with key 'k'"]))
    out = chip_smoke.phase_train(TINY, 4, "tpu")
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 4}
    assert out["losses"] == {10: 6.5, 20: 6.25}
    assert out["compiles"] == {"hits": ["jit_sharded_step"],
                               "misses": ["jit_zeros"]}


@pytest.mark.parametrize("gap, accepted", [(2.0 ** -9, True), (0.25, False)])
def test_reference_accepts_only_a_bf16_tie(monkeypatch, gap, accepted):
    """Logits near 2.5 are 2**-6 apart in bf16: a smaller gap between the
    two candidates is a tie, a larger one is a failure to explain."""
    fleet = [[1, 2, 3], [4, 5, 6]]

    def fake(name, chips, spec, timeout):
        return {"platform": "cpu", "results": [
            {"tokens": [1, 2, 3]},
            {"tokens": [4, 9, 9], "step": 1, "ref_token": 9,
             "fleet_token": 5, "ref_logit": 2.5, "fleet_logit": 2.5 - gap,
             "top_logit": 2.5}]}

    monkeypatch.setattr(chip_smoke, "run_on_chips", fake)
    if accepted:
        share = chip_smoke.phase_reference(TINY, 0, "cpu", 0, [[1], [2]],
                                           fleet)
        assert math.isclose(share, 0.5)
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="more than bf16"):
            chip_smoke.phase_reference(TINY, 0, "cpu", 0, [[1], [2]], fleet)


def test_one_chip_smoke_runs_on_the_cpu_at_tiny_sizes(monkeypatch, capfd):
    """Every phase for real — kernels, tfrun trainer, fleet, reference —
    through the scheduler and the local backend with 0-chip tasks."""
    monkeypatch.setenv("XLA_FLAGS", "")     # tasks see one CPU device
    device = chip_smoke.smoke_one_chip(TINY, seed=0, chips=0, platform="cpu")
    assert device == {"platform": "cpu", "kind": "cpu",
                      "count": device["count"]}
    out = capfd.readouterr().out
    assert "serve: received == admitted == completed: [4, 4, 4]" in out
    assert "reference: 4 of 4 requests agree token for token" in out
    # On the CPU the kernel gates stay shut, and the smoke says so.
    assert "tpu_custom_call count: train step (T=64, B=4) 0;" in out


@pytest.mark.slow
def test_four_chip_smoke_runs_on_four_virtual_cpu_devices(monkeypatch,
                                                          capfd):
    """The ``--chips 4`` flow (run it before spending four chips): two
    CPU replicas behind the router, and the trainer on an fsdp=2,tp=2
    mesh of virtual devices against the plain run."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    device = chip_smoke.smoke_four_chips(
        TINY, seed=0, replicas=2, replica_chips=0, trainer_chips=0,
        platform="cpu")
    assert device["count"] == 4
    out = capfd.readouterr().out
    assert "serve4: 8 of 8 completed" in out
    assert "train4: fsdp=2,tp=2 losses" in out
    json.dumps(device)
