"""End-to-end integration over real subprocesses (no Mesos, no TPU): the
full path launch → rendezvous → config broadcast → Mode A/B runtime."""

import time

import pytest

from tfmesos_tpu import ClusterError, Job, cluster
from tfmesos_tpu.backends.local import LocalBackend


def test_mode_b_echo_cluster_finishes():
    jobs = Job(name="worker", num=2, cpus=0.5, mem=64.0,
               cmd="echo hello-from-{job_name}-{task_index}")
    with cluster(jobs, backend=LocalBackend(), quiet=True,
                 start_timeout=60.0) as c:
        deadline = time.time() + 30
        while not c.finished():
            assert time.time() < deadline, "workers never finished"
            time.sleep(0.05)


def test_mode_a_dispatch_no_jax():
    jobs = [Job(name="ps", num=1, cpus=0.5, mem=64.0),
            Job(name="worker", num=2, cpus=0.5, mem=64.0)]
    with cluster(jobs, backend=LocalBackend(), quiet=True, start_timeout=60.0,
                 extra_config={"no_jax": True}) as c:
        results = c.run_all("support_funcs:ping", "hi")
        assert [r["rank"] for r in results] == [0, 1, 2]
        assert results[0]["job"] == "ps:0"
        assert results[2] == {"rank": 2, "world": 3, "job": "worker:1",
                              "value": "hi"}
        # Env contract visible to tasks (reference server.py:76-84).
        assert c.run("support_funcs:read_env", "TFMESOS_DISTRIBUTED") == "1"
        assert c.run_all("support_funcs:read_env", "TPUMESOS_RANK") == \
            ["0", "1", "2"]


def test_mode_a_distributed_worker_only_dp_mesh():
    """Workers-only spec: the dp-branch of the default mesh, across a real
    2-process runtime (keeps both _default_mesh_axes branches covered)."""
    with cluster(Job(name="worker", num=2, cpus=1.0, mem=512.0),
                 backend=LocalBackend(), quiet=True, start_timeout=120.0) as c:
        topo = c.run("support_funcs:runtime_topology")
        assert topo["process_count"] == 2, topo
        assert c.run("support_funcs:sharded_sum", 42.0) == 42.0


def test_remote_exception_propagates():
    with cluster(Job(name="w", num=1, cpus=0.5, mem=64.0),
                 backend=LocalBackend(), quiet=True, start_timeout=60.0,
                 extra_config={"no_jax": True}) as c:
        with pytest.raises(ClusterError, match="No module named"):
            c.run("no_such_module_xyz:func")


def test_mode_a_distributed_jax_sharded_sum():
    """The 'plus' smoke test, TPU-native: a ps + a worker process join one
    jax.distributed runtime (ps jobs → fsdp default mesh axis — the exact
    config examples/plus.py runs); a global sharded array reduces to 42."""
    jobs = [Job(name="ps", num=1, cpus=1.0, mem=512.0),
            Job(name="worker", num=1, cpus=1.0, mem=512.0)]
    with cluster(jobs, backend=LocalBackend(), quiet=True,
                 start_timeout=120.0) as c:
        # Guard against silent degradation into independent single-process
        # runtimes: the cluster must really be ONE runtime spanning both
        # processes.
        topo = c.run("support_funcs:runtime_topology")
        assert topo["process_count"] == 2, topo
        results = c.run_all("support_funcs:sharded_sum", 42.0)
        assert results == [42.0, 42.0]


def test_cross_process_multiaxis_meshes():
    """The production shape of the north star (VERDICT r3 missing #2): a
    mesh whose MODEL axes cross process boundaries, brought up through the
    scheduler.  2 Mode-A processes x 4 virtual CPU devices each; meshes
    {dp:2, tp:4} (vocab-parallel fused CE) and {fsdp:8} (param sharding
    spanning hosts); plus one sharded ragged decode step.  device_count==8
    on every process proves the collectives really span the runtime."""
    import math

    jobs = Job(name="worker", num=2, cpus=1.0, mem=1024.0)
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    with cluster(jobs, backend=LocalBackend(), quiet=True,
                 start_timeout=180.0, env=env) as c:
        for axes, want_mode in (({"dp": 2, "tp": 4}, "tp"),
                                ({"fsdp": 8}, None)):
            rs = c.run_all("support_funcs:multiaxis_train_step", axes)
            assert len(rs) == 2
            for r in rs:
                assert r["process_count"] == 2, r
                assert r["device_count"] == 8, r
                assert math.isfinite(r["loss"]), r
                assert r["mesh_shape"] == axes
                if want_mode is not None:
                    assert r["fused_mode"] == want_mode, r
            # Both processes computed the SAME loss — one global program,
            # not two coincidentally-similar local ones.
            assert rs[0]["loss"] == rs[1]["loss"]

        rd = c.run("support_funcs:multiaxis_ragged_decode",
                   {"dp": 2, "tp": 4})
        assert rd["device_count"] == 8 and rd["logits_finite"], rd


def test_cross_process_hybrid_dcn_mesh():
    """--mesh dcn.dp=2,dp=1,tp=2 semantics through the REAL plumbing: each
    process is one 'slice'; build_hybrid_mesh's process-grouping must keep
    every tp group inside a process while dp spans them (VERDICT r3 next
    #8 — previously unit-tested only on single-process virtual devices)."""
    jobs = Job(name="worker", num=2, cpus=1.0, mem=512.0)
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    with cluster(jobs, backend=LocalBackend(), quiet=True,
                 start_timeout=180.0, env=env) as c:
        r = c.run("support_funcs:hybrid_mesh_probe",
                  {"dcn.dp": 2, "dp": 1, "tp": 2})
        assert r["process_count"] == 2 and r["device_count"] == 4, r
        assert r["mesh_shape"] == {"dp": 2, "tp": 2}, r
        assert r["tp_groups_intra_process"], \
            "a tp collective would cross the DCN boundary"
        assert r["dp_axis_crosses_processes"], \
            "dp must be the axis spanning slices"


def test_mode_a_task_killed_mid_dispatch_raises_cluster_error():
    """SIGKILL a Mode-A task while a dispatched call is in flight: the
    caller must see ClusterError (not a raw OSError/WireError), the cluster
    must be marked fatal, and supervise() must treat it as retryable."""
    import os
    import signal
    import threading

    from tfmesos_tpu.scheduler import RemoteError
    from tfmesos_tpu.train.supervisor import supervise

    attempts = []

    def run_attempt(attempt):
        attempts.append(attempt)
        if attempt >= 1:
            return "recovered"
        with cluster([Job(name="w", num=2, cpus=0.5, mem=64.0)],
                     backend=LocalBackend(), quiet=True, start_timeout=60.0,
                     extra_config={"no_jax": True}) as c:
            pids = c.run_all("support_funcs:my_pid")
            errs = []

            def dispatch():
                try:
                    c.run_all("support_funcs:sleep_forever", 60.0)
                except BaseException as e:  # noqa: BLE001 - recorded for asserts
                    errs.append(e)

            t = threading.Thread(target=dispatch)
            t.start()
            time.sleep(1.0)  # let the call get in flight
            os.kill(pids[1], signal.SIGKILL)
            t.join(timeout=30)
            assert not t.is_alive(), "dispatch never unblocked after kill"
            assert errs, "dispatch did not raise"
            assert isinstance(errs[0], ClusterError), errs[0]
            assert not isinstance(errs[0], RemoteError)
            # The whole dispatch channel is poisoned: later calls fail fast.
            with pytest.raises(ClusterError):
                c.run("support_funcs:my_pid")
            raise errs[0]

    result = supervise(run_attempt, max_restarts=2, restart_wait=0.1)
    assert result.value == "recovered"
    assert result.attempts == 2


def test_cross_process_continuous_batching():
    """Multi-chip SERVING end to end (VERDICT r4 next #1): the
    ContinuousBatcher admission loop running identically on 2 processes x
    4 devices with decode sharded dp x tp over per-shard paged pools.
    Both processes must yield identical token streams, equal to a
    single-host no-mesh batcher's run in THIS process."""
    import support_funcs
    from tfmesos_tpu.serving import ContinuousBatcher

    jobs = Job(name="worker", num=2, cpus=1.0, mem=1024.0)
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    with cluster(jobs, backend=LocalBackend(), quiet=True,
                 start_timeout=180.0, env=env) as c:
        rs = c.run_all("support_funcs:continuous_batching_mesh",
                       {"dp": 2, "tp": 4})
        # The pipelined (one block of lag) loop over the SAME
        # cross-process mesh: still lockstep, still the same tokens.
        ov = c.run("support_funcs:continuous_batching_mesh",
                   {"dp": 2, "tp": 4}, pipeline_depth=1)
    assert len(rs) == 2
    for r in rs:
        assert r["process_count"] == 2 and r["device_count"] == 8, r
    # Both processes run ONE global program — exact equality is required.
    assert rs[0]["tokens"] == rs[1]["tokens"]
    assert ov["tokens"] == rs[0]["tokens"]
    # vs the single-host no-mesh batcher, tp=4's partial-sum order can
    # legitimately fork greedy argmax at float ties — use the
    # tie-tolerant comparator, like the in-process mesh tests.
    from test_serving import _assert_tokens_match_modulo_ties

    cfg, params, reqs, kw = support_funcs._cb_workload()
    plain = ContinuousBatcher(cfg, params, **kw)
    want = {str(cc.rid): cc.tokens for cc in plain.run(reqs)}
    assert rs[0]["tokens"].keys() == want.keys()
    for rid, req in enumerate(reqs):
        _assert_tokens_match_modulo_ties(
            cfg, params, req.prompt, rs[0]["tokens"][str(rid)],
            want[str(rid)])


# -- chip ownership (jax-free: the commands are shell one-liners) ------------


class _Sink:
    """The scheduler callbacks a backend needs, recording what arrives."""

    def __init__(self):
        self.statuses = []

    def on_registered(self, info):
        pass

    def on_offers(self, offers):
        pass

    def on_status(self, status):
        self.statuses.append(status)

    def states(self, task_id):
        return [s.state for s in self.statuses if s.task_id == task_id]


def _chip_backend(chips):
    backend = LocalBackend(chips=chips)
    backend._scheduler = _Sink()       # launch() without the offer thread
    return backend


def _launch(backend, chips, cmd):
    """Launch one task that asks for ``chips`` and runs shell ``cmd``
    (rendered by the real TaskInfo renderer, command swapped in)."""
    from tfmesos_tpu.spec import Offer, Task

    offer = Offer(id="o", agent_id="local", hostname="127.0.0.1",
                  cpus=8, mem=1 << 20, chips=backend.chips)
    task = Task("w", 0, cpus=0.1, mem=1.0, chips=chips)
    info = task.to_task_info(offer, "127.0.0.1:1", "tok")
    info["command"]["value"] = cmd
    backend.launch(offer, [info])
    return task.id


def _wait_for(cond, what, timeout=20.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.02)


_ENV_CMD = ("echo $JAX_PLATFORMS/$TPU_VISIBLE_CHIPS/"
            "$TPU_CHIPS_PER_PROCESS_BOUNDS/$TPU_PROCESS_BOUNDS/"
            "$TPUMESOS_CHIPS > {out}; "
            "while [ ! -e {gate} ]; do sleep 0.02; done")


def _read_env(path):
    _wait_for(lambda: path.exists() and path.read_text().endswith("\n"),
              f"{path.name}")
    return path.read_text().strip().split("/")


def test_host_chip_nodes_sorted_by_index(monkeypatch):
    import tfmesos_tpu.backends.local as local_mod

    found = {"/dev/accel[0-9]*": [],
             "/dev/vfio/[0-9]*": ["/dev/vfio/10", "/dev/vfio/2",
                                  "/dev/vfio/1"]}
    monkeypatch.setattr(local_mod.glob, "glob", lambda pat: found[pat])
    assert local_mod.host_chip_nodes() == ["/dev/vfio/1", "/dev/vfio/2",
                                           "/dev/vfio/10"]
    assert LocalBackend().chips == 3
    found["/dev/vfio/[0-9]*"] = []
    assert LocalBackend().chips == 0


def test_two_one_chip_tasks_get_disjoint_chips(tmp_path):
    backend = _chip_backend(2)
    gate = tmp_path / "gate"
    try:
        for i in range(2):
            _launch(backend, 1, _ENV_CMD.format(out=tmp_path / f"{i}.env",
                                                gate=gate))
        envs = [_read_env(tmp_path / f"{i}.env") for i in range(2)]
        # Both alive at once: the platform for both, one chip each, and
        # the bounds of a one-chip process.
        assert [e[0] for e in envs] == ["tpu", "tpu"]
        assert sorted(e[1] for e in envs) == ["0", "1"]
        assert {(e[2], e[3]) for e in envs} == {("1,1,1", "1,1,1")}
        assert [e[4] for e in envs] == [e[1] for e in envs]
        assert backend._free_chips == []
        gate.touch()
        _wait_for(lambda: backend._free_chips == [0, 1],
                  "chips back on the free list")
    finally:
        gate.touch()
        backend.stop()


@pytest.mark.parametrize("host_chips", [0, 2])
def test_zero_chip_task_stays_on_cpu(tmp_path, host_chips, monkeypatch):
    # Even where the scheduler's own environment names another platform.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    backend = _chip_backend(host_chips)
    gate = tmp_path / "gate"
    gate.touch()
    try:
        _launch(backend, 0, _ENV_CMD.format(out=tmp_path / "e", gate=gate))
        assert _read_env(tmp_path / "e") == ["cpu", "", "", "", ""]
        assert backend._free_chips == list(range(host_chips))
    finally:
        backend.stop()


def test_task_owning_every_chip_keeps_host_topology(tmp_path):
    """k == the host's chips: the platform is set, the visibility
    variables are not (the host's own topology settings stand)."""
    backend = _chip_backend(4)
    gate = tmp_path / "gate"
    gate.touch()
    try:
        _launch(backend, 4, _ENV_CMD.format(out=tmp_path / "e", gate=gate))
        assert _read_env(tmp_path / "e") == ["tpu", "", "", "", "0,1,2,3"]
    finally:
        backend.stop()


def test_chips_are_reused_after_exit(tmp_path):
    backend = _chip_backend(2)
    gate = tmp_path / "gate"
    try:
        for name in "ab":
            _launch(backend, 1, _ENV_CMD.format(out=tmp_path / name,
                                                gate=gate))
        assert sorted(_read_env(tmp_path / n)[1] for n in "ab") == ["0", "1"]
        # Every chip is taken: a launch the offer should not have allowed
        # is dropped, and nothing leaks.
        tid = _launch(backend, 1, "true")
        assert backend._scheduler.states(tid) == ["TASK_DROPPED"]
        gate.touch()
        _wait_for(lambda: backend._free_chips == [0, 1], "release")
        gate.unlink()
        _launch(backend, 1, _ENV_CMD.format(out=tmp_path / "c", gate=gate))
        assert _read_env(tmp_path / "c")[1] == "0"
    finally:
        gate.touch()
        backend.stop()


def test_a_task_owns_one_chip_or_the_whole_host():
    from tfmesos_tpu.scheduler import TPUMesosScheduler

    with pytest.raises(ClusterError,
                       match="w:0 asks for 2 chips; a task owns one chip or "
                             "every chip of its host"):
        TPUMesosScheduler([Job(name="w", num=1, chips=2, cmd="true")],
                          backend=LocalBackend(chips=4), quiet=True).start()


def test_spawn_failure_returns_the_chips(monkeypatch):
    import tfmesos_tpu.backends.local as local_mod

    def failing(*a, **k):
        raise OSError(2, "No such file or directory")

    backend = _chip_backend(2)
    monkeypatch.setattr(local_mod.subprocess, "Popen", failing)
    tid = _launch(backend, 1, "true")
    assert backend._scheduler.states(tid) == ["TASK_DROPPED"]
    assert backend._free_chips == [0, 1]
    assert backend._in_use == [0.0, 0.0]


@pytest.mark.parametrize("dynamic", [False, True])
def test_more_chips_than_the_host_has_fails_fast(dynamic):
    from tfmesos_tpu.scheduler import TPUMesosScheduler

    t0 = time.monotonic()
    with pytest.raises(ClusterError,
                       match=r"w:0 asks for 3 chip\(s\) but this host "
                             r"has 2: chip0, chip1"):
        if dynamic:
            s = TPUMesosScheduler([], dynamic=True, quiet=True,
                                  backend=LocalBackend(chips=2),
                                  start_timeout=60.0)
            s.start()
            try:
                s.add_task("w", cmd="true", chips=3)
            finally:
                s.stop()
        else:
            with cluster(Job(name="w", num=1, chips=3, cmd="true"),
                         backend=LocalBackend(chips=2), quiet=True,
                         start_timeout=60.0):
                pass
    assert time.monotonic() - t0 < 10.0          # << start_timeout


def test_chip_env_reaches_the_users_command_through_the_scheduler(tmp_path):
    """The whole path: Job(chips=1) -> offer -> first_fit -> launch ->
    node runtime -> the user's command sees its own chip."""
    cmd = ("echo $JAX_PLATFORMS/$TPU_VISIBLE_CHIPS > "
           f"{tmp_path}/{{task_index}}.env")
    with cluster(Job(name="worker", num=2, cpus=0.5, mem=64.0, chips=1,
                     cmd=cmd),
                 backend=LocalBackend(chips=2), quiet=True,
                 start_timeout=60.0) as c:
        _wait_for(c.finished, "workers to finish", timeout=30.0)
    got = sorted((tmp_path / f"{i}.env").read_text().strip()
                 for i in range(2))
    assert got == ["tpu/0", "tpu/1"]
