"""Local subprocess backend: run a whole "cluster" on this host.

The reference has no equivalent — its only path to a running cluster is a
real Mesos master (SURVEY §4: the de-facto test was a live cluster).  This
backend exists precisely to fix that: it synthesizes offers describing the
local host and launches tasks as child processes, so the full control plane
(rendezvous, config broadcast, Mode A/B node runtime, failure policy) is
exercisable in CI with no Mesos and no TPU.

It also owns the host's TPU chips: a task that asks for k chips is launched
with exactly k of them visible to libtpu and to no other live task, and with
``JAX_PLATFORMS=tpu`` so ``runtime.initialize`` fails the task if JAX comes
up on anything else.  A 0-chip task is pinned to the CPU.
"""

from __future__ import annotations

import glob
import os
import re
import shlex
import signal
import subprocess
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence

from tfmesos_tpu.backends import ResourceBackend
from tfmesos_tpu.runtime import ENV_CHIPS
from tfmesos_tpu.spec import Offer, TaskStatus
from tfmesos_tpu.utils.logging import get_logger


def host_chip_nodes() -> List[str]:
    """The TPU device nodes this host exposes, in chip-index order — read
    from /dev so the scheduler's process never imports JAX (which would
    take the chips for itself).  ``/dev/accel<N>`` on older TPU VMs, one
    ``/dev/vfio/<group>`` per chip on v5e and later; the PCI bus is not
    consulted because a VM can see functions it was not given."""
    nodes = glob.glob("/dev/accel[0-9]*") or glob.glob("/dev/vfio/[0-9]*")
    return sorted(nodes, key=lambda p: int(re.search(r"\d+$", p).group()))


class LocalBackend(ResourceBackend):
    colocated = True

    def __init__(self, cpus: Optional[float] = None, mem: float = 1 << 20,
                 chips: Optional[int] = None, offer_interval: float = 0.05,
                 inherit_env: bool = True,
                 default_platform: Optional[str] = "cpu",
                 chaos=None):
        # The platform of a task that asked for NO chips: co-located
        # processes cannot share a chip, so they run on the CPU.
        self.default_platform = default_platform
        # "cpus" here are scheduling slots, not a pinning claim: this backend
        # exists to run many-task dev clusters on small hosts, so advertise a
        # generous floor rather than the literal core count.
        self.cpus = float(cpus if cpus is not None else max(os.cpu_count() or 1, 16))
        self.mem = float(mem)
        # ``chips=None`` asks the host; a number overrides it (tests).
        self.chip_nodes = host_chip_nodes() if chips is None \
            else [f"chip{i}" for i in range(chips)]
        self.chips = len(self.chip_nodes)
        self._free_chips = list(range(self.chips))
        self.offer_interval = offer_interval
        self.inherit_env = inherit_env
        # Optional chaos.FaultPlan: launched pids register with it (so
        # kill_task faults can SIGKILL by job:index name) and drop_agent
        # faults execute through chaos_drop_agent below.
        self.chaos = chaos
        self.log = get_logger("tfmesos_tpu.local")

        self._scheduler = None
        self._suppressed = threading.Event()
        self._shutdown = threading.Event()
        self._offer_thread: Optional[threading.Thread] = None
        self._procs: Dict[str, subprocess.Popen] = {}
        self._in_use = [0.0, 0.0]  # cpus, mem
        self._lock = threading.Lock()

    # -- ResourceBackend ---------------------------------------------------

    def start(self, scheduler) -> None:
        self._scheduler = scheduler
        if self.chaos is not None:
            self.chaos.bind_backend(self)
        scheduler.on_registered({"backend": "local", "cpus": self.cpus,
                                 "mem": self.mem, "chips": self.chips})
        self._offer_thread = threading.Thread(target=self._offer_loop,
                                              name="local-offers", daemon=True)
        self._offer_thread.start()

    def _offer_loop(self) -> None:
        while not self._shutdown.is_set():
            if not self._suppressed.is_set():
                with self._lock:
                    free = Offer(
                        id=str(uuid.uuid4()), agent_id="local",
                        hostname="127.0.0.1",
                        cpus=self.cpus - self._in_use[0],
                        mem=self.mem - self._in_use[1],
                        chips=len(self._free_chips),
                    )
                if free.cpus > 0 and free.mem > 0:
                    try:
                        self._scheduler.on_offers([free])
                    except Exception as e:  # pragma: no cover - defensive
                        self.log.exception("offer delivery failed: %s", e)
            self._shutdown.wait(self.offer_interval)

    def unplaceable(self, task) -> Optional[str]:
        have = (f"this host has {self.chips}: "
                f"{', '.join(self.chip_nodes) or 'none'}")
        if task.chips > self.chips:
            return f"asks for {task.chips} chip(s) but {have}"
        if task.chips not in (0, 1, self.chips):
            # What was established on a v5e 2x2 host: one-chip processes
            # side by side, and one process on all four.  Two two-chip
            # processes did not come up, so other sizes are refused here
            # instead of failing inside libtpu.
            return (f"asks for {task.chips} chips; a task owns one chip "
                    f"or every chip of its host ({have})")
        return None

    def _take_chips(self, k: int) -> Optional[List[int]]:
        """Reserve k chips (lock held): the lowest free one, or all."""
        if k > len(self._free_chips):
            return None
        taken, self._free_chips = self._free_chips[:k], self._free_chips[k:]
        return taken

    def _chip_env(self, chips: List[int]) -> Dict[str, str]:
        """What a task that owns ``chips`` must see.  The platform is set
        for every chip task; libtpu's visibility variables only for one
        chip of several (a task that owns every chip inherits the host's
        own topology settings untouched)."""
        env = {"JAX_PLATFORMS": "tpu",
               ENV_CHIPS: ",".join(map(str, chips))}
        if len(chips) < self.chips:
            env.update(
                TPU_VISIBLE_CHIPS=env[ENV_CHIPS],
                TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                TPU_PROCESS_BOUNDS="1,1,1",
                # Several libtpu processes on one host, each on its own
                # chip (the setting JAX's own multi-process TPU tests use).
                ALLOW_MULTIPLE_LIBTPU_LOAD="1")
        return env

    def _release(self, used, chips: List[int]) -> None:
        with self._lock:
            self._in_use[0] -= used[0]
            self._in_use[1] -= used[1]
            self._free_chips = sorted(self._free_chips + chips)

    def launch(self, offer: Offer, task_infos: Sequence[dict]) -> None:
        for info in task_infos:
            task_id = info["task_id"]["value"]
            res = info["resources"]
            used = [_res(res, "cpus"), _res(res, "mem")]
            n_chips = int(_res(res, "tpus"))
            with self._lock:
                chips = self._take_chips(n_chips) if n_chips else []
                if chips is not None:
                    self._in_use[0] += used[0]
                    self._in_use[1] += used[1]
            if chips is None:
                self._drop(task_id, f"{n_chips} chips are not free "
                                    f"(free: {self._free_chips})")
                continue
            env = dict(os.environ) if self.inherit_env else {}
            if chips:
                env.update(self._chip_env(chips))
            elif self.default_platform:
                # Before the task-env merge, so an explicit JAX_PLATFORMS
                # passed via the scheduler's env= still wins.
                env["JAX_PLATFORMS"] = self.default_platform
            for var in info["command"]["environment"]["variables"]:
                env[var["name"]] = var["value"]
            cmd = info["command"]["value"]
            argv = cmd if info["command"].get("shell") else shlex.split(cmd)
            try:
                proc = subprocess.Popen(argv,
                                        shell=bool(info["command"].get("shell")),
                                        env=env, start_new_session=True)
            except OSError as e:
                # A spawn failure (bad interpreter, ENOENT, EMFILE...) must
                # feed the failure policy, not vanish into a log line with
                # the task stuck offered=True until start_timeout.
                self._release(used, chips)
                self._drop(task_id, str(e))
                continue
            self._procs[task_id] = proc
            self.log.info("launched local task %s pid=%d chips=%s",
                          task_id[:8], proc.pid, chips)
            if self.chaos is not None:
                self.chaos.observe_launch(info.get("name", task_id),
                                          task_id, proc.pid)
            self._scheduler.on_status(TaskStatus(task_id, "TASK_RUNNING",
                                                 agent_id="local"))
            threading.Thread(target=self._watch,
                             args=(task_id, proc, used, chips),
                             name=f"watch-{task_id[:8]}", daemon=True).start()

    def _drop(self, task_id: str, why: str) -> None:
        self.log.warning("local launch of %s failed: %s", task_id[:8], why)
        self._scheduler.on_status(TaskStatus(
            task_id, "TASK_DROPPED", message=f"launch failed: {why}",
            agent_id="local"))

    def _watch(self, task_id: str, proc: subprocess.Popen, used,
               chips: List[int]) -> None:
        rc = proc.wait()
        self._release(used, chips)
        if self._shutdown.is_set():
            return
        state = "TASK_FINISHED" if rc == 0 else "TASK_FAILED"
        self._scheduler.on_status(
            TaskStatus(task_id, state, message=f"exit code {rc}", agent_id="local"))

    def decline(self, offer: Offer, refuse_seconds: float = 5.0) -> None:
        pass  # synthetic offers; nothing to return

    def suppress(self) -> None:
        self._suppressed.set()

    def revive(self) -> None:
        self._suppressed.clear()

    def kill(self, task_id: str) -> None:
        proc = self._procs.get(task_id)
        if proc is not None and proc.poll() is None:
            _terminate(proc)

    def chaos_drop_agent(self) -> None:
        """Fault-injection entry (chaos.FaultPlan 'drop_agent'): the whole
        agent vanishes — every task process SIGKILLed at once, then the
        agent-lost callback, exactly the order a real host loss presents."""
        for proc in list(self._procs.values()):
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        self._scheduler.on_agent_lost("local")

    def stop(self) -> None:
        self._shutdown.set()
        for proc in self._procs.values():
            if proc.poll() is None:
                _terminate(proc)
        deadline = time.monotonic() + 5.0
        for proc in self._procs.values():
            remaining = deadline - time.monotonic()
            try:
                proc.wait(timeout=max(0.0, remaining))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        if self._offer_thread is not None:
            self._offer_thread.join(timeout=2.0)


def _terminate(proc: subprocess.Popen) -> None:
    # Tasks are session leaders (start_new_session=True) so Mode B shell
    # children die with them.
    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError):
        try:
            proc.terminate()
        except ProcessLookupError:
            pass


def _res(resources: List[dict], name: str) -> float:
    for r in resources:
        if r["name"] == name:
            return float(r["scalar"]["value"])
    return 0.0
