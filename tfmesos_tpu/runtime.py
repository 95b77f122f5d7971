"""Task-side runtime: env contract, distributed init, mesh handles.

The reference exports ``TFMESOS_*`` env vars to between-graph user programs
(server.py:76-84) which then build their own ``tf.train.ClusterSpec``.  The
TPU-native contract keeps those names for drop-in compatibility and adds the
``TPUMESOS_*`` set carrying what a ``jax.distributed`` process actually
needs: rank, world size, coordinator address, and mesh axes.  A user program
calls :func:`initialize` once and gets a :class:`TaskContext` whose
``mesh()`` replaces the reference's ``ClusterSpec``+``tf.train.Server``
bring-up (mnist_replica.py:85-90) entirely.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ENV_RANK = "TPUMESOS_RANK"
ENV_WORLD = "TPUMESOS_WORLD_SIZE"
ENV_COORDINATOR = "TPUMESOS_COORDINATOR"
ENV_CLUSTER_DEF = "TPUMESOS_CLUSTER_DEF"
ENV_JOB_NAME = "TPUMESOS_JOB_NAME"
ENV_TASK_INDEX = "TPUMESOS_TASK_INDEX"
ENV_MESH_AXES = "TPUMESOS_MESH_AXES"
#: The host chips the local backend gave this task ("2", "0,1,2,3"), as
#: indices into the host's device nodes — whatever else libtpu is told.
ENV_CHIPS = "TPUMESOS_CHIPS"

_initialized = False


@dataclass
class TaskContext:
    """Everything one cluster member knows about itself and its peers."""

    rank: int = 0
    world_size: int = 1
    job_name: str = "worker"
    task_index: int = 0
    coordinator: Optional[str] = None
    cluster_def: Dict[str, List[str]] = field(default_factory=dict)
    mesh_axes: Optional[Dict[str, int]] = None
    extra_config: Dict[str, Any] = field(default_factory=dict)

    @property
    def is_chief(self) -> bool:
        return self.rank == 0

    def mesh(self, axes: Optional[Dict[str, int]] = None):
        """Build a ``jax.sharding.Mesh`` over all global devices.

        This is the successor of the reference's ``.targets`` map
        (scheduler.py:279-286): instead of per-task gRPC session targets, user
        code gets one mesh handle and lets shardings decide placement.
        """
        from tfmesos_tpu.parallel.mesh import build_mesh
        return build_mesh(axes or self.mesh_axes)

    @classmethod
    def from_env(cls) -> "TaskContext":
        cluster_def = json.loads(os.environ.get(ENV_CLUSTER_DEF, "{}"))
        mesh_axes_raw = os.environ.get(ENV_MESH_AXES, "")
        return cls(
            rank=int(os.environ.get(ENV_RANK, "0")),
            world_size=int(os.environ.get(ENV_WORLD, "1")),
            job_name=os.environ.get(ENV_JOB_NAME, "worker"),
            task_index=int(os.environ.get(ENV_TASK_INDEX, "0")),
            coordinator=os.environ.get(ENV_COORDINATOR) or None,
            cluster_def=cluster_def,
            mesh_axes=json.loads(mesh_axes_raw) if mesh_axes_raw else None,
        )

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "TaskContext":
        return cls(
            rank=int(config.get("rank", 0)),
            world_size=int(config.get("world_size", 1)),
            job_name=config.get("job_name", "worker"),
            task_index=int(config.get("task_index", 0)),
            coordinator=config.get("coordinator"),
            cluster_def=config.get("cluster_def") or {},
            mesh_axes=config.get("mesh_axes"),
            extra_config=config.get("extra_config") or {},
        )


def task_env(config: Dict[str, Any]) -> Dict[str, str]:
    """Render the env-var contract for a task config (both the compatible
    ``TFMESOS_*`` set, reference server.py:76-84, and the new ``TPUMESOS_*``
    set)."""
    cluster_def = config.get("cluster_def") or {}
    env = {
        # Reference-compatible set (hard-coded ps/worker names as in
        # server.py:72-75; empty when those jobs don't exist).
        "TFMESOS_PS_HOSTS": ",".join(cluster_def.get("ps", [])),
        "TFMESOS_WORKER_HOSTS": ",".join(cluster_def.get("worker", [])),
        "TFMESOS_JOB_NAME": str(config.get("job_name", "")),
        "TFMESOS_TASK_INDEX": str(config.get("task_index", 0)),
        "TFMESOS_DISTRIBUTED": "1",
        # TPU-native set.
        ENV_RANK: str(config.get("rank", 0)),
        ENV_WORLD: str(config.get("world_size", 1)),
        ENV_JOB_NAME: str(config.get("job_name", "")),
        ENV_TASK_INDEX: str(config.get("task_index", 0)),
        ENV_CLUSTER_DEF: json.dumps(cluster_def, separators=(",", ":")),
        "PYTHONUNBUFFERED": "1",
    }
    if config.get("coordinator"):
        env[ENV_COORDINATOR] = config["coordinator"]
    if config.get("mesh_axes"):
        env[ENV_MESH_AXES] = json.dumps(config["mesh_axes"], separators=(",", ":"))
    return env


def initialize(ctx: Optional[TaskContext] = None) -> TaskContext:
    """Join the distributed runtime.

    Replaces the reference's ``tf.train.Server(ServerDef).join()`` bring-up
    (server.py:52-66): one call wires this process into the global XLA
    runtime; afterwards ``jax.devices()`` sees every chip in the slice and
    collectives ride ICI.  Safe to call in a single-process run (no-op).
    """
    global _initialized
    if ctx is None:
        ctx = TaskContext.from_env()
    from tfmesos_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    import jax
    if ctx.world_size > 1 and not _initialized:
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator,
            num_processes=ctx.world_size,
            process_id=ctx.rank,
        )
        _initialized = True
    check_platform()
    return ctx


def check_platform():
    """Fail unless JAX came up on a platform ``JAX_PLATFORMS`` names, and
    return the first local device.  The local backend launches a chip task
    with ``JAX_PLATFORMS=tpu`` and a 0-chip task with ``cpu``, so a task
    never carries on silently on the wrong one.  Call only after
    ``jax.distributed.initialize``: querying devices earlier would
    initialize the local backend and break it."""
    import jax
    device = jax.local_devices()[0]
    requested = os.environ.get("JAX_PLATFORMS")
    if requested:
        allowed = [p.strip() for p in requested.split(",") if p.strip()]
        if device.platform not in allowed:
            raise RuntimeError(
                f"JAX_PLATFORMS={requested} was requested but the backend "
                f"initialized as {device.platform!r}")
    return device
