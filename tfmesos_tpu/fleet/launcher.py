"""Fleet bring-up: registry + gateway + dynamically-launched replicas.

``FleetServer`` is the one-object front: it generates a cluster token,
starts the registry and gateway locally, then launches the replicas as
**Mode-B tasks through the backend abstraction** — ``LocalBackend``
(the default with no master) runs whole fleets as CPU subprocesses for
development and CI; a Mesos master runs them on TPU agents with
per-replica chip/mem reservations.  The scheduler, registry, and
gateway share ONE token, delivered to replicas over the scheduler's
existing transport (mode-0600 token file for co-located backends), so
every hop of the serving path is authenticated with the same secret.

Replica membership is a RUNTIME property, not a launch-time constant
(the TF-Replicator stance): the scheduler runs in dynamic mode with an
initially-empty task table, and each tier converges toward a target
count — ``launch_replica``/``kill_replica`` grow and shrink it one
Mode-B task at a time, ``--autoscale`` hands the targets to a
:class:`~tfmesos_tpu.fleet.autoscaler.FleetAutoscaler` feedback loop,
and :meth:`FleetServer.rollout` replaces a whole tier's weights
blue-green with zero downtime (launch new-version replicas, warm them,
shift the router's version preference, bake, drain, reap — with the
registry's generation fence keeping reaped-generation stragglers out of
the serving path forever).

Replica death is a SERVING event here, not a cluster event: the
scheduler's fail-fast policy is for training meshes (which cannot
hot-swap members); the fleet instead routes around dead replicas and —
with the autoscaler on — relaunches them from the convergence loop.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from tfmesos_tpu import wire
from tfmesos_tpu.fleet.admission import AdmissionController, PriorityClass
from tfmesos_tpu.fleet.autoscaler import AutoscalerConfig, FleetAutoscaler
from tfmesos_tpu.fleet.catalog import (POOL, POOL_KEY, ModelCatalog,
                                       ModelSpec, ModelTrader,
                                       TraderConfig, filter_members,
                                       model_key, pack_adapter,
                                       split_key)
from tfmesos_tpu.fleet.client import FleetClient
from tfmesos_tpu.fleet.gateway import Gateway
from tfmesos_tpu.fleet.metrics import FleetMetrics
from tfmesos_tpu.fleet.registry import (ALIVE, DEAD, DECODE, KV,
                                        PREFILL, UNIFIED, WARMING,
                                        ReplicaRegistry,
                                        validate_model_id)
from tfmesos_tpu.fleet.router import Router
from tfmesos_tpu.fleet.tracing import TraceBook
from tfmesos_tpu.scheduler import (MAX_FAILURE_COUNT, ClusterError,
                                   TPUMesosScheduler)
from tfmesos_tpu.utils.logging import get_logger

__all__ = ["FleetServer", "RolloutError"]

#: tier role -> the scheduler job name its Mode-B tasks launch under.
TIER_JOBS = {UNIFIED: "replica", PREFILL: "prefill", DECODE: "decode",
             KV: "kv"}

#: weights_version labels join the replica COMMAND LINE, which Mode-B
#: agents execute with shell=True — the charset is a hard security
#: boundary, not cosmetics: a serve-token holder drives rollout through
#: the gateway op, and PR 4's hardening promise (a token cannot become
#: code execution) must hold for this surface too.
_VERSION_RE = re.compile(r"[A-Za-z0-9][A-Za-z0-9._-]{0,63}")

#: the KV-tier disk directory joins the same shell=True command line —
#: same boundary (conservative path charset, no whitespace, no shell
#: metacharacters, and no leading '-' that argparse would eat as a
#: flag).
_KV_DIR_RE = re.compile(r"[A-Za-z0-9/._~][A-Za-z0-9/._~+-]{0,255}")


def validate_kv_tier_dir(path: str) -> str:
    path = str(path)
    if not _KV_DIR_RE.fullmatch(path):
        raise ValueError(
            f"kv_tier_dir {path!r} is not a safe path: want 1-256 "
            f"chars of [A-Za-z0-9/._~+-] not starting with '-' or '+' "
            f"(it joins the replica command line, so the charset is a "
            f"security boundary)")
    return path


def validate_weights_version(version: str) -> str:
    version = str(version)
    # fullmatch, not match-with-$: '$' would accept a trailing newline,
    # which shell=True treats as a command terminator.
    if not _VERSION_RE.fullmatch(version):
        raise ValueError(
            f"weights_version {version!r} is not a valid label: want "
            f"1-64 chars of [A-Za-z0-9._-] starting alphanumeric (it "
            f"joins the replica command line, so the charset is a "
            f"security boundary)")
    return version


class RolloutError(RuntimeError):
    """A blue-green rollout aborted (the old version kept serving)."""


class FleetServer:
    """Bring up (and tear down) a whole serving fleet."""

    def __init__(self, replicas: int = 2, rows: int = 4,
                 tiny: bool = False, seed: int = 0,
                 max_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 prefill_bucket: Optional[int] = None,
                 multi_step: int = 1,
                 prefix_cache_pages: int = 0,
                 pipeline_depth: Optional[int] = None,
                 fused_prefill: bool = False,
                 tokens_per_tick: Optional[int] = None,
                 draft: bool = False,
                 n_draft: int = 4,
                 kv_tier_mb: float = 0.0,
                 kv_tier_dir: Optional[str] = None,
                 kv_replication: int = 1,
                 kv_placement: str = "rendezvous",
                 kv_replicas: int = 0,
                 warmup: bool = False,
                 prefill_replicas: int = 0,
                 decode_replicas: int = 0,
                 models: Optional[List[ModelSpec]] = None,
                 gang_size: int = 1,
                 warm_pool: int = 0,
                 model_budget: Optional[int] = None,
                 trader_config: Optional[TraderConfig] = None,
                 weights_version: str = "v0",
                 autoscale: bool = False,
                 min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 autoscale_config: Optional[AutoscalerConfig] = None,
                 backend=None, master: Optional[str] = None,
                 replica_cpus: float = 1.0, replica_mem: float = 1024.0,
                 replica_chips: int = 0,
                 gateway_host: str = "127.0.0.1", gateway_port: int = 0,
                 gateways: int = 1,
                 gateway_processes: int = 0,
                 http_port: Optional[int] = None,
                 workers: int = 8, max_queue: int = 64,
                 rate: Optional[float] = None,
                 burst: Optional[float] = None,
                 priority_classes: Optional[List[PriorityClass]] = None,
                 batch_lane: bool = False,
                 migrate_on_drain: bool = True,
                 breakers: bool = True,
                 max_retries: int = 2, request_timeout: float = 120.0,
                 start_timeout: float = 300.0,
                 heartbeat_interval: float = 0.3,
                 report_interval: Optional[float] = None,
                 metrics_port: Optional[int] = None,
                 trace_sample: float = 0.05,
                 trace_slow_ms: float = 1000.0,
                 quiet: bool = True, token: Optional[str] = None):
        if min(replicas, prefill_replicas, decode_replicas) < 0:
            raise ValueError(
                f"replica counts must be >= 0, got replicas={replicas} "
                f"prefill_replicas={prefill_replicas} "
                f"decode_replicas={decode_replicas}")
        if (prefill_replicas > 0) != (decode_replicas > 0):
            raise ValueError(
                f"prefill_replicas and decode_replicas come together — "
                f"a lone tier cannot serve the disaggregated handoff "
                f"(got prefill_replicas={prefill_replicas}, "
                f"decode_replicas={decode_replicas})")
        # Gang replicas (docs/SERVING.md "Gang replicas"): each unified
        # "replica" is N member tasks forming one pod-slice mesh,
        # scheduled as an atomic gang and routed as ONE replica (the
        # leader).  gang_size=1 is the classic single-process fleet —
        # zero behavior change.  Role-split tiers stay single-process
        # (the disaggregated handoff is a per-request hop, not a mesh).
        self.gang_size = int(gang_size)
        if self.gang_size < 1:
            raise ValueError(
                f"gang_size must be >= 1, got {gang_size}")
        if self.gang_size > 1 and (prefill_replicas or decode_replicas):
            raise ValueError(
                "gang replicas serve the unified tier; drop "
                "prefill_replicas/decode_replicas or gang_size")
        # Model catalog (docs/SERVING.md "Model catalog"): with
        # ``models``, the catalog entries size the fleet (each entry's
        # own ``replicas``), a ``warm_pool`` of undedicated pre-warmed
        # replicas caps cold-start TTFT, and every replica count lives
        # under ONE fleet-wide ``model_budget`` the trader reallocates
        # within.  ``replicas`` (the single-model knob) is ignored,
        # and the disaggregated role split is per-model routing only —
        # launching per-model role tiers is a later PR.
        self.catalog: Optional[ModelCatalog] = None
        self.warm_pool = int(warm_pool)
        self.trader_config = trader_config
        self.trader: Optional[ModelTrader] = None
        self.replica_budget: Optional[int] = None
        if self.warm_pool < 0:
            raise ValueError(f"warm_pool must be >= 0, got {warm_pool}")
        if models:
            if prefill_replicas or decode_replicas:
                raise ValueError(
                    "a model catalog runs unified tiers; drop "
                    "prefill_replicas/decode_replicas")
            self.catalog = ModelCatalog(models)
            # Budget math is in SLOTS (member tasks): a gang replica
            # of size N occupies N of them.
            boot = sum(s.replicas * s.gang_size for s in self.catalog)
            if boot + self.warm_pool < 1:
                raise ValueError(
                    "the catalog fleet needs at least one replica: "
                    "every entry boots 0 and warm_pool is 0")
            self.replica_budget = int(model_budget) \
                if model_budget is not None else boot + self.warm_pool
            if self.replica_budget < max(1, boot + self.warm_pool):
                raise ValueError(
                    f"model_budget ({self.replica_budget}) is below "
                    f"the boot footprint ({boot} model replicas + "
                    f"{self.warm_pool} warm pool)")
            replicas = 0
        elif self.warm_pool or model_budget is not None:
            raise ValueError("warm_pool/model_budget need a model "
                             "catalog (models=[...])")
        if self.catalog is None \
                and replicas + prefill_replicas + decode_replicas < 1:
            raise ValueError(
                f"the fleet needs at least one replica, got "
                f"replicas={replicas} + prefill_replicas="
                f"{prefill_replicas} + decode_replicas={decode_replicas}")
        self.replicas = int(replicas)
        self.prefill_replicas = int(prefill_replicas)
        self.decode_replicas = int(decode_replicas)
        initial = {UNIFIED: self.replicas, PREFILL: self.prefill_replicas,
                   DECODE: self.decode_replicas}
        # Autoscale bounds are PER TIER: an explicit --max-replicas
        # applies to every tier, but the default ceiling is twice EACH
        # tier's own initial count (a decode tier booted at 1 must not
        # inherit a 4-replica prefill tier's headroom), and a
        # non-autoscaled fleet's ceiling is exactly what was asked for.
        self.min_replicas = 1 if min_replicas is None else int(min_replicas)
        if self.min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1 (a routable tier can never "
                f"scale to zero), got {self.min_replicas}")
        self._tier_max: Dict[str, int] = {}
        for role, n in initial.items():
            if not n:
                continue
            if max_replicas is not None:
                self._tier_max[role] = int(max_replicas)
            else:
                self._tier_max[role] = max(2 * n, n + 1) if autoscale \
                    else n
        if self.catalog is not None:
            # Per-(model, tier) bounds are the trader's business: each
            # key may range [0, budget] — floors and scale-to-zero live
            # in the catalog entries, the ceiling is the shared budget.
            self.max_replicas = self.replica_budget
        else:
            self.max_replicas = max(self._tier_max.values())
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                f"max_replicas ({self.max_replicas}) must be >= "
                f"min_replicas ({self.min_replicas})")
        for role, n in initial.items():
            if n and not (self.min_replicas <= n
                          <= self._tier_max[role]):
                raise ValueError(
                    f"initial {role} tier count {n} lies outside the "
                    f"autoscale bounds [{self.min_replicas}, "
                    f"{self._tier_max[role]}]")
        self.weights_version = validate_weights_version(weights_version)
        self.autoscale = bool(autoscale)
        self.autoscale_config = autoscale_config
        self.rows = int(rows)
        self.tiny = bool(tiny)
        self.seed = int(seed)
        self.max_len = max_len
        self.page_size = page_size
        self.prefill_bucket = prefill_bucket
        self.multi_step = int(multi_step)
        self.prefix_cache_pages = int(prefix_cache_pages)
        #: None: not passed on, each replica's batcher chooses
        self.pipeline_depth = (None if pipeline_depth is None
                               else int(pipeline_depth))
        #: stall-free fused scheduling per replica (docs/SERVING.md
        #: "Stall-free fused scheduling"): one dispatch per tick covers
        #: the decode block AND a budgeted batch of prefill chunk
        #: slots.  Default off; modes the fused program cannot cover
        #: bypass inside the batcher with a recorded reason.  Both
        #: values join the shell=True replica command line, so both are
        #: validated as ints/bools here (str(int) is charset-safe).
        self.fused_prefill = bool(fused_prefill)
        self.tokens_per_tick = (None if tokens_per_tick is None
                                else int(tokens_per_tick))
        if self.tokens_per_tick is not None and self.tokens_per_tick < 1:
            raise ValueError(f"tokens_per_tick must be >= 1, got "
                             f"{tokens_per_tick}")
        #: speculative decoding per replica (replicas serve with the
        #: preset draft companion model; the acceptance rate rides
        #: heartbeats into the gateway's ``spec`` gauge).  Composes
        #: with the prefix cache, the KV tier, migration, and the
        #: disagg role split — the bypass registry enforces what
        #: doesn't (docs/SERVING.md "Speculative decoding &
        #: composition").
        self.draft = bool(draft)
        self.n_draft = int(n_draft)
        if self.draft and self.n_draft < 1:
            raise ValueError(f"n_draft must be >= 1, got {n_draft}")
        #: tiered KV store per replica (docs/SERVING.md "KV tiering &
        #: sessions"): a >0 RAM budget turns it on; with no explicit
        #: disk dir the launcher mints ONE host-shared temp directory
        #: so every co-located replica can resume any sibling's parked
        #: sessions (removed on stop).  0/None = off: zero behavior
        #: change.
        if kv_tier_mb < 0:
            raise ValueError(f"kv_tier_mb must be >= 0, got {kv_tier_mb}")
        self.kv_tier_mb = float(kv_tier_mb)
        self.kv_tier_dir = (validate_kv_tier_dir(kv_tier_dir)
                            if kv_tier_dir is not None else None)
        self._kv_tier_tmp: Optional[str] = None
        #: cross-host KV fabric (docs/SERVING.md "Cross-host KV
        #: fabric"): replication is the K-way parking factor each
        #: replica's fabric wrapper enforces (1 = local-only, the
        #: pre-fabric behavior exactly); kv_replicas boots that many
        #: dedicated KV-role holders — storage-only peers that park
        #: sessions/prefixes but never serve tokens, so artifacts
        #: survive every serving replica scaling to zero.  Both join
        #: the shell=True replica command line, so both are validated
        #: as ints here (str(int) emits [0-9]+ only — charset-safe).
        self.kv_replication = int(kv_replication)
        if not 1 <= self.kv_replication <= 8:
            raise ValueError(
                f"kv_replication must be in [1, 8], got {kv_replication}")
        #: replica-copy placement policy for the KV fabric (PR 18's sim
        #: knob promoted to production): "rendezvous" = pure HRW hash;
        #: "loaded" = HRW within occupancy buckets, so loaded peers
        #: shed copy traffic (tuned via ``tfserve simulate sessions
        #: --sweep kv.placement=rendezvous,loaded``).  Validated against
        #: the closed set here because it joins the shell=True replica
        #: command line.
        if kv_placement not in ("rendezvous", "loaded"):
            raise ValueError(f"kv_placement must be 'rendezvous' or "
                             f"'loaded', got {kv_placement!r}")
        self.kv_placement = kv_placement
        self.kv_replicas = int(kv_replicas)
        if self.kv_replicas < 0:
            raise ValueError(
                f"kv_replicas must be >= 0, got {kv_replicas}")
        if self.kv_replicas and self.kv_tier_mb <= 0:
            raise ValueError(
                "dedicated KV-role replicas hold tier artifacts — they "
                "need kv_tier_mb > 0")
        if self.kv_replicas:
            # The kv tier is pinned at its boot size: the autoscaler's
            # signals (queue wait, utilization) never move for a
            # storage-only holder, so letting the loop retarget it
            # would only ever shrink it.
            self._tier_max[KV] = self.kv_replicas
        self.warmup = bool(warmup)
        self.backend = backend
        self.master = master
        self.replica_cpus = float(replica_cpus)
        self.replica_mem = float(replica_mem)
        self.replica_chips = int(replica_chips)
        self.gateway_host = gateway_host
        self.gateway_port = int(gateway_port)
        #: horizontal front-door scale (docs/SERVING.md "Front-door
        #: scaling"): N stateless gateways over ONE shared
        #: registry/router/admission view.  The first listens on
        #: ``gateway_port``, the rest on OS-assigned ports; all
        #: register for the ``gateways`` discovery op, and
        #: FleetClient fails over between them.
        self.n_gateways = int(gateways)
        if self.n_gateways < 1:
            raise ValueError(
                f"gateways must be >= 1, got {gateways}")
        #: multi-PROCESS front door (docs/SERVING.md "Multi-process
        #: gateways"): > 0 replaces the in-process gateway threads with
        #: N ``python -m tfmesos_tpu.fleet.gateway`` OS processes, each
        #: running its own WireServer/admission/router over a registry-
        #: client sidecar's mirrored view.  They share ONE public port
        #: via SO_REUSEPORT where the platform has it, else fall back
        #: to per-process ports behind the ``gateways`` discovery op.
        #: 0 = in-process mode, the pre-PR behavior exactly.
        self.gateway_processes = int(gateway_processes)
        if self.gateway_processes < 0:
            raise ValueError(
                f"gateway_processes must be >= 0, got {gateway_processes}")
        if self.gateway_processes and self.catalog is not None:
            # The trader answers cold-start demand through the SHARED
            # in-process router; a subprocess gateway's private router
            # has no trader to ask, so a catalog fleet would silently
            # lose scale-from-zero.  Refuse loudly instead.
            raise ValueError(
                "gateway_processes and a model catalog are mutually "
                "exclusive: catalog cold-start demand rides the "
                "in-process router")
        #: HTTP/1.1 + SSE ingress (docs/SERVING.md "HTTP/SSE edge"):
        #: None = off (the pre-PR wire-only surface).  In-process mode
        #: gives the port to the FIRST gateway; in subprocess mode the
        #: first gateway process carries it.
        self.http_port = None if http_port is None else int(http_port)
        self.workers = int(workers)
        self.max_queue = int(max_queue)
        self.rate = rate
        self.burst = burst
        #: admission priority classes (weighted-fair queues at the
        #: gateway + preemption ranks inside the replicas); None = one
        #: default class, the pre-priority behavior exactly.
        self.priority_classes = list(priority_classes) \
            if priority_classes else None
        #: the OFFLINE lane (docs/SERVING.md "Offline lane"): appends a
        #: deadline-less ``batch`` class that dispatches only when
        #: every interactive queue is empty (strict background at the
        #: gateway's WFQ) and ranks BELOW every other class, so its
        #: resident rows yield their decode slots to the first
        #: interactive arrival via the replicas' preemption machinery.
        self.batch_lane = bool(batch_lane)
        if self.batch_lane:
            specs = (list(self.priority_classes)
                     if self.priority_classes
                     else [PriorityClass("interactive", weight=1.0,
                                         rank=0)])
            if not any(c.name == "batch" for c in specs):
                floor = min(c.rank for c in specs)
                specs.append(PriorityClass("batch", weight=1.0,
                                           rank=floor - 1, batch=True))
            self.priority_classes = specs
        #: drain-migrate-kill: when a drain is pinned (autoscaler
        #: scale-down, rollout reap), ask the victim to SUSPEND its
        #: in-flight rows so the router re-places them on survivors —
        #: instead of waiting for them to finish (or worse, flushing
        #: them).  False restores plain drain-then-kill.
        self.migrate_on_drain = bool(migrate_on_drain)
        #: per-replica circuit breakers in the router (consecutive-
        #: failure + latency-outlier tripping); False is the bench's
        #: control arm and an operator escape hatch, never the default.
        self.breakers = bool(breakers)
        self.max_retries = int(max_retries)
        self.request_timeout = float(request_timeout)
        self.start_timeout = float(start_timeout)
        self.heartbeat_interval = float(heartbeat_interval)
        self.report_interval = report_interval
        #: optional stdlib-HTTP Prometheus exposition (GET /metrics on
        #: loopback); None = no endpoint, the pre-PR-10 behavior.
        self.metrics_port = metrics_port
        #: request-tracing knobs (docs/SERVING.md "Observability"):
        #: head-sample fraction retained with FULL span detail, and the
        #: slower-than-this tail threshold that retains detail
        #: regardless — failures/sheds/deadline-exceeded always retain.
        self.trace_sample = float(trace_sample)
        self.trace_slow_ms = float(trace_slow_ms)
        self.quiet = quiet
        self.log = get_logger("tfmesos_tpu.fleet", quiet=quiet)

        # An explicit token lets external clients authenticate (tfserve
        # resolves one from the standard TPUMESOS_TOKEN/_FILE contract);
        # by default each bring-up mints its own.
        self._token = token
        self.token: Optional[str] = None
        self.metrics: Optional[FleetMetrics] = None
        self.tracebook: Optional[TraceBook] = None
        self._metrics_http = None
        self.registry: Optional[ReplicaRegistry] = None
        self.router: Optional[Router] = None
        self.admission: Optional[AdmissionController] = None
        self.gateway: Optional[Gateway] = None
        #: every running front door (``gateway`` is ``gateways[0]``).
        self.gateways: List[Gateway] = []
        #: gateway OS processes (subprocess mode); empty in-process.
        self._gateway_procs: list = []
        #: the HTTP/SSE edge address once bound (either mode).
        self.http_addr: Optional[str] = None
        self.scheduler: Optional[TPUMesosScheduler] = None
        self.autoscaler: Optional[FleetAutoscaler] = None
        #: per-tier replica targets — what the control plane WANTS; the
        #: convergence loops (autoscaler, _wait_replicas) drive actuals
        #: toward these.
        self.targets: Dict[str, int] = {}
        #: serializes every scaling decision: autoscaler ticks and
        #: rollouts are mutually exclusive (a rollout must not race the
        #: loop retargeting the tier it is replacing).
        self.scale_lock = threading.RLock()
        #: node id -> target key ("role", or "model/role" / POOL_KEY in
        #: catalog mode): how per-(model, tier) actuals are counted
        #: when every model's tasks share one scheduler job.  Updated
        #: at launch and on warm-pool adoption.
        self._node_keys: Dict[str, str] = {}
        #: gang id -> {key, job, size, task_ids, leader_node,
        #: weights_version}.  The gang book: popped on the FIRST member
        #: death (or a deliberate kill) so sibling deaths and racing
        #: reforms dedup to exactly one action per gang.
        self._gangs: Dict[str, dict] = {}
        self._gang_lock = threading.Lock()
        self._started = False

    # -- bring-up ----------------------------------------------------------

    def _replica_cmd(self, role: str = UNIFIED,
                     weights_version: Optional[str] = None,
                     model: Optional[ModelSpec] = None,
                     pool: bool = False) -> str:
        version = self.weights_version if weights_version is None \
            else weights_version
        parts = [sys.executable, "-m", "tfmesos_tpu.fleet.replica",
                 "--registry", self.registry.addr,
                 "--rows", str(self.rows),
                 "--seed", str(self.seed),
                 "--heartbeat-interval", str(self.heartbeat_interval)]
        if model is not None:
            # model_id is validated at catalog construction — the same
            # shell=True boundary as weights_version.
            parts += ["--model-id", model.model_id,
                      "--model-seed", str(model.seed)]
        if pool:
            parts += ["--warm-pool"]
        if role != UNIFIED:
            parts += ["--role", role]
        if version:
            parts += ["--weights-version", version]
        if self.tiny:
            parts.append("--tiny")
        if self.max_len is not None:
            parts += ["--max-len", str(self.max_len)]
        if self.page_size is not None:
            parts += ["--page-size", str(self.page_size)]
        if self.prefill_bucket is not None:
            parts += ["--prefill-bucket", str(self.prefill_bucket)]
        if self.multi_step != 1:
            parts += ["--multi-step", str(self.multi_step)]
        if self.prefix_cache_pages:
            parts += ["--prefix-cache-pages", str(self.prefix_cache_pages)]
        if self.pipeline_depth is not None:
            parts += ["--pipeline-depth", str(self.pipeline_depth)]
        if self.fused_prefill:
            parts.append("--fused-prefill")
        if self.tokens_per_tick is not None:
            parts += ["--tokens-per-tick", str(self.tokens_per_tick)]
        if self.draft:
            parts += ["--draft", "--n-draft", str(self.n_draft)]
        if self.kv_tier_mb > 0:
            parts += ["--kv-tier-mb", str(self.kv_tier_mb)]
            tier_dir = self.kv_tier_dir or self._kv_tier_tmp
            if tier_dir:
                parts += ["--kv-tier-dir", tier_dir]
        elif self.kv_tier_dir:
            parts += ["--kv-tier-dir", self.kv_tier_dir]
        if self.kv_replication > 1:
            parts += ["--kv-replication", str(self.kv_replication)]
        if self.kv_placement != "rendezvous":
            # Validated against the closed set at construction (the
            # same shell=True boundary as the ints above).
            parts += ["--kv-placement", self.kv_placement]
        if self.warmup:
            # Every launch of this cmd — boot, an autoscale-up, OR a
            # later elastic/Mode-B relaunch — registers warming,
            # compiles, then takes traffic: re-warming is a property of
            # the command line, not of the first bring-up.
            parts.append("--warmup")
        return " ".join(parts)

    def _gateway_cmd(self, port: int, reuseport: bool,
                     http_port: Optional[int]) -> List[str]:
        """One gateway process's argv (exec'd directly, never through a
        shell): the wire listener address plus the same admission/
        routing constants every in-process gateway gets.  The cluster
        token rides the environment (``TPUMESOS_TOKEN``), never the
        command line."""
        parts = [sys.executable, "-m", "tfmesos_tpu.fleet.gateway",
                 "--registry", self.registry.addr,
                 "--host", self.gateway_host,
                 "--port", str(int(port)),
                 "--workers", str(self.workers),
                 "--max-queue", str(self.max_queue),
                 "--max-retries", str(self.max_retries),
                 "--request-timeout", str(self.request_timeout)]
        if reuseport:
            parts.append("--reuseport")
        if self.rate is not None:
            parts += ["--rate", str(self.rate)]
        if self.burst is not None:
            parts += ["--burst", str(self.burst)]
        if http_port is not None:
            parts += ["--http-port", str(int(http_port)),
                      "--http-host", self.gateway_host]
        return parts

    def _start_gateway_procs(self) -> None:
        """Launch ``gateway_processes`` front-door OS processes.  They
        share ONE public port via SO_REUSEPORT where the platform has
        it (the kernel load-balances accepts); elsewhere each takes an
        OS-assigned port and clients discover the set through the
        ``gateways`` op.  Either way every process leases a discovery
        entry in the central registry, which is also how this method
        knows bring-up finished."""
        n = self.gateway_processes
        reuseport = wire.reuseport_available()
        shared_port = 0
        if reuseport:
            shared_port = self.gateway_port
            if not shared_port:
                # Pick the shared port up front: bind-with-REUSEPORT,
                # read, close.  The tiny close-to-spawn window is the
                # standard ephemeral-port race; a loser fails loudly
                # at bind and the bring-up wait reports it.
                probe = wire.bind_ephemeral(self.gateway_host, 0,
                                            reuseport=True)
                shared_port = probe.getsockname()[1]
                probe.close()
        env = dict(os.environ)
        env["TPUMESOS_TOKEN"] = self.token
        env.pop("TPUMESOS_TOKEN_FILE", None)
        sink = subprocess.DEVNULL if self.quiet else None
        for i in range(n):
            if reuseport:
                port = shared_port
            else:
                port = self.gateway_port if i == 0 else 0
            cmd = self._gateway_cmd(
                port, reuseport,
                self.http_port if i == 0 else None)
            self._gateway_procs.append(subprocess.Popen(
                cmd, env=env, stdout=sink, stderr=sink))
        # Every process holds its OWN lease (keyed by its private
        # scrape addr), so N leases = N processes up even when
        # SO_REUSEPORT collapses the public discovery set to one addr.
        deadline = time.monotonic() + min(self.start_timeout, 30.0)
        while time.monotonic() < deadline:
            dead = [p for p in self._gateway_procs
                    if p.poll() is not None]
            if dead:
                raise ClusterError(
                    f"{len(dead)} gateway process(es) died during "
                    f"bring-up (first exit code "
                    f"{dead[0].returncode})")
            if len(self.registry.gateway_leases()) >= n:
                break
            time.sleep(0.05)
        else:
            raise ClusterError(
                f"only {len(self.registry.gateway_leases())} of {n} "
                f"gateway lease(s) registered within the bring-up "
                f"window")
        if self.http_port:
            self.http_addr = f"{self.gateway_host}:{self.http_port}"
        # Fleet-level scrape: the launcher's own /metrics (and
        # fleet_snapshot()) fold every gateway process's raw state in
        # at scrape time.
        self.metrics.fanin = self._scrape_gateway_raws
        self.log.info(
            "%d gateway process(es) up%s", n,
            f" sharing :{shared_port} via SO_REUSEPORT" if reuseport
            else " on per-process ports (no SO_REUSEPORT; clients "
                 "discover via the gateways op)")

    def _wait_gateway_mirrors(self, timeout: float = 15.0) -> None:
        """Block until every gateway process's sidecar mirror can route
        to as many alive replicas as the central registry lists RIGHT
        NOW — without this, a client's first request races the mirror's
        poll cadence and sheds with "no alive replicas" on a fleet
        that is, in fact, up."""
        want = len(self.registry.alive())
        if not want:
            return
        pending = set(self.registry.gateway_leases())
        deadline = time.monotonic() + timeout
        while pending and time.monotonic() < deadline:
            for addr in sorted(pending):
                try:
                    sock = wire.connect(addr, timeout=2.0)
                    try:
                        sock.settimeout(2.0)
                        wire.send_msg(sock, {"op": "status"}, self.token)
                        reply = wire.recv_msg(sock, self.token)
                    finally:
                        sock.close()
                except (OSError, wire.WireError):
                    continue
                alive = reply.get("alive") if isinstance(reply, dict) \
                    else None
                if isinstance(alive, int) and alive >= want:
                    pending.discard(addr)
            if pending:
                time.sleep(0.05)
        if pending:
            raise ClusterError(
                f"{len(pending)} gateway process(es) never mirrored "
                f"the {want} alive replica(s) within {timeout:.0f}s")

    def _scrape_gateway_raws(self) -> List[dict]:
        """Every gateway process's mergeable metrics state (``metrics``
        op with ``raw: true`` against each process's PRIVATE scrape
        listener — the shared REUSEPORT public addr would land on a
        kernel-chosen process); an unreachable process costs its
        contribution, never the scrape."""
        raws: List[dict] = []
        registry = self.registry
        if registry is None:
            return raws
        for addr in registry.gateway_leases():
            try:
                sock = wire.connect(addr, timeout=2.0)
                try:
                    sock.settimeout(2.0)
                    wire.send_msg(sock, {"op": "metrics", "raw": True},
                                  self.token)
                    reply = wire.recv_msg(sock, self.token)
                finally:
                    sock.close()
            except (OSError, wire.WireError):
                continue
            raw = reply.get("raw") if isinstance(reply, dict) else None
            if isinstance(raw, dict):
                raws.append(raw)
        return raws

    def fleet_snapshot(self) -> dict:
        """The FLEET-level metrics snapshot: in subprocess-gateway mode
        this merges every gateway process's counters/histograms into
        the launcher's own registry at scrape time; otherwise it is
        :meth:`snapshot` exactly."""
        if self.metrics is None:
            return {}
        if self.metrics.fanin is None:
            return self.metrics.snapshot()
        return self.metrics.merged().snapshot()

    def start(self) -> "FleetServer":
        self.token = self._token or wire.new_token()
        self.metrics = FleetMetrics()
        if self.kv_tier_mb > 0 and self.kv_tier_dir is None \
                and self._kv_tier_tmp is None:
            import tempfile

            # One HOST-shared disk tier for every co-located replica:
            # parked sessions resume on any same-version sibling, the
            # cross-replica half of the session contract.  mkdtemp is
            # mode 0700 and the entries are HMAC-framed with the
            # cluster token, so a foreign write reads as corruption.
            self._kv_tier_tmp = tempfile.mkdtemp(prefix="tfserve-kvtier-")
        try:
            # Liveness thresholds scale with the heartbeat cadence: a
            # slower (perfectly legal) interval must not make healthy
            # replicas flap alive -> draining between beats.
            hb = self.heartbeat_interval
            self.registry = ReplicaRegistry(
                token=self.token, metrics=self.metrics,
                suspect_after=max(1.5, 5.0 * hb),
                dead_after=max(3.0, 10.0 * hb),
                evict_after=max(10.0, 20.0 * hb)).start()
            self.router = Router(self.registry, self.metrics,
                                 token=self.token,
                                 max_retries=self.max_retries,
                                 request_timeout=self.request_timeout,
                                 breakers=self.breakers)
            self.admission = AdmissionController(
                max_queue=self.max_queue, rate=self.rate,
                burst=self.burst, classes=self.priority_classes)
            self.tracebook = TraceBook(sample=self.trace_sample,
                                       slow_ms=self.trace_slow_ms)
            # N stateless gateways over the ONE registry/router/
            # admission/tracebook view: any gateway serves any client,
            # so the set is purely a connection-capacity and failure-
            # isolation multiplier.  The shared router's lifecycle is
            # the launcher's (close_router=False) — a stopping gateway
            # must not tear down its siblings' replica links.
            if self.gateway_processes:
                # Multi-PROCESS front door: N OS processes, each with
                # its own WireServer loop, admission WFQ, and router
                # over a registry-sidecar view — the in-process Gateway
                # objects (and their shared-object wiring: rollout_fn,
                # catalog, swap_adapter) do not exist in this mode.
                self._start_gateway_procs()
            else:
                self.gateways = []
                for i in range(self.n_gateways):
                    gw = Gateway(self.router, self.admission, self.metrics,
                                 token=self.token, host=self.gateway_host,
                                 port=self.gateway_port if i == 0 else 0,
                                 workers=self.workers,
                                 registry=self.registry,
                                 tracebook=self.tracebook,
                                 close_router=False,
                                 http_port=self.http_port
                                 if i == 0 else None).start()
                    self.gateways.append(gw)
                self.gateway = self.gateways[0]
                self.http_addr = self.gateway.http_addr
            if self.metrics_port is not None:
                self._metrics_http = self.metrics.start_http_server(
                    self.metrics_port)
                self.log.info(
                    "prometheus exposition on :%d/metrics",
                    self._metrics_http.server_address[1])
            # The scheduler starts EMPTY in dynamic mode: the task table
            # is a runtime property, and every replica — boot ones
            # included — goes through the same launch_replica path the
            # autoscaler and rollouts use.
            self.scheduler = TPUMesosScheduler(
                [], dynamic=True, backend=self.backend, master=self.master,
                quiet=self.quiet, start_timeout=self.start_timeout,
                token=self.token)
            # A gang member's death is the GANG's death: the scheduler
            # reports it (off its status thread) and the fleet tears
            # down the siblings and re-forms the gang whole.
            self.scheduler.on_dynamic_death = self._on_dynamic_death
            self.scheduler.start()
            if self.catalog is not None:
                # Per-(model, tier) targets + the warm pool, all under
                # one budget.  Entries booting 0 replicas start scaled
                # to zero and cold-start through the pool on demand.
                for spec in self.catalog:
                    key = model_key(spec.model_id)
                    self.set_target(key, spec.replicas)
                    for _ in range(spec.replicas):
                        self.launch_replica(key)
                if self.warm_pool:
                    self.set_target(POOL_KEY, self.warm_pool)
                    for _ in range(self.warm_pool):
                        self.launch_replica(POOL_KEY)
            else:
                for role, n in ((UNIFIED, self.replicas),
                                (PREFILL, self.prefill_replicas),
                                (DECODE, self.decode_replicas)):
                    if n:
                        self.set_target(role, n)
                        for _ in range(n):
                            self.launch_replica(role)
            if self.kv_replicas:
                # Dedicated KV holders ride the same launch/convergence
                # path as serving tiers (a crashed holder relaunches),
                # but capacity-0: the router never routes tokens at one.
                self.set_target(KV, self.kv_replicas)
                for _ in range(self.kv_replicas):
                    self.launch_replica(KV)
            self._wait_replicas()
            if self.gateway_processes:
                self._wait_gateway_mirrors()
            for gw in self.gateways:
                gw.rollout_fn = self.rollout
                gw.catalog = self.catalog
                if self.catalog is not None:
                    gw.swap_adapter_fn = self._swap_adapter_packed
            if self.catalog is not None:
                # The trader IS the catalog fleet's control loop: it
                # reallocates the budget between models, scales idle
                # ones to zero, and answers the router's cold-start
                # demands from the warm pool.
                self.trader = ModelTrader(
                    self, self.catalog, self.autoscale_config,
                    trader_config=self.trader_config).start()
                self.autoscaler = self.trader
                self.router.on_model_demand = self.trader.demand
            elif self.autoscale:
                self.autoscaler = FleetAutoscaler(
                    self, self.autoscale_config).start()
        except Exception:
            self.stop()
            raise
        self._started = True
        if self.report_interval:
            self.metrics.start_reporter(self.log, self.report_interval)
        self.log.info("fleet up: gateway%s %s, %d replica(s) "
                      "(%d unified / %d prefill / %d decode)%s",
                      "s" if self.n_gateways > 1 else "",
                      ", ".join(self.addrs),
                      self.total_replicas, self.replicas,
                      self.prefill_replicas, self.decode_replicas,
                      f", autoscaling within [{self.min_replicas}, "
                      f"{self.max_replicas}]" if self.autoscale else "")
        return self

    @property
    def total_replicas(self) -> int:
        return self.replicas + self.prefill_replicas + self.decode_replicas

    # -- dynamic tier management -------------------------------------------

    def set_target(self, role: str, n: int) -> None:
        """Record one tier's wanted replica count (mirrored into the
        registry so the ``roles`` gauge shows target vs actual)."""
        self.targets[role] = int(n)
        self.registry.set_target(role, int(n))

    def bounds(self, key: str) -> Tuple[int, int]:
        """The autoscale bounds this tier's target must stay within
        (the floor is fleet-wide, the ceiling per tier).  Composite
        per-(model, tier) keys range [0, budget] — their floors and
        scale-to-zero policy live in the catalog entries the trader
        enforces."""
        model, _ = split_key(key)
        if model is not None:
            return 0, self.replica_budget or self.max_replicas
        return self.min_replicas, self._tier_max.get(key,
                                                     self.max_replicas)

    def gang_size_for(self, key: str) -> int:
        """How many member tasks one replica of ``key`` launches as:
        the catalog entry's ``gang_size`` for model keys, the fleet's
        for the unified tier, and always 1 for role-split tiers and
        the warm pool (a pool replica has no model to shard yet)."""
        model, role = split_key(key)
        if model == POOL:
            return 1
        if model is not None:
            return int(getattr(self.catalog.get(model),
                               "gang_size", 1) or 1)
        return self.gang_size if role == UNIFIED else 1

    def launch_replica(self, key: str,
                       weights_version: Optional[str] = None) -> str:
        """Launch ONE new Mode-B replica for ``key`` — a plain
        role, a composite ``"<model>/<role>"``, or the warm pool's
        :data:`POOL_KEY` — and return its node id ("job:index"); with
        ``--warmup`` on the cmd line it registers ``warming`` and
        never takes traffic cold.  With a gang size > 1 the "replica"
        is a whole gang (N tasks, one routable leader) and the node id
        is the LEADER's."""
        size = self.gang_size_for(key)
        if size > 1:
            return self.launch_gang(key, weights_version, size)
        model, role = split_key(key)
        spec = None
        pool = model == POOL
        if model is not None and not pool:
            spec = self.catalog.get(model)
        job = TIER_JOBS[role]
        task = self.scheduler.add_task(
            job, cmd=self._replica_cmd(role, weights_version,
                                       model=spec, pool=pool),
            cpus=self.replica_cpus, mem=self.replica_mem,
            chips=self.replica_chips)
        node = f"{job}:{task.task_index}"
        self._node_keys[node] = key
        return node

    def launch_gang(self, key: str,
                    weights_version: Optional[str] = None,
                    size: Optional[int] = None) -> str:
        """Launch one GANG replica for ``key``: N identical member
        cmds enter the scheduler as an atomic all-or-nothing gang
        (the gang env contract — id/size/rank — is stamped by
        ``add_gang``), rank 0 leads and registers as the one routable
        node this method returns."""
        size = self.gang_size_for(key) if size is None else int(size)
        model, role = split_key(key)
        spec = None
        if model is not None and model != POOL:
            spec = self.catalog.get(model)
        job = TIER_JOBS[role]
        cmd = self._replica_cmd(role, weights_version, model=spec)
        members = self.scheduler.add_gang(
            job, [cmd] * size, cpus=self.replica_cpus,
            mem=self.replica_mem, chips=self.replica_chips)
        gang_id = members[0].gang
        node = f"{job}:{members[0].task_index}"
        with self._gang_lock:
            self._gangs[gang_id] = {
                "key": key, "job": job, "size": size,
                "task_ids": [t.id for t in members],
                "leader_node": node,
                "weights_version": weights_version}
        self._node_keys[node] = key
        return node

    def kill_replica(self, node: str) -> bool:
        """Kill one replica by its node id ("job:index").  A gang
        leader's node kills the WHOLE gang — members without a leader
        are not a smaller replica, they are debris."""
        # The node->key book entry dies with the task either way — a
        # churning trader (trade = kill + relaunch per cooldown) must
        # not grow the book, and tier_actual scans it per tick.
        self._node_keys.pop(node, None)
        with self._gang_lock:
            gang_id = next(
                (g for g, info in self._gangs.items()
                 if info["leader_node"] == node), None)
            info = self._gangs.pop(gang_id, None) if gang_id else None
        if info is not None:
            # remove_task pulls each member from the table BEFORE the
            # kill, so the sibling deaths report under unknown ids and
            # never re-enter the gang-death path.
            killed = False
            for tid in info["task_ids"]:
                killed = self.scheduler.remove_task(tid) or killed
            return killed
        job, _, idx = node.rpartition(":")
        try:
            task = self.scheduler.task_by_index(job, int(idx))
        except ValueError:
            return False
        if task is None:
            return False
        return self.scheduler.remove_task(task.id)

    def _on_dynamic_death(self, task) -> None:
        """Scheduler death hook (on its own thread, never the status
        thread): a gang member died, so tear the gang down whole and
        re-form it under a FRESH generation and a fresh gang id — the
        double fence that makes a zombie member of the dead gang
        unroutable forever (its gang_lookup never resolves, and the
        new leader rejects joins of any other (gang, generation))."""
        gang_id = getattr(task, "gang", None)
        if gang_id is None:
            return
        with self._gang_lock:
            info = self._gangs.pop(gang_id, None)
        if info is None:
            return      # sibling already took the gang down
        self._node_keys.pop(info["leader_node"], None)
        for tid in info["task_ids"]:
            if tid == task.id:
                continue
            try:
                self.scheduler.remove_task(tid)
            except Exception as e:
                self.log.warning("gang %s sibling %s teardown failed: "
                                 "%s", gang_id, tid, e)
        if not self._started or self.scheduler is None:
            return
        try:
            self.scheduler.bump_generation()
            node = self.launch_gang(info["key"],
                                    info.get("weights_version"),
                                    info["size"])
            if self.metrics is not None:
                self.metrics.inc("gang_reforms")
            self.log.warning(
                "gang %s lost a member; torn down and re-forming as "
                "%s (leader %s)", gang_id, info["key"], node)
        except Exception:
            self.log.exception("gang %s re-form failed; the "
                               "convergence loop will retry", gang_id)

    def tier_actual(self, key: str) -> int:
        """Live tasks launched for one tier (registered or not) — the
        convergence loops' notion of "actual".  A gang counts as ONE
        unit (its N member tasks are one replica).  Composite keys
        count through the node->key map intersected with the
        scheduler's live task table (all models share one job)."""
        model, role = split_key(key)
        job = TIER_JOBS[role]
        if model is None:
            loose, gangs = 0, set()
            for t in self.scheduler.tasks_of(job):
                gang_id = getattr(t, "gang", None)
                if gang_id is None:
                    loose += 1
                else:
                    gangs.add(gang_id)
            return loose + len(gangs)
        # Only gang LEADERS enter the node->key book, so the
        # intersection already counts a gang once.
        live = {f"{job}:{t.task_index}"
                for t in self.scheduler.tasks_of(job)}
        return sum(1 for node, k in self._node_keys.items()
                   if k == key and node in live)

    def tier_members(self, key: str):
        """Registry members of one target key (the trader's
        membership query): role-filtered by the registry, model/pool-
        filtered here."""
        model, role = split_key(key)
        return filter_members(self.registry.members(role), key)

    def adopt_replica(self, addr: str, model_id: str,
                      timeout: float = 60.0) -> bool:
        """Assign a warm-pool replica a catalog model via the
        ``adopt`` control op (a weight install on a pre-warmed
        process — the cold-start path that skips launch + compile).
        Updates the node->key book immediately so the trader's actuals
        follow without waiting a heartbeat."""
        spec = self.catalog.get(model_id)
        try:
            reply = self.router.control(
                addr, {"op": "adopt", "model_id": spec.model_id,
                       "seed": spec.seed}, timeout=timeout)
        except Exception as e:
            self.log.warning("adoption of %s for model %s failed: %s",
                             addr, model_id, e)
            return False
        if not isinstance(reply, dict) or reply.get("op") != "adopted":
            self.log.warning("adoption of %s for model %s rejected: %r",
                             addr, model_id, reply)
            return False
        node = next((r.node for r in self.registry.members()
                     if r.addr == addr and r.node), None)
        if node is not None:
            self._node_keys[node] = model_key(model_id)
        return True

    def swap_adapter(self, model_id: str, adapter_version: str,
                     delta=None, packed: Optional[Tuple[dict, bytes]]
                     = None, timeout: float = 120.0) -> dict:
        """Hot-swap a LoRA-style weight delta onto EVERY alive replica
        of one model: the delta ships as ONE raw HMAC frame per
        replica (``swap_adapter`` op), each batcher folds it behind
        its weight-update fence (in-flight requests finish on the old
        delta; zero downtime), and the call returns once every replica
        acked.  ``delta`` is a param-path -> array dict (packed here);
        ``packed`` supplies pre-encoded ``(meta, body)`` instead (the
        gateway op's path — no numpy on the gateway).  Raises on an
        unknown model, a replica rejection, or a partial failure —
        a fleet serving two delta versions of one model would break
        the token-identical-streams contract, so partial application
        is an ERROR, not a success."""
        if self.catalog is None:
            raise RuntimeError("swap_adapter needs a model catalog")
        spec = self.catalog.get(model_id)     # KeyError on unknown
        adapter_version = validate_model_id(adapter_version)
        if packed is None:
            if delta is None:
                raise ValueError("swap_adapter needs delta or packed")
            packed = pack_adapter(delta)
        meta, body = packed
        members = self.registry.members(model=spec.model_id)
        if any(r.state == WARMING for r in members):
            # A warming replica would turn ALIVE on BASE weights right
            # after the swap acked — one model serving two weight
            # states, the exact partial-application state documented
            # as an error.  Fail up front; the operator retries once
            # the tier settles.
            raise RuntimeError(
                f"model {model_id!r} has replica(s) still warming; "
                f"they would come up on the old weights — retry the "
                f"swap once the tier is fully routable")
        targets = [r for r in members if r.state == ALIVE]
        if not targets:
            raise RuntimeError(
                f"no alive replica serves model {model_id!r} (scaled "
                f"to zero? the swap applies at the next cold start "
                f"only if re-issued)")
        failures = []
        for r in targets:
            call = dict(meta)
            call.update(op="swap_adapter", model_id=spec.model_id,
                        adapter_version=adapter_version)
            try:
                reply = self.router.control_raw(r.addr, call, body,
                                                timeout=timeout)
            except Exception as e:
                failures.append(f"{r.addr}: {e}")
                continue
            if not isinstance(reply, dict) \
                    or reply.get("op") != "adapter_swapped":
                err = reply.get("error") if isinstance(reply, dict) \
                    else repr(reply)
                failures.append(f"{r.addr}: {err}")
        if failures:
            raise RuntimeError(
                f"adapter swap {adapter_version!r} on model "
                f"{model_id!r} failed on {len(failures)}/"
                f"{len(targets)} replica(s): {'; '.join(failures)}")
        self.metrics.inc("adapter_swaps")
        self.log.info("adapter %s swapped onto %d replica(s) of model "
                      "%s", adapter_version, len(targets), model_id)
        return {"model_id": spec.model_id,
                "adapter_version": adapter_version,
                "replicas": len(targets)}

    def _alive_of(self, key: str,
                  weights_version: Optional[str] = None) -> int:
        model, role = split_key(key)
        members = filter_members(self.registry.members(role), key)
        return sum(1 for r in members
                   if r.state == ALIVE
                   and (weights_version is None
                        or r.weights_version == weights_version))

    def _swap_adapter_packed(self, model_id: str, adapter_version: str,
                             meta: dict, body: bytes) -> dict:
        """The gateway op's entry point: the delta arrived base64 over
        the public port (which rejects raw frames pre-auth) and ships
        onward to the replicas as raw HMAC frames."""
        return self.swap_adapter(model_id, adapter_version,
                                 packed=(meta, body))

    def request_migration(self, addr: str) -> bool:
        """Ask one (already drained) replica to SUSPEND its in-flight
        rows — the victim answers each pending generate with a
        ``suspended`` export the router re-places on a survivor, so the
        drain flushes in one round-trip instead of a full generation's
        tail latency, and a kill-after-timeout can no longer lose work.
        Best-effort: any failure just leaves the plain drain-then-kill
        behavior (the victim keeps finishing its rows)."""
        if not self.migrate_on_drain or self.router is None:
            return False
        msg: dict = {"op": "migrate"}
        try:
            # Broker a direct-stream target up front: the victim pushes
            # each suspended artifact straight at the survivor (one
            # bounded attempt) and the router adopts by reference —
            # artifact bytes cross the wire once instead of twice.  No
            # eligible survivor (or an old victim binary) just leaves
            # the relay path: the suspended RawFrames flow through the
            # router exactly as before.
            target = self.router.migration_target(addr)
            if target:
                msg["push_to"] = target
        except Exception:
            pass
        try:
            self.router.control(addr, msg, timeout=30.0)
        except Exception as e:
            self.log.warning("migrate request to %s failed (%s); its "
                             "in-flight work drains normally", addr, e)
            return False
        self.metrics.inc("migrations_requested")
        return True

    def _drain_and_flush(self, reps, drain_timeout: float) -> None:
        """ONE copy of the reap discipline both rollout paths share:
        pinned drains on every given replica (healthy members keep
        heartbeating while their in-flight work finishes), ask each to
        migrate its in-flight rows away (drain-migrate-kill; see
        :meth:`request_migration`), then wait until BOTH flush signals
        read zero for all of them — the heartbeat-reported outstanding
        AND the router's own in-flight count (a request dispatched
        after the last beat is invisible to the first) — or the drain
        deadline passes."""
        addrs = [r.addr for r in reps]
        for r in reps:
            self.registry.begin_drain(r.addr, pinned=True)
        for r in reps:
            self.request_migration(r.addr)
        deadline = time.monotonic() + float(drain_timeout)
        while addrs and time.monotonic() < deadline:
            table = {m.addr: m for m in self.registry.members()}
            busy = any(
                (table.get(a) is not None and table[a].state != DEAD
                 and table[a].outstanding > 0)
                or self.router.outstanding(a) > 0
                for a in addrs)
            if not busy:
                return
            time.sleep(0.05)

    def _wait_replicas(self) -> None:
        """Target-based bring-up: every tier must reach its target alive
        count.  Boot crashes are relaunched (the convergence discipline)
        up to the scheduler's per-task failure budget scaled by the
        tier size — a crash-looping replica cmd still fails the
        bring-up loudly instead of idling to timeout."""
        deadline = time.monotonic() + self.start_timeout
        while time.monotonic() < deadline:
            # finished() raises ClusterError on backend-fatal errors —
            # surface those instead of idling to timeout.
            self.scheduler.finished()
            if all(self._alive_of(role) >= n
                   for role, n in self.targets.items()):
                return
            for key, n in self.targets.items():
                job = TIER_JOBS[split_key(key)[1]]
                fails = self.scheduler.dynamic_failures.get(job, 0)
                if fails >= MAX_FAILURE_COUNT * max(1, n):
                    raise ClusterError(
                        f"replica job {job!r} failed {fails} times "
                        f"during fleet bring-up")
                for _ in range(n - self.tier_actual(key)):
                    self.log.warning("bring-up relaunch of a crashed "
                                     "%s replica", key)
                    self.launch_replica(key)
            time.sleep(0.1)
        warming = len(self.registry.warming())
        counts = {role: self._alive_of(role) for role in self.targets}
        raise ClusterError(
            f"replicas routable after {self.start_timeout:.0f}s: "
            f"{counts} of targets {self.targets}"
            + (f" ({warming} still warming — raise start_timeout for "
               f"slow compiles)" if warming else ""))

    # -- blue-green rollout ------------------------------------------------

    def rollout(self, weights_version: str, bake_s: float = 1.0,
                warm_timeout: Optional[float] = None,
                drain_timeout: float = 120.0) -> dict:
        """Replace every tier's weights blue-green with zero downtime:

        1. bump the scheduler generation (PR 3's fencing epoch) and
           launch a full NEW-version replica set next to the old one —
           same per-tier targets, same cmd line (``--warmup`` included,
           so the new tier warms before it can be routed);
        2. wait until every tier's new-version alive count reaches its
           target — if that never happens the rollout ABORTS: the new
           tasks are reaped and the old version keeps serving;
        3. the SHIFT: one atomic router update prefers the new
           weights_version (the old tier stays registered as fallback
           through the bake window, so the shift itself cannot shed);
        4. after ``bake_s``, drain the old tier (pinned drains — the
           healthy old replicas keep heartbeating while their in-flight
           work flushes, and those beats must not revive them), wait
           for the flush, kill the old tasks, and raise the registry's
           generation fence so a stalled old-generation straggler can
           never re-register and serve stale weights.

        Returns a summary dict; raises :class:`RolloutError` on abort.
        """
        version = validate_weights_version(weights_version)
        if self.scheduler is None or self.registry is None:
            raise RuntimeError("fleet not started")
        with self.scale_lock:
            old_version = self.weights_version
            if version == old_version:
                raise ValueError(
                    f"fleet already serves weights_version {version!r}")
            gen = self.scheduler.bump_generation()
            warm_timeout = self.start_timeout if warm_timeout is None \
                else float(warm_timeout)
            new_nodes: List[Tuple[str, str]] = []
            for role, target in self.targets.items():
                for _ in range(target):
                    new_nodes.append(
                        (role, self.launch_replica(role, version)))
            self.log.info(
                "rollout %s -> %s: %d new-version replica(s) launched "
                "(generation %d); old tier keeps serving", old_version,
                version, len(new_nodes), gen)
            deadline = time.monotonic() + warm_timeout
            while time.monotonic() < deadline:
                self.scheduler.finished()
                if all(self._alive_of(role, version) >= target
                       for role, target in self.targets.items()):
                    break
                time.sleep(0.1)
            else:
                # Abort: the new tier never left warming (or its tasks
                # kept dying).  Reap it; the old version never stopped
                # serving, so this is a no-downtime failure.  Routing
                # is version-blind BEFORE the shift, so any new-version
                # replica that did reach ALIVE may already carry
                # traffic — drain those and wait for the flush before
                # the kill, exactly like the post-shift reap path.
                new_set = {node for _, node in new_nodes}
                self._drain_and_flush(
                    [r for r in self.registry.members()
                     if r.node in new_set and r.state == ALIVE],
                    drain_timeout)
                for _, node in new_nodes:
                    self.kill_replica(node)
                self.metrics.inc("rollouts_aborted")
                raise RolloutError(
                    f"rollout to {version!r} aborted: new tier not "
                    f"routable within {warm_timeout:.0f}s "
                    f"({len(self.registry.warming())} still warming, "
                    f"{len(new_set)} launched); {old_version!r} keeps "
                    f"serving")
            # The shift point: one atomic preference update.  From the
            # next pick on, the router selects old-version replicas only
            # if NO new-version replica is routable.
            self.router.set_preferred_version(version)
            self.weights_version = version
            self.metrics.inc("rollouts")
            self.log.info("rollout shift: router now prefers "
                          "weights_version %s (old %s is fallback for "
                          "%.1fs bake)", version, old_version, bake_s)
            if bake_s:
                time.sleep(bake_s)
            # Drain the old tier: pinned — these replicas are healthy
            # and keep heartbeating while their last requests flush.
            # The drain set is computed NOW, not at rollout start: a
            # replica that registered during the warm wait (an
            # autoscaler launch racing the scale lock) is old-version
            # fallback traffic too and must flush before the reap.
            managed_roles = {split_key(k)[1] for k in self.targets}
            old_members = [r for r in self.registry.members()
                           if (r.role or UNIFIED) in managed_roles
                           and r.weights_version != version
                           and r.state != DEAD]
            self._drain_and_flush(old_members, drain_timeout)
            # Reap every old-generation task of the managed tiers (the
            # registry's node field maps members back; the scheduler
            # table diff catches launched-but-never-registered ones).
            new_set = {node for _, node in new_nodes}
            # Gang-aware reap: a NEW gang's members carry node ids that
            # never entered new_nodes (only the leader did) — keep any
            # task whose gang's leader is new; reap old gangs whole and
            # drop their book entries so no death hook re-forms them.
            with self._gang_lock:
                keep_gangs = {g for g, info in self._gangs.items()
                              if info["leader_node"] in new_set}
                for g in [g for g, info in self._gangs.items()
                          if g not in keep_gangs
                          and info["job"] in {TIER_JOBS[r]
                                              for r in managed_roles}]:
                    del self._gangs[g]
            reaped = 0
            for job in {TIER_JOBS[r] for r in managed_roles}:
                for t in self.scheduler.tasks_of(job):
                    if getattr(t, "gang", None) in keep_gangs:
                        continue
                    node = f"{job}:{t.task_index}"
                    if node not in new_set:
                        self.scheduler.remove_task(t.id)
                        reaped += 1
            # The fence: beats of generations before this rollout are
            # dropped from here on — a SIGSTOP'd straggler that wakes up
            # tomorrow cannot re-register and serve stale weights.
            self.registry.fence_generation(gen)
            self.log.info(
                "rollout to %s complete: %d old replica(s) drained and "
                "reaped, registry fenced at generation %d", version,
                reaped, gen)
            return {"old_version": old_version, "new_version": version,
                    "replicas": len(new_nodes), "reaped": reaped,
                    "generation": gen}

    # -- surface -----------------------------------------------------------

    @property
    def addr(self) -> Optional[str]:
        if self.gateway is not None:
            return self.gateway.addr
        addrs = self.addrs
        return addrs[0] if addrs else None

    @property
    def addrs(self) -> List[str]:
        """Every front door's address (multi-gateway deployments).  In
        subprocess mode this is the central registry's leased discovery
        set — with SO_REUSEPORT all N processes share one address, so
        one entry stands for the whole set."""
        if self.gateways:
            return [gw.addr for gw in self.gateways if gw.addr]
        if self._gateway_procs and self.registry is not None:
            return sorted(self.registry.gateway_addrs())
        return []

    def client(self, timeout: float = 120.0) -> FleetClient:
        """A client over EVERY gateway: it spreads nothing (one
        connection at a time) but fails over to a surviving gateway —
        replaying idempotent in-flight generates — when its own dies."""
        return FleetClient(self.addrs or [self.addr], self.token,
                           timeout=timeout)

    def snapshot(self) -> dict:
        """The fleet metrics snapshot; the ``roles`` gauge carries each
        tier's target vs actual counts and weights_version distribution,
        and ``autoscaler`` (when scaling) the control loop's beliefs."""
        return self.metrics.snapshot() if self.metrics is not None else {}

    # -- teardown ----------------------------------------------------------

    def stop(self) -> None:
        self._started = False
        if self.autoscaler is not None:
            self.autoscaler.stop()
            self.autoscaler = None
        if self.metrics is not None:
            self.metrics.stop_reporter()
        if self._metrics_http is not None:
            self._metrics_http.shutdown()
            self._metrics_http.server_close()
            self._metrics_http = None
        for gw in self.gateways:
            if not gw.killed:
                gw.stop()
        self.gateways = []
        self.gateway = None
        self.http_addr = None
        if self.metrics is not None:
            self.metrics.fanin = None
        for proc in self._gateway_procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self._gateway_procs:
            try:
                proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
        self._gateway_procs = []
        # The gateways share the router (close_router=False); its
        # links close exactly once, here.
        if self.router is not None:
            self.router.close()
            self.router = None
        if self.scheduler is not None:
            # Teardown kills are deliberate: no gang death hook may
            # re-form what stop() is reaping.
            self.scheduler.on_dynamic_death = None
            self.scheduler.stop()
            self.scheduler = None
        with self._gang_lock:
            self._gangs.clear()
        if self.registry is not None:
            self.registry.stop()
            self.registry = None
        if self._kv_tier_tmp is not None:
            import shutil

            shutil.rmtree(self._kv_tier_tmp, ignore_errors=True)
            self._kv_tier_tmp = None

    def __enter__(self) -> "FleetServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
