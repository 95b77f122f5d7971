"""Continuous batching over the paged KV cache.

The reference framework stops at training jobs (its serving story is
"run a session somewhere"); this module is the inference-side scheduler
the paged cache layout exists for: a persistent page pool plus an
admission loop that feeds new prompts into a RUNNING batched decode —
rows free on stop-token, arrivals prefill into freed rows, and
:class:`~tfmesos_tpu.models.transformer.PageAllocator` state persists
across the whole stream (docs/SERVING.md).  Offline batch serving
(``examples/serve.py`` without ``--continuous``) allocates and releases
pages per closed batch; this loop keeps the decode step hot and bounds
memory by LIVE tokens, not by batch-max shapes.

Determinism contract: a request's tokens depend only on (its prompt,
its ``rid``-folded sampling key) — never on what else is in flight.
Greedy streams are bit-identical to a per-request
:func:`~tfmesos_tpu.models.transformer.generate` call; sampled streams
are invariant to batching/staggering because every row draws from its
own fold of the batcher RNG (``fold_in(rng, rid)`` then per-step
``fold_in(key, step)``), not from a shared stream.  The folds happen
IN-GRAPH from ``rid``/``step`` vectors, so the host loop issues no
per-row dispatches.

Two compiled shapes serve everything: one decode step at ``[rows, 1]``
with a fixed-width page table, and one prefill per prompt-length bucket
(lengths round up to ``prefill_bucket``).  Admission reserves each
request's WORST-CASE page count against the pool up front, while the
allocator backs pages incrementally as the row grows — so memory use is
length-proportional but mid-flight pool exhaustion is impossible by
construction.
"""

from __future__ import annotations

import dataclasses
import gc
import itertools
import logging
import queue as _queue
import statistics
import threading
import time
from collections import deque
from functools import partial
from typing import Any, Dict, Iterable, Iterator, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map

from tfmesos_tpu import prefixhash as _ph
from tfmesos_tpu.fleet.tracing import FlightView, flight
from tfmesos_tpu.models.transformer import (PageAllocator, TransformerConfig,
                                            decode_step,
                                            greedy_accept_counts,
                                            init_paged_cache,
                                            rejection_accept, sample_logits)
from tfmesos_tpu.ops.moe import tile_rows as moe_tile_rows
from tfmesos_tpu.ops.quant import QTensor
from tfmesos_tpu.utils.profiling import annotate

try:
    import resource
except ImportError:         # no such counts on this platform: they read 0
    resource = None
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)

log = logging.getLogger(__name__)

__all__ = ["Request", "Completion", "Suspended", "Expired",
           "ContinuousBatcher", "SubmissionQueue", "Prefilled",
           "pack_prefilled", "unpack_prefilled",
           "BYPASS_ALLOWLIST", "compute_bypass_reasons"]

# SubmissionQueue.poll's end-of-stream marker (distinct from None, which
# means "nothing available right now, more may come").
_CLOSED = object()
#: SubmissionQueue wake-up sentinel (see SubmissionQueue.kick): wakes
#: an idle-blocked serve loop without submitting work, so a queued
#: weight update (swap_adapter / set_weights) applies promptly on an
#: otherwise idle batcher instead of waiting for the next request.
_KICK = object()

#: THE bypass registry's documented allowlist: every reason string a
#: ``*_bypass_reason`` attribute is allowed to carry, per registry.
#: The burn-down is ENFORCED, not aspirational — the audit test
#: (tests/test_serving.py::test_bypass_registry_audit) enumerates every
#: reachable :class:`ContinuousBatcher` config through
#: :func:`compute_bypass_reasons` and fails on any value not listed
#: here, so a new bypass cannot land silently and a removed one cannot
#: regress.  A reason is a property of the design: "speculative
#: decoding" was burned out of the ``prefix_cache`` and ``kv_tier``
#: registries (spec rows are first-class citizens of the paged-KV
#: machinery now), spec+multi_step COMPOSES (R spec rounds per
#: dispatch — see ``_make_spec_round``), and suspend-under-lag is an
#: enforced bypass below.
BYPASS_ALLOWLIST = {
    # An int8 pool's tail-recompute path (chunk writer) is not
    # bit-stable against the cold fused prefill, so shared pages could
    # break the warm==cold equivalence bar; the draft pool's int8 mode
    # shares the same writer, hence the same reason.
    # (and under EVA attention — TransformerConfig.attention == "eva" — a
    # row's pages hold summaries and one window's exact entries, not 64
    # consecutive positions each: nothing that shares, moves or snapshots
    # pages by position carries that layout yet.  One reason string for
    # every surface it closes, "eva summary pages".  The lagged carry
    # composes: a window's close depends on no token, only on a position,
    # and positions advance at dispatch, so the close is enqueued behind
    # the block that fills the window and the donated pool orders it
    # before the next one.)
    # (and with a recurrent row state — a typed stack's mamba layers,
    # TransformerConfig.layer_types — a row is its pages AND a state that
    # no page holds: the state after position p cannot be cut back to an
    # earlier position, shared between rows or rebuilt from pages, so
    # everything built on "a row is its pages" is closed with ONE reason
    # string, "recurrent row state".  The pipelined carry composes: the
    # state store rides the donated pool through every block.)
    # (and with a sliding-window ring — a typed stack's "window" layers —
    # a row is its pages AND, a window layer, the K/V of its last ``window``
    # positions in a ring no page holds: the positions behind the window
    # are gone, so a row can be neither rebuilt from pages nor cut back nor
    # shared from a prefix's pages.  The same surfaces close with ONE reason
    # of their own, "sliding-window ring"; a stack that keeps a recurrent
    # state too gives that reason.  The carry composes as above.)
    "prefix_cache": ("quantized kv cache", "eva summary pages",
                     "recurrent row state", "sliding-window ring"),
    # Mesh data shards pin pages locally (no single-shard scatter to
    # move), and the int8 tail recompute above breaks resume==cold.
    "kv_tier": ("mesh data sharding", "quantized kv cache",
                "eva summary pages", "recurrent row state",
                "sliding-window ring"),
    # The pipelined carry (tokens, positions, steps on device, one
    # block of lag) has no speculative form: a round's commit counts
    # decide the next round's positions, and _step_spec reads them on
    # the host.  A speculative batcher asked for pipeline_depth=1
    # serves synchronously and records the reason.
    "pipeline": ("speculative decoding",),
    # Per-row suspend/export needs a host-synchronous row snapshot;
    # the pipelined carry holds in-flight device state the host view
    # lags one block behind, and mesh data shards pin pages locally
    # like the kv_tier/export surface.
    "suspend": ("mesh data sharding", "lagged decode carry",
                "eva summary pages", "recurrent row state",
                "sliding-window ring"),
    # Stall-free fused prefill+decode ticks (one dispatch covers the
    # decode block AND a budgeted batch of prefill chunk slots).  Mesh
    # data shards dispatch chunks one-hot per shard (the fused slot
    # layout has no shard axis to ride); a speculative round's dispatch
    # is the verify program — its chunk writes advance the DRAFT pool
    # in lockstep, a second fused surface the single-program layout
    # does not cover yet (burn-down: fold the chunk writes into
    # _make_spec_round's body); the pipelined loop retires a block
    # behind and a chunk slot's first-token sample is host-synchronous
    # by design.
    "fused_prefill": ("mesh data sharding", "speculative decoding",
                      "lagged decode carry"),
    # REFUSALS, not bypasses: a batcher asked for these raises with the
    # reason (at construction: a draft model; at validate()/submit() and
    # export_kv(): a KV artifact).  EVA's 8 prediction heads are the
    # multi-byte self-speculation a later PR routes through _step_spec.
    # A recurrent row state closes both too: a rejected draft token cannot
    # be taken back out of a state, and a KV artifact carries pages only.
    "speculative": ("eva summary pages", "recurrent row state",
                    "sliding-window ring"),
    "kv_export": ("eva summary pages", "recurrent row state",
                  "sliding-window ring"),
}


def compute_bypass_reasons(*, speculative: bool = False,
                           n_shards: int = 1,
                           quantized_cache: bool = False,
                           draft_quantized_cache: bool = False,
                           pipeline_depth: int = 0,
                           eva: bool = False,
                           recurrent: bool = False,
                           window: bool = False
                           ) -> Dict[str, Optional[str]]:
    """The ``*_bypass_reason`` values a :class:`ContinuousBatcher`
    built from these mode flags records — ONE pure function, used by
    ``__init__`` itself, so the bypass-registry audit test can
    enumerate every reachable config without building batchers.  Keys
    mirror :data:`BYPASS_ALLOWLIST`; ``None`` = the feature composes."""
    quant = quantized_cache or (speculative and draft_quantized_cache)
    # what a row keeps beside its pages (``init_row_state``), as a reason
    row_state = ("recurrent row state" if recurrent
                 else "sliding-window ring" if window else None)
    out: Dict[str, Optional[str]] = {
        "prefix_cache": None, "kv_tier": None, "pipeline": None,
        "suspend": None, "fused_prefill": None, "speculative": None,
        "kv_export": None}
    if eva:
        out["prefix_cache"] = out["speculative"] = out["kv_export"] = \
            "eva summary pages"
    elif row_state:
        out["prefix_cache"] = out["speculative"] = out["kv_export"] = \
            row_state
    elif quant:
        out["prefix_cache"] = "quantized kv cache"
    if n_shards != 1:
        out["kv_tier"] = "mesh data sharding"
    elif eva:
        out["kv_tier"] = "eva summary pages"
    elif row_state:
        out["kv_tier"] = row_state
    elif quant:
        out["kv_tier"] = "quantized kv cache"
    if pipeline_depth and speculative:
        out["pipeline"] = "speculative decoding"
    # The one lag mode in effect AFTER the bypasses above.
    pipelined = bool(pipeline_depth) and out["pipeline"] is None
    if n_shards != 1:
        out["suspend"] = "mesh data sharding"
    elif eva:
        out["suspend"] = "eva summary pages"
    elif row_state:
        out["suspend"] = row_state
    elif pipelined:
        out["suspend"] = "lagged decode carry"
    if n_shards != 1:
        out["fused_prefill"] = "mesh data sharding"
    elif speculative:
        out["fused_prefill"] = "speculative decoding"
    elif pipelined:
        out["fused_prefill"] = "lagged decode carry"
    return out


def _request_of(item) -> "Request":
    """The request a queued item is or, a ``Prefilled``, carries."""
    return item.request if isinstance(item, Prefilled) else item


def _stamp_submit(item) -> None:
    """A request enters a batcher's queue: ``Request.t_submit``, from
    which the request ring counts its wait."""
    _request_of(item).t_submit = time.perf_counter()


class SubmissionQueue:
    """Thread-safe incremental :class:`Request` source for
    :meth:`ContinuousBatcher.run` — the online front door's adapter
    around the loop's internal ``pull()``.

    Any thread may :meth:`submit` at any time; :meth:`close` marks the
    end of the stream (submissions after it raise).  The run loop polls
    NON-blocking while rows are decoding — an empty queue never stalls
    in-flight requests the way a blocking iterable would — and blocks
    only when the batcher is otherwise idle.
    """

    def __init__(self) -> None:
        self._q: "_queue.Queue" = _queue.Queue()
        self._closed = False
        self._lock = threading.Lock()

    def submit(self, request) -> None:
        if not isinstance(request, (Request, Prefilled)):
            raise TypeError(f"submit() takes a Request or Prefilled, got "
                            f"{type(request).__name__}")
        with self._lock:
            if self._closed:
                raise RuntimeError("submission queue is closed")
            _stamp_submit(request)
            self._q.put(request)

    def close(self) -> None:
        """End the stream: the serve loop drains what was submitted and
        returns.  Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(_CLOSED)

    def kick(self) -> None:
        """Wake a blocked serve loop WITHOUT submitting work (the
        weight-update path: an idle loop must notice a queued
        swap_adapter/set_weights now, not at the next request).
        Harmless after close."""
        with self._lock:
            if not self._closed:
                self._q.put(_KICK)

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def poll(self, block: bool):
        """Next request; ``None`` when empty (and more may come), the
        ``_CLOSED`` sentinel at end of stream.  ``block=True`` waits for
        one of the two.  Wake-up kicks are swallowed here (they exist
        only to end a blocking poll early)."""
        while True:
            try:
                item = self._q.get(block=block)
            except _queue.Empty:
                return None
            if item is _KICK:
                if block:
                    return None     # woken: let the loop re-check state
                continue
            if item is _CLOSED:
                self._q.put(_CLOSED)  # keep re-polls (and peers) terminal
                return _CLOSED
            return item


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` is a 1-D int32 token array.
    ``priority`` is the preemption rank (higher = more important):
    under allocation pressure the batcher may SUSPEND the
    lowest-priority resident row to admit a strictly-higher-priority
    arrival, parking its KV state for later resumption — resumed
    streams are token-identical to uninterrupted ones
    (docs/SERVING.md "Priorities, preemption & migration").

    ``deadline_ms`` is the request's remaining END-TO-END budget at
    construction time (the fleet forwards the shrinking remainder hop
    by hop — absolute clock readings mean nothing across hosts): the
    batcher sheds an arrival whose deadline already passed without
    burning a prefill, and CANCELS an expired resident row like a
    finished one — pages freed immediately, an :class:`Expired` yielded
    in the completion stream — so work the client has abandoned never
    occupies a decode slot.  ``None`` (the default) never expires.

    ``session_id`` (optional) names a multi-turn CONVERSATION: on a
    batcher with a KV tier (``kv_tier=``), the finished request's KV
    parks in the tier under this id, and a later request whose prompt
    EXTENDS the parked history resumes from it — the parked pages
    import and only the new tail prefills, token-identical to a cold
    full-history prefill (docs/SERVING.md "KV tiering & sessions")."""

    prompt: np.ndarray
    max_new_tokens: int
    stop_token: Optional[int] = None
    priority: int = 0
    deadline_ms: Optional[float] = None
    session_id: Optional[str] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32)
        if self.prompt.ndim != 1 or self.prompt.size == 0:
            raise ValueError("Request.prompt must be a non-empty 1-D "
                             "token array (there is no position to "
                             "continue from otherwise)")
        if self.max_new_tokens < 1:
            raise ValueError(f"Request.max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        self.priority = int(self.priority)
        if self.session_id is not None:
            self.session_id = str(self.session_id)
        # Request tracing (docs/SERVING.md "Observability"): the fleet
        # replica attaches the hop's TraceContext here; the batcher
        # records its per-request events (admit, preempt, suspend,
        # resume, deadline cancel, finish) onto it when present.  None
        # (the default) costs nothing.
        self.trace = None
        # Incremental token streaming (docs/SERVING.md "Front-door
        # scaling"): ``on_tokens(new_tokens, offset)`` is called from
        # the serve loop once per decode block with the tokens emitted
        # since the last call (``offset`` = tokens already streamed).
        # The Completion still carries the full list — streaming is
        # additive, and a raising callback costs the stream, never the
        # request.  None (the default) costs one attribute read per
        # block.
        self.on_tokens = None
        # ``perf_counter`` when the request entered a batcher's queue
        # (``SubmissionQueue.submit``, or the pull from ``run``'s
        # iterable): the start of the wait the request ring records.
        self.t_submit: Optional[float] = None
        self.deadline: Optional[float] = None
        if self.deadline_ms is not None:
            if not self.deadline_ms > 0:
                raise ValueError(f"Request.deadline_ms must be > 0, got "
                                 f"{self.deadline_ms}")
            self.deadline = (time.perf_counter()
                             + float(self.deadline_ms) / 1000.0)

    @property
    def expired(self) -> bool:
        """Whether the end-to-end deadline has passed (always False
        without one)."""
        return (self.deadline is not None
                and time.perf_counter() >= self.deadline)


@dataclasses.dataclass
class Prefilled:
    """One IMPORTED prefill — the disaggregated-serving admission unit:
    the original :class:`Request` plus the KV artifact a prefill-role
    batcher exported for it (:meth:`ContinuousBatcher.export_kv`).
    Submit one with ``submit(request, prefilled=artifact)`` (or put it
    on a ``run()`` iterable directly): admission installs the artifact's
    pages into the local pool and the row enters decode with the
    prefill's first token already emitted — no prefill compute runs on
    the importing batcher."""

    request: Request
    artifact: dict

    def __post_init__(self):
        if not isinstance(self.request, Request):
            raise TypeError("Prefilled.request must be a Request")
        if not isinstance(self.artifact, dict):
            raise TypeError("Prefilled.artifact must be an export_kv() "
                            "artifact dict")


# Artifact array leaves, in their fixed wire order (pack/unpack below).
# ``dk``/``dv`` (+ scales) are the DRAFT pool's paired payload on a
# speculative batcher's exports — per-layer draft pages covering the
# same positions as the target's, so a spec row is suspendable,
# migratable, disagg-importable, and KV-tier-parkable like any other.
_KV_ARRAY_KEYS = ("k", "v", "k_scales", "v_scales",
                  "dk", "dv", "dk_scales", "dv_scales")
# Everything else in the artifact is a small scalar/dict header.
# ``step``/``tokens`` carry a SUSPENDED request's mid-stream sampler
# state (tokens emitted so far); a fresh prefill export has step 1 and
# tokens == [first_token], so one artifact shape serves both.  For a
# SPECULATIVE row this (rid, step, tokens) triple is the entire spec
# sampler state too: draft proposals and acceptance/correction draws
# are pure per-(rid, step+j) key folds, so there is no separate draft
# rng position to carry — resuming at ``step`` continues the exact
# streams.  ``draft`` is the draft-side geometry header
# (layers/heads/dim, quantized flag, n_draft) paired with dk/dv.
# ``history`` is the SESSION-park addition (the full conversation —
# prompt + every emitted token — the artifact's pages cover, which is
# what a resume validates the new turn's prompt against); absent on
# plain prefill/suspend artifacts.
_KV_META_KEYS = ("version", "page_size", "prefix_len", "shared_len",
                 "pos", "prompt_len", "first_token", "rid", "quantized",
                 "model", "step", "tokens", "history", "draft")


def pack_prefilled(artifact: dict) -> tuple:
    """Split an :meth:`~ContinuousBatcher.export_kv` artifact into a
    small JSON-encodable ``meta`` dict and one contiguous ``body`` buffer —
    the shape :func:`tfmesos_tpu.wire.send_raw_msg` ships without
    re-encoding multi-MB tensor data.  The caller may merge transport
    fields (``op``/``id``/request params) into ``meta`` before
    sending."""
    meta = {k: artifact[k] for k in _KV_META_KEYS if k in artifact}
    specs, parts = [], []
    for name in _KV_ARRAY_KEYS:
        a = artifact.get(name)
        if a is None:
            continue
        a = np.ascontiguousarray(a)
        specs.append({"name": name, "dtype": str(a.dtype),
                      "shape": list(a.shape)})
        parts.append(a)
    meta["arrays"] = specs

    def buf(a):
        # Zero-copy for buffer-protocol dtypes; extension dtypes
        # (bfloat16) reject memoryview and copy through tobytes —
        # frombuffer on the unpack side reads either encoding.
        try:
            return memoryview(a).cast("B")
        except (ValueError, TypeError):
            return a.tobytes()

    return meta, b"".join(buf(a) for a in parts)


def unpack_prefilled(meta: dict, body) -> dict:
    """Inverse of :func:`pack_prefilled`: rebuild the artifact dict from
    a received raw frame.  Array leaves are zero-copy views into
    ``body``; malformed frames raise ``ValueError`` (the import
    admission path rejects them as bad requests)."""
    art = {k: meta[k] for k in _KV_META_KEYS if k in meta}
    specs = meta.get("arrays")
    if not isinstance(specs, (list, tuple)):
        raise ValueError("prefilled meta carries no array manifest")
    view = memoryview(body).cast("B")
    off = 0
    for spec in specs:
        try:
            name = spec["name"]
            dtype = np.dtype(spec["dtype"])
            shape = tuple(int(d) for d in spec["shape"])
        except (TypeError, KeyError, ValueError) as e:
            raise ValueError(f"bad prefilled array spec {spec!r}") from e
        if name not in _KV_ARRAY_KEYS:
            raise ValueError(f"unexpected prefilled array {name!r}")
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = count * dtype.itemsize
        if off + nbytes > len(view):
            raise ValueError("prefilled body shorter than its manifest")
        art[name] = np.frombuffer(view, dtype=dtype, count=count,
                                  offset=off).reshape(shape)
        off += nbytes
    if off != len(view):
        raise ValueError(f"prefilled body has {len(view) - off} trailing "
                         f"bytes beyond its manifest")
    return art


@dataclasses.dataclass
class Completion:
    """A finished request: ``tokens`` are the generated continuation
    (including the stop token when one was emitted), ``rid`` the
    admission-order id the batcher assigned.  ``ttft_s`` is wall time
    from admission (prefill start) to the first token; ``total_s`` to
    the last; ``queue_s`` the wait before admission, from submission
    (neither of the other two includes it)."""

    rid: int
    request: Request
    tokens: List[int]
    ttft_s: float = 0.0
    total_s: float = 0.0
    queue_s: float = 0.0


@dataclasses.dataclass
class Suspended:
    """An in-flight request the batcher gave BACK instead of finishing —
    yielded by :meth:`ContinuousBatcher.serve`/``run`` after
    :meth:`ContinuousBatcher.preempt_all` (the drain-migration path).

    ``artifact`` is an :meth:`~ContinuousBatcher.export_kv`-shaped dict
    carrying the row's KV pages AND its mid-stream sampler state
    (``step``, ``tokens``): re-admitting it anywhere via
    ``submit(request, prefilled=artifact)`` resumes the stream
    token-identically to an uninterrupted run.  ``artifact`` is ``None``
    when the request held no resumable state (still queued, still
    prefilling, or a serving mode without per-row export) — the caller
    re-runs it from scratch, which is lossless too: nothing was
    delivered, and completions are deterministic functions of the
    request."""

    rid: int
    request: Request
    artifact: Optional[dict] = None


@dataclasses.dataclass
class Expired:
    """A request the batcher CANCELLED because its end-to-end deadline
    passed — yielded in the completion stream wherever the Completion
    would have gone (docs/SERVING.md "Deadlines & failure
    containment").  A resident row's pages are freed the moment it
    expires (dead work never occupies a decode slot); a queued arrival
    is shed before its prefill ever dispatches.  ``rid`` is -1 when the
    request never reached admission."""

    rid: int
    request: Request


@dataclasses.dataclass
class _Row:
    """Host-side state of one in-flight row."""

    rid: int
    req: Request
    pos: int            # next cache position to write (= current length)
    step: int           # tokens generated so far
    last: int           # last emitted token (feeds the next decode step)
    out: List[int]
    worst_pages: int    # admission-time reservation (target pool)
    worst_draft: int = 0    # ... and the draft pool's, in speculative mode
    t_admit: float = 0.0    # perf_counter at prefill start
    t_first: float = 0.0    # ... at first-token availability
    # What the request ring records beside the two stamps: the ticks
    # that admitted the row and read its first token (-1 outside a
    # serve loop) and the padded prompt width dispatched for it, chunks
    # summed (a prefix or session hit dispatches less than the prompt,
    # an import nothing).
    admit_tick: int = -1
    first_tick: int = -1
    prefill_tokens: int = 0
    # Chunked-prefill state (prefill_chunk mode): the padded prompt and
    # how much of it has been written; rows decode only once filled.
    padded: Optional[np.ndarray] = None
    filled: int = 0
    decoding: bool = True
    # Absolute position cap the admission reservation covers: multi-step
    # blocks clamp their ensure() calls here so a row's allocations can
    # never exceed its reservation (the headroom() accounting depends on
    # allocated <= worst); in-block overshoot writes past it land on
    # sink columns of the table instead.
    limit: int = 0
    # Incremental streaming (Request.on_tokens): how many of ``out``'s
    # tokens have been flushed to the callback so far — the serve loop
    # pushes the [streamed:] suffix once per block.
    streamed: int = 0

    def __post_init__(self):
        # the request's own stamp; admission itself where nothing queued
        # it (export_kv)
        t = self.req.t_submit
        self.t_submit = self.t_admit if t is None else t


@dataclasses.dataclass
class _PrefixPlan:
    """Admission-time decision to serve a request's leading prompt
    pages from the prefix cache: map ``nodes``' pages read-only and
    prefill only from ``tail_start`` on.  ``cow`` marks the
    page-aligned full hit, where the one-token logits chunk must write
    INTO the deepest cached page — that page is first copied into a
    freshly reserved own page (copy-on-write) so shared state is never
    written."""

    nodes: list
    cow: bool
    tail_start: int     # first ABSOLUTE position the prefill writes

    @property
    def save(self) -> int:
        """Own-page reservations the mapping saves (a COW hit re-backs
        its deepest page with an own copy)."""
        return len(self.nodes) - (1 if self.cow else 0)


class _ShardedAlloc:
    """``PageAllocator``'s surface over per-shard sub-pools: rows are
    partitioned into ``n_shards`` contiguous groups (shard = row //
    rows_per_shard — the layout ``PartitionSpec("dp")`` gives a sharded
    axis), each group allocating from its own shard of the physical
    pool, and every page id handed out is LOCAL to its shard.  With
    ``n_shards=1`` this is exactly one PageAllocator.  Reservations
    (``reserve_page``) are taken symmetrically in every shard and must
    land on the same local id — so a single id names the sink in every
    shard's sub-pool."""

    def __init__(self, n_pages_per_shard: int, page_size: int,
                 n_shards: int = 1, rows_per_shard: int = 0):
        self.page_size = int(page_size)
        self.n_shards = int(n_shards)
        self.rows_per_shard = int(rows_per_shard)
        self.shards = [PageAllocator(n_pages_per_shard, page_size)
                       for _ in range(self.n_shards)]

    def shard_of(self, row: int) -> int:
        return row // self.rows_per_shard if self.n_shards > 1 else 0

    @property
    def rows(self) -> Dict[int, list]:
        """Merged row → local-page-list view (global row ids never
        collide across shards)."""
        out: Dict[int, list] = {}
        for a in self.shards:
            out.update(a.rows)
        return out

    @property
    def free(self) -> list:
        """All shards' free local ids, concatenated (sizing/tests)."""
        return [p for a in self.shards for p in a.free]

    def ensure(self, row: int, length: int) -> None:
        self.shards[self.shard_of(row)].ensure(row, length)

    def release(self, row: int) -> None:
        self.shards[self.shard_of(row)].release(row)

    def trim(self, row: int, length: int) -> int:
        return self.shards[self.shard_of(row)].trim(row, length)

    def allocated(self, row: int) -> int:
        return self.shards[self.shard_of(row)].allocated(row)

    def free_count(self, shard: Optional[int] = None) -> int:
        if shard is not None:
            return self.shards[shard].free_count()
        return sum(a.free_count() for a in self.shards)

    def reserve_page(self) -> int:
        ids = [a.reserve_page() for a in self.shards]
        assert all(i == ids[0] for i in ids), \
            "asymmetric reservation — shards must reserve in lockstep"
        return ids[0]


class _PagedSide:
    """Host-side state of ONE paged pool — the target's, or (speculative
    mode) the draft's: the per-shard allocator, the reserved sink
    page, the device pool, and the cached page tables the jitted steps
    consume.  Table entries are LOCAL page ids (see
    :class:`_ShardedAlloc`); a row with no allocation is all-sink."""

    def __init__(self, n_pages: int, page_size: int, rows: int,
                 np_max: int, n_shards: int = 1):
        if n_pages % n_shards:
            raise ValueError(f"n_pages ({n_pages}) must divide over "
                             f"{n_shards} mesh data shards")
        if rows % n_shards:
            raise ValueError(f"rows ({rows}) must divide over "
                             f"{n_shards} mesh data shards")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.rows = int(rows)
        self.np_max = int(np_max)
        self.alloc = _ShardedAlloc(n_pages // n_shards, page_size,
                                   n_shards, rows // n_shards)
        # Inactive decode rows still execute the batched paged scatter —
        # their table entries must point somewhere writable that no live
        # request owns.  Reserve one pool page (per shard) as that sink.
        self.sink = self.alloc.reserve_page()
        self.pool = None                  # device arrays, set by owner
        self.peak = 0                      # observability: high-water mark
        # Cross-request prefix cache (set by the owning batcher): pages
        # a row references READ-ONLY ahead of its own allocation (row
        # table = [cached | own]).
        self.pcache = None                        # _PrefixCache or None
        self.row_cached: Dict[int, List[int]] = {}
        self._cache = None        # device table; rebuilt when dirty
        self._cache_np = None     # host master copy of the table
        self._masked = None       # (masked_rows, device table)

    def dirty(self) -> None:
        """Invalidate every derived table (host master, device copy,
        masked variants) after ANY page-mapping change — allocation
        growth, release, cached-prefix (re)mapping, COW remap.  One
        choke point so a new mapping path cannot forget one of the
        three caches (stale device tables are silent wrong-output
        bugs)."""
        self._cache = self._cache_np = self._masked = None

    def ensure(self, row: int, length: int) -> None:
        """Back ABSOLUTE positions [0, length): mapped cached-prefix
        pages cover the first ``len(row_cached[row]) * page_size``; the
        row's own allocation covers the rest."""
        before = self.alloc.allocated(row)
        covered = self.page_size * len(self.row_cached.get(row, ()))
        self.alloc.ensure(row, max(0, length - covered))
        if self.alloc.allocated(row) != before:
            self.dirty()
        used = self.n_pages - self.alloc.free_count()
        if used > self.peak:
            self.peak = used

    def release(self, row: int) -> None:
        if self.pcache is not None:
            self.pcache.release_row(row)
        self.alloc.release(row)
        self.dirty()

    def trim(self, row: int, length: int) -> None:
        """Keep the pages behind ``row``'s first ``length`` entries only."""
        if self.alloc.trim(row, length):
            self.dirty()

    def headroom(self, active: Dict[int, _Row], worst_of,
                 shard: int) -> int:
        """Free pages in ``shard`` not spoken for by in-flight rows'
        admission reservations (``worst_of(row)`` — worst_pages or
        worst_draft).  Zero-ref cached-prefix pages count as free: the
        allocator reclaims them on demand (LRU eviction), so they must
        not block admission."""
        outstanding = sum(
            worst_of(row) - self.alloc.allocated(r)
            for r, row in active.items()
            if self.alloc.shard_of(r) == shard)
        reclaimable = (self.pcache.reclaimable(shard)
                       if self.pcache is not None else 0)
        return self.alloc.free_count(shard) + reclaimable - outstanding

    def table_np(self) -> np.ndarray:
        """Host master copy of the table (chunked prefill masks per-step
        variants off it)."""
        if self._cache_np is None:
            # Rows WITH allocations see [cached-prefix pages | own
            # pages]; rows without stay all-sink (an inactive row writes
            # its garbage step at position 0 — that must never land on
            # a shared or live page).
            t = np.full((self.rows, self.np_max), self.sink, np.int32)
            rows_map = self.alloc.rows
            for r in range(self.rows):
                own = rows_map.get(r) or []
                cached = self.row_cached.get(r) or []
                nc = len(cached)
                if nc:
                    t[r, :nc] = cached
                t[r, nc:nc + len(own)] = own
            self._cache_np = t
        return self._cache_np

    def table(self) -> jnp.ndarray:
        """Fixed-shape [rows, np_max] device table, rebuilt only when the
        allocation actually changed (page-boundary growth, admission,
        release) — not every token."""
        if self._cache is None:
            self._cache = jnp.asarray(self.table_np())
        return self._cache

    def bucket_width(self) -> int:
        """Smallest power-of-two table width covering every allocated
        row (cached-prefix pages + own pages), capped at ``np_max``.
        The table's width sizes what every decode dispatch carries: the
        paged kernel's scalar-prefetched tables and the XLA ops that
        build them, and the gather path (no TPU, or pages the kernel
        does not take), which reads the whole width.  The kernel's own
        grid walks the blocks that hold a live page and no other since
        PR 31 (``_paged_walk``; PERF.md section 6), so there the width
        no longer costs a step; on the grid of round 5 the worst-case
        width made short-lived requests on a long-max_len pool pay for
        context they don't have (measured 3.4x on an 8k pool early in
        generation, v5e round 5).  Power-of-two bucketing bounds the
        jit cache at log2(np_max) decode variants.  Safety: every
        decoding row's reads (kernel block bound <= its allocation) and
        writes stay inside the slice, and the width is STRICTLY greater
        than the widest allocation, so an overrun row's clamped
        out-of-reservation write (quota-finished mid-block) hits a
        column past its own pages — sink — never its last live page
        (at the np_max cap the pre-bucketing invariant already held)."""
        rows_map = self.alloc.rows
        occ = max((len(self.row_cached.get(r, ()))
                   + len(rows_map.get(r, ()))
                   for r in set(rows_map) | set(self.row_cached)
                   if rows_map.get(r) or self.row_cached.get(r)),
                  default=1)
        return self.width_for(occ, self.np_max)

    @staticmethod
    def width_for(occ: int, np_max: int) -> int:
        """The table width dispatched for ``occ`` allocated pages — the
        ONE bucketing formula, shared with ``ContinuousBatcher.
        _decode_widths`` so warmup compiles exactly the widths the
        serve loop will request."""
        return min(1 << occ.bit_length(), np_max)

    def decode_table(self, active: Dict[int, _Row],
                     decoding: Dict[int, _Row]) -> jnp.ndarray:
        """The batched step's device table, sliced to ``bucket_width``
        columns: the plain cached table when every active row
        participates; otherwise a masked variant with non-participating
        rows' entries pinned to the sink (still-filling rows' chunked
        prefill owns their pages; the pipelined loop's quota-finished
        rows await retire).  Cached keyed on (masked set, width) until
        the allocation changes — steady-state decode must neither
        re-upload nor re-slice the table every block."""
        w = self.bucket_width()
        masked = (frozenset() if len(decoding) == len(active)
                  else frozenset(r for r in active if r not in decoding))
        if self._masked is None or self._masked[0] != (masked, w):
            if masked:
                t = self.table_np().copy()
                for r in masked:
                    t[r, :] = self.sink
                t = t[:, :w]
            else:
                t = self.table_np()[:, :w]
            self._masked = ((masked, w), jnp.asarray(t))
        return self._masked[1]


class _PrefixNode:
    """One cached page-aligned chunk: a trie node owning one resident
    pool page — and, on a speculative batcher, its DRAFT-pool twin
    (``dpage``): the two pools cover the same token chunk, so they
    share one refcount and live or die together.  ``ref`` counts the
    live rows referencing the page read-only; a zero-ref node keeps
    its page(s) RESIDENT (that is the cache) until the LRU evictor
    reclaims it under allocation pressure or the budget."""

    __slots__ = ("digest", "page", "ref", "parent", "children", "last",
                 "shard", "dpage")

    def __init__(self, digest: bytes, page: int, parent, last: int,
                 shard: int, dpage: Optional[int] = None):
        self.digest = digest
        self.page = page
        self.ref = 1
        self.parent = parent        # _PrefixNode or None (root level)
        self.children: Dict[bytes, "_PrefixNode"] = {}
        self.last = last            # LRU tick of the last touch
        self.shard = shard
        self.dpage = dpage          # draft-pool twin (speculative mode)


class _PrefixCache:
    """Cross-request prefix cache over ONE :class:`_PagedSide`: a hash
    trie per mesh data shard (pages are shard-pinned, so a cached page
    is only reachable from rows of its own shard) mapping chain digests
    of page-aligned prompt chunks (:mod:`tfmesos_tpu.prefixhash`) to
    resident pool pages with refcounts.

    Lifecycle: admission walks the trie for the longest cached prefix
    and maps those pages read-only into the row's table (``acquire`` —
    refcount++); the prefill writes only the uncached tail, after which
    the tail's full prompt pages are PUBLISHED into the trie
    (``insert_row`` — ownership moves from the row's allocator list to
    the cache, the row keeping a reference).  ``release_row`` drops the
    references when the request finishes; zero-ref pages stay resident
    and are reclaimed lazily — the allocator's ``reclaim`` hook evicts
    LRU leaves only when an allocation would otherwise fail, and
    ``budget`` caps total cached pages per shard at insert time.

    Twin-pool mode (``dside`` — a speculative batcher's draft pool):
    every node couples one target page with one draft page covering
    the same chunk, under ONE refcount.  Acquire maps both into the
    row's tables, publish moves both sides' leading own pages, COW
    remaps both deepest pages, and eviction frees both — the budget
    counts NODES (so it caps ``budget`` pages per shard on EACH
    side).  Either side's allocation pressure can trigger the
    reclaim, which always frees a page on both.

    Thread safety: all mutation happens on the batcher's serve loop;
    ``summary()``/``stats()`` are read from the replica heartbeat
    thread, so every public method takes the lock.
    """

    def __init__(self, side: _PagedSide, page_size: int, budget: int,
                 n_shards: int = 1, dside: Optional[_PagedSide] = None):
        self.side = side
        self.dside = dside
        self.page_size = int(page_size)
        self.budget = int(budget)   # max cached pages PER SHARD
        self.n_shards = int(n_shards)
        self.roots: List[Dict[bytes, _PrefixNode]] = [
            {} for _ in range(self.n_shards)]
        self.row_nodes: Dict[int, List[_PrefixNode]] = {}
        # O(1) occupancy counters (the admission hot path reads these
        # per shard per attempt — walking the trie there would be
        # O(cached pages) per tick): total resident nodes, and nodes at
        # ref 0 (= reclaimable; a referenced descendant keeps every
        # ancestor referenced, so zero-ref <=> evictable).
        self._n_nodes = [0] * self.n_shards
        self._n_zero = [0] * self.n_shards
        self._tick = 0
        self._lock = threading.Lock()
        # Eviction-callback seam (the KV-tier spill hook, and anything
        # else that wants the page's content before it returns to the
        # free list): called as ``on_evict(shard, digest, page,
        # dpage)`` (dpage None without a draft twin) BEFORE the pages
        # free, while their pool content is still the published chunk.
        # A raising callback costs the spill, never the eviction —
        # reclaim must always make progress, or the allocation
        # pressure that triggered it deadlocks admission.
        self.on_evict = None
        self._stats = {"hits": 0, "misses": 0, "hit_pages": 0,
                       "hit_tokens": 0, "inserted": 0, "evicted": 0,
                       "cow_copies": 0, "skipped": 0, "promoted": 0}
        side.pcache = self
        for s, alloc in enumerate(side.alloc.shards):
            alloc.reclaim = partial(self._reclaim_cb, s)
        if dside is not None:
            # Draft-side pressure evicts through the SAME trie (one
            # eviction frees a page on both sides), and the draft's
            # headroom() counts the shared zero-ref nodes reclaimable.
            dside.pcache = self
            for s, alloc in enumerate(dside.alloc.shards):
                alloc.reclaim = partial(self._reclaim_cb, s)

    def _dirty(self) -> None:
        self.side.dirty()
        if self.dside is not None:
            self.dside.dirty()

    # -- trie walks (call under the lock) ---------------------------------

    def _walk(self, shard: int):
        stack = list(self.roots[shard].values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    def _match(self, shard: int, digests) -> List[_PrefixNode]:
        level = self.roots[shard]
        path: List[_PrefixNode] = []
        for d in digests:
            node = level.get(d)
            if node is None:
                break
            path.append(node)
            level = node.children
        return path

    def match(self, shard: int, digests) -> List[_PrefixNode]:
        """Longest cached path for ``digests`` (read-only; refs are
        taken by ``acquire`` once admission commits to the row)."""
        with self._lock:
            return self._match(shard, digests)

    # -- row mapping -------------------------------------------------------

    def acquire(self, row: int, nodes: List[_PrefixNode]) -> None:
        """Map ``nodes``' pages read-only into ``row``'s table
        (refcount++ each) — the row's table becomes
        [these pages | own] — on BOTH pools in twin mode."""
        with self._lock:
            self._tick += 1
            for n in nodes:
                n.ref += 1
                if n.ref == 1:
                    self._n_zero[n.shard] -= 1
                n.last = self._tick
            self.row_nodes[row] = list(nodes)
            self.side.row_cached[row] = [n.page for n in nodes]
            if self.dside is not None:
                self.dside.row_cached[row] = [n.dpage for n in nodes]
        self._dirty()

    def unmap_last(self, row: int) -> _PrefixNode:
        """Drop the DEEPEST mapped page (both pools' twins in twin
        mode) from ``row``'s table (the copy-on-write remap: its
        content moves into a freshly reserved own page); the node's
        reference is still held — release it via ``release_nodes``
        once the copy has been dispatched so the evictor cannot
        reclaim the source mid-copy."""
        with self._lock:
            node = self.row_nodes[row][-1]
            self.side.row_cached[row].pop()
            if self.dside is not None:
                self.dside.row_cached[row].pop()
        self._dirty()
        return node

    def _drop_ref(self, n: _PrefixNode) -> None:
        n.ref -= 1
        if n.ref == 0:
            self._n_zero[n.shard] += 1
        n.last = self._tick

    def release_nodes(self, row: int, nodes) -> None:
        with self._lock:
            self._tick += 1
            held = self.row_nodes.get(row, [])
            for n in nodes:
                self._drop_ref(n)
                held.remove(n)

    def release_row(self, row: int) -> None:
        """The row finished: drop every reference it holds.  Pages stay
        resident (zero-ref = the reusable cache) up to the budget.
        Idempotent — in twin mode BOTH sides' release() paths call
        here, and the second call finds nothing left to drop."""
        with self._lock:
            self._tick += 1
            for n in self.row_nodes.pop(row, []):
                self._drop_ref(n)
            self.side.row_cached.pop(row, None)
            if self.dside is not None:
                self.dside.row_cached.pop(row, None)

    def insert_row(self, row: int, shard: int, digests, state) -> None:
        """Publish ``row``'s freshly prefilled full prompt pages into
        the trie: ownership of the leading own pages moves to the cache
        (the row keeps referencing them at the SAME table slots, so no
        table rebuild is needed), extending the path the row already
        holds — both pools' pages move together in twin mode.  Stops
        at the first chunk already published by a concurrent twin (its
        pages stay own — never two owners for one trie node) or when
        the per-shard budget cannot be met by evicting."""
        with self._lock:
            self._tick += 1
            held = self.row_nodes.setdefault(row, [])
            own = self.side.alloc.rows.get(row, [])
            down = (self.dside.alloc.rows.get(row, [])
                    if self.dside is not None else None)
            cached = self.side.row_cached.setdefault(row, [])
            dcached = (self.dside.row_cached.setdefault(row, [])
                       if self.dside is not None else None)
            level = (held[-1].children if held else self.roots[shard])
            moved = 0
            for d in digests[len(held):]:
                if not own or (down is not None and not down):
                    break
                if d in level:
                    break       # a twin published this chunk first
                while (self._size(shard) >= self.budget
                       and self._evict_one(shard)):
                    pass
                if self._size(shard) >= self.budget:
                    self._stats["skipped"] += 1
                    break
                node = _PrefixNode(d, own.pop(0),
                                   held[-1] if held else None,
                                   self._tick, shard,
                                   dpage=(down.pop(0)
                                          if down is not None else None))
                level[d] = node
                self._n_nodes[shard] += 1
                held.append(node)
                cached.append(node.page)
                if dcached is not None:
                    dcached.append(node.dpage)
                level = node.children
                moved += 1
            self._stats["inserted"] += moved
        # The row's remaining claim on the pool is unchanged — the
        # moved pages still back its positions — so its reservation
        # shrinks with its allocation to keep headroom() exact (per
        # side: the draft twin's reservation shrinks identically).
        state.worst_pages -= moved
        if self.dside is not None:
            state.worst_draft -= moved

    # -- eviction ----------------------------------------------------------

    def _size(self, shard: int) -> int:
        return self._n_nodes[shard]

    def reclaimable(self, shard: int) -> int:
        """Pages reclaimable on demand: zero-ref nodes (a referenced
        descendant would keep its ancestors referenced too, so a
        zero-ref subtree is entirely evictable).  O(1) — the admission
        path reads this per shard per attempt."""
        return self._n_zero[shard]

    def _evict_one(self, shard: int) -> bool:
        """Reclaim the LRU zero-ref LEAF (deepest-first keeps every
        remaining node's chain valid); its page — and its draft twin —
        return to their shards' free lists.  Caller holds the lock."""
        best = None
        for n in self._walk(shard):
            if n.ref == 0 and not n.children:
                if best is None or n.last < best.last:
                    best = n
        if best is None:
            return False
        if self.on_evict is not None:
            try:
                self.on_evict(shard, best.digest, best.page, best.dpage)
            except Exception:
                pass    # the spill is best-effort; the eviction stands
        level = (best.parent.children if best.parent is not None
                 else self.roots[shard])
        del level[best.digest]
        self._n_nodes[shard] -= 1
        self._n_zero[shard] -= 1
        self.side.alloc.shards[shard].free.append(best.page)
        if self.dside is not None:
            self.dside.alloc.shards[shard].free.append(best.dpage)
        self._stats["evicted"] += 1
        return True

    def _reclaim_cb(self, shard: int) -> bool:
        with self._lock:
            return self._evict_one(shard)

    def clear(self) -> int:
        """Drop EVERY cached node, returning its page (and draft twin)
        to the free lists — the weight-swap invalidation: pages
        prefilled under the OLD weights must neither map into new rows
        nor spill to the KV tier, so the eviction callback is
        deliberately NOT fired.  Only legal with no resident rows
        (every node at ref 0); the batcher's weight-update fence
        guarantees that.  Returns the number of nodes dropped."""
        with self._lock:
            if self.row_nodes:
                raise RuntimeError(
                    "prefix cache clear with live row references — the "
                    "weight-update fence must drain resident rows first")
            dropped = 0
            for shard in range(self.n_shards):
                for n in self._walk(shard):
                    self.side.alloc.shards[shard].free.append(n.page)
                    if self.dside is not None:
                        self.dside.alloc.shards[shard].free.append(
                            n.dpage)
                    dropped += 1
                self.roots[shard] = {}
                self._n_nodes[shard] = 0
                self._n_zero[shard] = 0
            self._stats["evicted"] += dropped
        if dropped:
            self._dirty()
        return dropped

    def insert_chain(self, shard: int, parent_digests, digest: bytes,
                     page: int, dpage: Optional[int] = None) -> bool:
        """Insert ONE already-resident page (plus its draft twin in
        twin mode) as a zero-ref trie node under the path
        ``parent_digests`` — the KV-tier PROMOTION path: the caller
        took ``page`` (and ``dpage``) off the shard's free list(s) and
        scattered the tier's stored content into them; on True the
        cache owns them (zero-ref ⇒ reclaimable, so headroom
        accounting is unchanged: free lost one page per side,
        reclaimable gained one).  False (parent path gone, a twin
        already published the chunk, or the budget cannot be met) —
        the caller returns the page(s) to the free list(s)."""
        with self._lock:
            self._tick += 1
            # Budget FIRST: evicting after the walk could reclaim a
            # zero-ref leaf on the very parent path just validated.
            while (self._size(shard) >= self.budget
                   and self._evict_one(shard)):
                pass
            if self._size(shard) >= self.budget:
                self._stats["skipped"] += 1
                return False
            level = self.roots[shard]
            parent = None
            for d in parent_digests:
                node = level.get(d)
                if node is None:
                    return False
                parent = node
                level = node.children
            if digest in level:
                return False        # already resident (a twin won)
            node = _PrefixNode(digest, int(page), parent, self._tick,
                               shard,
                               dpage=(None if dpage is None
                                      else int(dpage)))
            node.ref = 0            # resident, unreferenced — the cache
            self._n_zero[shard] += 1
            level[digest] = node
            self._n_nodes[shard] += 1
            self._stats["promoted"] += 1
            return True

    # -- accounting / export ----------------------------------------------

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._stats[name] += n

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._stats)
            out["cached_pages"] = sum(self._n_nodes)
            out["retained_pages"] = sum(self._n_zero)
        return out

    def summary(self, max_entries: int = 64) -> Dict[str, Any]:
        """Wire-facing cache summary for registry heartbeats: the chunk
        geometry plus the most-recently-touched chain digests, which is
        what the gateway's prefix-affinity router matches incoming
        prompts against (fleet/router.py)."""
        with self._lock:
            nodes = [n for s in range(self.n_shards)
                     for n in self._walk(s)]
            nodes.sort(key=lambda n: n.last, reverse=True)
            # ``stats`` rides along for fleet-wide accounting (the
            # sessions scenario sums misses across replicas to assert a
            # common prompt prefilled once per FLEET); the router's
            # matcher only reads the geometry + hashes.  ``first`` and
            # ``seed`` are prefixhash's chunk-0 width and chain seed:
            # every chunk is one page and the chain starts empty.
            return {"page": self.page_size, "first": self.page_size,
                    "seed": "",
                    "hashes": [n.digest.hex()
                               for n in nodes[:max_entries]],
                    "stats": dict(self._stats)}


def _pool_leaves(cache):
    """What a program hands back as the donated pool, out of the cache
    ``decode_step`` returned: the K/V pages and, where rows keep one, the
    recurrent row state (``pool["state"]``)."""
    return {k: cache[k] for k in ("k", "v", "state") if k in cache}


@jax.jit
def _gather_pages(pool, ids):
    """Gather pool pages ``ids`` (page axis 1) on every layer and leaf —
    K and V, int8 QTensor values and scales alike: the device side of
    :meth:`ContinuousBatcher.export_kv`.  One trace per page count."""
    return jax.tree_util.tree_map(lambda buf: buf[:, ids], pool)


@partial(jax.jit, donate_argnums=0)
def _install_pages(pool, payload, ids):
    """Scatter an imported page payload (same tree structure as the
    pool, page axis 1 sized to ``ids``) into pool pages ``ids`` — the
    device side of the ``submit(prefilled=...)`` import admission.  One
    trace per page count."""
    return jax.tree_util.tree_map(
        lambda buf, src: buf.at[:, ids].set(src), pool, payload)


@partial(jax.jit, donate_argnums=0)
def _copy_page(pool, src, dst):
    """Copy pool page ``src`` into page ``dst`` on every layer and leaf
    (K and V; int8 QTensors copy values and scales alike) — the
    copy-on-write step behind a page-aligned full prefix-cache hit."""
    return jax.tree_util.tree_map(
        lambda buf: buf.at[:, dst].set(buf[:, src]), pool)


#: The tick ring (docs/SERVING.md "Observability"): one record per pass
#: of every batcher's serve loop in this process, in the process-global
#: ``flight(TICK_COMPONENT)`` so that it outlives the batcher.  16384
#: records are ~5 minutes of 20 ms ticks (a decode block at Mistral-7B
#: widths on the v5e since PR 25), under 8 MB once full.
TICK_COMPONENT = "batcher.tick"
TICK_RING = 16384

#: The request ring: one record per request, written once, when it
#: leaves a batcher (``_request_done``).  4096 records: the longest
#: backlog any cell submits is 2048.
REQUEST_COMPONENT = "batcher.request"
REQUEST_RING = 4096
#: The stall ring: the ticks ``_tick_roll`` called stalls, whole, where
#: the tick ring's turnover cannot flush them.
STALL_COMPONENT = "batcher.stall"
STALL_RING = 64
#: A tick is a stall where the time nothing accounts for (``held_ms``)
#: is over both STALL_FACTOR x the running median wall time of decode
#: ticks (the last STALL_WINDOW of them) and STALL_MIN_MS.
STALL_FACTOR = 8.0
STALL_MIN_MS = 250.0
STALL_WINDOW = 64
#: Seconds between two reads of the process-wide counters: the stall
#: rule's floor, so that a tick long enough to be a stall always closes
#: with a read, over itself and under a quarter second before it.
PROCESS_SAMPLE_S = STALL_MIN_MS / 1e3

_BATCHER_IDS = itertools.count()
#: backend compiles that finished in this process, and their seconds: a
#: tick record carries the difference across the tick (``compiles``)
_COMPILES = [0, 0.0]
#: collector pauses that ended in this process, on any thread (a pause
#: holds the interpreter lock whoever took it): their ms, their count,
#: the full collections among them, and the start of the one running
_GC = [0.0, 0, 0, 0.0]


def _count_compile(event: str, duration: float, **_kw) -> None:
    # fires once per jit cache miss that reaches the backend, whether XLA
    # compiled or the persistent cache answered
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1
        _COMPILES[1] += duration


def _count_gc(phase: str, info: Dict[str, Any]) -> None:
    # runs only when the collector does, under the interpreter lock
    if phase == "start":
        _GC[3] = time.perf_counter()
    else:
        _GC[0] += (time.perf_counter() - _GC[3]) * 1e3
        _GC[1] += 1
        _GC[2] += info["generation"] == 2


jax.monitoring.register_event_duration_secs_listener(_count_compile)
gc.callbacks.append(_count_gc)


def _tick_counters() -> tuple:
    """What a tick record carries the difference of, read once at every
    roll on the serve thread, ~1 us: backend compiles and their seconds,
    collector pauses (ms, count, full ones), this thread's CPU seconds
    and its involuntary context switches (0 where the platform does not
    count them)."""
    invol = 0
    if _RUSAGE_THREAD is not None:
        invol = resource.getrusage(_RUSAGE_THREAD).ru_nivcsw
    return (_COMPILES[0], _COMPILES[1], _GC[0], _GC[1], _GC[2],
            time.thread_time(), invol)


def _process_counters() -> tuple:
    """CPU seconds and major page faults of the whole process.  The
    kernel walks every thread for either (the runtime keeps hundreds,
    and reading a running one takes its run queue's lock), so a roll
    reads them only where PROCESS_SAMPLE_S have passed since it last
    did."""
    majflt = 0
    if resource is not None:
        majflt = resource.getrusage(resource.RUSAGE_SELF).ru_majflt
    return time.process_time(), majflt


class _Phase:
    """One phase of a tick: a span on the serve thread's line of a
    profiler trace (``annotate``, with the tick's number) whose
    ``perf_counter`` duration is added to the tick record on exit."""

    __slots__ = ("tick", "name", "span", "t0", "ms")

    def __init__(self, tick: Dict[str, Any], name: str, stats):
        self.tick = tick
        self.name = name
        self.span = annotate(name, tick=tick["tick"], **stats)

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self.t0) * 1e3
        self.span.__exit__(*exc)
        phases = self.tick["phases"]
        phases[self.name] = phases.get(self.name, 0.0) + self.ms
        return False


class ContinuousBatcher:
    """Admit a stream of :class:`Request`\\ s into a persistent paged
    decode of ``rows`` concurrent sequences.

    ``n_pages`` sizes the shared pool (default: fully backs
    ``rows x max_len``; smaller pools oversubscribe and admission waits
    for pages instead).  ``temperature``/``top_k``/``top_p`` fix the
    sampling config for the whole batcher (greedy at temperature 0);
    ``rng`` takes either key flavor (raw uint32 pair or typed
    ``jax.random.key``) — it is only ever folded in-graph.

    ``draft_cfg``/``draft_params`` (optional) turn on SPECULATIVE
    decoding inside the batcher: every tick, the draft proposes
    ``n_draft`` tokens per row (batched t=1 steps over its OWN paged
    pool — draft HBM tracks live tokens exactly like the target's;
    ``draft_n_pages`` sizes it, default fully backed;
    ``draft_quantized_cache=True`` stores it int8 like the target's
    ``quantized_cache``) and the target verifies them in ONE ragged
    chunk over the paged pool — rows commit their leading accepted run
    plus the target's correction, so each tick emits 1..n_draft+1
    tokens per row instead of exactly 1.  Greedy outputs equal the target-only
    batcher's (modulo float-tie argmax forks); with ``temperature > 0``
    the round is Leviathan-style rejection sampling (accept with
    min(1, pt/pd), corrections from norm(max(0, pt − pd))) whose draws
    all derive from per-(rid, token-index) key folds — so sampled
    speculative streams stay invariant to row packing, and committed
    tokens are distributed exactly as target-only sampling.  Composes
    with stop tokens, staggered admission, int8 target pools, and
    chunked prefill (the draft's chunks advance in lockstep with the
    target's).

    ``prefill_chunk`` (optional) turns on CHUNKED PREFILL: instead of
    prefilling a whole prompt in one call (stalling every decoding row
    for the full prompt length), admission writes the prompt in
    fixed-size chunks interleaved one-per-tick with the batched decode
    step — the stall per decoded token is bounded by one chunk's
    compute, whatever the prompt length.  Chunks of <= 64 ride the
    chunked flash-decode kernel on TPU.  The chunk size becomes the
    prompt padding bucket.  Note the chunked path runs every chunk
    through cache-attention (not the fused self-attention prefill), so
    greedy outputs can differ from the unchunked batcher only by
    float-tie argmax flips.

    ``pipeline_depth=1`` PIPELINES the decode loop with a
    device-resident carry — the batcher's ONE lag policy: block N+1 is
    dispatched BEFORE block N's tokens are synced to the host, fed
    straight from the previous dispatch's device outputs (tokens,
    positions, AND steps stay on device; the page table and the small
    host-merge inputs are refreshed only when admission/prefill/finish
    actually changed the dispatch set), and block N's tokens are synced
    one block behind via the in-flight async transfer, so the device
    never idles on a per-block host round-trip.  Host-side stop/quota
    detection and admission act one block late; the overshoot block's
    writes land inside the row's clamped reservation or on sink columns
    — the exact mid-block-stop discard semantics ``_step`` documents —
    so token streams are IDENTICAL to ``pipeline_depth=0`` (greedy AND
    sampled: the (rid, step) key folds are unchanged).  Composes with
    ``multi_step``, chunked prefill, int8 pools, ``mesh``, and the
    prefix cache; speculative decoding BYPASSES explicitly
    (``pipeline_bypass_reason``: the carry has no speculative form, a
    speculative batcher serves synchronously).  ``0`` preserves the
    synchronous loop exactly.  ``None`` leaves the choice to the
    batcher: the carry where it costs no surface, i.e. ``1`` where the
    model's own cache has already closed suspend, the one surface the
    lag closes (a recurrent row state, EVA's entry pages), else ``0``.

    ``multi_step`` composes with speculative decoding: R =
    ceil(multi_step / (n_draft+1)) rounds fuse into ONE dispatch,
    chained in-graph from each round's commit counts, committed
    round-by-round on the host.  The ``suspend`` registry gates
    :attr:`preemptible` the same enumerable way: per-row suspend/export
    needs the host-synchronous single-shard loop, so pipelined (lagged
    carry) and mesh-sharded batchers record ``suspend_bypass_reason``
    and requeue on preemption instead of exporting.

    :meth:`warmup` compiles every jitted entry point the configured
    mode can dispatch (admission prefill, chunk prefill, decode block
    per table-width bucket, speculative round, KV export/import
    scatter) against dummy all-sink shapes — call it at boot to move
    first-request compilation off the serving path.  The fleet's
    ``warming`` replica state rides on it: a replica registers as
    warming, warms, and only then advertises itself routable
    (docs/SERVING.md "Warmup & the warming state").

    ``mesh`` (optional) makes the WHOLE serving loop multi-chip: a
    data (dp/fsdp) x tp ``jax.sharding.Mesh`` — possibly spanning
    processes — over which every model call runs sharded.  Rows are
    partitioned into contiguous blocks, one per data shard; each shard
    owns an equal sub-pool of pages (target AND draft) that its rows'
    tables index with shard-LOCAL ids, so the page gather/scatter stays
    a per-shard shard_map island while the matmuls partition under
    GSPMD (heads/ff over tp).  Admission stays host-global and
    deterministic: on a multi-process mesh every process runs the same
    loop and reads the same replicated token outputs.  Prefill, chunked
    prefill, speculative rounds, prefix sharing, and int8 pools all
    ride the same path; outputs are token-identical to the no-mesh
    batcher (modulo float-tie argmax forks from tp partial-sum order).
    ``rows`` must divide over the data axes, tp must divide both
    models' head counts.

    ``prefix_cache_pages`` (> 0 enables; the value caps resident cached
    pages per mesh data shard) turns on the CROSS-REQUEST PREFIX CACHE:
    full page-aligned prompt chunks are published into a per-shard hash
    trie after prefill, and later requests sharing a leading prompt run
    map those pages read-only (refcounted) and prefill only the
    uncached tail — TTFT for a warm shared system prompt drops to the
    tail's compute.  A page-aligned full hit copies its deepest page
    copy-on-write before the one-token logits rewrite; finished
    requests leave zero-ref pages RESIDENT, reclaimed LRU-first only
    under allocation pressure (admission headroom counts them as free,
    so the cache can never deadlock admission).  Nothing needs
    declaring up front — any shared system/few-shot prompt is
    discovered at admission.  Greedy warm
    completions match cold-prefill completions exactly up to float-tie
    argmax flips (the tail prefill runs cache-attention, like chunked
    prefill; bit-identical in practice on the CPU test config).
    Composes with ``prefill_chunk``, ``pipeline_depth``,
    ``multi_step``, ``mesh``, and SPECULATIVE decoding — a
    spec batcher's trie couples every target page with its draft-pool
    twin (one refcount, COW on both deepest pages, twin publish after
    prefill),
    so a warm hit maps BOTH pools and prefills only the uncached tail
    through each side's chunk writer; ``quantized_cache`` (either
    pool's) BYPASSES sharing explicitly
    (``prefix_cache_bypass_reason``, see ``BYPASS_ALLOWLIST``).

    DISAGGREGATED serving splits the two phases across batchers:
    :meth:`export_kv` runs a prompt through (chunked) prefill only and
    returns its paged-KV state as a host artifact; a matching batcher
    imports it with ``submit(request, prefilled=artifact)`` — pages
    install into the local pool, the row enters decode directly, and
    greedy completions equal the unified batcher's token-for-token
    (sampled ones too, when the batchers share an rng: the artifact
    carries the sampler's rid fold).  Imported full prompt pages seed
    the importer's prefix cache like a local prefill's.  Requires a
    single-shard pool; int8 pools export/import bit-exactly.  A
    SPECULATIVE batcher's artifact carries the draft pool's paired
    payload (``dk``/``dv`` + the ``draft`` header) over the same
    positions — spec rows export, import, suspend, migrate, and park
    like any other — and a fresh (step-1) artifact from a draft-less
    prefill tier imports into a spec batcher by rebuilding the draft's
    prompt KV locally (the same chunk write a local spec admission
    dispatches).  The fleet's prefill/decode role split
    (docs/SERVING.md "Disaggregated prefill/decode") rides this surface.
    """

    def __init__(self, cfg: TransformerConfig, params, rows: int = 8,
                 max_len: Optional[int] = None, page_size: int = 64,
                 n_pages: Optional[int] = None, prefill_bucket: int = 64,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, rng=None,
                 quantized_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 draft_cfg: Optional[TransformerConfig] = None,
                 draft_params=None, n_draft: int = 4,
                 draft_n_pages: Optional[int] = None, mesh=None,
                 draft_quantized_cache: bool = False,
                 multi_step: int = 1,
                 prefix_cache_pages: int = 0,
                 pipeline_depth: Optional[int] = 0,
                 kv_tier=None,
                 rid_seed: int = 0,
                 fused_prefill: bool = False,
                 tokens_per_tick: Optional[int] = None):
        if rows < 1:
            raise ValueError(f"rows must be >= 1, got {rows}")
        if not 0 <= int(rid_seed) < 2 ** 30:
            # rids land in int32 arrays and key the (rid, step) sampling
            # folds; the seed must leave increment headroom below 2^31.
            raise ValueError(f"rid_seed must be in [0, 2^30), got "
                             f"{rid_seed}")
        if prefix_cache_pages < 0:
            raise ValueError(f"prefix_cache_pages must be >= 0, got "
                             f"{prefix_cache_pages}")
        if multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got {multi_step}")
        if pipeline_depth not in (0, 1, None):
            raise ValueError(f"pipeline_depth must be 0 (synchronous "
                             f"host sync), 1 (one block of device-"
                             f"resident lag) or None (the batcher's "
                             f"choice), got {pipeline_depth}")
        if fused_prefill and prefill_chunk is None:
            raise ValueError("fused_prefill requires prefill_chunk "
                             "(chunked prefill is the lane being fused)")
        if tokens_per_tick is not None and tokens_per_tick < 1:
            raise ValueError(f"tokens_per_tick must be >= 1, got "
                             f"{tokens_per_tick}")
        self.multi_step = int(multi_step)
        # Pipelined device-resident decode (pipeline_depth=1): block N+1
        # is dispatched from the device-side carry — tokens, positions,
        # AND steps never round-trip to the host between blocks — and
        # block N's tokens are synced one block behind.  Speculative
        # decoding bypasses explicitly (the carry has no speculative
        # form); the recorded reason makes the bypass observable, like
        # prefix_cache_bypass_reason.  The ``*_bypass_reason``
        # registries themselves are computed after the mesh parse below
        # (the shard count participates), and with them the depth where
        # it was left to the batcher (None).
        self._pipe_carry = None     # device (tok, pos, step) carry
        self._pipe_host = None      # cached host-side dispatch inputs
        # The block in flight: (its device token output, {row: rid}
        # ticket, its K), retired one dispatch later.
        self._inflight = None
        self.cfg = cfg
        self.params = params
        self.rows = rows
        self.mesh = mesh
        self.n_shards = 1
        self._tp = 1
        if mesh is not None:
            real = {a for a, s in mesh.shape.items() if s > 1}
            if not real <= {"dp", "fsdp", "tp"}:
                raise ValueError(
                    f"ContinuousBatcher meshes are data (dp/fsdp) x tp; "
                    f"got axes {sorted(real)}")
            for a in ("dp", "fsdp"):
                self.n_shards *= mesh.shape.get(a, 1)
            self._tp = mesh.shape.get("tp", 1)
            if rows % self.n_shards:
                raise ValueError(
                    f"rows ({rows}) must divide over the mesh data axes "
                    f"({self.n_shards}) — each data shard serves an equal "
                    f"row block")
            if cfg.kv_heads % self._tp or cfg.n_heads % self._tp:
                raise ValueError(
                    f"tp ({self._tp}) must divide kv_heads "
                    f"({cfg.kv_heads}) and n_heads ({cfg.n_heads})")
        # All ``*_bypass_reason`` registries come from ONE pure
        # helper (compute_bypass_reasons) so the audit test can
        # enumerate every reachable value against BYPASS_ALLOWLIST.
        eva = cfg.attention == "eva"
        # Rows that keep a recurrent state beside their pages (a typed
        # stack's mamba or kda layers): the row-slot state store lives in
        # the donated pool (``pool["state"]``, init_row_state).
        # A window layer's ring is such a state too, with a reason of its
        # own in the registries where no recurrent state gives one.
        recurrent = cfg.keeps_row_state
        self._recurrent = recurrent
        modes = dict(eva=eva, window=cfg.n_window_layers > 0,
                     recurrent=cfg.n_mamba_layers + cfg.n_kda_layers > 0)
        if pipeline_depth is None:
            # The lag policy left to the batcher (what ``fleet/replica.py``
            # passes when ``--pipeline-depth`` is not given): the carry
            # where it costs no surface.  What one block of lag costs a
            # deployment is ``suspend`` ("lagged decode carry"); where the
            # model's own cache has closed that already (a recurrent row
            # state, EVA's entry pages) the carry is free, and what it
            # buys is the host's tick behind every block (64 rows: 2.4 ms
            # of host phases and 3.3 ms of idle device behind a 30 ms
            # block).  A plain stack keeps its suspend and stays
            # synchronous.
            pipeline_depth = int(compute_bypass_reasons(
                **modes)["suspend"] is not None)
        self.pipeline_depth = int(pipeline_depth)
        self._bypass = compute_bypass_reasons(
            speculative=draft_cfg is not None, n_shards=self.n_shards,
            quantized_cache=quantized_cache,
            draft_quantized_cache=draft_quantized_cache,
            pipeline_depth=pipeline_depth, **modes)
        if cfg.layer_types is not None:
            # What a typed stack cannot do yet is refused here, before any
            # device state exists (the registries above bypass the rest).
            if draft_cfg is not None:
                raise ValueError(
                    f"speculative decoding is refused with typed layers: "
                    f"{self._bypass['speculative'] or 'one program per stack'}")
            for what, given in (("a mesh", mesh is not None),
                                ("prefill_chunk", prefill_chunk is not None),
                                ("quantized_cache", quantized_cache)):
                if given:
                    raise ValueError(
                        f"{what} does not compose with typed layers "
                        f"(layer_types): a prompt is prefilled whole, from "
                        f"position 0 and an empty row state, on one host")
        if eva:
            # What EVA's pages cannot do yet is refused here, before any
            # device state exists (the registries above bypass the rest).
            if draft_cfg is not None:
                raise ValueError(
                    f"speculative decoding is refused under "
                    f"attention='eva': {self._bypass['speculative']}")
            for what, given in (("a mesh", mesh is not None),
                                ("prefill_chunk", prefill_chunk is not None),
                                ("quantized_cache", quantized_cache)):
                if given:
                    raise ValueError(
                        f"{what} does not compose with attention='eva' "
                        f"(summaries and one window's exact entries in "
                        f"one page list per row)")
            if cfg.eva_window % int(prefill_bucket):
                raise ValueError(
                    f"eva_window ({cfg.eva_window}) must be a multiple of "
                    f"prefill_bucket ({prefill_bucket}): a prompt is "
                    f"prefilled window by window, then a padded tail")
        self.pipeline_bypass_reason: Optional[str] = \
            self._bypass["pipeline"]
        self.suspend_bypass_reason: Optional[str] = \
            self._bypass["suspend"]
        # Speculative multi_step>1 composes as R fused rounds per
        # dispatch (see _make_spec_round).
        self._spec_rounds = (0 if draft_cfg is None else max(
            1, -(-self.multi_step // max(1, n_draft + 1))))
        self.max_len = int(max_len or cfg.max_seq_len)
        if self.max_len > cfg.max_seq_len:
            raise ValueError(f"max_len ({self.max_len}) exceeds the "
                             f"config's max_seq_len ({cfg.max_seq_len})")
        self.page_size = int(page_size)
        # A row's table covers the most cache ENTRIES a context of max_len
        # can hold (one per position, but for EVA: cfg.cache_entries).
        self.np_max = -(-cfg.cache_entries_peak(0, self.max_len)
                        // self.page_size)
        # Default pool: per data shard, its row block's worst case plus
        # the shard's own inactive-row write sink — so the default
        # always fully backs rows x max_len of live data.
        per_shard = (rows // self.n_shards) * self.np_max + 1
        self.n_pages = int(n_pages or self.n_shards * per_shard)
        if prefill_chunk is not None:
            if prefill_chunk < 1 or prefill_chunk % 8:
                raise ValueError(f"prefill_chunk ({prefill_chunk}) must be "
                                 f"a positive multiple of 8")
            prefill_bucket = prefill_chunk
        self.prefill_chunk = prefill_chunk
        self.prefill_bucket = int(prefill_bucket)
        # Stall-free fused scheduling (docs/SERVING.md "Stall-free
        # fused scheduling"): one dispatch per tick covers every decode
        # row's K-step block AND up to (tokens_per_tick - n_decode*K)/c
        # prefill chunk tokens from still-filling rows — the chunk no
        # longer rides a separate device call ahead of the block, so
        # decoding rows stop paying a full chunk stall per tick.  Modes
        # the single fused program cannot cover BYPASS with a recorded
        # reason (fused_prefill_bypass_reason — same discipline as the
        # other registries), falling back to the phase-split tick.
        self.fused_prefill_bypass_reason: Optional[str] = None
        if fused_prefill:
            self.fused_prefill_bypass_reason = \
                self._bypass["fused_prefill"]
        self._fused = (fused_prefill
                       and self.fused_prefill_bypass_reason is None)
        #: the per-tick token budget the fused dispatch packs to:
        #: defaults to every row decoding a full block plus one chunk
        #: (>= the phase-split tick's work, so fusion never slows the
        #: schedule down; larger budgets coalesce more filling rows).
        self.tokens_per_tick = int(
            tokens_per_tick or rows * self.multi_step
            + (prefill_chunk or 0))
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self._rng = jax.random.PRNGKey(0) if rng is None else rng
        self.t_side = _PagedSide(self.n_pages, self.page_size, rows,
                                 self.np_max, n_shards=self.n_shards)
        self.t_side.pool = init_paged_cache(
            cfg, self.n_pages, self.page_size, quantized=quantized_cache)
        if recurrent:
            from tfmesos_tpu.models.transformer import init_row_state
            self.t_side.pool["state"] = init_row_state(cfg, rows)
        #: bytes the row slots hold beside the pages, whatever the contexts
        #: (recurrent states, window layers' rings)
        self.row_state_bytes = sum(
            leaf.nbytes for leaf in self.t_side.pool.get("state", {}).values())
        # the grouped expert layer counts its assignments per held expert;
        # a block's sums ride back with its tokens (see _make_decode)
        self._moe_counts = bool(cfg.n_experts) and \
            cfg.moe_impl == "grouped" and cfg.layer_types is not None
        self._state_rows = 0            # slots that hold a live state
        #: the window of a typed stack's "window" layers (0: none)
        self._swa = cfg.window if cfg.n_window_layers else 0
        if mesh is not None:
            from tfmesos_tpu.models.transformer import partition_specs
            self.params = self._place(params, partition_specs(cfg, mesh))
        self._init_side_device_state(self.t_side, cfg,
                                     quantized=quantized_cache)
        self._prefill_fns: Dict[int, Any] = {}
        self._decode = self._make_decode()
        # EVA: a window is closed by a program of its own, enqueued
        # behind the block that fills it (_eva_roll_row), so a K-step
        # block must not carry a row across a window's end: the tick
        # before one runs single steps (_decode1).
        self._decode1 = (self._make_decode(1)
                         if eva and self.multi_step > 1 else self._decode)
        self._eva_roll = self._make_eva_roll() if eva else None
        self.eva_rolls = 0             # observability: windows closed
        self._eva_live = (0, 0, 0)      # (summary, window) entries; pages
        self._chunk_prefill = (self._make_chunk_prefill()
                               if prefill_chunk is not None else None)
        self._fused_step = self._make_fused_step() if self._fused else None
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.n_draft = int(n_draft)
        if (draft_cfg is None) != (draft_params is None):
            raise ValueError("draft_cfg and draft_params come together")
        self.d_side: Optional[_PagedSide] = None
        if draft_cfg is not None:
            if self.n_draft < 1:
                raise ValueError(f"n_draft must be >= 1, got {n_draft}")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocab")
            # +1: the backfill draft step writes one past the proposals,
            # and parked rows sit at position max_len.
            depth = self.max_len + self.n_draft + 1
            if draft_cfg.max_seq_len < depth:
                raise ValueError(
                    f"draft max_seq_len ({draft_cfg.max_seq_len}) must "
                    f"cover max_len + n_draft + 1 ({depth}) — rows can "
                    f"overshoot by a draft run")
            # The draft's K/V is PAGED like the target's (same pool/table
            # layout, its own allocator): admitted requests never exceed
            # max_len positions even with the verify overshoot (_worst_pages
            # validates that), so the draft table is np_max wide too, and
            # draft HBM tracks LIVE tokens instead of a rows x
            # (max_len + n_draft + 1) worst-case buffer.  Parked free rows
            # write at position max_len through all-sink table rows (the
            # clamped block gather lands on the sink page).
            if mesh is not None and (draft_cfg.kv_heads % self._tp
                                     or draft_cfg.n_heads % self._tp):
                raise ValueError(
                    f"tp ({self._tp}) must divide the DRAFT's kv_heads "
                    f"({draft_cfg.kv_heads}) and n_heads "
                    f"({draft_cfg.n_heads}) too")
            self.n_draft_pages = int(draft_n_pages
                                     or self.n_shards * per_shard)
            self.d_side = _PagedSide(self.n_draft_pages, self.page_size,
                                     rows, self.np_max,
                                     n_shards=self.n_shards)
            self.d_side.pool = init_paged_cache(
                draft_cfg, self.n_draft_pages, self.page_size,
                quantized=draft_quantized_cache)
            if mesh is not None:
                self.draft_params = self._place(
                    draft_params, partition_specs(draft_cfg, mesh))
            self._init_side_device_state(self.d_side, draft_cfg,
                                         quantized=draft_quantized_cache)
            self._spec_round = self._make_spec_round()
            self._draft_chunk = self._make_draft_chunk()
        # Request-id stream base.  Sampled draws are pure (rid, step) key
        # folds, so two EXPORTERS whose rids collide would share an rng
        # stream across artifacts (the PR 4 caveat); per-replica seeding
        # (derived from the fleet node id) keeps exporter streams
        # disjoint.  Imports still continue the exporter's rid — that is
        # the point of the fold.
        self._next_rid = int(rid_seed)
        # Incremental submission (see submit()/serve()); lazily built so
        # plain run(iterable) batchers never pay for it.
        self._submissions: Optional[SubmissionQueue] = None
        self._submissions_lock = threading.Lock()
        # Disaggregated serving (export_kv / submit(prefilled=...)):
        # prefill-only exports serialize on this lock and borrow row 0,
        # so they must never run concurrently with a serve loop (the
        # loop owns the rows); _loop_active fences that.
        self._export_lock = threading.Lock()
        self._loop_active = False
        # Priority preemption / migration (docs/SERVING.md "Priorities,
        # preemption & migration"): artifacts of rows suspended under
        # allocation pressure, waiting for a free row to resume through
        # the import path; the event asks the serve loop to suspend
        # EVERYTHING (drain-migration) and yield Suspended items.
        self._parked: deque = deque()
        self._preempt_event = threading.Event()
        # Online weight updates (docs/SERVING.md "Model catalog"):
        # queued LoRA-style adapter folds (swap_adapter) and full
        # weight swaps (set_weights, the warm-pool adoption path),
        # applied by the serve loop BETWEEN generations — admission
        # gates while one is pending, resident rows finish on the old
        # weights, then the update folds and admission resumes: every
        # stream is token-identical to an offline run under exactly
        # one weights state.  The prefix cache flushes and the KV tier
        # restamps at apply time (old-weights KV must never feed a
        # new-weights decode).
        self._weight_updates: deque = deque()
        self._weights_lock = threading.Lock()
        #: label of the last adapter delta folded in ("" = base
        #: weights) — rides heartbeats and suspended exports so the
        #: router only ever resumes mid-stream KV under the same
        #: delta.
        self.adapter_version = ""
        self.weight_swaps = 0       # updates applied (folds + sets)
        #: optional hook fired (from the serve loop) after each update
        #: applies: ``on_weights_applied(kind, version)`` — the
        #: replica process uses it to refresh heartbeat fields.
        self.on_weights_applied = None
        self.preemptions = 0        # rows suspended for a higher class
        self.resumes = 0            # parked rows re-admitted locally
        # End-to-end deadlines: arrivals shed expired + resident rows
        # cancelled mid-decode (pages freed, Expired yielded) — the
        # replica-side half of fleet deadline conformance.
        self.deadline_cancels = 0
        # Speculative observability (see acceptance_rate).
        self.spec_rounds = 0        # jitted rounds executed
        self.spec_row_rounds = 0    # row-rounds (rows decoding per round)
        self.spec_committed = 0     # tokens committed across them
        # Fused-tick observability (see fused_tokens_per_tick).
        self.fused_ticks = 0          # fused prefill+decode dispatches
        self.fused_chunk_tokens = 0   # prefill tokens they coalesced
        self.fused_decode_tokens = 0  # decode tokens they covered
        # The tick recorder (docs/SERVING.md "Observability"): every
        # pass of the serve loop leaves one record, written by
        # _tick_roll, in the process-global tick ring; ``flight`` is
        # this batcher's share of it (records stamped with its id).
        bid = next(_BATCHER_IDS)
        self.flight = FlightView(flight(TICK_COMPONENT, TICK_RING),
                                 "batcher", bid)
        #: this batcher's share of the request ring and of the stall
        #: ring (_request_done, _tick_roll)
        self.requests = FlightView(flight(REQUEST_COMPONENT, REQUEST_RING),
                                   "batcher", bid)
        self.stalls = FlightView(flight(STALL_COMPONENT, STALL_RING),
                                 "batcher", bid)
        self._mode = ("spec" if draft_cfg is not None
                      else "pipelined" if self._pipelined else "sync")
        self._tick_n = 0
        self._tick_c0 = _tick_counters()
        # the last read of the process-wide counters, and when
        self._proc_c0 = (0.0, 0)
        self._proc_t = 0.0
        # the stall rule: the last decode ticks' wall time (its median
        # is taken when a tick is long enough to ask), the last line
        self._decode_walls: deque = deque(maxlen=STALL_WINDOW)
        self._stall_logged = 0.0
        self._tick = self._tick_open(None)
        # Cross-request prefix cache (prefix_cache_pages > 0 enables;
        # the value caps resident cached pages PER SHARD — per POOL in
        # speculative mode, where every trie node couples a target page
        # with its draft-pool twin under one refcount).  Modes whose
        # pages the cache cannot share bitwise-safely BYPASS explicitly
        # (prefix_cache_bypass_reason, from BYPASS_ALLOWLIST): an int8
        # pool's tail-recompute path is not bit-stable against the cold
        # fused prefill (target or draft side alike).
        self._pcache: Optional[_PrefixCache] = None
        self._tail_prefill = None
        self.prefix_cache_bypass_reason: Optional[str] = None
        if prefix_cache_pages:
            self.prefix_cache_bypass_reason = \
                self._bypass["prefix_cache"]
            if self.prefix_cache_bypass_reason is None:
                self._pcache = _PrefixCache(
                    self.t_side, self.page_size, prefix_cache_pages,
                    n_shards=self.n_shards, dside=self.d_side)
                self._tail_prefill = (self._chunk_prefill
                                      or self._make_chunk_prefill())
        # Tiered KV store (fleet/kvtier.py; docs/SERVING.md "KV tiering
        # & sessions"): prefix pages evicted from the device pool SPILL
        # into it (promoting back on the next matching admission), and
        # finished session-labeled requests PARK their KV artifacts in
        # it for leading-KV resumption next turn.  A speculative
        # batcher's spills and parks carry the draft pool's paired
        # payload, so spec sessions resume like any other.  Modes whose
        # per-row state the single-shard export/import scatter cannot
        # move BYPASS explicitly (kv_tier_bypass_reason — same
        # discipline as the other bypass registries).
        self.kv_tier = kv_tier
        self.kv_tier_bypass_reason: Optional[str] = None
        if kv_tier is not None:
            self.kv_tier_bypass_reason = self._bypass["kv_tier"]
            if self.kv_tier_bypass_reason is None:
                if self._tail_prefill is None:
                    self._tail_prefill = (self._chunk_prefill
                                          or self._make_chunk_prefill())
                if self._pcache is not None:
                    self._pcache.on_evict = self._spill_page
                    kv_tier.prefix_geometry = {
                        "page": self.page_size,
                        "first": self.page_size, "seed": ""}

    # -- the tick recorder ------------------------------------------------

    def _tick_open(self, t: Optional[float]) -> Dict[str, Any]:
        """A new tick record.  ``t`` None is the placeholder that takes
        the phases of work outside a serve loop (export_kv) and is never
        recorded."""
        if t is not None:
            self._tick_n += 1
        rec = {"name": "tick", "tick": self._tick_n if t is not None else -1,
               "t": t, "wall_ms": 0.0, "kind": "idle", "mode": self._mode,
               "k": 0, "rows": 0, "dur": 0.0, "admitted": 0,
               "prefill_tokens": 0, "phases": {}, "idle_ms": 0.0,
               "compiles": 0, "compile_s": 0.0,
               # what held the tick (docs/SERVING.md "Observability"):
               # from _tick_counters, over the tick
               "gc_ms": 0.0, "gc_n": 0, "gc_gen2": 0, "thread_cpu_ms": 0.0,
               "ctx_invol": 0,
               # from _process_counters, over the ``cpu_span_ms`` that end
               # with the tick, where the tick closed with a read of them
               "cpu_ms": None, "majflt": None, "cpu_span_ms": None,
               # the pipelined loop: had the lagged block finished on the
               # device before the serve thread asked for it
               "ready": None}
        if self._eva_roll is not None:
            # windows closed in this tick; entries live at its end
            rec.update(eva_rolls=0, eva_summary_entries=0,
                       eva_window_entries=0, eva_pages=0)
        if self._recurrent:
            rec["state_rows"] = 0       # slots with a live state, at its end
        if self._moe_counts:
            # assignments that fell on held experts in this tick's block,
            # the most any one expert (of any layer) took of them, and the
            # held experts of every layer and step that took at least one
            # and the rows of the live tiles those assignments were padded
            # to (each (step, layer, expert)'s up to whole tiles)
            # ``moe_routed``: the assignments the routers made over ALL
            # ``n_experts`` for the block's rows (``moe_assignments`` over
            # it is the share of them this shard's experts took)
            rec.update(moe_assignments=0, moe_expert_max=0,
                       moe_experts_touched=0, moe_tile_rows=0,
                       moe_routed=0)
        if self._swa:
            # a decode block's rows: the contexts they reach, and the
            # positions of them a window layer's ring holds
            rec.update(ctx_positions=0, swa_positions=0)
        return rec

    def _tick_roll(self, more: bool = True) -> None:
        """The one recorder write of the serve loop: close the open tick
        into the ring and open the next (``more``), at every pass's top
        and in the loop's ``finally``.  Ticks tile the loop's time: a
        tick's ``wall_ms`` runs to the next one's start, the consumer's
        time at a ``yield`` included."""
        now = time.perf_counter()
        c1 = _tick_counters()
        t = self._tick
        if t["t"] is not None:
            ph = t["phases"]
            c0 = self._tick_c0
            t["wall_ms"] = (now - t["t"]) * 1e3
            t["dur"] = round(ph.get("batcher.dispatch", 0.0)
                             + ph.get("batcher.readback", 0.0), 3)
            (t["compiles"], t["compile_s"], t["gc_ms"], t["gc_n"],
             t["gc_gen2"], thread_s,
             t["ctx_invol"]) = (b - a for a, b in zip(c0, c1))
            t["thread_cpu_ms"] = thread_s * 1e3
            if self._eva_roll is not None:
                (t["eva_summary_entries"], t["eva_window_entries"],
                 t["eva_pages"]) = self._eva_live
            if self._recurrent:
                t["state_rows"] = self._state_rows
            block = t["name"] == "decode.block"
            fill = t["prefill_tokens"] or t["admitted"]
            t["kind"] = ("fused" if t["mode"] == "fused"
                         else "mixed" if block and fill
                         else "decode" if block
                         else "prefill" if fill else "idle")
            if now - self._proc_t >= PROCESS_SAMPLE_S:
                t["cpu_span_ms"] = (now - self._proc_t) * 1e3
                p0, p1 = self._proc_c0, self._tick_process(now)
                t["cpu_ms"] = (p1[0] - p0[0]) * 1e3
                t["majflt"] = p1[1] - p0[1]
            self.flight.record(t)
            self._tick_stall(t, now)
        else:
            self._tick_process(now)     # the loop starts: a fresh baseline
        self._tick_c0 = c1
        self._tick = self._tick_open(now if more else None)

    def _tick_process(self, now: float) -> tuple:
        """Read the process-wide counters: the next read's baseline."""
        self._proc_t = now
        self._proc_c0 = _process_counters()
        return self._proc_c0

    def _tick_stall(self, t: Dict[str, Any], now: float) -> None:
        """The stall rule, on the tick just closed.  ``held_ms`` is the
        tick's wall time less what is honest work or no work at all: the
        idle pull, and admission with its prefill and the wait for its
        first token (a 7 k-token prefill is a third of a second).  A tick
        held for more than STALL_FACTOR x the running median of decode
        ticks and STALL_MIN_MS, in which nothing compiled (``compiles``
        names that time), goes whole into the stall ring with the two
        numbers it was judged by, and at most once a second into the log
        with the counters that tell the causes apart.  The median is of
        the decode ticks before this one."""
        ph = t["phases"]
        walls = self._decode_walls
        held = (t["wall_ms"] - t["idle_ms"] - ph.get("batcher.admit", 0.0)
                - ph.get("batcher.prefill_sync", 0.0))
        stall = held > STALL_MIN_MS and not t["compiles"]
        if stall:       # the one comparison a tick that did not stall pays
            median = statistics.median(walls) if walls else 0.0
            stall = held > STALL_FACTOR * median
        if t["kind"] == "decode":
            walls.append(t["wall_ms"])
        if not stall:
            return
        self.stalls.record(dict(t, held_ms=held, median_ms=median))
        if now - self._stall_logged < 1.0:
            return
        self._stall_logged = now
        log.warning(
            "batcher %d tick %d stalled: kind %s rows %d wall_ms %.1f "
            "(held %.1f, median decode tick %.1f) phases %s gc_ms %.1f "
            "thread_cpu_ms %.1f ctx_invol %d cpu_ms %.1f majflt %d (the "
            "process, over %.1f ms) ready %s", t["batcher"], t["tick"],
            t["kind"], t["rows"], t["wall_ms"], held, median,
            {k.split(".", 1)[1]: round(v, 1) for k, v in ph.items()
             if v > 1.0}, t["gc_ms"], t["thread_cpu_ms"], t["ctx_invol"],
            t["cpu_ms"], t["majflt"], t["cpu_span_ms"], t["ready"])

    def _phase(self, name: str, **stats) -> _Phase:
        """Open one phase of the current tick (``with``): flat siblings
        on the serve thread, never nested and never across a ``yield``."""
        return _Phase(self._tick, name, stats)

    def _tick_moe(self, block: np.ndarray) -> np.ndarray:
        """A block as read back, ``[rows (+ 5), K]``: with a grouped expert
        layer its last four rows are the block's expert counters
        (``[assignments, most one expert took, experts touched, rows of the
        live tiles, assignments routed over all experts]``, computed beside
        its tokens), which go into the tick record.  Returns the tokens,
        ``[rows, K]``."""
        if self._moe_counts:
            a, m, n, tr, routed = block[self.rows:, 0]
            self._tick["moe_routed"] += int(routed)
            self._tick["moe_assignments"] += int(a)
            self._tick["moe_expert_max"] = max(self._tick["moe_expert_max"],
                                               int(m))
            self._tick["moe_experts_touched"] += int(n)
            self._tick["moe_tile_rows"] += int(tr)
        return block[:self.rows]

    def _tick_block(self, mode: str, rows: int, k: int, ctx=()) -> None:
        """This tick ran a decode block (or speculative round); ``ctx``:
        the contexts its rows reach with it."""
        t = self._tick
        t["name"], t["mode"], t["rows"], t["k"] = "decode.block", mode, \
            rows, k
        if self._swa:
            ctx = list(ctx)
            t["ctx_positions"] += sum(ctx)
            t["swa_positions"] += sum(min(c, self._swa) for c in ctx)

    def _stamp_first(self, state: _Row) -> None:
        """``state``'s first token has reached the host."""
        state.t_first = time.perf_counter()
        state.first_tick = self._tick["tick"]

    @property
    def prefix_cache_active(self) -> bool:
        return self._pcache is not None

    @property
    def _pipelined(self) -> bool:
        """Pipelined decode is actually in effect (requested AND not
        bypassed)."""
        return self.pipeline_depth > 0 and \
            self.pipeline_bypass_reason is None

    @property
    def preemptible(self) -> bool:
        """Whether this batcher can SUSPEND a resident row (priority
        preemption, per-row drain migration): requires the same
        single-shard pool as the disaggregated export/import surface
        (a suspended request IS a KV export — a speculative batcher's
        export carries the draft pool's paired payload, so spec rows
        suspend like any other), and a host-synchronous decode loop —
        the pipelined mode carries in-flight device state the host
        view lags behind, so its rows cannot be snapshotted between
        blocks.  Non-preemptible batchers still honor
        :meth:`preempt_all`, by REQUEUEING every in-flight request
        (lossless through deterministic re-execution) instead of
        exporting it.  The gate IS the registry: ``suspend``'s
        bypass-reason entry (None = suspendable), so the audit test
        enumerates exactly when rows can be snapshotted."""
        return self.suspend_bypass_reason is None

    def fused_tokens_per_tick(self, n_decode: Optional[int] = None) -> int:
        """Tokens ONE device dispatch covers on a tick with ``n_decode``
        decoding rows (default: all rows).  Phase-split ticks dispatch
        only the decode block (the prefill chunk rides a SECOND call
        the decode rows stall behind); a fused tick packs the same
        block plus however many chunk slots the ``tokens_per_tick``
        budget leaves room for — floored at one slot, so a saturated
        decode set still makes prefill progress exactly like the
        phase-split tick did."""
        n = self.rows if n_decode is None else int(n_decode)
        dt = n * self.multi_step
        if not self._fused:
            return dt
        c = self.prefill_chunk
        return dt + max(1, (self.tokens_per_tick - dt) // c) * c

    def preempt_all(self) -> None:
        """Ask the serve loop to give back EVERY in-flight request as a
        :class:`Suspended` item on its next tick — the victim side of
        cross-replica drain migration: suspended artifacts re-placed on
        another replica (``submit(request, prefilled=artifact)``) resume
        token-identically; requests with no resumable state requeue with
        ``artifact=None``.  Thread-safe; a no-op until the serve loop
        runs (an idle loop processes it on its next submission)."""
        self._preempt_event.set()

    # -- online weight updates (adapter hot-swap / warm-pool adoption) ------

    def swap_adapter(self, delta: Dict[str, Any], version: str,
                     on_applied=None) -> None:
        """Fold a LoRA-style weight DELTA into the serving params with
        zero downtime: ``delta`` maps ``/``-joined param paths (e.g.
        ``"layers/wq"``) to arrays added onto the matching leaves.
        Validated NOW (unknown path / shape mismatch raises
        ``ValueError``); applied by the serve loop once every resident
        row has finished — new admissions wait behind the fence, so
        in-flight requests finish on the OLD delta and every stream is
        token-identical to an offline run under exactly one delta
        version.  ``version`` labels the resulting cumulative state
        (:attr:`adapter_version`); ``on_applied()`` fires from the
        serve loop after the fold (the replica replies to the control
        op from it).  On a batcher with no serve loop (prefill role,
        direct use) the fold applies synchronously."""
        if not isinstance(version, str) or not version:
            raise ValueError("adapter version must be a non-empty "
                             "string")
        resolved = self._resolve_delta(delta)
        self._queue_weight_update(("fold", resolved, version,
                                   on_applied))

    def set_weights(self, params, version: str = "",
                    on_applied=None) -> None:
        """Replace the FULL parameter tree (the warm-pool adoption
        path: a pre-warmed replica installs another model's weights —
        same config/shapes, so nothing recompiles).  Same fence and
        invalidation discipline as :meth:`swap_adapter`; ``version``
        feeds the KV tier's restamp so entries parked under the old
        weights read as version misses, never stale KV."""
        self._queue_weight_update(("set", params, str(version or ""),
                                   on_applied))

    def _resolve_delta(self, delta: Dict[str, Any]):
        """Validate a path->array delta against the live param tree;
        returns ``[(key_path_tuple, np_array), ...]``."""
        if not isinstance(delta, dict) or not delta:
            raise ValueError("adapter delta must be a non-empty dict "
                             "of param-path -> array")
        resolved = []
        for path in sorted(delta):
            keys = tuple(k for k in str(path).split("/") if k)
            node = self.params
            for k in keys:
                if not isinstance(node, dict) or k not in node:
                    raise ValueError(
                        f"adapter delta names unknown param path "
                        f"{path!r}")
                node = node[k]
            if not keys or isinstance(node, dict):
                # An empty path or an interior tree node is not a
                # foldable leaf — reject with the documented error,
                # not an AttributeError on .shape below.
                raise ValueError(
                    f"adapter delta path {path!r} does not name a "
                    f"param array (it is "
                    f"{'empty' if not keys else 'an interior node'})")
            arr = np.asarray(delta[path])
            if tuple(arr.shape) != tuple(node.shape):
                raise ValueError(
                    f"adapter delta shape mismatch at {path!r}: delta "
                    f"{tuple(arr.shape)} vs param {tuple(node.shape)}")
            resolved.append((keys, arr))
        return resolved

    def _queue_weight_update(self, update) -> None:
        with self._export_lock:
            if self._loop_active:
                # The serve loop owns the rows: it applies the update
                # at its next between-generations point; kick wakes an
                # idle-blocked loop so the apply never waits for
                # traffic.
                with self._weights_lock:
                    self._weight_updates.append(update)
                src = self._submissions
                if src is not None:
                    src.kick()
            else:
                # No loop (prefill role, direct export use): apply in
                # place, serialized against export_kv by the lock.
                self._apply_weight_update(update)

    def _apply_pending_weight_updates(self) -> None:
        while True:
            with self._weights_lock:
                if not self._weight_updates:
                    return
                update = self._weight_updates.popleft()
            self._apply_weight_update(update)

    def _apply_weight_update(self, update) -> None:
        kind, payload, version, cb = update
        if kind == "fold":
            new = self.params
            for keys, arr in payload:
                # Copy-on-write along the path only; the fold stays on
                # device for single-host batchers.
                node = new = dict(new)
                for k in keys[:-1]:
                    child = dict(node[k])
                    node[k] = child
                    node = child
                leaf = node[keys[-1]]
                node[keys[-1]] = leaf + jnp.asarray(arr).astype(
                    leaf.dtype)
            self.adapter_version = version
        else:
            new = payload
            self.adapter_version = ""
        if self.mesh is not None:
            from tfmesos_tpu.models.transformer import partition_specs
            new = self._place(new, partition_specs(self.cfg, self.mesh))
        self.params = new
        # The weights changed: every cached KV artifact computed under
        # the old ones is now WRONG for new decodes.  Flush the prefix
        # trie (no spill — stale pages must not enter the tier) and
        # restamp the KV tier so parked sessions/spilled pages from
        # before the update read as version misses (cold re-prefill,
        # never a silently wrong stream).
        if self._pcache is not None:
            self._pcache.clear()
        if self.kv_tier is not None \
                and self.kv_tier_bypass_reason is None:
            restamp = getattr(self.kv_tier, "restamp", None)
            if restamp is not None:
                if kind == "fold":
                    restamp(adapter=version)
                else:
                    restamp(weights_version=version or None, adapter="")
        self.weight_swaps += 1
        hook = self.on_weights_applied
        if hook is not None:
            try:
                hook(kind, version)
            except Exception:
                pass    # observer hook: never costs the update
        if cb is not None:
            try:
                cb()
            except Exception:
                pass    # a broken waiter costs its reply, not the loop

    def prefix_cache_stats(self) -> Optional[Dict[str, int]]:
        """Hit/miss/eviction counters plus current occupancy of the
        cross-request prefix cache (None when disabled or bypassed).
        Thread-safe — the replica heartbeat reads it live."""
        return None if self._pcache is None else self._pcache.stats()

    def prefix_cache_summary(self,
                             max_entries: int = 64) -> Optional[dict]:
        """Wire-facing summary of what the prefix cache holds (chunk
        geometry + recent chain digests) — piggybacked on registry
        heartbeats so the fleet router can steer shared-prefix traffic
        here (prefix-affinity routing).  None when disabled."""
        return (None if self._pcache is None
                else self._pcache.summary(max_entries))

    @property
    def acceptance_rate(self) -> Optional[float]:
        """Fraction of DRAFT proposals accepted: every row-round commits
        its accepted run plus exactly one non-draft token (the
        correction, or the bonus after a full accept), so accepted
        drafts = committed - row_rounds over row_rounds x n_draft
        opportunities.  1.0 = every proposal accepted (perfect draft);
        0.0 = the draft never helped; None before any speculative round
        ran (or without a draft)."""
        if self.d_side is None or not self.spec_row_rounds:
            return None
        return ((self.spec_committed - self.spec_row_rounds)
                / (self.spec_row_rounds * self.n_draft))

    # Back-compat accessors: the paged-side refactor (draft paging) moved
    # the target pool's state into ``t_side``; callers and tests keep the
    # original names.
    @property
    def pool(self):
        return self.t_side.pool

    @pool.setter
    def pool(self, v):
        self.t_side.pool = v

    @property
    def alloc(self) -> _ShardedAlloc:
        return self.t_side.alloc

    @property
    def peak_pages_used(self) -> int:
        return self.t_side.peak

    @property
    def _sink_page(self) -> int:
        return self.t_side.sink

    def _place(self, tree, specs):
        """Place ``tree`` onto the mesh per a PartitionSpec tree —
        through ``place_tree`` so host-identical values assemble into
        global arrays even when the mesh spans processes."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tfmesos_tpu.parallel.sharding import place_tree
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda n: isinstance(n, P))
        return place_tree(self.mesh, tree, shardings)

    def _init_side_device_state(self, side: _PagedSide, cfg,
                                quantized: bool = False) -> None:
        """Mesh mode: place the side's pool per ``paged_cache_specs`` and
        build its shard-aware copy-on-write page-copy fn (each shard
        copies the — symmetrically reserved — template page onto its own
        slot of a per-shard destination vector; shards not admitting the
        row scribble their sink).  Single-host mode keeps the plain
        module-level copy."""
        if self.mesh is None:
            side.copy = (lambda pool, src, dst:
                         _copy_page(pool, int(src), int(dst[0])))
            return
        from jax.sharding import PartitionSpec as P
        from tfmesos_tpu.models.transformer import paged_cache_specs
        from tfmesos_tpu.parallel.sharding import data_axes
        specs = paged_cache_specs(cfg, self.mesh, quantized=quantized)
        side.pool = self._place(side.pool, specs)
        mesh = self.mesh
        da = data_axes(mesh)

        @partial(jax.jit, donate_argnums=0)
        def copy(pool, src, dst):
            def local(pool, src, dst):
                return jax.tree_util.tree_map(
                    lambda buf: buf.at[:, dst[0]].set(buf[:, src[0]]),
                    pool)
            return shard_map(local, mesh=mesh,
                             in_specs=(specs, P(), P(da)),
                             out_specs=specs, check_vma=False)(
                pool, src, dst)

        side.copy = (lambda pool, src, dst, _c=copy:
                     _c(pool, jnp.asarray([src], jnp.int32),
                        jnp.asarray(dst, jnp.int32)))

    # -- compiled shapes --------------------------------------------------

    def _host_read(self, x):
        """Replicate a jit output the HOST loop reads (tokens, commit
        counts): on a (possibly multi-process) mesh a sharded global
        array is not fully addressable from every host, and the loop
        must see identical values on every process."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P()))

    @jax.named_scope("sampling")
    def _sample(self, last, rids, steps):
        """[n, V] logits -> [n] int32 tokens; sampling keys are folded
        in-graph per (rid, step) so the host loop never dispatches
        per-row fold_ins and either PRNG key flavor works."""
        if self.temperature <= 0.0:
            return jnp.argmax(last.astype(jnp.float32), axis=-1).astype(
                jnp.int32)

        def one(l, r, s):
            key = jax.random.fold_in(jax.random.fold_in(self._rng, r), s)
            return sample_logits(l, key, self.temperature, self.top_k,
                                 self.top_p)

        return jax.vmap(one)(last, rids, steps)

    def _make_decode(self, K: Optional[int] = None):
        """K decode steps fused into ONE dispatch (``lax.scan``): the host
        syncs a [rows, K] token block instead of one [rows] vector per
        token, so the per-dispatch + device-to-host round-trip cost
        amortizes over K tokens.  Stops and quota
        endings are detected at block granularity: in-block steps past a
        row's end compute garbage the host discards, and their cache
        writes land either inside the row's reservation-clamped own
        pages or on sink columns (the ensure() clamp at ``_Row.limit``
        guarantees allocations never exceed the admission reservation).
        Token streams are IDENTICAL across K: the scan body runs the
        same decode_step + per-(rid, step)-folded sample ops in the same
        order, only the host sync point moves.  ``multi_step=1`` is the
        classic per-token tick (a length-1 scan)."""
        sharded = self.mesh is not None
        K = self.multi_step if K is None else K
        max_len = self.max_len

        moe_counts = self._moe_counts

        def block(params, pool, table, tok0, positions, rids, steps):
            def body(carry, _):
                pool, tok, pos, stp = carry
                cache = dict(pool, pages=table)
                logits, cache = decode_step(
                    self.cfg, params, cache, tok[:, None],
                    jnp.minimum(pos, max_len), sharded=sharded,
                    mesh=self.mesh)
                nxt = self._sample(logits[:, -1], rids, stp)
                out = ((nxt, cache["expert_counts"]) if moe_counts
                       else nxt)
                return (_pool_leaves(cache), nxt, pos + 1, stp + 1), out

            (pool, _, _, _), toks_all = jax.lax.scan(
                body, (pool, tok0, positions, steps), None, length=K)
            if moe_counts:
                # [assignments on held experts, the most one expert took,
                # (step, layer, expert)s that took any, the rows of the
                # tiles they filled (``grouped_layout`` pads each expert's
                # to whole tiles), the assignments the routers made over all
                # ``n_experts``: every row's ``top_k`` a step and expert
                # layer, wherever they fell] over the block, as five rows
                # under the tokens: ONE array comes back to the
                # host (a second one is a second round trip every tick)
                toks_all, counts = toks_all             # [K, L, held]
                touched = jnp.sum(counts > 0)
                tile_rows = moe_tile_rows(counts, self.rows, self.cfg.top_k,
                                          self.cfg.n_experts)
                routed = (counts.shape[0] * counts.shape[1] * self.rows
                          * self.cfg.top_k)
                counts = jnp.sum(counts, axis=0)
                stats = jnp.stack([jnp.sum(counts), jnp.max(counts),
                                   touched, tile_rows,
                                   routed]).astype(jnp.int32)
                return pool, jnp.concatenate(
                    [toks_all.T, jnp.broadcast_to(stats[:, None], (5, K))])
            return pool, toks_all.T                         # [rows, K]

        if self._pipelined:
            # Device-resident pipelined blocks: tokens, positions, AND
            # steps ride the carry the previous dispatch returned, so a
            # steady-state block uploads NOTHING — the host merges fresh
            # admissions in via ``use_host`` (a cached device constant
            # while the dispatch set is unchanged; rows outside the
            # dispatch enter through it too, as zeros) and reads block
            # N's tokens one block behind.  Carries clamp at max_len + K;
            # live rows never reach the clamp (their reservations cap pos
            # at max_len).
            @partial(jax.jit, donate_argnums=1)
            def decode_block_pipelined(params, pool, table, use_host, toks,
                                       positions, steps, carry_tok,
                                       carry_pos, carry_steps, rids):
                tok0 = jnp.where(use_host, toks, carry_tok)
                pos0 = jnp.where(use_host, positions, carry_pos)
                stp0 = jnp.where(use_host, steps, carry_steps)
                pool, out = block(params, pool, table, tok0, pos0, rids,
                                  stp0)
                cap = max_len + K
                return (pool, self._host_read(out), out[:self.rows, -1],
                        jnp.minimum(pos0 + K, cap),
                        jnp.minimum(stp0 + K, cap))

            return decode_block_pipelined

        @partial(jax.jit, donate_argnums=1)
        def decode_block(params, pool, table, toks, positions, rids, steps):
            pool, out = block(params, pool, table, toks, positions, rids,
                              steps)
            return pool, self._host_read(out)

        return decode_block

    def _make_spec_round(self):
        """Jitted speculative round: k batched draft steps over the
        draft's OWN paged pool (its page table fixed across the scan —
        the caller pre-ensures pages for the round's writes), then one
        ragged (k+1)-token target verify over the target pool.  Returns
        the commit candidates [rows, k+1] and each row's commit count.

        Greedy (temperature 0): candidates are the target's greedy
        tokens, count = leading draft==target run + 1.  Sampling:
        Leviathan rejection — proposal j draws with key fold(rid,
        step+j) (the SAME stream the non-speculative batcher uses, so a
        perfect draft reproduces its proposals), acceptance uses an
        independent salted fold, and the correction/bonus at the
        rejection index draws from norm(max(0, pt − pd)) with another
        salted fold — every draw a pure function of (rid, token index),
        hence invariant to row packing."""
        k = self.n_draft
        T, tk_, tp_ = self.temperature, self.top_k, self.top_p
        sharded = self.mesh is not None
        sampling = T > 0.0
        if sampling:
            from tfmesos_tpu.models.transformer import filter_logits

        def keyf(rid, s):
            return jax.random.fold_in(jax.random.fold_in(self._rng, rid),
                                      s)

        def body(params, pool, dparams, dpool, table, dtable, toks,
                 positions, rids, steps):
            b = toks.shape[0]

            def dstep(carry, j):
                dc, dtok, dpos = carry
                lg, dc = decode_step(self.draft_cfg, dparams,
                                     dict(dc, pages=dtable),
                                     dtok[:, None], dpos,
                                     sharded=sharded, mesh=self.mesh)
                dc = {"k": dc["k"], "v": dc["v"]}
                if not sampling:
                    nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
                    return (dc, nxt, dpos + 1), (nxt, jnp.zeros(()))
                f = filter_logits(lg[:, -1], T, tk_, tp_)
                nxt = jax.vmap(
                    lambda fr, r, s: jax.random.categorical(
                        keyf(r, s + j), fr).astype(jnp.int32))(
                    f, rids, steps)
                return (dc, nxt, dpos + 1), (nxt, jax.nn.softmax(f, -1))

            # k+1 steps: the extra step writes the LAST proposal's K/V
            # at pos+k (its proposal is discarded) — otherwise a fully
            # accepted round advances past pos+k with that draft-cache
            # slot never written, and the draft conditions on a hole for
            # the rest of the request (silent acceptance-rate decay on
            # exactly the requests where the draft is best).
            (dpool, _, _), (drafts, pd) = jax.lax.scan(
                dstep, ({"k": dpool["k"], "v": dpool["v"]}, toks,
                        positions),
                jnp.arange(k + 1, dtype=jnp.int32))
            drafts = jnp.moveaxis(drafts, 0, 1)[:, :k]      # [rows, k]
            chunk = jnp.concatenate([toks[:, None], drafts], axis=1)
            cache = dict(pool, pages=table)
            lg, cache = decode_step(self.cfg, params, cache, chunk,
                                    positions, sharded=sharded,
                                    mesh=self.mesh)
            pool_out = {"k": cache["k"], "v": cache["v"]}
            if not sampling:
                g = jnp.argmax(lg, -1).astype(jnp.int32)    # [rows, k+1]
                return pool_out, dpool, g, greedy_accept_counts(drafts, g)

            pd = jnp.moveaxis(pd, 0, 1)[:, :k]              # [rows, k, V]
            pt = jax.nn.softmax(filter_logits(lg, T, tk_, tp_), -1)
            u = jax.vmap(lambda r, s: jax.vmap(
                lambda j: jax.random.uniform(
                    jax.random.fold_in(keyf(r, s + j), 1)))(
                jnp.arange(k, dtype=jnp.int32)))(rids, steps)
            # Accept/correct via the shared rejection math
            # (transformer.rejection_accept — same code path
            # speculative_generate's sampling_round runs).
            a, dist = rejection_accept(drafts, pd, pt, u)
            repl = jax.vmap(
                lambda dr, r, s, ar: jax.random.categorical(
                    jax.random.fold_in(keyf(r, s + ar), 2),
                    jnp.log(dr + 1e-20)).astype(jnp.int32))(
                dist, rids, steps, a)
            j = jnp.arange(k + 1, dtype=jnp.int32)[None]
            cand = jnp.concatenate(
                [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
            vals = jnp.where(j == a[:, None], repl[:, None], cand)
            return pool_out, dpool, vals, a + 1

        # multi_step>1 composes with speculation as R =
        # ceil(multi_step/(k+1)) rounds fused in ONE dispatch: each
        # round chains from the previous round's last-committed
        # token/positions IN-GRAPH (take_along_axis over its commit
        # counts), so the host syncs once per R rounds.  Rows that
        # finish (stop/quota) mid-dispatch keep executing later rounds
        # on device; their writes land on sink-clamped table columns and
        # the host discards their tokens at commit — the same overrun
        # argument the plain multi_step path documents at _worst_pages.
        R = max(1, self._spec_rounds)

        @partial(jax.jit, donate_argnums=(1, 3))
        def spec_round(params, pool, dparams, dpool, table, dtable,
                       toks, positions, rids, steps):
            if R == 1:
                pool_out, dpool_out, g, counts = body(
                    params, pool, dparams, dpool, table, dtable,
                    toks, positions, rids, steps)
                return (pool_out, dpool_out, self._host_read(g),
                        self._host_read(counts))
            gs, ns = [], []
            for _ in range(R):
                pool, dpool, g, counts = body(
                    params, pool, dparams, dpool, table, dtable,
                    toks, positions, rids, steps)
                gs.append(g)
                ns.append(counts)
                last = jnp.maximum(counts - 1, 0)
                toks = jnp.take_along_axis(
                    g, last[:, None], axis=1)[:, 0]
                positions = positions + counts
                steps = steps + counts
            # [R, rows, k+1] / [R, rows] — _step_spec commits
            # round-by-round so quota/stop truncation stays exact.
            return (pool, dpool, self._host_read(jnp.stack(gs)),
                    self._host_read(jnp.stack(ns)))

        return spec_round

    def _make_draft_chunk(self):
        """Jitted DRAFT prompt writer over the draft's paged pool: serves
        both the whole-prompt prefill (offset 0) and chunked prefill's
        per-chunk advance.  The caller passes a one-hot
        n_shards-row batch (``_one_hot_call``); one compile per chunk
        width."""
        sharded = self.mesh is not None

        @partial(jax.jit, donate_argnums=1)
        def draft_chunk(dparams, dpool, t, chunk, pos):
            cache = dict(dpool, pages=t)
            _, cache = decode_step(self.draft_cfg, dparams, cache, chunk,
                                   pos, sharded=sharded, mesh=self.mesh)
            return {"k": cache["k"], "v": cache["v"]}

        return draft_chunk

    def _make_eva_roll(self):
        """The program that closes one row's window (``jit_eva_roll``:
        a module of its own in a device trace): its exact entries pooled
        into summaries, in place (``transformer.eva_close_window``)."""
        from tfmesos_tpu.models.transformer import eva_close_window

        @partial(jax.jit, donate_argnums=1)
        def eva_roll(params, pool, table, window):
            return eva_close_window(self.cfg, params, pool, table, window)

        return eva_roll

    def _block_of(self, rows) -> tuple:
        """``(K, program)`` of a decode block over ``rows``: ``multi_step``
        steps, but single steps (``_decode1``) where an EVA window ends
        inside the block for some row, until it has been closed (streams
        do not depend on K).  In the pipelined loop the positions are the
        dispatched ones, and either program takes the other's carry."""
        K, w = self.multi_step, self.cfg.eva_window
        if self._eva_roll is not None and K > 1 and any(
                row.pos % w + K > w for row in rows):
            return 1, self._decode1
        return K, self._decode

    def _eva_roll_row(self, row: int, window: int) -> None:
        """Close ``row``'s window ``window`` (all of its positions are in
        the pool): dispatch the pooling, then hand the pages behind the
        new summaries back to the allocator — in this tick."""
        side = self.t_side
        self.pool = self._eva_roll(
            self.params, self.pool,
            jnp.asarray(side.table_np()[row:row + 1]),
            jnp.asarray(window, jnp.int32))
        side.trim(row, (window + 1) * self.cfg.eva_summaries)
        self.eva_rolls += 1
        self._tick["eva_rolls"] += 1

    def _eva_account(self, active: Dict[int, "_Row"]) -> None:
        """For the tick record: the entries the live rows hold, by kind
        (from their positions), and the pages the allocator holds for
        them (pages that ``trim`` left behind would show as
        ``eva_pages * page_size`` running away from the entries)."""
        w, s_ent = self.cfg.eva_window, self.cfg.eva_summaries
        self._eva_live = (
            sum(row.pos // w * s_ent for row in active.values()),
            sum(row.pos % w for row in active.values()),
            sum(self.alloc.allocated(r) for r in active))

    def _one_hot_call(self, side: _PagedSide, row: int, chunk: np.ndarray):
        """(shard, [nd, w] tokens, [nd, np] table) for a per-row model
        call batched one row per mesh data shard: the admitted row's
        tokens and table ride its shard's slot; every other shard's slot
        is an all-sink dummy whose writes land on that shard's sink page
        (and whose sampled token is discarded).  With one shard this is
        exactly the old single-row call."""
        nd = self.n_shards
        s = side.alloc.shard_of(row)
        table = np.full((nd, side.np_max), side.sink, np.int32)
        table[s] = side.table_np()[row]
        toks = np.zeros((nd, chunk.shape[1]), np.int32)
        toks[s] = chunk[0]
        return s, jnp.asarray(toks), jnp.asarray(table)

    def _make_chunk_prefill(self):
        """Jitted one-chunk prefill: writes chunk tokens at a TRACED
        offset (so one compile serves every chunk of every request) and
        samples the first token when this chunk contains the prompt's
        last position (cap_idx in range; callers ignore it otherwise).
        Batched one row per mesh data shard (``_one_hot_call``); returns
        the [nd] sampled-token vector, the caller indexes its shard."""
        sharded = self.mesh is not None

        @partial(jax.jit, donate_argnums=1)
        def chunk_prefill(params, pool, table, chunk, pos, cap_idx, rid):
            cache = dict(pool, pages=table)
            logits, cache = decode_step(self.cfg, params, cache, chunk,
                                        pos, sharded=sharded,
                                        mesh=self.mesh)
            cap = jnp.clip(cap_idx, 0, chunk.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, cap[:, None, None], axis=1)[:, 0]
            nxt = self._sample(last, rid, jnp.zeros_like(rid))
            return {"k": cache["k"], "v": cache["v"]}, self._host_read(nxt)

        return chunk_prefill

    def _make_fused_step(self):
        """ONE jitted program per tick over the ragged [decode rows |
        prefill chunk slots] layout: a budgeted batch of chunk slots
        (each slot = one still-filling row's next ``prefill_chunk``
        tokens at its own traced offset — the SAME chunk-writer ops
        :meth:`_make_chunk_prefill` runs, batched [S, c] instead of
        one-hot) followed by the decode block's K-step scan, threading
        one donated pool through both.  Decode rows therefore never
        stall behind a separate chunk dispatch, and the host syncs ONE
        result per tick ([rows, K] decode tokens + [S] first-token
        samples) instead of two.  Slot writes land on each slot row's
        own pages (dummy slots: all-sink tables, sampled token
        discarded), decode writes behave exactly as in
        :meth:`_make_decode` — same ops, same (rid, step) sample folds,
        so token streams are identical to the phase-split tick.  One
        compile per (decode table width, slot-count bucket) pair."""
        sharded = self.mesh is not None
        K = self.multi_step
        max_len = self.max_len

        @partial(jax.jit, donate_argnums=1)
        def fused_tick(params, pool, table, toks, positions, rids, steps,
                       ctable, chunks, cpos, caps, crids):
            # Chunk slots first (mirroring the phase-split tick's
            # chunk-then-block order — the sets touch disjoint pages,
            # but the donated pool threads through in program order).
            cache = dict(pool, pages=ctable)
            logits, cache = decode_step(self.cfg, params, cache, chunks,
                                        cpos, sharded=sharded,
                                        mesh=self.mesh)
            pool = {"k": cache["k"], "v": cache["v"]}
            cap = jnp.clip(caps, 0, chunks.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, cap[:, None, None], axis=1)[:, 0]
            first = self._sample(last, crids, jnp.zeros_like(crids))

            def body(carry, _):
                pool, tok, pos, stp = carry
                cache = dict(pool, pages=table)
                lg, cache = decode_step(
                    self.cfg, params, cache, tok[:, None],
                    jnp.minimum(pos, max_len), sharded=sharded,
                    mesh=self.mesh)
                nxt = self._sample(lg[:, -1], rids, stp)
                pool = {"k": cache["k"], "v": cache["v"]}
                return (pool, nxt, pos + 1, stp + 1), nxt

            (pool, _, _, _), toks_all = jax.lax.scan(
                body, (pool, toks, positions, steps), None, length=K)
            return (pool, self._host_read(toks_all.T),
                    self._host_read(first))

        return fused_tick

    def _fused_slot_buckets(self) -> List[int]:
        """Every chunk-slot count the fused dispatch can pad to (powers
        of two up to ``rows`` — at most ``rows`` rows can be filling),
        for warmup and the live dispatch's shared bucketing."""
        return sorted({self._pow2(s) for s in range(1, self.rows + 1)})

    def _prefill_fn(self, width: int):
        """Jitted prefill at one padded-width bucket, batched one row per
        mesh data shard (``_one_hot_call``)."""
        if width not in self._prefill_fns and self._eva_roll is not None:
            # EVA: one program per padded width up to a window; ``start``
            # (traced) is the position of the window the chunk begins, so
            # a long prompt is whole windows of the widest program and
            # one tail, whatever max_len is.
            @partial(jax.jit, donate_argnums=1)
            def prefill(params, pool, table, prompt, length, rid, start):
                cache = dict(pool, pages=table)
                logits, cache = decode_step(self.cfg, params, cache, prompt,
                                            start)
                last = jnp.take_along_axis(
                    logits, (length - 1)[:, None, None], axis=1)[:, 0]
                nxt = self._sample(last, rid, jnp.zeros_like(rid))
                return {"k": cache["k"], "v": cache["v"]}, nxt

            self._prefill_fns[width] = prefill
        if width not in self._prefill_fns and self.cfg.layer_types is not None:
            # A typed stack: the prompt whole, from position 0 and an EMPTY
            # row state whatever the slot held; ``slot`` is the row slot
            # the final state is written to, ``length`` keeps the bucket's
            # padding out of it, and the head runs at the last real
            # position only.
            @partial(jax.jit, donate_argnums=1)
            def prefill(params, pool, table, prompt, length, rid, slot):
                cache = dict(pool, pages=table, slots=slot, valid=length)
                logits, cache = decode_step(self.cfg, params, cache, prompt,
                                            0)
                nxt = self._sample(logits[:, 0], rid, jnp.zeros_like(rid))
                return _pool_leaves(cache), nxt

            self._prefill_fns[width] = prefill
        if width not in self._prefill_fns:
            sharded = self.mesh is not None

            @partial(jax.jit, donate_argnums=1)
            def prefill(params, pool, table, prompt, length, rid):
                cache = dict(pool, pages=table)
                logits, cache = decode_step(self.cfg, params, cache, prompt,
                                            0, sharded=sharded,
                                            mesh=self.mesh)
                last = jnp.take_along_axis(
                    logits, (length - 1)[:, None, None], axis=1)[:, 0]
                nxt = self._sample(last, rid, jnp.zeros_like(rid))
                return {"k": cache["k"], "v": cache["v"]}, \
                    self._host_read(nxt)

            self._prefill_fns[width] = prefill
        return self._prefill_fns[width]

    # -- host-side bookkeeping --------------------------------------------

    def _worst_pages(self, req: Request) -> tuple:
        """Worst-case OWN pages, per side, plus the absolute position
        cap the reservation covers:
        ``(target, draft, need_len)`` (draft 0 without speculative
        mode)."""
        width = -(-req.prompt.size // self.prefill_bucket) * \
            self.prefill_bucket
        need_len = max(width, req.prompt.size + req.max_new_tokens - 1)
        if self.draft_cfg is not None:
            # A speculative round at the final position still verifies a
            # (k+1)-token chunk: its writes overshoot by up to n_draft
            # (and the draft's k+1 scan steps write the same positions).
            need_len += self.n_draft
        # The lagged carry reserves nothing more.  A stop is detected one
        # block late, but the overshoot block is one the quota had let
        # through at dispatch (``row.step < max_new_tokens``): it writes
        # where the same request without a stop token writes.  With
        # multi_step > 1 a block may overrun a quota by up to K-1
        # positions, stop token or not — those are NOT reserved either:
        # the ensure() clamp at _Row.limit keeps allocations within this
        # reservation, and writes past it land on sink columns.
        if need_len > self.max_len:
            raise ValueError(
                f"request needs {need_len} cache positions (prompt "
                f"{req.prompt.size} padded to {width}, plus "
                f"{req.max_new_tokens} new tokens) > "
                f"max_len ({self.max_len})")
        # pages by the ENTRIES the context can hold on its way to need_len
        # (EVA: summaries and one window; otherwise one per position)
        wt = -(-self.cfg.cache_entries_peak(0, need_len) // self.page_size)
        wd = 0
        if self.d_side is not None:
            wd = -(-need_len // self.page_size)
        return wt, wd, need_len

    def _req_digests(self, req: Request) -> list:
        """Chain digests of ``req``'s complete page-aligned prompt
        chunks (memoized on the request, keyed by the page size so a
        request replayed into a differently-paged batcher rehashes —
        without the memo, a request waiting for a row would rehash its
        prompt every admission tick)."""
        ps = self.page_size
        memo = getattr(req, "_pfx_digests", None)
        if memo is None or memo[0] != ps:
            memo = (ps, _ph.prompt_digests(req.prompt, ps))
            req._pfx_digests = memo
        return memo[1]

    def _prefix_plan(self, req: Request, shard: int,
                     max_nodes: Optional[int] = None
                     ) -> Optional[_PrefixPlan]:
        """The longest USABLE cached prefix for ``req`` on ``shard``
        (capped at ``max_nodes`` — _admit_row retries shallower when a
        deep plan doesn't fit the shard's headroom): the trie match,
        trimmed until the uncached tail's padded prefill window fits
        inside the page table (``np_max * page_size`` positions — the
        allocation itself is clamped at the reservation by
        _admit_cached's ensure, and pad writes past it land in
        reserved-but-unread positions or on sink columns, exactly like
        the cold path's prompt padding) and, in chunked mode, starts on
        the chunk grid.  A page-aligned full hit keeps its deepest page
        and marks it COW: the one-token logits chunk rewrites position
        E-1 inside a private copy."""
        digs = self._req_digests(req)
        if not digs:
            return None
        nodes = self._pcache.match(shard, digs)
        if not nodes:
            return None
        E = int(req.prompt.size)
        ps, bucket = self.page_size, self.prefill_bucket
        n = len(nodes)
        if max_nodes is not None:
            n = min(n, max_nodes)
            if not n:
                return None
        if self.prefill_chunk is not None:
            c = self.prefill_chunk
            while n and (n * ps > E - 1 or (n * ps) % c):
                n -= 1
            return _PrefixPlan(nodes[:n], False, n * ps) if n else None
        while n:
            cow = n * ps >= E
            ts = E - 1 if cow else n * ps
            w = -(-(E - ts) // bucket) * bucket
            if ts + w <= self.np_max * ps:
                return _PrefixPlan(nodes[:n], cow, ts)
            n -= 1
        return None

    def _admit_row(self, free_rows: List[int], active: Dict[int, _Row],
                   wt: int, wd: int, req: Request,
                   use_cache: bool = True) -> tuple:
        """Pop a free row whose shard's pool(s) can take both worst-case
        reservations, preferring the shard with the longest cached
        prefix for ``req`` (pages are shard-pinned, so a hit is only a
        hit on its own shard), then the most target headroom (load
        balance across mesh data shards; with one shard and no cache
        this is just a headroom check).  Returns ``(row, plan)``;
        ``(None, None)`` means wait for in-flight rows to release
        pages.  Raises when some free row's shard has NO in-flight work
        and still can't fit — waiting would deadlock."""
        best = None
        empty_shard = None
        by_shard: Dict[int, tuple] = {}     # s -> (ok, headroom, plan)
        for i, r in enumerate(free_rows):
            s = self.t_side.alloc.shard_of(r)
            if s not in by_shard:        # headroom is a per-SHARD fact
                ht = self.t_side.headroom(active,
                                          lambda x: x.worst_pages, s)
                plan = (self._prefix_plan(req, s)
                        if self._pcache is not None and use_cache
                        else None)
                hd = (self.d_side.headroom(
                          active, lambda x: x.worst_draft, s)
                      if self.d_side is not None else None)
                while True:
                    save = plan.save if plan is not None else 0
                    zref = (sum(1 for n in plan.nodes if n.ref == 0)
                            if plan is not None else 0)
                    # headroom() counts zero-ref cached pages as
                    # reclaimable, but accepting THIS plan references
                    # its nodes — they can no longer be evicted to
                    # satisfy the same admission.  Discounting wt by
                    # plan.save AND counting those pages reclaimable
                    # would double-count them and over-admit (a "page
                    # pool exhausted" crash out of the serve loop,
                    # exactly what reservations exist to prevent).
                    ok = (wt - save) <= ht - zref
                    if ok and hd is not None:
                        # Twin-pool plans save the SAME page count on
                        # the draft side (coupled nodes), and the same
                        # zero-ref double-count adjustment applies.
                        ok = (wd - save) <= hd - zref
                    if ok or plan is None:
                        break
                    # A deep plan that doesn't fit (the COW full hit
                    # needs a fresh copy page ON TOP of referencing
                    # every reclaimable cached page) must not condemn
                    # the request: retry shallower — down to the plain
                    # cold admission, which evicts the unused cached
                    # pages on demand.
                    depth = len(plan.nodes) - 1
                    plan = (self._prefix_plan(req, s, max_nodes=depth)
                            if depth else None)
                by_shard[s] = (ok, ht, plan)
            ok, ht, plan = by_shard[s]
            if ok:
                key = (plan.save if plan is not None else 0, ht)
                if best is None or key > best[1]:
                    best = (i, key, plan)
            elif not any(self.t_side.alloc.shard_of(rr) == s
                         for rr in active):
                empty_shard = s
        if best is not None:
            return free_rows.pop(best[0]), best[2]
        if empty_shard is not None:
            s = empty_shard
            free_t = self.t_side.alloc.free_count(s)
            if self._pcache is not None:
                free_t += self._pcache.reclaimable(s)
            free_d = (0 if self.d_side is None
                      else self.d_side.alloc.free_count(s))
            raise RuntimeError(
                f"request needs {wt} target pages (+ {wd} draft) but "
                f"shard {s} only has {free_t} target / {free_d} draft "
                f"free with nothing in flight to wait for — raise "
                f"n_pages")
        return None, None

    # -- incremental (online) submission ----------------------------------

    def validate(self, req) -> None:
        """Raise ``ValueError`` if ``req`` (a :class:`Request` or
        :class:`Prefilled`) can never be served by this batcher
        (prefix + padded prompt + new tokens exceed max_len; for an
        import, an artifact whose geometry does not match this pool).
        Online front doors call this at ingress so an un-servable
        request is rejected immediately instead of via run()'s
        drain-then-raise path."""
        if isinstance(req, Prefilled):
            self._check_disagg_mode("importing a KV artifact")
            self._worst_pages(req.request)
            self._validate_artifact(req.artifact, req.request)
            return
        self._worst_pages(req)

    # -- ahead-of-time warmup ----------------------------------------------

    def _decode_widths(self) -> List[int]:
        """Every table width ``bucket_width`` can hand the batched
        step — one jit trace each.  Derived by enumerating occupancies
        through the SAME ``_PagedSide.width_for`` the live dispatch
        buckets with, so warmup can never drift from the widths the
        serve loop actually requests."""
        np_max = self.t_side.np_max
        return sorted({_PagedSide.width_for(occ, np_max)
                       for occ in range(1, np_max + 1)})

    def _prefill_widths(self) -> List[int]:
        """Every padded prompt width non-chunked admission can dispatch:
        ``_admit_dispatch`` pads prompts to multiples of
        ``prefill_bucket``, and ``_worst_pages`` admits only widths
        whose reservation (``width`` at minimum) fits ``max_len`` — one
        jit trace each, mirroring the linear ``_prefill_fns`` cache the
        live path fills lazily.  (Chunked
        mode has ONE chunk width and doesn't use this.)"""
        b = self.prefill_bucket
        cap = (self.max_len // b) * b
        if self._eva_roll is not None:
            cap = min(cap, self.cfg.eva_window)     # windows, then a tail
        return list(range(b, cap + 1, b)) or [b]

    def warmup(self, decode: bool = True,
               prefill: bool = True) -> Dict[str, Any]:
        """Compile every jitted entry point this batcher's serving mode
        dispatches — admission prefill at every reachable padded prompt
        width (or the single chunked/tail prefill writer), the batched
        decode block at every bucketed table width (or the speculative
        round + draft chunk writer with a draft), and the disaggregated
        KV export/import scatter where the mode supports it — against
        dummy all-sink shapes, and block until the executables are
        built.  ``decode=False`` skips the per-width decode/spec-round
        blocks: a prefill-ROLE fleet replica never decodes, and
        compiling log2(np_max) executables it cannot dispatch would
        only lengthen its warming window on every elastic relaunch.
        ``prefill=False`` is the mirror for decode-ROLE replicas —
        they only import exported KV (rows enter decode directly;
        plain generates route to the unified tier), so the per-width
        prefill/tail/draft-chunk compiles are skipped the same way.

        Every write a warmup call dispatches lands on the sink page
        (the table is all-sink), so no live row or prefix-cache state
        is touched: a warmed batcher's outputs are
        bit-identical to a cold one's.  Call at boot, before
        :meth:`serve`/:meth:`run` — moving first-request compilation
        off the serving path is what the fleet's ``warming`` replica
        state exists for (a replica only advertises itself routable
        once this returns).  Coverage is every first-request shape the
        configured mode can dispatch (a mixed spec table-width pair can
        still compile lazily); non-chunked prefill has one trace per
        reachable width, so a long-``max_len`` pool that cares about
        warmup time should serve with ``prefill_chunk`` (one trace).

        Returns ``{"compiled": [...], "seconds": float}``."""
        t0 = time.perf_counter()
        compiled: List[str] = []
        with self._export_lock:
            if self._loop_active:
                raise RuntimeError(
                    "warmup() cannot run while the batcher's serve loop "
                    "is active — warm at boot, before serve()/run()")
            nd = self.n_shards
            zrow = jnp.asarray(np.zeros((nd,), np.int32))

            def sink_table(side):
                return jnp.asarray(np.full((nd, side.np_max), side.sink,
                                           np.int32))

            eva = self._eva_roll is not None
            # a window's start (EVA), or the row slot a typed stack's
            # prefill fills (slot 0: no row is live during a warm-up)
            more = ([jnp.asarray(0, jnp.int32)] if eva
                    else [zrow] if self.cfg.layer_types is not None else [])
            if prefill and self._chunk_prefill is None:
                for w in self._prefill_widths():
                    self.pool, tok = self._prefill_fn(w)(
                        self.params, self.pool, sink_table(self.t_side),
                        jnp.asarray(np.zeros((nd, w), np.int32)),
                        jnp.asarray(np.ones((nd,), np.int32)), zrow, *more)
                    np.asarray(tok)
                    compiled.append(f"prefill[{w}]")
            if eva and self.max_len >= self.cfg.eva_window:
                self.pool = self._eva_roll(
                    self.params, self.pool, sink_table(self.t_side),
                    jnp.asarray(0, jnp.int32))
                jax.block_until_ready(self.pool)
                compiled.append("eva_roll")
            cfn = self._chunk_prefill or self._tail_prefill
            if prefill and cfn is not None:
                # The chunk loop always feeds the fixed chunk width,
                # but the prefix-cache TAIL path dispatches this same
                # callable at every multiple-of-bucket tail width (one
                # retrace each, like the live path) — cover them all,
                # or a warmed replica's first multi-bucket warm-cache
                # hit pays a live XLA trace.
                # Session resume dispatches the same writer at every
                # tail width too, so a KV tier widens the set the same
                # way the prefix cache does.
                tiered = (self.kv_tier is not None
                          and self.kv_tier_bypass_reason is None)
                widths = (self._prefill_widths()
                          if self._pcache is not None or tiered
                          else [self.prefill_chunk or self.prefill_bucket])
                for w in widths:
                    self.pool, tok = cfn(
                        self.params, self.pool, sink_table(self.t_side),
                        jnp.asarray(np.zeros((nd, w), np.int32)),
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray(np.full((nd,), -1, np.int32)), zrow)
                    np.asarray(tok)
                    compiled.append(f"chunk_prefill[{w}]")
            zt = jnp.asarray(np.zeros((self.rows,), np.int32))
            no_host = jnp.asarray(np.zeros((self.rows,), bool))
            for w in self._decode_widths() if decode else ():
                table = jnp.asarray(np.full((self.rows, w),
                                            self.t_side.sink, np.int32))
                if self.draft_cfg is not None:
                    dtable = jnp.asarray(np.full(
                        (self.rows, w), self.d_side.sink, np.int32))
                    parked = jnp.asarray(np.full(
                        (self.rows,), self.max_len, np.int32))
                    self.pool, self.d_side.pool, g, nc = self._spec_round(
                        self.params, self.pool, self.draft_params,
                        self.d_side.pool, table, dtable, zt, parked,
                        zt, zt)
                    np.asarray(nc)
                    compiled.append(f"spec_round[{w}]")
                elif self._pipelined:
                    for fn in dict.fromkeys((self._decode, self._decode1)):
                        self.pool, out, _, _, _ = fn(
                            self.params, self.pool, table, no_host, zt, zt,
                            zt, zt, zt, zt, zt)
                        np.asarray(out)
                    compiled.append(f"decode[{w}]")
                else:
                    for fn in dict.fromkeys((self._decode, self._decode1)):
                        self.pool, out = fn(
                            self.params, self.pool, table, zt, zt, zt, zt)
                        np.asarray(out)
                    compiled.append(f"decode[{w}]")
                if self._fused:
                    # The fused tick's (decode width x slot bucket)
                    # grid — every shape _step_fused can dispatch.
                    c = self.prefill_chunk
                    for S in self._fused_slot_buckets():
                        ctable = jnp.asarray(np.full(
                            (S, self.t_side.np_max), self.t_side.sink,
                            np.int32))
                        self.pool, out, first = self._fused_step(
                            self.params, self.pool, table, zt, zt, zt,
                            zt, ctable,
                            jnp.asarray(np.zeros((S, c), np.int32)),
                            jnp.asarray(np.zeros((S,), np.int32)),
                            jnp.asarray(np.full((S,), -1, np.int32)),
                            jnp.asarray(np.zeros((S,), np.int32)))
                        np.asarray(out)
                        np.asarray(first)
                        compiled.append(f"fused[{w},{S}]")
            if prefill and self.draft_cfg is not None:
                # Chunked mode feeds the draft the fixed chunk width;
                # non-chunked admission feeds it the PADDED PROMPT
                # width — every multiple-of-bucket trace the live path
                # would fill lazily.
                dws = ([self.prefill_chunk] if self._chunk_prefill
                       is not None else self._prefill_widths())
                for w in dws:
                    self.d_side.pool = self._draft_chunk(
                        self.draft_params, self.d_side.pool,
                        sink_table(self.d_side),
                        jnp.asarray(np.zeros((nd, w), np.int32)),
                        jnp.asarray(0, jnp.int32))
                    jax.block_until_ready(self.d_side.pool)
                    compiled.append(f"draft_chunk[{w}]")
            for side in (self.t_side, self.d_side):
                if side is None:
                    continue
                if side.pcache is not None:
                    dst = np.full((nd,), side.sink, np.int32)
                    side.pool = side.copy(side.pool, side.sink, dst)
                    jax.block_until_ready(side.pool)
                    compiled.append("page_copy")
            if self.n_shards == 1 and not self._recurrent:
                # (a recurrent row state refuses KV export: nothing to warm)
                # The disaggregated surface (export gather + import
                # scatter) — compiled at the one-page count; larger
                # transfers trace lazily per page count.  A KV tier
                # buckets its session park/resume transfers to
                # power-of-two counts, so warm those too — log2(np_max)
                # traces, and a resumed turn's TTFT never carries one.
                # A speculative batcher's exports carry the DRAFT
                # pool's paired payload, so its gather/scatter pair is
                # warmed at the same counts.
                counts = [1]
                if self.kv_tier is not None \
                        and self.kv_tier_bypass_reason is None:
                    counts = sorted({self._pow2(c) for c in
                                     range(1, self.t_side.np_max + 1)})
                for c in counts:
                    ids = jnp.asarray([self.t_side.sink] * c, jnp.int32)
                    payload = _gather_pages(self.pool, ids)
                    jax.block_until_ready(payload)
                    self.pool = _install_pages(self.pool, payload, ids)
                    jax.block_until_ready(self.pool)
                    if self.d_side is not None:
                        dids = jnp.asarray([self.d_side.sink] * c,
                                           jnp.int32)
                        dpayload = _gather_pages(self.d_side.pool, dids)
                        jax.block_until_ready(dpayload)
                        self.d_side.pool = _install_pages(
                            self.d_side.pool, dpayload, dids)
                        jax.block_until_ready(self.d_side.pool)
                    compiled.append(f"kv_export_import[{c}]")
        return {"compiled": compiled,
                "seconds": round(time.perf_counter() - t0, 3)}

    # -- disaggregated serving: KV export / import -------------------------

    def _check_disagg_mode(self, what: str) -> None:
        # Speculative batchers compose: their exports carry the draft
        # pool's paired payload (dk/dv + the ``draft`` header) and the
        # spec sampler state is already the (rid, step, tokens) triple.
        if self.n_shards != 1:
            raise ValueError(f"{what} requires a single-shard pool "
                             f"(mesh data shards pin pages locally)")
        if self._bypass["kv_export"] is not None:
            raise ValueError(f"{what} is refused: "
                             f"{self._bypass['kv_export']} (a KV artifact "
                             f"lists pages of consecutive positions)")

    def kv_headroom(self) -> int:
        """Free KV pool pages this batcher could hand to a new request
        right now: the free list plus zero-ref cached prefix pages (the
        allocator reclaims those on demand).  A heartbeat-grade load
        signal — it does NOT subtract in-flight rows' unallocated
        reservations — which decode-tier routing uses to place imported
        prefills where the pages are."""
        free = self.t_side.alloc.free_count()
        if self._pcache is not None:
            free += sum(self._pcache.reclaimable(s)
                        for s in range(self.n_shards))
        return free

    def export_kv(self, request: Request) -> dict:
        """PREFILL-ONLY execution: run ``request``'s prompt through this
        batcher's (chunked) prefill on a borrowed row and return its
        paged-KV state as a compact host artifact — per-layer page
        buffers for every position (int8 pools export values AND scales
        bit-exactly), page-table/geometry metadata, and the sampler
        state (first token, the ``rid`` whose in-graph key folds
        produced it).  The row's pages are released
        before returning; a matching batcher imports the artifact with
        ``submit(request, prefilled=artifact)`` and enters decode
        directly, token-for-token equivalent to admitting the request
        here.  Prefix-cache hits apply (a warm shared system prompt
        prefills only its tail) and the freshly prefilled pages are
        published for later exports.

        This is the prefill-role replica's serving surface: it must not
        run concurrently with this batcher's own serve loop (exports
        borrow row 0); concurrent export_kv calls serialize."""
        if not isinstance(request, Request):
            raise TypeError(f"export_kv() takes a Request, got "
                            f"{type(request).__name__}")
        self._check_disagg_mode("export_kv")
        with self._export_lock:
            if self._loop_active:
                raise RuntimeError(
                    "export_kv cannot run concurrently with this "
                    "batcher's serve loop (prefill-role batchers never "
                    "start one)")
            wt, wd, need = self._worst_pages(request)
            self._tier_promote(request)
            active: Dict[int, _Row] = {}
            row, plan = self._admit_row([0], active, wt, wd, request)
            assert row == 0     # nothing in flight: fit, or _admit_row raised
            rid = self._next_rid
            self._next_rid += 1
            try:
                res = self._admit_dispatch(row, rid, request, wt, wd,
                                           need, active, plan)
                state = active[row]
                if res is not None:
                    _, st, tok, s = res
                    self._stamp_first(st)
                    first = int(np.asarray(tok)[s])
                    st.last = first
                    st.out = [first]
                else:
                    # Chunked mode: drive the per-tick chunk writer to
                    # completion (no decode interleaves here — the whole
                    # point of a dedicated prefill tier).
                    while not state.decoding:
                        if self._advance_prefill(active) is not None:
                            break
                art = self._export_row(row, state)
                self._request_done("suspended", request, state)
                return art
            finally:
                # Unconditional: a failed dispatch may have allocated
                # pages before raising, and _finish releases safely
                # even when the row never became active.
                self._finish(row, active, [])

    @staticmethod
    def _pow2(n: int) -> int:
        """Smallest power of two >= n (the tier transfer bucket: the
        gather/scatter jits trace per page count, and bucketing bounds
        the compile set at log2 like the decode-table widths)."""
        return 1 << max(0, int(n) - 1).bit_length()

    def _draft_geom(self) -> Dict[str, Any]:
        """The draft-side geometry contract: stamped on every export's
        ``draft`` header and checked field-for-field at every
        import/resume site — ONE source, so a new header field cannot
        be added and forgotten in a validator.  (``_tier_geom``'s
        draft sub-dict is deliberately different: spilled PAGES need
        the dtype and not n_draft.)"""
        return {"n_layers": int(self.draft_cfg.n_layers),
                "kv_heads": int(self.draft_cfg.kv_heads),
                "head_dim": int(self.draft_cfg.head_dim),
                "quantized": isinstance(self.d_side.pool["k"], QTensor),
                "n_draft": int(self.n_draft)}

    def _side_page_export(self, side: _PagedSide, pool, row: int,
                          n: int, pad_pow2: bool):
        """Gather ``row``'s first ``n`` pages from ``pool`` to host —
        one side of an export.  ``pad_pow2`` buckets the gather's page
        count to a power of two (padding with sink reads, sliced off
        host-side)."""
        ids = np.asarray(side.table_np()[row, :n], np.int32)
        if pad_pow2:
            m = self._pow2(n)
            if m > n:
                ids = np.concatenate(
                    [ids, np.full((m - n,), side.sink, np.int32)])
        kv = _gather_pages(pool, jnp.asarray(ids))
        if pad_pow2 and len(ids) > n:
            kv = jax.tree_util.tree_map(lambda a: a[:, :n], kv)
        return kv

    def _export_row(self, row: int, state: _Row,
                    pad_pow2: bool = False,
                    final: bool = False) -> dict:
        """Snapshot ``row``'s post-prefill KV into a host artifact: the
        pages covering absolute positions [0, pos) — cached prefix
        pages and own pages alike, in table order — pulled to host in
        one gather.  A speculative batcher's artifact carries the DRAFT
        pool's paired payload over the same positions (``dk``/``dv`` +
        the ``draft`` geometry header), so a spec row moves whole.
        ``pad_pow2`` buckets the GATHER's page count to a power of two
        (padding with sink reads, sliced off host-side) so the tier's
        park path dispatches log2(np_max) compiled gathers instead of
        one per exact count; the artifact itself is unchanged.

        ``final=True`` exports a FINISHED row at its COMMITTED
        boundary: the pipelined loop advances ``pos``/``step`` at
        dispatch, so a finished row's host view can overshoot the
        committed stream by the in-flight block — but every position below
        ``prompt + len(out) - 1`` was written exactly once
        with the true token sequence (positions only move forward), so
        clamping there exports exactly the resumable state.  This is
        what lets session parking work in every decode mode instead of
        silently missing cold in the lagged one."""
        side = self.t_side
        ps = self.page_size
        E = state.pos
        step = int(state.step)
        toks = [int(t) for t in state.out]
        if final:
            step = len(toks)
            E = int(state.req.prompt.size) + step - 1
        n = -(-E // ps)
        kv = self._side_page_export(side, self.pool, row, n, pad_pow2)
        quantized = isinstance(self.pool["k"], QTensor)
        art = {
            "version": 1,
            "page_size": ps,
            # (a batcher-level static prefix once offset every position:
            # the wire format keeps its two fields, always 0, and an
            # importer still refuses any other value)
            "prefix_len": 0,
            "shared_len": 0,
            "pos": int(E),
            "prompt_len": int(state.req.prompt.size),
            "first_token": int(state.out[0]),
            # Mid-stream sampler state: a SUSPENDED row carries the
            # tokens it already emitted (step > 1) so the importer
            # resumes exactly where this row stopped; a fresh prefill
            # export is the step-1 degenerate case.  For speculative
            # rows this triple is the whole spec sampler state too:
            # draft proposals and acceptance draws are pure
            # per-(rid, step+j) key folds — no separate draft rng
            # position exists to carry.
            "step": step,
            "tokens": toks,
            "rid": int(state.rid),
            "quantized": quantized,
            "model": {"n_layers": int(self.cfg.n_layers),
                      "kv_heads": int(self.cfg.kv_heads),
                      "head_dim": int(self.cfg.head_dim)},
        }
        if quantized:
            art["k"] = np.asarray(kv["k"].values)
            art["k_scales"] = np.asarray(kv["k"].scales)
            art["v"] = np.asarray(kv["v"].values)
            art["v_scales"] = np.asarray(kv["v"].scales)
        else:
            art["k"] = np.asarray(kv["k"])
            art["v"] = np.asarray(kv["v"])
        if self.d_side is not None:
            # The paired draft-side payload: same positions, the draft
            # pool's pages.
            dkv = self._side_page_export(self.d_side, self.d_side.pool,
                                         row, n, pad_pow2)
            art["draft"] = self._draft_geom()
            dquant = art["draft"]["quantized"]
            if dquant:
                art["dk"] = np.asarray(dkv["k"].values)
                art["dk_scales"] = np.asarray(dkv["k"].scales)
                art["dv"] = np.asarray(dkv["v"].values)
                art["dv_scales"] = np.asarray(dkv["v"].scales)
            else:
                art["dk"] = np.asarray(dkv["k"])
                art["dv"] = np.asarray(dkv["v"])
        return art

    def _validate_artifact(self, art: dict, req: Request) -> None:
        """Reject an import whose artifact cannot drop into THIS pool
        bit-exactly — every mismatch is a loud ``ValueError`` (the
        fleet's bad_request), never a silently wrong decode."""
        self._check_disagg_mode("submit(prefilled=...)")
        if art.get("version") != 1:
            raise ValueError(f"unknown KV artifact version "
                             f"{art.get('version')!r}")
        quantized = isinstance(self.pool["k"], QTensor)
        for key, want in (("page_size", self.page_size),
                          ("prefix_len", 0),
                          ("shared_len", 0),
                          ("quantized", quantized)):
            if art.get(key) != want:
                raise ValueError(
                    f"KV artifact {key} {art.get(key)!r} does not match "
                    f"this batcher's {want!r}")
        model = art.get("model") or {}
        for key, want in (("n_layers", int(self.cfg.n_layers)),
                          ("kv_heads", int(self.cfg.kv_heads)),
                          ("head_dim", int(self.cfg.head_dim))):
            if model.get(key) != want:
                raise ValueError(
                    f"KV artifact model {key} {model.get(key)!r} does "
                    f"not match this config's {want}")
        # Mid-stream (suspended) artifacts carry step/tokens; a fresh
        # prefill export is step 1.  Every inconsistency is a loud
        # rejection — resuming from mismatched state would be a
        # silently wrong stream, the one failure mode this surface
        # must never have.
        try:
            step = int(art.get("step", 1))
        except (TypeError, ValueError):
            raise ValueError(f"KV artifact step {art.get('step')!r} is "
                             f"not an int") from None
        if step < 1:
            raise ValueError(f"KV artifact step {step} must be >= 1")
        toks = art.get("tokens")
        if step > 1 or toks is not None:
            if not isinstance(toks, (list, tuple)) or len(toks) != step:
                raise ValueError(
                    f"KV artifact tokens must list exactly step "
                    f"({step}) emitted tokens, got {toks!r}")
            if int(toks[0]) != int(art.get("first_token", -1)):
                raise ValueError("KV artifact tokens[0] does not match "
                                 "its first_token")
        if step > 1:
            if step >= req.max_new_tokens:
                raise ValueError(
                    f"suspended KV artifact already emitted {step} of "
                    f"{req.max_new_tokens} tokens — a finished request "
                    f"is never suspended")
            if req.stop_token is not None \
                    and int(toks[-1]) == int(req.stop_token):
                raise ValueError("suspended KV artifact ends at the "
                                 "stop token — nothing to resume")
        E = art.get("pos")
        if E != int(req.prompt.size) + step - 1 \
                or art.get("prompt_len", -1) != int(req.prompt.size):
            raise ValueError(
                f"KV artifact covers {E!r} positions; this request needs "
                f"prompt {req.prompt.size} (+ {step - 1} resumed tokens)")
        n = -(-E // self.page_size)
        pool_k = self.pool["k"].values if quantized else self.pool["k"]
        self._check_payload_arrays(art, quantized, n, self.cfg, pool_k)
        self._validate_artifact_draft(art, n, step)

    def _check_payload_arrays(self, art: dict, quantized: bool, n: int,
                              mcfg, pool_k, prefix: str = "") -> None:
        """ONE shape/dtype contract for one side's page payload:
        ``prefix`` '' checks ``k``/``v`` (+ scales) against the target
        config, ``'d'`` checks ``dk``/``dv`` against the draft's — the
        two sides' validators cannot silently diverge."""
        want_shape = (int(mcfg.n_layers), n, int(mcfg.kv_heads),
                      self.page_size, int(mcfg.head_dim))
        names = (("k", "v", "k_scales", "v_scales") if quantized
                 else ("k", "v"))
        side = "draft " if prefix else ""
        for name in names:
            key = prefix + name
            a = art.get(key)
            if not isinstance(a, np.ndarray):
                raise ValueError(f"KV artifact is missing {side}array "
                                 f"{key!r}")
            if name.endswith("_scales"):
                want = want_shape[:3] + (1, self.page_size)
                dtype = np.float32
            else:
                want = want_shape
                dtype = np.dtype(pool_k.dtype)
            if a.shape != want:
                raise ValueError(f"KV artifact {key} shape {a.shape} != "
                                 f"expected {want}")
            if a.dtype != dtype:
                raise ValueError(f"KV artifact {key} dtype {a.dtype} != "
                                 f"{side}pool dtype {dtype}")

    def _validate_artifact_draft(self, art: dict, n: int,
                                 step: int) -> None:
        """The draft half of :meth:`_validate_artifact`.  A draft-less
        batcher rejects artifacts carrying a draft payload (resuming a
        spec row without its draft state would fork sampled streams —
        loud beats subtly different); a speculative batcher requires a
        matching draft payload for MID-STREAM artifacts, but accepts a
        fresh (step-1) prefill export without one: the import rebuilds
        the draft's prompt KV with exactly the chunk write a local spec
        admission dispatches, which is what lets a draft-less prefill
        tier feed draft-equipped decode replicas."""
        draft = art.get("draft")
        has_payload = isinstance(art.get("dk"), np.ndarray)
        if self.d_side is None:
            if draft is not None or has_payload:
                raise ValueError(
                    "KV artifact carries a draft-side payload but this "
                    "batcher has no draft model (speculative exports "
                    "resume on speculative batchers)")
            return
        if not has_payload:
            if step > 1:
                raise ValueError(
                    "suspended KV artifact has no draft-side payload; "
                    "a speculative batcher cannot rebuild mid-stream "
                    "draft state bit-exactly")
            return      # fresh prefill: the import rebuilds the draft
        if not isinstance(draft, dict):
            raise ValueError("KV artifact has draft arrays but no "
                             "'draft' geometry header")
        geom = self._draft_geom()
        for key, want in geom.items():
            if draft.get(key) != want:
                raise ValueError(
                    f"KV artifact draft {key} {draft.get(key)!r} does "
                    f"not match this batcher's {want!r}")
        dquant = geom["quantized"]
        dpool_k = (self.d_side.pool["k"].values if dquant
                   else self.d_side.pool["k"])
        self._check_payload_arrays(art, dquant, n, self.draft_cfg,
                                   dpool_k, prefix="d")

    def _admit_import(self, row: int, pre: Prefilled, wt: int,
                      wd: int, need: int, active: Dict[int, _Row]
                      ) -> tuple:
        """Admission of an imported prefill: back the payload's
        positions with own pages, scatter the artifact's page buffers
        into them, and enter the row straight into decode at the
        exported position with the exported first token — the
        disaggregated analogue of _admit_dispatch, with no model call.
        The imported full prompt pages then seed the prefix cache
        exactly like a local prefill's (insert_row already refuses a
        chunk a twin published, so pages never gain two owners)."""
        t_admit = time.perf_counter()
        art = pre.artifact
        req = pre.request
        self._tick["admitted"] += 1
        self._trace_event(req, "import", rid=int(art.get("rid", -1)),
                          row=row, pos=int(art.get("pos", 0)),
                          resumed=int(art.get("step", 1)) > 1,
                          tick=self._tick["tick"])
        side = self.t_side
        n = art["k"].shape[1]
        side.ensure(row, n * self.page_size)
        ids = side.alloc.rows[row]
        if art["quantized"]:
            payload = {
                "k": QTensor(jnp.asarray(art["k"]),
                             jnp.asarray(art["k_scales"])),
                "v": QTensor(jnp.asarray(art["v"]),
                             jnp.asarray(art["v_scales"])),
            }
        else:
            payload = {"k": jnp.asarray(art["k"]),
                       "v": jnp.asarray(art["v"])}
        self.pool = _install_pages(self.pool, payload,
                                   jnp.asarray(ids, jnp.int32))
        if self.d_side is not None:
            self._admit_import_draft(row, req, art, n, need)
        # The exported rid keeps the row's in-graph sampling folds on
        # the stream the prefill side started (greedy never reads it;
        # with equal batcher rngs, sampled disaggregated streams equal
        # the unified batcher's exactly).  Caveat: rids from DIFFERENT
        # exporters (or an exporter and this batcher's own counter) can
        # coincide, correlating the sampled draws of unrelated rows —
        # deployments sampling across several prefill replicas should
        # give them distinct seeds/rngs.
        #
        # A SUSPENDED artifact (step > 1) resumes mid-stream: the row
        # re-enters decode with the exported emitted-token list, last
        # token, and step — the (rid, step) sample folds continue on
        # exactly the stream the suspension interrupted, so the resumed
        # completion is token-identical to an uninterrupted run.
        step = int(art.get("step", 1))
        toks = [int(t) for t in (art.get("tokens") or ())]
        resumed = step > 1
        state = _Row(rid=int(art["rid"]), req=req, pos=int(art["pos"]),
                     step=step, last=(toks[-1] if resumed else 0),
                     out=(list(toks) if resumed else []), worst_pages=wt,
                     worst_draft=wd, t_admit=t_admit,
                     admit_tick=self._tick["tick"], limit=need)
        active[row] = state
        self._pcache_insert(row, state)
        return row, state, np.asarray([int(art["first_token"])]), 0

    def _admit_import_draft(self, row: int, req: Request, art: dict,
                            n: int, need: int) -> None:
        """The draft half of :meth:`_admit_import`: scatter the
        artifact's paired draft payload into own draft pages — or, for
        a fresh (step-1) export from a draft-less prefill tier, rebuild
        the draft's prompt KV with EXACTLY the chunk write a local spec
        admission dispatches (same widths, same offsets), so the draft
        cache is bit-identical to a local admission's."""
        dside = self.d_side
        if isinstance(art.get("dk"), np.ndarray):
            dside.ensure(row, n * self.page_size)
            dids = dside.alloc.rows[row]
            if art["draft"]["quantized"]:
                dpayload = {
                    "k": QTensor(jnp.asarray(art["dk"]),
                                 jnp.asarray(art["dk_scales"])),
                    "v": QTensor(jnp.asarray(art["dv"]),
                                 jnp.asarray(art["dv_scales"])),
                }
            else:
                dpayload = {"k": jnp.asarray(art["dk"]),
                            "v": jnp.asarray(art["dv"])}
            dside.pool = _install_pages(dside.pool, dpayload,
                                        jnp.asarray(dids, jnp.int32))
            return
        # Rebuild (validated: only fresh step-1 artifacts reach here).
        length = int(req.prompt.size)
        bucket = self.prefill_chunk or self.prefill_bucket
        width = -(-length // bucket) * bucket
        dside.ensure(row, min(width, need))
        padded = np.zeros((1, width), np.int32)
        padded[0, :length] = req.prompt
        if self._chunk_prefill is not None:
            # Chunked admission writes the draft chunk by chunk; mirror
            # it so the rebuilt cache is bit-identical.
            c = self.prefill_chunk
            for off in range(0, width, c):
                _, dtoks, dtable = self._one_hot_call(
                    dside, row, padded[:, off:off + c])
                dside.pool = self._draft_chunk(
                    self.draft_params, dside.pool, dtable, dtoks,
                    jnp.asarray(off, jnp.int32))
        else:
            _, dtoks, dtable = self._one_hot_call(dside, row, padded)
            dside.pool = self._draft_chunk(
                self.draft_params, dside.pool, dtable, dtoks,
                jnp.asarray(0, jnp.int32))

    # -- the KV tier: prefix spill/promote + session park/resume -----------

    @property
    def _tier_active(self) -> bool:
        return (self.kv_tier is not None
                and self.kv_tier_bypass_reason is None)

    def _tier_geom(self) -> Dict[str, Any]:
        """The geometry stamped on every spilled prefix page and
        checked on promotion — a tier entry cut for a different pool
        layout or model must read as a miss, never install.  The
        ``draft`` sub-geometry (None without one) makes a speculative
        batcher's twin-page spills unreadable by draft-less peers and
        vice versa."""
        geom: Dict[str, Any] = {
            "page_size": self.page_size,
            "n_layers": int(self.cfg.n_layers),
            "kv_heads": int(self.cfg.kv_heads),
            "head_dim": int(self.cfg.head_dim),
            "dtype": str(np.dtype(self.pool["k"].dtype)),
            "draft": None}
        if self.d_side is not None:
            geom["draft"] = {
                "n_layers": int(self.draft_cfg.n_layers),
                "kv_heads": int(self.draft_cfg.kv_heads),
                "head_dim": int(self.draft_cfg.head_dim),
                "dtype": str(np.dtype(self.d_side.pool["k"].dtype))}
        return geom

    def _spill_page(self, shard: int, digest: bytes, page: int,
                    dpage: Optional[int] = None) -> None:
        """The prefix cache's eviction callback: gather the evicted
        page's content to host and park it in the KV tier,
        content-addressed by its chain digest — the device→host spill
        of the memory hierarchy.  In speculative mode the node's DRAFT
        twin rides the same entry (body = target k+v then draft k+v),
        so a promotion restores both pools.  Runs on the serve-loop
        thread (the eviction happens under its allocation pressure)
        while the pages still hold the published chunk; any failure
        costs the spill, never the eviction."""
        tier = self.kv_tier
        if tier is None:
            return
        # Pre-check the tier's hard bounds BEFORE paying the device-
        # to-host gather: a page that can never fit must not cost a
        # blocking transfer on the reclaim path (which runs mid-
        # admission, under the cache lock).
        nbytes = (2 * int(self.cfg.n_layers) * int(self.cfg.kv_heads)
                  * self.page_size * int(self.cfg.head_dim)
                  * np.dtype(self.pool["k"].dtype).itemsize)
        if self.d_side is not None:
            nbytes += (2 * int(self.draft_cfg.n_layers)
                       * int(self.draft_cfg.kv_heads) * self.page_size
                       * int(self.draft_cfg.head_dim)
                       * np.dtype(self.d_side.pool["k"].dtype).itemsize)
        accept = getattr(tier, "would_accept", None)
        if accept is not None and not accept(nbytes + 512):
            tier.count("evictions")
            return
        kv = _gather_pages(self.pool, jnp.asarray([int(page)], jnp.int32))
        k = np.ascontiguousarray(np.asarray(kv["k"]))
        v = np.ascontiguousarray(np.asarray(kv["v"]))
        meta = dict(self._tier_geom())
        meta["k_bytes"] = int(k.nbytes)
        body = k.tobytes() + v.tobytes()
        if self.d_side is not None and dpage is not None:
            dkv = _gather_pages(self.d_side.pool,
                                jnp.asarray([int(dpage)], jnp.int32))
            dk = np.ascontiguousarray(np.asarray(dkv["k"]))
            dv = np.ascontiguousarray(np.asarray(dkv["v"]))
            meta["dk_bytes"] = int(dk.nbytes)
            body += dk.tobytes() + dv.tobytes()
        tier.put_prefix(digest.hex(), meta, body)

    def _tier_page_payload(self, meta: dict, body: bytes):
        """Rebuild one spilled page's device payload(s): ``(target,
        draft)`` — each a ``{"k", "v"}`` tree of shape [layers, 1,
        kv_heads, page, dim], draft None without one; None (the whole
        result) when the entry was cut for a different geometry or is
        malformed."""
        geom = self._tier_geom()
        if any(meta.get(k) != geom[k] for k in geom):
            return None
        shape = (int(self.cfg.n_layers), 1, int(self.cfg.kv_heads),
                 self.page_size, int(self.cfg.head_dim))
        dtype = np.dtype(geom["dtype"])
        kb = meta.get("k_bytes")
        count = int(np.prod(shape, dtype=np.int64))
        want = 2 * count * dtype.itemsize
        if not isinstance(kb, int) or 2 * kb != want \
                or len(body) < want:
            return None
        k = np.frombuffer(body, dtype=dtype, count=count).reshape(shape)
        v = np.frombuffer(body, dtype=dtype, count=count,
                          offset=kb).reshape(shape)
        target = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
        if self.d_side is None:
            if len(body) != want:
                return None
            return target, None
        dshape = (int(self.draft_cfg.n_layers), 1,
                  int(self.draft_cfg.kv_heads), self.page_size,
                  int(self.draft_cfg.head_dim))
        ddtype = np.dtype(geom["draft"]["dtype"])
        dkb = meta.get("dk_bytes")
        dcount = int(np.prod(dshape, dtype=np.int64))
        if not isinstance(dkb, int) or dkb != dcount * ddtype.itemsize \
                or len(body) != want + 2 * dkb:
            return None
        dk = np.frombuffer(body, dtype=ddtype, count=dcount,
                           offset=want).reshape(dshape)
        dv = np.frombuffer(body, dtype=ddtype, count=dcount,
                           offset=want + dkb).reshape(dshape)
        return target, {"k": jnp.asarray(dk), "v": jnp.asarray(dv)}

    def _tier_promote(self, req: Request) -> None:
        """Opportunistic tier→device promotion at admission: for each
        of ``req``'s prompt chunks just past the trie's longest match,
        a tier hit installs the spilled page into a FREE pool page and
        re-inserts it as a zero-ref trie node — the normal prefix-plan
        path then maps it like any resident hit.  Free pages only
        (promotion never evicts resident cache to make room — that
        would just rotate the working set through the tier); checked
        once per request (memoized), so a queued arrival does not
        re-scan the tier every admission tick."""
        if not self._tier_active or self._pcache is None:
            return
        if getattr(req, "_tier_checked", False):
            return
        req._tier_checked = True
        digs = self._req_digests(req)
        if not digs:
            return
        pc = self._pcache
        alloc = self.t_side.alloc.shards[0]
        dalloc = (self.d_side.alloc.shards[0]
                  if self.d_side is not None else None)
        n = len(pc.match(0, digs))
        while n < len(digs):
            d = digs[n]
            got = self.kv_tier.get_prefix(d.hex())
            if got is None:
                break
            payloads = self._tier_page_payload(got[0], got[1])
            if payloads is None or not alloc.free \
                    or (dalloc is not None and not dalloc.free):
                break
            payload, dpayload = payloads
            page = alloc.free.pop()
            dpage = dalloc.free.pop() if dalloc is not None else None
            if not pc.insert_chain(0, digs[:n], d, page, dpage):
                alloc.free.append(page)
                if dalloc is not None:
                    dalloc.free.append(dpage)
                break
            self.pool = _install_pages(self.pool, payload,
                                       jnp.asarray([page], jnp.int32))
            if dpayload is not None:
                self.d_side.pool = _install_pages(
                    self.d_side.pool, dpayload,
                    jnp.asarray([dpage], jnp.int32))
            self.kv_tier.count("promotions")
            self._trace_event(req, "tier_promote", digest=d.hex()[:16],
                              depth=n + 1)
            n += 1

    def _validate_session(self, art: dict, req: Request) -> None:
        """Reject a parked session artifact that cannot resume THIS
        request bit-exactly (every mismatch → ``ValueError`` → the
        lookup treats it as a miss and the turn re-prefills cold —
        deterministic, never stale KV)."""
        if art.get("version") != 1:
            raise ValueError(f"unknown session artifact version "
                             f"{art.get('version')!r}")
        for key, want in (("page_size", self.page_size),
                          ("prefix_len", 0),
                          ("shared_len", 0),
                          ("quantized", False)):
            if art.get(key) != want:
                raise ValueError(
                    f"session artifact {key} {art.get(key)!r} does not "
                    f"match this batcher's {want!r}")
        model = art.get("model") or {}
        for key, want in (("n_layers", int(self.cfg.n_layers)),
                          ("kv_heads", int(self.cfg.kv_heads)),
                          ("head_dim", int(self.cfg.head_dim))):
            if model.get(key) != want:
                raise ValueError(
                    f"session artifact model {key} {model.get(key)!r} "
                    f"does not match this config's {want}")
        hist = art.get("history")
        if not isinstance(hist, (list, tuple)) or len(hist) < 2:
            raise ValueError("session artifact carries no usable "
                             "history")
        if req.prompt.size < len(hist):
            raise ValueError(
                f"request prompt ({req.prompt.size} tokens) does not "
                f"extend the parked history ({len(hist)} tokens)")
        if not np.array_equal(req.prompt[:len(hist)],
                              np.asarray(hist, np.int32)):
            raise ValueError("request prompt diverges from the parked "
                             "session history")
        covered = len(hist) - 1     # the last token is the tail's input
        E_art = art.get("pos")
        if E_art != covered:
            raise ValueError(
                f"session artifact covers {E_art!r} positions; its "
                f"history implies {covered}")
        ps = self.page_size
        n = -(-E_art // ps)
        want_shape = (int(self.cfg.n_layers), n, int(self.cfg.kv_heads),
                      ps, int(self.cfg.head_dim))
        dtype = np.dtype(self.pool["k"].dtype)
        for key in ("k", "v"):
            a = art.get(key)
            if not isinstance(a, np.ndarray) or a.shape != want_shape \
                    or a.dtype != dtype:
                raise ValueError(
                    f"session artifact {key} is not a "
                    f"{want_shape}/{dtype} array")
        # Speculative sessions: the parked artifact must carry (or not
        # carry) a draft payload matching THIS batcher — a mismatch is
        # a miss (the caller re-prefills cold), never a half-resume.
        draft = art.get("draft")
        if self.d_side is None:
            if draft is not None or isinstance(art.get("dk"),
                                               np.ndarray):
                raise ValueError("session artifact carries a draft "
                                 "payload this batcher has no draft "
                                 "model for")
        else:
            # The tier bypasses quantized pools, so _draft_geom()'s
            # quantized field is necessarily False here.
            for key, want in self._draft_geom().items():
                if not isinstance(draft, dict) \
                        or draft.get(key) != want:
                    raise ValueError(
                        f"session artifact draft geometry does not "
                        f"match this batcher ({key})")
            dshape = (int(self.draft_cfg.n_layers), n,
                      int(self.draft_cfg.kv_heads), ps,
                      int(self.draft_cfg.head_dim))
            ddtype = np.dtype(self.d_side.pool["k"].dtype)
            for key in ("dk", "dv"):
                a = art.get(key)
                if not isinstance(a, np.ndarray) \
                        or a.shape != dshape or a.dtype != ddtype:
                    raise ValueError(
                        f"session artifact {key} is not a "
                        f"{dshape}/{ddtype} array")
        # The tail's padded prefill window must fit the page table
        # (same bound the prefix-plan trimmer enforces).
        E = int(req.prompt.size)
        w = -(-(E - E_art) // self.prefill_bucket) * self.prefill_bucket
        if E_art + w > self.np_max * ps:
            raise ValueError("session tail window exceeds the page "
                             "table; resuming cold instead")

    def _session_lookup(self, req: Request) -> Optional[dict]:
        """The usable parked artifact for ``req.session_id``, or None
        (no tier, no entry, stale weights, corrupt, or it does not
        cover this prompt — every miss path means a cold full-history
        prefill, which is always correct).  Memoized per request so a
        queued arrival does not re-read the tier every tick."""
        if not self._tier_active or not req.session_id:
            return None
        memo = getattr(req, "_session_art", None)
        if memo is not None:
            return memo[0]
        art = None
        got = self.kv_tier.resume(req.session_id)
        if got is not None:
            try:
                art = unpack_prefilled(dict(got[0]), got[1])
                self._validate_session(art, req)
            except ValueError:
                art = None
        if art is not None:
            self.kv_tier.count("resume")
        req._session_art = (art,)
        return art

    def _admit_session(self, row: int, rid: int, req: Request, wt: int,
                       wd: int, need: int, active: Dict[int, _Row],
                       art: dict) -> tuple:
        """Admission of a session RESUME: install the parked artifact's
        pages (they back the conversation so far) and prefill only the
        new turn's tail at its true offset — the cross-turn analogue of
        a prefix-cache hit, built from the import scatter plus the
        traced-offset chunk writer.  Returns the burst tuple like
        ``_admit_dispatch``."""
        t_admit = time.perf_counter()
        side = self.t_side
        n = art["k"].shape[1]
        self._tick["admitted"] += 1
        self._trace_event(req, "session_resume", rid=rid, row=row,
                          session=str(req.session_id),
                          covered=int(art["pos"]), tick=self._tick["tick"])
        side.ensure(row, n * self.page_size)
        ids = list(side.alloc.rows[row])
        # Bucket the install to a power-of-two page count (pad slots
        # scatter zeros onto the sink page — a write dump by
        # construction) so resume dispatches one of log2(np_max)
        # compiled scatters, never a fresh trace on the TTFT path.
        def pow2_install(pool, sink, page_ids, k, v):
            m = self._pow2(n)
            page_ids = list(page_ids)
            if m > n:
                pad = np.zeros(k.shape[:1] + (m - n,) + k.shape[2:],
                               k.dtype)
                k = np.concatenate([k, pad], axis=1)
                v = np.concatenate([v, pad], axis=1)
                page_ids = page_ids[:n] + [sink] * (m - n)
            payload = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
            return _install_pages(pool, payload,
                                  jnp.asarray(page_ids, jnp.int32))

        self.pool = pow2_install(self.pool, side.sink, ids,
                                 art["k"], art["v"])
        if self.d_side is not None:
            # The paired draft payload backs the same positions of the
            # draft pool (validated present and shape-matched).
            dside = self.d_side
            dside.ensure(row, n * self.page_size)
            dside.pool = pow2_install(dside.pool, dside.sink,
                                      dside.alloc.rows[row],
                                      art["dk"], art["dv"])
        E = int(req.prompt.size)
        ts = int(art["pos"])
        tlen = E - ts
        w = -(-tlen // self.prefill_bucket) * self.prefill_bucket
        # Clamp at the reservation: pad writes past ``need`` land on
        # reserved-but-unread slots or sink columns (the cold path's
        # prompt padding discipline).
        side.ensure(row, min(ts + w, need))
        self._tick["prefill_tokens"] += w
        padded = np.zeros((1, w), np.int32)
        padded[0, :tlen] = req.prompt[req.prompt.size - tlen:]
        s, toks, table = self._one_hot_call(side, row, padded)
        caps = np.full((self.n_shards,), -1, np.int32)
        caps[s] = tlen - 1
        rids = np.zeros((self.n_shards,), np.int32)
        rids[s] = rid
        self.pool, tok = self._tail_prefill(
            self.params, self.pool, table, toks,
            jnp.asarray(ts, jnp.int32), jnp.asarray(caps),
            jnp.asarray(rids))
        if self.d_side is not None:
            # The draft's tail advances in lockstep (same tokens, same
            # offset) so the next spec round proposes from a complete
            # draft cache.
            dside = self.d_side
            dside.ensure(row, min(ts + w, need))
            _, dtoks, dtable = self._one_hot_call(dside, row, padded)
            dside.pool = self._draft_chunk(
                self.draft_params, dside.pool, dtable, dtoks,
                jnp.asarray(ts, jnp.int32))
        tok.copy_to_host_async()    # transfer overlaps later dispatches
        state = _Row(rid=rid, req=req, pos=E, step=1, last=0, out=[],
                     worst_pages=wt, worst_draft=wd, t_admit=t_admit,
                     admit_tick=self._tick["tick"], prefill_tokens=w,
                     limit=need)
        active[row] = state
        self._pcache_insert(row, state)
        return row, state, tok, s

    def _park_session(self, r: int, state: _Row) -> None:
        """Park a FINISHED session-labeled row's KV in the tier (called
        before its pages release): the artifact is the row's export
        plus the full conversation history, so the next turn can resume
        from it on this replica — or, through a shared disk tier, on
        any same-weights replica of the host.  EVERY decode mode parks
        — the pipelined loop exports at the COMMITTED boundary
        (``_export_row(final=True)`` clamps the overshooting host view
        to ``prefix + prompt + len(out) - 1``, below which every
        position holds the true stream).  A full tier is an explicit
        rejected park, never a failed request."""
        if not self._tier_active:
            return
        sid = state.req.session_id
        if not sid or not state.out or state.t_first <= 0:
            return
        try:
            art = self._export_row(r, state, pad_pow2=True, final=True)
            art["history"] = ([int(t) for t in state.req.prompt]
                              + [int(t) for t in state.out])
            meta, body = pack_prefilled(art)
        except Exception:
            return      # parking is best-effort; the completion stands
        try:
            self.kv_tier.park(str(sid), meta, body)
        except Exception:
            # KVTierFull (counted park_rejected by the store) or an
            # unexpected failure: explicit and observable, and the
            # request's completion is unaffected.
            return
        self._trace_event(state.req, "session_park", session=str(sid),
                          bytes=len(body))

    def _finish_completed(self, r: int, active: Dict[int, _Row],
                          free_rows: List[int]) -> None:
        """Finish a COMPLETED row: park its session KV (when labeled
        and parkable) before the pages release, then the normal
        finish."""
        state = active.get(r)
        if state is not None:
            self._park_session(r, state)
        self._finish(r, active, free_rows)

    def _submission_source(self) -> SubmissionQueue:
        with self._submissions_lock:
            if self._submissions is None:
                self._submissions = SubmissionQueue()
            return self._submissions

    def submit(self, request: Request, prefilled: Optional[dict] = None
               ) -> None:
        """Thread-safe online admission: queue ``request`` for the
        :meth:`serve` loop.  May be called from any thread, before or
        while serve() runs; raises after :meth:`close`.

        ``prefilled`` (an :meth:`export_kv` artifact) switches the
        request onto the IMPORT path: its KV pages install into the
        local pool and the row enters decode directly — the decode half
        of disaggregated serving."""
        if prefilled is not None:
            if self._bypass["kv_export"] is not None:
                self._check_disagg_mode("submit(prefilled=...)")
            request = Prefilled(request, prefilled)
        self._submission_source().submit(request)

    def close(self) -> None:
        """End the online stream: serve() drains everything submitted
        and returns (or, called before serve(), makes it return
        immediately).  Idempotent."""
        self._submission_source().close()

    def serve(self) -> Iterator[Completion]:
        """:meth:`run` over the incremental submission queue: yields
        Completions in finish order as submit()ted requests finish,
        decoding continuously while the queue is empty, blocking only
        when fully idle, and returning once :meth:`close` is called and
        the stream drains.  One serve() loop per batcher."""
        return self.run(self._submission_source())

    # -- the loop ---------------------------------------------------------

    def run(self, requests: Iterable[Request]) -> Iterator[Completion]:
        """Serve ``requests`` (any iterable — a generator staggers
        arrivals naturally — or a :class:`SubmissionQueue` for online
        thread-safe submission, see :meth:`serve`), yielding
        :class:`Completion`\\ s in FINISH order.  Pulls from the
        iterable lazily: a request is consumed only when a row and
        pages are available for it.  Abandoning the
        iterator early releases every in-flight row's pages.  An invalid
        request (longer than ``max_len`` allows) raises — but only AFTER
        every already-admitted request has drained and yielded, so one
        malformed arrival never discards valid in-flight work."""
        incremental = isinstance(requests, SubmissionQueue)
        source = None if incremental else iter(requests)
        pending: deque = deque()
        active: Dict[int, _Row] = {}
        free_rows = list(range(self.rows))
        exhausted = False
        bad_request: Optional[Exception] = None

        def rank_of(item):
            return _request_of(item).priority

        def rank_insert(item):
            # Class-aware admission order (the batcher-side twin of the
            # gateway's WFQ): pending stays sorted by priority rank,
            # FIFO within a rank — an outranking arrival admits before
            # earlier lower-class ones, and single-class traffic keeps
            # the exact FIFO of old.  Stable: insert BEHIND every item
            # of equal-or-higher rank.
            p = rank_of(item)
            i = len(pending)
            while i > 0 and rank_of(pending[i - 1]) < p:
                i -= 1
            pending.insert(i, item)

        def pull(block=True):
            # ``block`` only matters for a SubmissionQueue source: the
            # admission loop polls non-blocking so an empty online queue
            # never stalls rows that are mid-decode, while the idle
            # branch blocks (there is nothing else to do).  An
            # incremental pull drains EVERYTHING already submitted (the
            # items are in host memory either way, and admission cannot
            # rank-order arrivals it has not seen); iterables keep their
            # original lazy one-at-a-time semantics — next() blocks when
            # the generator does, and a generator's order is its order.
            nonlocal exhausted
            if exhausted:
                return
            # ``idle``: the pull of the idle branch, which may sleep
            # until an arrival — kept out of the tick's host time.
            with self._phase("batcher.pull", idle=int(block)) as ph:
                if incremental:
                    want_block = block and not pending
                    while True:
                        item = requests.poll(want_block)
                        want_block = False
                        if item is _CLOSED:
                            exhausted = True
                            break
                        if item is None:
                            break
                        rank_insert(item)
                elif not pending:
                    try:
                        pending.append(next(source))
                    except StopIteration:
                        exhausted = True
                    else:
                        _stamp_submit(pending[-1])
            if block:
                self._tick["idle_ms"] += ph.ms

        # Fences export_kv's row borrowing: taken under _export_lock so
        # the check-then-borrow in export_kv and this set cannot
        # interleave (a loop starting mid-export waits the export out;
        # an export starting after this sees the flag and raises).
        with self._export_lock:
            self._loop_active = True
        try:
            while True:
                self._tick_roll()
                if self._preempt_event.is_set():
                    # Drain-migration: every in-flight request (resident
                    # rows, parked artifacts, queued arrivals) is given
                    # back as a Suspended item for re-placement
                    # elsewhere; the loop itself keeps serving whatever
                    # arrives after.
                    yield from self._preempt_everything(
                        pending, active, free_rows,
                        requests if incremental else None)
                # Admit while a row is free and the pool can take the
                # newcomer's worst case.  Prefills DISPATCH inside the
                # loop but their first-token fetches are deferred to one
                # burst sync after it — admitting W requests costs one
                # device-to-host round-trip, not W.
                # End-to-end deadlines: cancel expired resident rows
                # NOW, before admission — their pages free this tick,
                # so dead work never holds a decode slot a live arrival
                # could take.
                yield from self._cancel_expired(active, free_rows)
                burst = []
                # Parked (preempted) artifacts resume FIRST: they
                # arrived before anything still queued, so a sustained
                # same-class arrival stream must not starve them.  A
                # strictly-OUTRANKING queued arrival still goes first
                # (the gate below — and past it, the preemption rule
                # itself); one eager pull makes such an arrival visible.
                if self._parked and incremental:
                    pull(block=False)
                while free_rows and self._parked and bad_request is None:
                    pre = self._parked[0]
                    if pre.request.expired:
                        # The client gave up while the artifact was
                        # parked: drop it without re-importing.
                        self._parked.popleft()
                        self.deadline_cancels += 1
                        self._trace_event(pre.request, "deadline_cancel",
                                          where="parked")
                        self._request_done("expired", pre)
                        yield Expired(rid=int(pre.artifact.get("rid",
                                                               -1)),
                                      request=pre.request)
                        continue
                    if pending:
                        h = pending[0]
                        hreq = h.request if isinstance(h, Prefilled) \
                            else h
                        if hreq.priority > pre.request.priority:
                            break
                    try:
                        with self._phase("batcher.admit"):
                            wt, wd, need = self._worst_pages(pre.request)
                            row, _ = self._admit_row(free_rows, active, wt,
                                                     wd, pre.request,
                                                     use_cache=False)
                    except RuntimeError:
                        # The resume can never fit this pool (e.g. the
                        # original admission rode a prefix-cache plan
                        # the full-page import cannot): fall back to a
                        # from-scratch re-run through the normal path —
                        # deterministic, the waiter's callback intact —
                        # instead of killing the serve loop.
                        # (_maybe_preempt's fit check keeps this
                        # unreachable in practice.)
                        self._parked.popleft()
                        pending.appendleft(pre.request)
                        continue
                    if row is None:
                        break       # resume once pages free up
                    self._parked.popleft()
                    self.resumes += 1
                    self._trace_event(pre.request, "resume")
                    with self._phase("batcher.admit"):
                        burst.append(self._admit_import(row, pre, wt, wd,
                                                        need, active))
                while free_rows and bad_request is None \
                        and not self._weight_updates:
                    # (A pending weight update gates NEW admissions —
                    # resident rows and parked resumes finish on the
                    # old weights first; see _apply_weight_update.)
                    if not pending and not exhausted and burst \
                            and not incremental:
                        # pull() may BLOCK in next(source) (a staggered
                        # stream): settle the in-flight admissions first
                        # so their first tokens (and any instant
                        # completions) are not held hostage to the next
                        # arrival — this also keeps t_first honest.
                        # (A SubmissionQueue source never blocks here.)
                        yield from self._finalize_burst(burst, active,
                                                        free_rows)
                    pull(block=False)
                    if not pending:
                        break
                    item = pending[0]
                    imported = isinstance(item, Prefilled)
                    req0 = item.request if imported else item
                    if req0.expired:
                        # Shed BEFORE any prefill work (or import)
                        # dispatches: the deadline passed while the
                        # request waited, and serving it would burn
                        # device time nobody is waiting for.
                        pending.popleft()
                        self.deadline_cancels += 1
                        self._trace_event(req0, "deadline_cancel",
                                          where="queued")
                        self._request_done("shed", item)
                        yield Expired(
                            rid=(int(item.artifact.get("rid", -1))
                                 if imported else -1),
                            request=req0)
                        continue
                    with self._phase("batcher.admit"):
                        try:
                            wt, wd, need = self._worst_pages(req0)
                            if imported:
                                self._validate_artifact(item.artifact, req0)
                        except ValueError as e:
                            bad_request = e     # raise after draining
                            break
                        # KV tier: a usable parked session artifact takes
                        # the resume path instead of prefilling the whole
                        # history — checked FIRST, because a resume
                        # installs those positions from the artifact and
                        # promoting their spilled prefix pages too would
                        # be a second, unused device install.  Otherwise,
                        # promote any spilled prefix pages this prompt
                        # could map (they re-enter the trie as zero-ref
                        # nodes, so the prefix plan below sees them).
                        sess_art = (None if imported
                                    else self._session_lookup(req0))
                        if not imported and sess_art is None:
                            self._tier_promote(req0)
                        # Imports (and session resumes) skip the
                        # prefix-plan mapping: their pages arrive in the
                        # payload (installing everything, then publishing,
                        # is what keeps import admission one code path
                        # with local prefill).
                        row, plan = self._admit_row(
                            free_rows, active, wt, wd, req0,
                            use_cache=not imported and sess_art is None)
                        if row is None:
                            # Allocation pressure: a strictly-higher-
                            # priority head may suspend the lowest-priority
                            # resident row (its pages free, its artifact
                            # parks for resumption) and retry.
                            if self._maybe_preempt(req0.priority, active,
                                                   free_rows):
                                continue
                            break   # wait for an in-flight row to finish
                        pending.popleft()
                        if imported:
                            # Imports keep their exporter's rid (the
                            # sampling folds must continue that stream) —
                            # the local counter is neither consulted nor
                            # burned.
                            res = self._admit_import(row, item, wt, wd,
                                                     need, active)
                        elif sess_art is not None:
                            rid = self._next_rid
                            self._next_rid += 1
                            res = self._admit_session(row, rid, item, wt,
                                                      wd, need, active,
                                                      sess_art)
                        else:
                            rid = self._next_rid
                            self._next_rid += 1
                            res = self._admit_dispatch(row, rid, item, wt,
                                                       wd, need, active,
                                                       plan)
                        if res is not None:
                            burst.append(res)
                # Every row busy: an incremental arrival of strictly
                # higher priority must not wait a full request behind
                # lower-priority residents — one eager non-blocking
                # pull (pending stays <= 1, preserving the lazy-pull
                # bound) makes it visible, and a successful preemption
                # loops back to admit it before the next decode block.
                if (not free_rows and incremental and self.preemptible
                        and bad_request is None
                        and not self._weight_updates):
                    pull(block=False)
                    if pending:
                        it0 = pending[0]
                        r0 = it0.request if isinstance(it0, Prefilled) \
                            else it0
                        with self._phase("batcher.admit"):
                            preempted = self._maybe_preempt(
                                r0.priority, active, free_rows)
                        if preempted:
                            yield from self._finalize_burst(
                                burst, active, free_rows)
                            continue
                yield from self._finalize_burst(burst, active, free_rows)
                # Streaming flush point 1: freshly admitted rows' first
                # tokens (prefill output) go out NOW — the streamed
                # TTFT is the prefill latency, not prefill + one block.
                self._flush_streams(active)
                if not active:
                    if bad_request is not None:
                        raise bad_request
                    if self._parked:
                        continue    # resume parked work before idling
                    if self._weight_updates:
                        # Between generations, nothing resident: THE
                        # weight-update point — fold/replace, flush
                        # stale KV caches, then resume admission.
                        self._apply_pending_weight_updates()
                        continue
                    pull()
                    if not pending and exhausted:
                        return
                    continue
                if (self._fused
                        and any(row.decoding for row in active.values())
                        and any(not row.decoding
                                for row in active.values())):
                    # Stall-free tick: decode block + budgeted chunk
                    # slots in ONE dispatch (see _step_fused).  Ticks
                    # with only one phase live take the plain paths
                    # below — there is nothing to fuse.
                    yield from self._step_fused(active, free_rows)
                    self._flush_streams(active)
                    continue
                if self._chunk_prefill is not None:
                    done_row = self._advance_prefill(active)
                    if done_row is not None:
                        with self._phase("batcher.retire"):
                            done = self._completion(active[done_row])
                            self._finish_completed(done_row, active,
                                                   free_rows)
                        yield done
                if any(row.decoding for row in active.values()):
                    if self.draft_cfg is not None:
                        yield from self._step_spec(active, free_rows)
                    elif self._pipelined:
                        yield from self._step_pipelined(active, free_rows)
                    else:
                        yield from self._step(active, free_rows)
                    # Streaming flush point 2: this block's tokens, one
                    # call per still-resident streaming row (rows that
                    # FINISHED inside the block already yielded their
                    # Completion — the full list — so their tail never
                    # needs a partial).
                    self._flush_streams(active)
        finally:
            # A consumer that stops early (break / close) must not leak
            # the in-flight rows' pages (or a stale pipelined dispatch
            # and its device carry).
            self._inflight = None
            self._pipe_carry = self._pipe_host = None
            # Whatever the consumer left behind leaves the batcher here.
            for item in itertools.chain(self._parked, pending):
                self._request_done("abandoned", item)
            self._parked.clear()    # pages already released at suspend
            for row, state in list(active.items()):
                self._request_done("abandoned", state.req, state)
                self._finish(row, active, free_rows)
            self._tick_roll(more=False)
            # Dropped only after the rows are released, so an export
            # admitted the instant the fence clears can never borrow a
            # row the dying loop still owns.  Weight updates still
            # queued apply HERE (under the same lock a new
            # swap_adapter would take) so their waiters always get
            # their callback — a dying loop must not strand a swap.
            with self._export_lock:
                self._loop_active = False
                self._apply_pending_weight_updates()

    def _flush_streams(self, active: Dict[int, "_Row"]) -> None:
        """Push each streaming row's not-yet-streamed ``out`` suffix to
        its ``Request.on_tokens`` callback (per-token incremental
        replies on the serving path).  Token STREAMS are not touched —
        this only reads ``out`` — so every mode's equivalence contract
        is unaffected; under the pipelined loop tokens stream when they
        RETIRE, exactly when the host learns them.  A raising callback
        is disarmed: a broken consumer costs its stream, never the
        request or the loop."""
        with self._phase("batcher.emit"):
            for row in active.values():
                cb = row.req.on_tokens
                if cb is None:
                    continue
                n = len(row.out)
                if n <= row.streamed:
                    continue
                chunk = [int(t) for t in row.out[row.streamed:n]]
                off = row.streamed
                row.streamed = n
                try:
                    cb(chunk, off)
                except Exception:
                    row.req.on_tokens = None

    def _ensure_sides(self, row: int, length: int, start: int = 0) -> None:
        """Back ABSOLUTE positions [0, length) of ``row`` on the target
        (and, speculative mode, draft) side.  Under EVA the pages back
        entries: the most the row holds while its context grows from
        ``start`` to ``length``."""
        length = self.cfg.cache_entries_peak(start, length)
        self.t_side.ensure(row, length)
        if self.d_side is not None:
            self.d_side.ensure(row, length)

    def _admit_dispatch(self, row: int, rid: int, req: Request, wt: int,
                        wd: int, need: int, active: Dict[int, _Row],
                        plan: Optional[_PrefixPlan] = None
                        ) -> Optional[tuple]:
        """Reserve + DISPATCH ``req``'s prefill into ``row`` without the
        first-token host sync; ``wt``/``wd``/``need`` are the per-side
        page reservations (and the position cap they cover) run()
        admitted it under, ``plan`` the prefix-cache mapping it chose
        the row's shard for.  Returns ``(row, state, device_token,
        shard)`` for run()'s burst finalize — ``None`` in chunked mode,
        which makes no model call here."""
        t_admit = time.perf_counter()
        tick = self._tick
        tick["admitted"] += 1
        self._trace_event(req, "admit", rid=rid, row=row,
                          prompt_len=int(req.prompt.size),
                          cached=plan is not None, tick=tick["tick"])
        length = req.prompt.size
        width = -(-length // self.prefill_bucket) * self.prefill_bucket
        if plan is not None:
            # Map the cached prefix pages read-only BEFORE any ensure()
            # call: the references protect them from the LRU evictor
            # while this admission allocates its own pages.
            self._pcache.acquire(row, plan.nodes)
            self._pcache.count("hits")
            self._pcache.count("hit_pages", len(plan.nodes))
            self._pcache.count("hit_tokens", plan.tail_start)
            wt -= plan.save
            if self.d_side is not None:
                # Coupled nodes: the draft-side reservation shrinks by
                # the same mapped-page count.
                wd -= plan.save
        elif self._pcache is not None and self._req_digests(req):
            self._pcache.count("misses")
        if self._chunk_prefill is not None:
            # Chunked mode: no model call here — the run loop advances
            # one chunk per tick, interleaved with the batched decode
            # step.  On a cache hit, filling starts AT THE TAIL (the
            # mapped pages already hold chunks [0, filled)).
            self._ensure_sides(row, width)
            padded = np.zeros((1, width), np.int32)
            padded[0, :length] = req.prompt
            state = _Row(rid=rid, req=req, pos=int(length),
                         step=1, last=0, out=[], worst_pages=wt,
                         worst_draft=wd, t_admit=t_admit,
                         admit_tick=tick["tick"], padded=padded,
                         filled=0 if plan is None else plan.tail_start,
                         decoding=False, limit=need)
            active[row] = state
            return None
        if plan is not None:
            return self._admit_cached(row, rid, req, wt, wd, need,
                                      active, plan, t_admit)
        tick["prefill_tokens"] += width
        if self._eva_roll is not None:
            tok = self._eva_prefill(row, rid, req.prompt)
            tok.copy_to_host_async()
            # The prompt's windows are closed: from here the row can hold
            # no more than its decode still adds (a whole window is held
            # only across a close), and the reservation shrinks to that.
            wt = -(-self.cfg.cache_entries_peak(int(length), need)
                   // self.page_size)
            state = _Row(rid=rid, req=req, pos=int(length), step=1, last=0,
                         out=[], worst_pages=wt, t_admit=t_admit,
                         admit_tick=tick["tick"], prefill_tokens=width,
                         limit=need)
            active[row] = state
            self._eva_account(active)
            return row, state, tok, 0
        self._ensure_sides(row, width)
        padded = np.zeros((1, width), np.int32)
        padded[0, :length] = req.prompt
        s, toks, table = self._one_hot_call(self.t_side, row, padded)
        lengths = np.ones((self.n_shards,), np.int32)
        lengths[s] = length
        rids = np.zeros((self.n_shards,), np.int32)
        rids[s] = rid
        # a typed stack's prefill fills row slot ``row`` from an empty state
        slot = ([jnp.asarray([row], jnp.int32)]
                if self.cfg.layer_types is not None else [])
        self.pool, tok = self._prefill_fn(width)(
            self.params, self.pool, table, toks,
            jnp.asarray(lengths), jnp.asarray(rids), *slot)
        if self.d_side is not None:
            _, dtoks, dtable = self._one_hot_call(self.d_side, row, padded)
            self.d_side.pool = self._draft_chunk(
                self.draft_params, self.d_side.pool, dtable, dtoks,
                jnp.asarray(0, jnp.int32))
        tok.copy_to_host_async()    # transfer overlaps later dispatches
        state = _Row(rid=rid, req=req, pos=int(length), step=1,
                     last=0, out=[], worst_pages=wt, worst_draft=wd,
                     t_admit=t_admit, admit_tick=tick["tick"],
                     prefill_tokens=width, limit=need)
        active[row] = state
        self._pcache_insert(row, state)
        return row, state, tok, s

    def _eva_prefill(self, row: int, rid: int, prompt: np.ndarray):
        """Prefill ``prompt`` into ``row`` window by window: each chunk
        attends the summaries so far and itself, writes its exact entries
        behind them, and a whole window is closed at once (its pages but
        the summaries' return to the allocator before the next window
        takes them).  Returns the device token sampled at the prompt's
        last position; nothing here waits for the device."""
        w = self.cfg.eva_window
        length, bucket = int(prompt.size), self.prefill_bucket
        tok = None
        for start in range(0, length, w):
            n = min(w, length - start)
            width = -(-n // bucket) * bucket
            self._ensure_sides(row, start + width, start=start)
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = prompt[start:start + n]
            self.pool, tok = self._prefill_fn(width)(
                self.params, self.pool,
                jnp.asarray(self.t_side.table_np()[row:row + 1]),
                jnp.asarray(padded), jnp.asarray([n], jnp.int32),
                jnp.asarray([rid], jnp.int32), jnp.asarray(start, jnp.int32))
            if n == w:
                self._eva_roll_row(row, start // w)
        return tok

    def _admit_cached(self, row: int, rid: int, req: Request, wt: int,
                      wd: int, need: int, active: Dict[int, _Row],
                      plan: _PrefixPlan, t_admit: float) -> tuple:
        """Admission with a mapped cached prefix: prefill ONLY the
        uncached tail at its true offset (the jitted traced-offset
        chunk writer — one compile per tail-width bucket) and sample
        the first token from the prompt's last position.  A
        page-aligned full hit first copies the deepest cached page into
        a fresh own page (``_copy_page`` copy-on-write) so the
        last-token rewrite never touches shared state."""
        side = self.t_side
        E = int(req.prompt.size)
        if plan.cow:
            cow_node = plan.nodes[-1]
            src = cow_node.page
            self._pcache.unmap_last(row)
            side.ensure(row, len(plan.nodes) * self.page_size)
            dst = np.full((self.n_shards,), side.sink, np.int32)
            dst[side.alloc.shard_of(row)] = side.alloc.rows[row][0]
            side.pool = side.copy(side.pool, src, dst)
            if self.d_side is not None:
                # The deepest page's DRAFT twin gets the same one-token
                # rewrite at E-1 (the spec round's draft scan writes
                # it), so it is copied-on-write symmetrically.
                dside = self.d_side
                dside.ensure(row, len(plan.nodes) * self.page_size)
                ddst = np.full((self.n_shards,), dside.sink, np.int32)
                ddst[dside.alloc.shard_of(row)] = dside.alloc.rows[row][0]
                dside.pool = dside.copy(dside.pool, cow_node.dpage, ddst)
            # The reference protected the source page(s) through the
            # ensure() above (eviction runs under allocation pressure);
            # the copies are dispatched, so it can be dropped now.
            self._pcache.release_nodes(row, [cow_node])
            self._pcache.count("cow_copies")
        ts = plan.tail_start
        tlen = E - ts
        w = -(-tlen // self.prefill_bucket) * self.prefill_bucket
        # Clamp the allocation at the reservation: pad positions past
        # ``need`` write reserved-but-unread slots or sink columns (the
        # cold path's prompt padding behaves identically), and
        # allocations beyond ``worst_pages`` would corrupt headroom().
        self._ensure_sides(row, min(ts + w, need))
        self._tick["prefill_tokens"] += w
        padded = np.zeros((1, w), np.int32)
        padded[0, :tlen] = req.prompt[req.prompt.size - tlen:]
        s, toks, table = self._one_hot_call(side, row, padded)
        caps = np.full((self.n_shards,), -1, np.int32)
        caps[s] = tlen - 1
        rids = np.zeros((self.n_shards,), np.int32)
        rids[s] = rid
        self.pool, tok = self._tail_prefill(
            self.params, self.pool, table, toks,
            jnp.asarray(ts, jnp.int32), jnp.asarray(caps),
            jnp.asarray(rids))
        if self.d_side is not None:
            # The draft pool's tail: the same uncached suffix written
            # at the same offset through the draft chunk writer — its
            # cached prefix pages (the twins mapped above) already
            # cover [0, ts).
            _, dtoks, dtable = self._one_hot_call(self.d_side, row,
                                                  padded)
            self.d_side.pool = self._draft_chunk(
                self.draft_params, self.d_side.pool, dtable, dtoks,
                jnp.asarray(ts, jnp.int32))
        tok.copy_to_host_async()    # transfer overlaps later dispatches
        state = _Row(rid=rid, req=req, pos=E, step=1, last=0, out=[],
                     worst_pages=wt, worst_draft=wd, t_admit=t_admit,
                     admit_tick=self._tick["tick"], prefill_tokens=w,
                     limit=need)
        active[row] = state
        self._pcache_insert(row, state)
        return row, state, tok, s

    def _pcache_insert(self, row: int, state: _Row) -> None:
        """Publish ``row``'s freshly prefilled full prompt pages into
        the prefix cache (no-op without one)."""
        if self._pcache is None:
            return
        digs = self._req_digests(state.req)
        if digs:
            self._pcache.insert_row(
                row, self.t_side.alloc.shard_of(row), digs, state)

    def _admit_finalize(self, state: _Row,
                        tok: int) -> Optional[Completion]:
        """Record a burst-synced first token; Completion when it already
        finishes the request."""
        self._stamp_first(state)
        if state.out:
            # Resumed suspended import: the stream up to the suspension
            # point is already in place (and a finished row is never
            # suspended, so no instant completion here either).
            return None
        state.last = tok
        state.out = [tok]
        if tok == state.req.stop_token or state.req.max_new_tokens == 1:
            return self._completion(state)
        return None

    def _finalize_burst(self, burst: list, active: Dict[int, _Row],
                        free_rows: List[int]) -> Iterator[Completion]:
        """Drain a dispatch burst: fetch each admission's first token
        (the async transfers have been in flight since dispatch, so
        these mostly find the data ready) and yield any instant
        completions.  Clears ``burst`` in place."""
        finished = []
        for row, state, tok, s in burst:
            with self._phase("batcher.prefill_sync"):
                first = int(np.asarray(tok)[s])
            with self._phase("batcher.retire"):
                done = self._admit_finalize(state, first)
                if done is not None:
                    self._finish_completed(row, active, free_rows)
                    finished.append(done)
        burst.clear()
        yield from finished

    def _advance_prefill(self, active: Dict[int, _Row]) -> Optional[int]:
        """Write ONE chunk of the oldest still-prefilling row; flips the
        row to decoding once its whole padded prompt is in.  Returns the
        row id when that row just finished a request outright (first
        token == stop, or max_new_tokens == 1)."""
        filling = [(row.rid, r) for r, row in active.items()
                   if not row.decoding]
        if not filling:
            return None
        with self._phase("batcher.admit"):
            _, r = min(filling)
            row = active[r]
            c = self.prefill_chunk
            self._tick["prefill_tokens"] += c
            row.prefill_tokens += c
            chunk = row.padded[:, row.filled:row.filled + c]
            length = row.req.prompt.size
            cap = length - 1 - row.filled   # in-range only on last chunk
            s, ctoks, table = self._one_hot_call(self.t_side, r, chunk)
            caps = np.full((self.n_shards,), -1, np.int32)
            caps[s] = cap
            rids = np.zeros((self.n_shards,), np.int32)
            rids[s] = row.rid
            self.pool, tok = self._chunk_prefill(
                self.params, self.pool, table, ctoks,
                jnp.asarray(row.filled, jnp.int32),
                jnp.asarray(caps), jnp.asarray(rids))
            if self.d_side is not None:
                # The draft's prompt chunks advance in lockstep so it is
                # ready to propose the moment the row flips to decoding.
                _, dtoks, dtable = self._one_hot_call(self.d_side, r, chunk)
                self.d_side.pool = self._draft_chunk(
                    self.draft_params, self.d_side.pool, dtable, dtoks,
                    jnp.asarray(row.filled, jnp.int32))
            row.filled += c
        if row.filled < row.padded.shape[1]:
            return None
        with self._phase("batcher.prefill_sync"):
            tok = int(np.asarray(tok)[s])   # the capture chunk's sample
        with self._phase("batcher.retire"):
            self._stamp_first(row)
            row.last = tok
            row.out.append(tok)
            row.decoding = True
            # Publish the now fully-dispatched prompt pages; chunked mode
            # must wait until here — at admission the chunks had not been
            # written, and a concurrent hit would have mapped garbage.
            self._pcache_insert(r, row)
        if tok == row.req.stop_token or row.req.max_new_tokens == 1:
            return r
        return None

    def _step_fused(self, active: Dict[int, _Row],
                    free_rows: List[int]) -> Iterator[Completion]:
        """One FUSED tick: the decode block over every decoding row
        plus up to ``(tokens_per_tick - n_decode*K) // c`` prefill
        chunk slots (oldest filling rows first, at most one chunk per
        row — chunk N+1's attention reads chunk N's cache writes, so a
        row cannot coalesce with itself), all in ONE dispatch and ONE
        host sync.  The budget floor is one slot, so a saturated
        decode set still fills exactly as fast as the phase-split tick;
        the budget ceiling is what stops a burst of long prompts from
        monopolizing ticks.  Chunk bookkeeping mirrors
        :meth:`_advance_prefill` (a row whose last chunk lands here
        flips to decoding with its sampled first token and joins the
        NEXT tick's block — tokens are pure (rid, step) functions, so
        the stream is unchanged); decode commits mirror :meth:`_step`."""
        K = self.multi_step
        c = self.prefill_chunk
        with self._phase("batcher.prep"):
            decoding = {r: row for r, row in active.items()
                        if row.decoding}
            filling = sorted((row.rid, r) for r, row in active.items()
                             if not row.decoding)
            slots = max(1, (self.tokens_per_tick - len(decoding) * K) // c)
            picks = [r for _, r in filling[:slots]]
            S = self._pow2(len(picks))
            ctable = np.full((S, self.t_side.np_max), self.t_side.sink,
                             np.int32)
            chunks = np.zeros((S, c), np.int32)
            cpos = np.zeros((S,), np.int32)
            caps = np.full((S,), -1, np.int32)
            crids = np.zeros((S,), np.int32)
            tbl = self.t_side.table_np()
            for i, r in enumerate(picks):
                row = active[r]
                ctable[i] = tbl[r]
                chunks[i] = row.padded[0, row.filled:row.filled + c]
                cpos[i] = row.filled
                caps[i] = row.req.prompt.size - 1 - row.filled
                crids[i] = row.rid
            toks = np.zeros((self.rows,), np.int32)
            positions = np.zeros((self.rows,), np.int32)
            rids = np.zeros((self.rows,), np.int32)
            steps = np.zeros((self.rows,), np.int32)
            for r, row in decoding.items():
                self._ensure_sides(r, min(row.pos + K, row.limit))
                toks[r] = row.last
                positions[r] = row.pos
                rids[r] = row.rid
                steps[r] = row.step
            table = self.t_side.decode_table(active, decoding)
        with self._phase("batcher.dispatch"):
            self.pool, nxt, first = self._fused_step(
                self.params, self.pool, table, jnp.asarray(toks),
                jnp.asarray(positions), jnp.asarray(rids),
                jnp.asarray(steps), jnp.asarray(ctable),
                jnp.asarray(chunks), jnp.asarray(cpos), jnp.asarray(caps),
                jnp.asarray(crids))
        with self._phase("batcher.readback"):
            nxt = np.asarray(nxt)   # ONE sync covers chunks AND block
            first = np.asarray(first)
        self.fused_ticks += 1
        self.fused_chunk_tokens += len(picks) * c
        self.fused_decode_tokens += len(decoding) * K
        self._tick_block("fused", len(decoding), K)
        self._tick["prefill_tokens"] += len(picks) * c
        finished = []
        with self._phase("batcher.retire"):
            for i, r in enumerate(picks):
                row = active[r]
                row.filled += c
                row.prefill_tokens += c
                if row.filled < row.padded.shape[1]:
                    continue
                tok = int(first[i])     # the capture chunk's sample
                self._stamp_first(row)
                row.last = tok
                row.out.append(tok)
                row.decoding = True
                self._pcache_insert(r, row)
                if tok == row.req.stop_token \
                        or row.req.max_new_tokens == 1:
                    finished.append(self._completion(row))
                    self._finish_completed(r, active, free_rows)
            for r in list(decoding):
                row = active[r]
                for j in range(K):
                    tok = int(nxt[r, j])
                    row.out.append(tok)
                    row.step += 1
                    row.pos += 1
                    row.last = tok
                    if tok == row.req.stop_token or row.step >= \
                            row.req.max_new_tokens:
                        finished.append(self._completion(row))
                        self._finish_completed(r, active, free_rows)
                        break
        yield from finished

    def _step(self, active: Dict[int, _Row],
              free_rows: List[int]) -> Iterator[Completion]:
        """One K-step block (``multi_step``; K=1 = classic per-token
        tick): a single dispatch decodes K tokens per decoding row and
        the host syncs one [rows, K] block.  Rows that stop (or exhaust
        quota) mid-block have their remaining in-block tokens discarded
        here; the corresponding device writes landed inside the row's
        reservation (ensure clamped at ``row.limit``) or on sink
        columns, so no live state was touched.  Admission and
        chunked-prefill advance happen between blocks.  (Chunked prefill
        keeps still-filling rows out: their table rows mask to the sink
        so the batched scatter cannot touch their pages.)"""
        self._state_rows = len(active)
        eva_w = self.cfg.eva_window if self._eva_roll is not None else 0
        with self._phase("batcher.prep"):
            toks = np.zeros((self.rows,), np.int32)
            positions = np.zeros((self.rows,), np.int32)
            rids = np.zeros((self.rows,), np.int32)
            steps = np.zeros((self.rows,), np.int32)
            decoding = {r: row for r, row in active.items()
                        if row.decoding}
            K, decode = self._block_of(decoding.values())
            for r, row in decoding.items():
                self._ensure_sides(r, min(row.pos + K, row.limit),
                                   start=row.pos)
                toks[r] = row.last
                positions[r] = row.pos
                rids[r] = row.rid
                steps[r] = row.step
            table = self.t_side.decode_table(active, decoding)
        with self._phase("batcher.dispatch"):
            self.pool, nxt = decode(
                self.params, self.pool, table, jnp.asarray(toks),
                jnp.asarray(positions), jnp.asarray(rids),
                jnp.asarray(steps))
        with self._phase("batcher.readback"):
            nxt = self._tick_moe(np.asarray(nxt))   # ONE host sync a block
        self._tick_block("sync", len(decoding), K,
                         (row.pos + K for row in decoding.values()))
        finished = []
        with self._phase("batcher.retire"):
            for r in list(decoding):
                row = active[r]
                for j in range(K):
                    tok = int(nxt[r, j])
                    row.out.append(tok)
                    row.step += 1
                    row.pos += 1
                    row.last = tok
                    if tok == row.req.stop_token or row.step >= \
                            row.req.max_new_tokens:
                        finished.append(self._completion(row))
                        self._finish_completed(r, active, free_rows)
                        break
                else:
                    if eva_w and row.pos % eva_w == 0:
                        # the block's last step filled the row's window
                        self._eva_roll_row(r, row.pos // eva_w - 1)
            if eva_w:
                self._eva_account(active)
        yield from finished

    def _step_pipelined(self, active: Dict[int, _Row],
                        free_rows: List[int]) -> Iterator[Completion]:
        """One PIPELINED K-block tick (``pipeline_depth=1``): dispatch
        block N+1 BEFORE syncing block N, with the whole decode carry —
        last token, positions, AND steps — resident on device: the
        jitted block returns them as outputs that feed the next dispatch
        directly, so a steady-state block uploads nothing at all, and
        retire the previous block (host bookkeeping one block late).
        Deterministic state (pos, step) advances at dispatch;
        token-dependent state (out, last, stop detection) at retire.
        Quota gating at dispatch uses dispatched-token counts, so a
        block may overrun a quota by up to K-1 tokens; retire truncates.
        Host-side inputs (fresh admissions' token/position/step, the rid
        vector, the ``use_host`` merge mask) are rebuilt only when the
        dispatch set actually changed — admission, a finish, a
        chunked-prefill flip — exactly like the page table, and are
        cached device constants otherwise.

        Stop/quota detection lags one block; the overshoot block's
        writes land inside the row's clamped reservation or on sink
        columns and its tokens fail :meth:`_retire`'s rid-checked
        ticket — the discard semantics ``_step`` already documents for
        mid-block stops — so token streams are IDENTICAL to
        ``pipeline_depth=0`` (same ops, same (rid, step) sample folds,
        only the sync point moves).

        Under EVA a window is closed where its last position is
        DISPATCHED: a close takes the pool, the row's table and a window
        number, never a token, and the position it hangs on advanced just
        above, so ``jit_eva_roll`` is enqueued behind the block that fills
        the window and the row's pages are trimmed in the same tick
        (:meth:`_retire` closes nothing).  The ring's ``eva_*`` fields
        are the host's view, which here is the dispatched one."""
        self._state_rows = len(active)
        eva_w = self.cfg.eva_window if self._eva_roll is not None else 0
        dispatch = {r: row for r, row in active.items()
                    if row.decoding and row.step < row.req.max_new_tokens}
        K, decode = self._block_of(dispatch.values())
        prev = self._inflight
        if dispatch:
            with self._phase("batcher.prep"):
                prev_ticket = {} if prev is None else prev[1]
                ticket = {r: row.rid for r, row in dispatch.items()}
                # Rows entering this block from HOST values: fresh
                # admissions, chunked-prefill flips, re-admissions into
                # a freed row — anything the device carry does not cover.
                fresh = frozenset(r for r, rid in ticket.items()
                                  if prev_ticket.get(r) != rid)
                for r, row in dispatch.items():
                    self._ensure_sides(r, min(row.pos + K, row.limit),
                                       start=row.pos)
                table = self.t_side.decode_table(active, dispatch)
                key = (tuple(sorted(ticket.items())), fresh)
                host = self._pipe_host
                stale = host is None or host[0] != key
                if stale:
                    # Rows outside the dispatch (free, or parked until
                    # their last block retires) enter from the host too,
                    # at position 0 like ``_step``'s: left on the carry
                    # their positions would grow by K every block, and
                    # the paged kernel would walk a context of that
                    # length over sink pages for each, in every layer.
                    toks = np.zeros((self.rows,), np.int32)
                    use_host = np.ones((self.rows,), bool)
                    positions = np.zeros((self.rows,), np.int32)
                    steps = np.zeros((self.rows,), np.int32)
                    rids = np.zeros((self.rows,), np.int32)
                    for r, row in dispatch.items():
                        rids[r] = row.rid
                        if r in fresh:
                            toks[r] = row.last
                            positions[r] = row.pos
                            steps[r] = row.step
                        else:
                            use_host[r] = False
            with self._phase("batcher.dispatch"):
                if stale:
                    host = (key, jnp.asarray(use_host), jnp.asarray(toks),
                            jnp.asarray(positions), jnp.asarray(steps),
                            jnp.asarray(rids))
                    self._pipe_host = host
                carry = self._pipe_carry
                if carry is None:       # pipeline start: fresh rows only
                    carry = (jnp.asarray(np.zeros((self.rows,),
                                                  np.int32)),) * 3
                self.pool, nxt, ct, cp, cs = decode(
                    self.params, self.pool, table, host[1], host[2],
                    host[3], host[4], carry[0], carry[1], carry[2],
                    host[5])
                nxt.copy_to_host_async()    # transfer overlaps the block
                self._pipe_carry = (ct, cp, cs)
                self._inflight = (nxt, ticket, K)
                for r, row in dispatch.items():
                    row.pos += K
                    row.step += K
                    if (eva_w and row.pos % eva_w == 0
                            and row.step < row.req.max_new_tokens):
                        # The block just enqueued fills the row's window
                        # and the row is owed more: close it NOW, behind
                        # that block (the donated pool orders the two, and
                        # the next block after them).  No token enters a
                        # close, so nothing here waits for the readback; a
                        # row that stops on a token still in flight gets a
                        # close it did not need, over pages of its own.
                        self._eva_roll_row(r, row.pos // eva_w - 1)
            self._tick_block("pipelined", len(dispatch), K,
                             (row.pos for row in dispatch.values()))
        else:
            self._inflight = None
            self._pipe_carry = self._pipe_host = None
        if prev is not None:
            yield from self._retire(prev, active, free_rows)
        if eva_w:
            self._eva_account(active)

    def _retire(self, inflight, active: Dict[int, _Row],
                free_rows: List[int]) -> Iterator[Completion]:
        """Sync ONE pipelined K-block (a block behind the newest) and do
        its token-dependent bookkeeping; rows that stopped at the
        previous retire (or were re-admitted since) fail the rid check
        and their block is dropped."""
        nxt, ticket, K = inflight
        # The lagged-block sync IS the pipelined loop's per-block wait
        # (dispatch is a non-blocking enqueue).
        # Had the device finished the lagged block before the host asked
        # for it?  Ready on arrival, the device was waiting for the host;
        # not ready, the readback long and every stall counter near 0,
        # the host was waiting for the device.  (A synchronous loop asks
        # right after it dispatches: never ready, so it does not ask.)
        self._tick["ready"] = int(nxt.is_ready())
        with self._phase("batcher.readback"):
            nxt = self._tick_moe(np.asarray(nxt))   # one block behind
        if self._tick["name"] != "decode.block":    # the draining tick
            self._tick_block(self._mode, len(ticket), K)
        finished = []
        with self._phase("batcher.retire"):
            for r, rid in ticket.items():
                row = active.get(r)
                if row is None or row.rid != rid:
                    continue            # overshoot block of a freed row
                for j in range(K):
                    tok = int(nxt[r, j])
                    row.out.append(tok)
                    row.last = tok
                    if (tok == row.req.stop_token
                            or len(row.out) >= row.req.max_new_tokens):
                        finished.append(self._completion(row))
                        # _finish_completed parks session KV first: the
                        # export clamps to the committed boundary, so
                        # the lagged host view cannot overshoot the
                        # artifact.
                        self._finish_completed(r, active, free_rows)
                        break
        yield from finished

    def _step_spec(self, active: Dict[int, _Row],
                   free_rows: List[int]) -> Iterator[Completion]:
        """One speculative dispatch over every decoding row: commit
        each row's leading accepted run + correction (1..n_draft+1
        tokens) — times R in-graph rounds when multi_step composes
        (R = _spec_rounds > 1), committed round-by-round so stop/quota
        truncation is exact per round."""
        R = max(1, self._spec_rounds)
        with self._phase("batcher.prep"):
            toks = np.zeros((self.rows,), np.int32)
            # Rows with no live request still run the jitted round: park
            # their positions at max_len (within the draft cache's
            # +n_draft slack, clamped onto the sink page in the paged
            # target) so their dummy draft writes can never clobber the
            # broadcast prefix at positions 0..n_draft-1 of a draft-cache
            # row a future request will reuse.
            positions = np.full((self.rows,), self.max_len, np.int32)
            rids = np.zeros((self.rows,), np.int32)
            steps = np.zeros((self.rows,), np.int32)
            decoding = {r: row for r, row in active.items()
                        if row.decoding}
            for r, row in decoding.items():
                # The verify chunk writes positions [pos, pos + n_draft]
                # (and the draft's k+1 scan steps write the same range of
                # ITS pool); R fused rounds extend the worst case to
                # R*(n_draft+1), clamped at limit — past-limit writes
                # land on sink-clamped columns and their tokens are
                # discarded at commit (same overrun argument as plain
                # multi_step).
                self._ensure_sides(r, min(row.pos + R * (self.n_draft + 1),
                                          row.limit))
                toks[r] = row.last
                positions[r] = row.pos
                rids[r] = row.rid
                steps[r] = row.step
            table = self.t_side.decode_table(active, decoding)
            dtable = self.d_side.decode_table(active, decoding)
        with self._phase("batcher.dispatch"):
            self.pool, self.d_side.pool, g, n_commit = self._spec_round(
                self.params, self.pool, self.draft_params,
                self.d_side.pool, table, dtable, jnp.asarray(toks),
                jnp.asarray(positions), jnp.asarray(rids),
                jnp.asarray(steps))
        with self._phase("batcher.readback"):
            g = np.asarray(g)
            n_commit = np.asarray(n_commit)
        self._tick_block("spec", len(decoding), R * (self.n_draft + 1))
        if R == 1:
            g, n_commit = g[None], n_commit[None]   # [R=1, rows, ...]
        # Observability: the acceptance rate is THE speculative-serving
        # health number (a weak draft only costs rate, never correctness).
        self.spec_rounds += R
        finished = []
        with self._phase("batcher.retire"):
            for i in range(R):
                live = [r for r in decoding if r in active]
                if not live:
                    break
                self.spec_committed += int(sum(int(n_commit[i, r])
                                               for r in live))
                self.spec_row_rounds += len(live)
                finished += self._commit_rows(g[i], n_commit[i], live,
                                              active, free_rows)
        yield from finished

    def _commit_rows(self, g, nc, rows, active: Dict[int, _Row],
                     free_rows: List[int]) -> List[Completion]:
        """Commit one speculative round's outputs to ``rows`` — ONE code
        path for every round of a dispatch (_step_spec).  Quota and stop
        truncation: either way the row FINISHES, so the committed-
        stream/cache consistency question is moot.  Returns the rows'
        completions, in finish order."""
        finished = []
        for r in rows:
            row = active[r]
            emit = list(g[r, :int(nc[r])])
            remaining = row.req.max_new_tokens - row.step
            emit = emit[:remaining]
            if row.req.stop_token is not None and \
                    row.req.stop_token in emit:
                emit = emit[:emit.index(row.req.stop_token) + 1]
            row.out.extend(int(t) for t in emit)
            row.step += len(emit)
            row.pos += len(emit)
            row.last = int(emit[-1]) if emit else row.last
            if (row.step >= row.req.max_new_tokens
                    or (row.req.stop_token is not None
                        and row.out and row.out[-1]
                        == row.req.stop_token)):
                finished.append(self._completion(row))
                self._finish_completed(r, active, free_rows)
        return finished

    # -- end-to-end deadlines ----------------------------------------------

    def _cancel_expired(self, active: Dict[int, _Row],
                        free_rows: List[int]) -> Iterator["Expired"]:
        """Cancel every resident row whose deadline has passed —
        exactly like a finish (pages released, row freed for the next
        admission) except an :class:`Expired` is yielded instead of a
        Completion.  The pipelined loop may have one more block in
        flight for the row; its writes land inside the clamped
        reservation or on sink columns and its tokens fail the
        rid-checked retire ticket, the same discard semantics a
        mid-block stop already has."""
        expired = [r for r, row in active.items()
                   if row.req.deadline is not None and row.req.expired]
        for r in expired:
            row = active[r]
            self.deadline_cancels += 1
            rid, req = row.rid, row.req
            self._trace_event(req, "deadline_cancel", rid=rid,
                              where="resident", step=row.step)
            self._request_done("expired", req, row)
            self._finish(r, active, free_rows)
            yield Expired(rid=rid, request=req)

    # -- priority preemption / drain migration ----------------------------

    def _suspendable(self, state: _Row) -> bool:
        """A row whose mid-stream state can be snapshotted right now:
        it is decoding (a still-filling chunked prefill has no complete
        KV to export), its first token has been fetched (an un-settled
        admission burst entry has not), and the mode supports per-row
        export at all."""
        return (self.preemptible and state.decoding and bool(state.out)
                and state.t_first > 0)

    def _suspend_row(self, r: int, active: Dict[int, _Row],
                     free_rows: List[int]) -> dict:
        """Snapshot row ``r`` into a resumable KV artifact (its pages +
        sampler state incl. the emitted tokens) and release it — a
        suspended request IS a KV export, re-admitted through
        ``submit(prefilled=...)`` here or on any matching batcher."""
        state = active[r]
        art = self._export_row(r, state)
        self._request_done("suspended", state.req, state)
        self._finish(r, active, free_rows)
        return art

    def _resume_fits(self, req: Request) -> bool:
        """Whether ``req``'s suspended artifact could EVER re-import
        into this pool: the import backs every position with own pages
        (no prefix-cache discount — the pages arrive in the payload),
        so a row admitted only thanks to a deep cache plan on a tight
        pool must not be suspended locally — its resume would exceed
        the pool outright and the parked artifact could never land."""
        wt, wd, _ = self._worst_pages(req)
        # (one page of each pool is the reserved sink)
        if wt > self.t_side.n_pages - 1:
            return False
        return self.d_side is None or wd <= self.d_side.n_pages - 1

    def _maybe_preempt(self, priority: int, active: Dict[int, _Row],
                       free_rows: List[int]) -> bool:
        """Suspend the lowest-priority suspendable row STRICTLY below
        ``priority`` (ties: the newest) and park its artifact for local
        resumption; False when no such victim exists.  Strictness is
        the anti-thrash rule: equal-priority work never preempts, and a
        parked row can only displace classes below its own."""
        if not self.preemptible:
            return False
        victims = [(row.req.priority, -row.rid, r)
                   for r, row in active.items()
                   if self._suspendable(row)
                   and row.req.priority < priority
                   and self._resume_fits(row.req)]
        if not victims:
            return False
        _, _, r = min(victims)
        req = active[r].req
        self._trace_event(req, "preempt", by_priority=priority,
                          priority=req.priority)
        art = self._suspend_row(r, active, free_rows)
        self._parked.append(Prefilled(req, art))
        _stamp_submit(req)      # its second wait starts here
        self.preemptions += 1
        return True

    def _preempt_everything(self, pending: deque, active: Dict[int, _Row],
                            free_rows: List[int],
                            source: Optional[SubmissionQueue]
                            ) -> Iterator[Suspended]:
        """:meth:`preempt_all`'s loop side — drain migration: yield a
        :class:`Suspended` for EVERY in-flight request.  Resident
        suspendable rows carry their KV artifact; everything else
        (still-filling rows, queued arrivals, modes without per-row
        export) requeues with ``artifact=None`` — lossless either way,
        since nothing was delivered and completions are deterministic.
        Parked artifacts and not-yet-admitted imports keep theirs."""
        if source is not None:
            # Queued arrivals must resolve too — a drained replica dies
            # soon after, and a dangling submitter would hang forever.
            while True:
                item = source.poll(False)
                if item is None or item is _CLOSED:
                    break
                pending.append(item)
        # Stale pipelined device state dies with its rows.
        self._inflight = None
        self._pipe_carry = self._pipe_host = None
        for r in sorted(active):
            state = active[r]
            art = (self._export_row(r, state)
                   if self._suspendable(state) else None)
            req = state.req
            rid = state.rid
            self._trace_event(req, "suspend", rid=rid,
                              exported=art is not None)
            self._request_done("suspended", req, state)
            self._finish(r, active, free_rows)
            yield Suspended(rid=rid, request=req, artifact=art)
        while self._parked or pending:
            item = (self._parked or pending).popleft()
            self._request_done("suspended", item)
            if isinstance(item, Prefilled):
                yield Suspended(rid=int(item.artifact.get("rid", -1)),
                                request=item.request,
                                artifact=item.artifact)
            else:
                yield Suspended(rid=-1, request=item, artifact=None)
        self._preempt_event.clear()

    @staticmethod
    def _trace_event(req: Request, name: str, **attrs) -> None:
        """One batcher event on the request's trace (no-op without
        one — local runs cost nothing)."""
        tr = getattr(req, "trace", None)
        if tr is not None:
            tr.event("batcher", name, **attrs)

    def _request_done(self, status: str, item, row: Optional[_Row] = None,
                      now: Optional[float] = None) -> None:
        """The one write of the request ring: ``item`` (a request, or the
        ``Prefilled`` that carries it) leaves this batcher, as ``status``
        says: ``completed``, ``expired`` (its deadline passed while it
        held or had held a row), ``shed`` (it passed in the queue),
        ``suspended`` (given back: preempted, drained, exported) or
        ``abandoned`` (the consumer closed the loop).  ``row`` is its
        row's state where it held one; without one the admit and first
        stamps and their ticks are None.  Every time is a
        ``perf_counter`` reading, the clock of the tick ring's ``t``; a
        tick is the one whose pass of the loop made the edge, -1 outside
        a loop (docs/SERVING.md "Observability")."""
        req = _request_of(item)
        now = time.perf_counter() if now is None else now
        rec = {"name": "request", "status": status,
               "rid": (-1 if item is req
                       else int(item.artifact.get("rid", -1))),
               "t_submit": now if req.t_submit is None else req.t_submit,
               "t_admit": None, "t_first": None, "t_done": now,
               "prompt_tokens": int(req.prompt.size), "prefill_tokens": 0,
               "out_tokens": 0, "admit_tick": None, "first_tick": None,
               "done_tick": self._tick["tick"]}
        if row is not None:
            rec.update(rid=row.rid, t_submit=row.t_submit,
                       t_admit=row.t_admit, admit_tick=row.admit_tick,
                       prefill_tokens=row.prefill_tokens,
                       out_tokens=len(row.out))
            if row.t_first > 0:
                rec.update(t_first=row.t_first, first_tick=row.first_tick)
        self.requests.record(rec)

    def _completion(self, row: _Row) -> Completion:
        now = time.perf_counter()
        tr = getattr(row.req, "trace", None)
        if tr is not None:
            # The three phase spans every waterfall wants: submission ->
            # admission (this batcher's own queue), admission -> first
            # token (prefill + queue-for-burst) and first token ->
            # finish (decode), from the row's own perf_counter stamps —
            # hop-local by construction.
            tr.span_between("batcher", "queue", row.t_submit, row.t_admit,
                            rid=row.rid)
            tr.span_between("batcher", "prefill", row.t_admit,
                            max(row.t_first, row.t_admit), rid=row.rid)
            tr.span_between("batcher", "decode",
                            max(row.t_first, row.t_admit), now,
                            rid=row.rid, tokens=len(row.out))
        self._request_done("completed", row.req, row, now=now)
        return Completion(rid=row.rid, request=row.req,
                          tokens=list(row.out),
                          ttft_s=row.t_first - row.t_admit,
                          total_s=now - row.t_admit,
                          queue_s=row.t_admit - row.t_submit)

    def _finish(self, row: int, active: Dict[int, _Row],
                free_rows: List[int]) -> None:
        active.pop(row, None)
        self._state_rows = len(active)      # a freed slot's state is dead
        self.t_side.release(row)
        if self.d_side is not None:
            self.d_side.release(row)
        free_rows.append(row)
