"""A serving replica: one ``ContinuousBatcher`` behind a TCP server.

This is the process the fleet launcher schedules N of as Mode-B tasks
(``python -m tfmesos_tpu.fleet.replica --registry HOST:PORT ...``): it
builds the model, starts the batcher's incremental serve loop on a
dedicated thread, accepts multiplexed ``generate`` requests over the
authenticated wire protocol, and streams each completion back on the
connection it arrived on as soon as the batcher finishes it — requests
from many gateway workers interleave into ONE continuous batch, which
is the entire point of fronting the batcher with a fleet.

The cluster token arrives through the standard task env contract
(``TPUMESOS_TOKEN_FILE`` / ``TPUMESOS_TOKEN``, resolved by
:func:`tfmesos_tpu.wire.load_token`), so only processes launched by our
scheduler can join the serving path.

Liveness: a heartbeat thread dials the registry and streams
``{op: heartbeat, addr, capacity, outstanding}`` on a persistent
connection; the connection dying IS the registry's earliest death
signal.  On SIGTERM the replica announces a drain, stops accepting, and
exits.  With ``--warmup`` the replica registers with
``status: warming`` — present but never routed — compiles every jitted
serving entry point (``ContinuousBatcher.warmup``), and only then
drops the status to take traffic, so a cold start (boot, elastic
relaunch, Mode-B restart) never pays its compiles on a live request.

:class:`ReplicaServer` itself is model-agnostic — it serves whatever
``handler(msg, reply)`` it is given, which keeps the whole fleet
machinery unit-testable without JAX (see ``tests/test_fleet.py``'s stub
replicas).
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import sys
import threading
from typing import Any, Callable, Dict, List, Optional

from tfmesos_tpu import wire
from tfmesos_tpu.fleet import tracing
from tfmesos_tpu.utils.logging import get_logger

__all__ = ["ReplicaServer", "BatcherServing", "batcher_handler",
           "prefill_handler", "fabric_handler", "tiny_model",
           "flagship_model", "tiny_draft_model", "flagship_draft_model",
           "build_parser", "main"]


def _hop_trace(head) -> Optional["tracing.TraceContext"]:
    """The replica-side hop context for a request carrying a
    ``trace_id``: spans are offsets from THIS moment (receipt) and
    piggyback on the reply — absolute clocks never cross the wire.
    A malformed field costs the trace, never the request."""
    tid = head.get("trace_id")
    if not isinstance(tid, str) or not tid:
        return None
    slow = head.get("trace_slow_ms")
    return tracing.TraceContext(
        trace_id=tid, detailed=bool(head.get("trace_detail")),
        slow_ms=(float(slow) if isinstance(slow, (int, float))
                 and not isinstance(slow, bool) and slow > 0 else None))


def _attach_trace(out: Dict[str, Any], tr, failed: bool = False
                  ) -> Dict[str, Any]:
    """Piggyback the hop's spans on a reply dict per the tail rule:
    detail was requested, the hop failed, or the hop ran slow."""
    if tr is not None and tr.should_export(failed=failed):
        out["trace"] = tr.export()
    return out


class ReplicaServer:
    """Threaded request server + registry heartbeater.

    ``handler(msg, reply)`` serves one ``generate`` message; it may call
    ``reply(dict)`` synchronously or later from another thread (the
    batcher's completion loop).  ``reply`` is single-shot and maintains
    the server's outstanding count.
    """

    def __init__(self, handler: Callable[[Dict[str, Any], Callable], None],
                 token: str = "", capacity: int = 0,
                 host: str = "127.0.0.1", port: int = 0,
                 registry_addr: Optional[str] = None,
                 heartbeat_interval: float = 0.3,
                 advertise_host: Optional[str] = None,
                 extra_info: Optional[Callable[[], Dict[str, Any]]] = None,
                 status: Optional[str] = None):
        self.handler = handler
        self.token = token
        self.capacity = int(capacity)
        self.host = host
        self.port = int(port)
        self.registry_addr = registry_addr
        self.heartbeat_interval = float(heartbeat_interval)
        self.advertise_host = advertise_host
        # Extra fields merged into every heartbeat (must be cheap and
        # never raise) — the batcher's prefix-cache summary rides here
        # so the gateway's prefix-affinity routing knows what this
        # replica has resident.
        self.extra_info = extra_info
        # Lifecycle status advertised on the hello AND every beat
        # ("warming" while the batcher compiles its entry points; None
        # = routable).  It rides the hello so the registry never has a
        # window where a still-compiling replica looks routable, and
        # the replica flips itself live by just dropping the field
        # (set_status(None)) once warmup returns.
        self._status = status
        self.log = get_logger("tfmesos_tpu.fleet.replica")
        self.addr: Optional[str] = None
        self._listen: Optional[socket.socket] = None
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._conns: set = set()
        self._outstanding = 0
        self._olock = threading.Lock()

    @property
    def outstanding(self) -> int:
        with self._olock:
            return self._outstanding

    def set_status(self, status: Optional[str]) -> None:
        """Change the advertised lifecycle status.  The next beat (one
        ``heartbeat_interval`` away at most) carries it; flipping to
        ``None`` is how a warmed replica advertises itself routable."""
        self._status = status

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ReplicaServer":
        self._listen = wire.bind_ephemeral(self.host, port=self.port)
        advertise = self.advertise_host or (
            None if self.host in ("0.0.0.0", "::") else self.host)
        self.addr = wire.sock_addr(self._listen, advertise_host=advertise)
        self.log.info("replica serving on %s (capacity %d)", self.addr,
                      self.capacity)
        t = threading.Thread(target=self._accept_loop,
                             name="replica-accept", daemon=True)
        t.start()
        self._threads = [t]
        if self.registry_addr:
            hb = threading.Thread(target=self._heartbeat_loop,
                                  name="replica-heartbeat", daemon=True)
            hb.start()
            self._threads.append(hb)
        return self

    def stop(self) -> None:
        self._stop.set()
        # close() alone does not interrupt a blocked accept(): poke the
        # listener awake so the accept thread exits NOW instead of
        # burning its whole join timeout (this is also what keeps a
        # SIGTERM'd replica's exit prompt — the drain the fleet waits
        # on rides the process death).
        wire.wake_listener(self._listen)
        if self._listen is not None:
            try:
                self._listen.close()
            except OSError:
                pass
        with self._olock:
            conns = list(self._conns)
            self._conns.clear()
        for conn in conns:  # unblock reader threads; peers see EOF
            # shutdown BEFORE close: our own reader thread is blocked
            # in recv on this socket, and close() alone neither wakes
            # it nor sends the peer its FIN until that recv returns —
            # a stopping replica's in-flight callers would ride their
            # full timeouts instead of failing over promptly.
            wire.shutdown_socket(conn)
            try:
                conn.close()
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=2.0)

    # -- request serving ---------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listen.accept()
            except OSError:
                return
            with self._olock:
                self._conns.add(conn)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name="replica-conn", daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        # Replica links legitimately carry multi-MB raw KV frames (the
        # disaggregated import path) — the one listener that opts in.
        framer = wire.Framer(self.token, allow_raw=True)
        send_lock = threading.Lock()
        try:
            conn.settimeout(None)
            for msg in wire.iter_msgs(conn, framer):
                self._handle(conn, send_lock, msg)
        except wire.WireError as e:
            self.log.warning("rejecting connection: %s", e)
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._olock:
                self._conns.discard(conn)

    def _send(self, conn: socket.socket, lock: threading.Lock,
              msg) -> None:
        try:
            with lock:
                if isinstance(msg, wire.RawFrame):
                    wire.send_raw_msg(conn, msg.meta, msg.body, self.token)
                else:
                    wire.send_msg(conn, msg, self.token)
        except OSError:
            pass    # peer gone; its requests died with it

    def _handle(self, conn: socket.socket, send_lock: threading.Lock,
                msg: Any) -> None:
        # Raw binary frames (the disaggregated KV handoff) carry their
        # op/id in the JSON meta header; the handler receives the
        # whole RawFrame so the body never copies through a re-encode.
        if isinstance(msg, wire.RawFrame):
            head = msg.meta if isinstance(msg.meta, dict) else {}
        elif isinstance(msg, dict):
            head = msg
        else:
            return
        op = head.get("op")
        mid = head.get("id")
        if op == "ping":
            self._send(conn, send_lock, {"op": "pong", "id": mid})
            return
        # "migrate" is the drain-migration control op (the fleet's
        # control plane asks this replica to suspend its in-flight rows
        # so the router can re-place them); "adopt" assigns a warm-pool
        # replica its model, "swap_adapter" ships a weight delta as one
        # raw frame — authenticated like every frame, and
        # handler-interpreted like generate/prefill.  The kv_* ops are
        # the cross-host KV fabric's surface: "kv_put" lands a peer's
        # replicated park, "kv_fetch" serves a peer's resume, and
        # "kv_stage" lands a direct peer-to-peer KV stream ahead of
        # the router's small generate call referencing it.
        # "cancel" is the advisory client-disconnect op: one-way from
        # the router (id 0), it asks the batcher to release the row of
        # an in-flight streamed request whose client is gone.
        if op not in ("generate", "prefill", "migrate", "adopt",
                      "swap_adapter", "kv_put", "kv_fetch", "kv_stage",
                      "cancel"):
            self._send(conn, send_lock,
                       {"op": "error", "id": mid,
                        "kind": "bad_request",
                        "error": f"unknown op {op!r}"})
            return
        with self._olock:
            self._outstanding += 1
        done = threading.Event()    # single-shot guard

        def reply(out) -> None:
            if done.is_set():
                return
            done.set()
            with self._olock:
                self._outstanding -= 1
            self._send(conn, send_lock, out)

        def partial(out) -> None:
            # Streaming side channel: PARTIAL frames (op: tokens) may
            # precede the single final reply — they share the
            # connection's send lock but never consume the single-shot
            # guard or the outstanding count.
            if done.is_set():
                return
            self._send(conn, send_lock, out)

        reply.partial = partial
        # Per-connection identity for the in-flight registry: a cancel
        # names its target by the mux call id, which is only unique PER
        # ROUTER CONNECTION — keying on (conn, id) keeps two routers'
        # colliding ids from cross-cancelling each other's requests.
        reply.conn_key = id(conn)
        try:
            self.handler(msg, reply)
        except Exception as e:      # handler bug: fail THIS request only
            self.log.exception("handler failed: %s", e)
            reply({"op": "error", "id": mid, "kind": "internal",
                   "error": repr(e)})

    # -- heartbeats --------------------------------------------------------

    def _merge_extra(self, beat: Dict[str, Any]) -> None:
        status = self._status
        if status is not None:
            beat["status"] = status
        if self.extra_info is None:
            return
        try:
            beat.update(self.extra_info())
        except Exception:
            # A broken callback costs its fields, never the heartbeat —
            # losing the beat would get a healthy replica marked dead.
            self.log.exception("heartbeat extra_info failed; beat "
                               "sent bare")

    def _heartbeat_loop(self) -> None:
        while not self._stop.is_set():
            sock = None
            try:
                sock = wire.connect(self.registry_addr, timeout=5.0)
                hello = {"op": "hello", "addr": self.addr,
                         "capacity": self.capacity}
                self._merge_extra(hello)    # role must land BEFORE any
                wire.send_msg(sock, hello, self.token)  # routing decision
                while not self._stop.wait(self.heartbeat_interval):
                    beat = {"op": "heartbeat", "addr": self.addr,
                            "capacity": self.capacity,
                            "outstanding": self.outstanding}
                    self._merge_extra(beat)
                    wire.send_msg(sock, beat, self.token)
                # Graceful exit: tell the registry we are draining so it
                # stops routing to us before the process dies.
                wire.send_msg(sock, {"op": "drain", "addr": self.addr},
                              self.token)
            except OSError as e:
                self.log.warning("registry %s unreachable: %s; retrying",
                                 self.registry_addr, e)
                self._stop.wait(self.heartbeat_interval)
            finally:
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass


class BatcherServing:
    """Bridge from the request/reply surface to the batcher's
    incremental submission API: ``submit()`` registers a completion
    callback keyed by request identity, a dedicated thread drains
    ``batcher.serve()`` and fires callbacks in finish order."""

    def __init__(self, batcher):
        self.batcher = batcher
        self._callbacks: Dict[int, Callable] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "BatcherServing":
        self._thread = threading.Thread(target=self._loop,
                                        name="batcher-serve", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        try:
            for comp in self.batcher.serve():
                with self._lock:
                    cb = self._callbacks.pop(id(comp.request), None)
                if cb is not None:
                    cb(comp, None)
        except BaseException as e:  # loop died: fail every waiter loudly
            with self._lock:
                cbs = list(self._callbacks.values())
                self._callbacks.clear()
            for cb in cbs:
                cb(None, f"batcher serve loop died: {e!r}")
            raise

    def submit(self, request, on_done: Callable,
               prefilled: Optional[dict] = None) -> None:
        """``on_done(completion, error)``: exactly one of the two is
        set — ``completion`` may also be a
        :class:`~tfmesos_tpu.serving.Suspended` (drain migration gave
        the request back instead of finishing it) or an
        :class:`~tfmesos_tpu.serving.Expired` (the batcher cancelled
        it because its end-to-end deadline passed).  ``prefilled``
        routes the request through the batcher's KV-import admission
        (disaggregated decode, or a migrated resume)."""
        with self._lock:
            self._callbacks[id(request)] = on_done
        if prefilled is not None:
            self.batcher.submit(request, prefilled=prefilled)
        else:
            self.batcher.submit(request)

    def close(self) -> None:
        self.batcher.close()
        if self._thread is not None:
            self._thread.join(timeout=30.0)


def _deadline_ms(head) -> Optional[float]:
    """The remaining end-to-end budget the router forwarded (ms), or
    None — a malformed or non-positive value costs the field, never
    the request (the fleet's standard optional-field discipline)."""
    dl = head.get("deadline_ms")
    if isinstance(dl, (int, float)) and not isinstance(dl, bool) \
            and dl > 0:
        return float(dl)
    return None


def _handle_swap_adapter(batcher, msg, reply: Callable) -> None:
    """Serve one ``swap_adapter`` raw frame (the adapter hot-swap,
    docs/SERVING.md "Model catalog"): unpack the HMAC-verified delta,
    queue the fold behind the batcher's weight-update fence, and reply
    once it has APPLIED — in-flight requests finish on the old delta
    first, so the ack means "every stream from here on runs the new
    version".  Shared by the decode/unified and prefill handlers (a
    prefill batcher has no serve loop, so its fold applies — and
    replies — synchronously)."""
    from tfmesos_tpu.fleet import catalog as catalog_mod

    head = msg.meta if isinstance(msg, wire.RawFrame) else msg
    mid = head.get("id")
    if not isinstance(msg, wire.RawFrame):
        reply({"op": "error", "id": mid, "kind": "bad_request",
               "error": "swap_adapter ships its delta as a raw frame"})
        return
    try:
        from tfmesos_tpu.fleet.registry import validate_model_id
        version = validate_model_id(head.get("adapter_version"))
        delta = catalog_mod.unpack_adapter(head, msg.body)
    except (TypeError, ValueError) as e:
        reply({"op": "error", "id": mid, "kind": "bad_request",
               "error": str(e)})
        return

    def applied() -> None:
        reply({"op": "adapter_swapped", "id": mid,
               "adapter_version": version,
               "swaps": batcher.weight_swaps})

    try:
        batcher.swap_adapter(delta, version, on_applied=applied)
    except ValueError as e:
        reply({"op": "error", "id": mid, "kind": "bad_request",
               "error": str(e)})


def batcher_handler(serving: BatcherServing, generation: int = 0,
                    weights_version: str = "",
                    model_state: Optional[Dict[str, Any]] = None,
                    adopt_fn: Optional[Callable] = None,
                    token: str = "",
                    self_addr: Optional[Callable[[], str]] = None
                    ) -> Callable:
    """The model-backed ``ReplicaServer`` handler (decode/unified
    roles): validate, submit, stream the completion back when the
    batcher finishes it.  A plain ``generate`` dict takes the local
    prefill path; a RAW ``generate`` frame (meta + KV body) takes the
    disaggregated IMPORT path — the payload pages install into the
    pool and the row enters decode directly (mid-stream suspended
    artifacts resume exactly where they stopped).

    A ``migrate`` control message asks the batcher to SUSPEND every
    in-flight request: each pending generate then gets a ``suspended``
    reply instead of a completion — a raw frame carrying the row's
    resumable KV artifact (stamped with this replica's launch
    ``generation`` so the registry fence can reject a zombie's export,
    and its ``weights_version`` so the router resumes onto matching
    weights), or a plain requeue marker when the request held no
    exportable state.  The router re-places either form on a surviving
    replica; the client sees one completion, never the move."""
    import time as _time

    import numpy as np

    from tfmesos_tpu import serving as serving_mod
    from tfmesos_tpu.serving import Expired, Prefilled, Request, Suspended

    batcher = serving.batcher
    log = get_logger("tfmesos_tpu.fleet.replica")
    # Direct-stream staging area (docs/SERVING.md "Cross-host KV
    # fabric"): a peer lands a KV artifact here as one ``kv_stage`` raw
    # frame, the router's later small ``generate`` call references it
    # by ``kv_ref`` — the bytes never transit the control plane.
    # Bounded and TTL'd so an abandoned transfer (router died between
    # broker and generate) cannot pin replica RAM.
    _staged: Dict[str, tuple] = {}
    _stage_lock = threading.Lock()
    _stage_max = 8
    _stage_ttl_s = 120.0
    # Direct-push target for drain migration: the migrate control op
    # may name the survivor the router already picked (``push_to``), in
    # which case each Suspended artifact streams peer-to-peer as a
    # kv_stage frame and only a small ``pushed`` suspended reply rides
    # back through the control plane.
    _push_state: Dict[str, Any] = {"to": None}
    # In-flight requests keyed by (connection identity, call id): the
    # advisory ``cancel`` op (sent by the router when a streaming
    # client disconnects) looks its target up here and stamps the live
    # Request's deadline into the past — the batcher's own per-tick
    # expiry check then cancels the row, frees its pages, and resolves
    # the pending generate as deadline_exceeded.  Keyed per connection
    # because call ids are only unique per router link.
    _inflight: Dict[tuple, Any] = {}
    _inflight_lock = threading.Lock()

    def _push_stage(addr: str, smeta: Dict[str, Any],
                    body: bytes) -> Any:
        from tfmesos_tpu.fleet.kvtier import fabric_rpc

        return fabric_rpc(addr, smeta, body, token=token, timeout=30.0,
                          self_addr=self_addr() if self_addr else "")

    def handler(msg, reply: Callable) -> None:
        raw = isinstance(msg, wire.RawFrame)
        head = msg.meta if raw else msg
        mid = head.get("id")
        if head.get("op") == "cancel":
            target = head.get("target")
            key = (getattr(reply, "conn_key", None), target)
            with _inflight_lock:
                req = _inflight.get(key)
            if req is not None:
                req.deadline = _time.perf_counter()
            # The router sends cancels one-way (id 0) and drops this
            # reply as unmatched; answering anyway keeps the server's
            # outstanding count balanced and gives tests a surface.
            reply({"op": "cancelled", "id": mid,
                   "found": req is not None})
            return
        if head.get("op") == "kv_stage":
            if not raw:
                reply({"op": "error", "id": mid, "kind": "bad_request",
                       "error": "kv_stage ships its artifact as a raw "
                                "frame"})
                return
            xfer = head.get("xfer")
            if not isinstance(xfer, str) or not xfer:
                reply({"op": "error", "id": mid, "kind": "bad_request",
                       "error": "kv_stage needs a string xfer id"})
                return
            now = _time.monotonic()
            with _stage_lock:
                for k in [k for k, (t, _m, _b) in _staged.items()
                          if now - t > _stage_ttl_s]:
                    del _staged[k]
                if len(_staged) >= _stage_max:
                    reply({"op": "error", "id": mid,
                           "kind": "overloaded",
                           "error": f"kv stage full ({_stage_max} "
                                    f"transfers pending)"})
                    return
                _staged[xfer] = (now, dict(head), msg.body)
            reply({"op": "kv_staged", "id": mid, "xfer": xfer,
                   "bytes": len(msg.body)})
            return
        if head.get("op") == "migrate":
            # Ack immediately: the suspensions themselves surface as
            # the in-flight requests' own replies on the next loop
            # tick, and the drain waits on outstanding reaching zero.
            pt = head.get("push_to")
            _push_state["to"] = pt if isinstance(pt, str) and pt \
                else None
            batcher.preempt_all()
            reply({"op": "migrated", "id": mid})
            return
        if head.get("op") == "swap_adapter":
            _handle_swap_adapter(batcher, msg, reply)
            return
        if head.get("op") == "adopt":
            # Warm-pool adoption (docs/SERVING.md "Model catalog"):
            # install one catalog model's weights on this pre-warmed,
            # undedicated replica.  The closure comes from main() —
            # it knows the preset family and updates the heartbeat's
            # model identity once the install applies.
            if adopt_fn is None:
                reply({"op": "error", "id": mid, "kind": "bad_request",
                       "error": "this replica has no model-adoption "
                                "surface (started without a warm-pool "
                                "role)"})
            else:
                adopt_fn(head, reply)
            return
        if head.get("op") == "prefill":
            reply({"op": "error", "id": mid, "kind": "bad_request",
                   "error": "this replica does not serve the prefill "
                            "op (role: decode/unified); route prefill "
                            "to a prefill-role replica"})
            return
        staged_body = None
        if not raw and head.get("kv_ref") is not None:
            # Direct-streamed generate: the KV artifact already landed
            # here as a kv_stage frame; the router's small call names
            # it.  The staged meta merged under the call's own fields
            # reconstructs exactly the raw-frame head the relay path
            # would have delivered.
            kv_ref = head.get("kv_ref")
            with _stage_lock:
                ent = _staged.pop(kv_ref, None) \
                    if isinstance(kv_ref, str) else None
            if ent is None:
                reply({"op": "error", "id": mid, "kind": "bad_request",
                       "error": f"unknown kv_ref {kv_ref!r}: staged "
                                f"transfer expired or never landed"})
                return
            _t0, smeta, staged_body = ent
            merged = {k: v for k, v in smeta.items()
                      if k not in ("op", "id", "xfer", "trace",
                                   "prefill_ms")}
            merged.update(head)
            head = merged
        want_model = head.get("model")
        if isinstance(want_model, str) and want_model \
                and model_state is not None \
                and model_state.get("model_id") != want_model:
            # A pick racing a warm-pool adoption (or a stale routing
            # view): answering with THIS replica's weights would be
            # silently wrong.  Transient (not bad_request) — the
            # router retries another replica of the right model.
            reply({"op": "error", "id": mid, "kind": "wrong_model",
                   "error": f"this replica serves model "
                            f"{model_state.get('model_id') or '(none)'!r}"
                            f", not {want_model!r}"})
            return
        tr = _hop_trace(head)
        if tr is not None:
            tr.event("replica", "recv", op="generate", raw=raw)
        prefilled = None
        try:
            prio = head.get("priority")
            # Session label (docs/SERVING.md "KV tiering & sessions"):
            # with a KV tier attached, the batcher parks this request's
            # finished KV under the id and resumes a later turn from
            # it.  Malformed values cost the field, never the request.
            sid = head.get("session")
            req = Request(
                prompt=np.asarray(head.get("prompt"), np.int32),
                max_new_tokens=int(head.get("max_new_tokens") or 0),
                stop_token=head.get("stop_token"),
                priority=int(prio) if prio is not None else 0,
                deadline_ms=_deadline_ms(head),
                session_id=(str(sid) if isinstance(sid, str) and sid
                            else None))
            req.trace = tr      # the batcher records its events here
            send_partial = getattr(reply, "partial", None)
            if head.get("stream") and send_partial is not None:
                # Per-token incremental replies: the batcher's serve
                # loop flushes each decode block's new tokens through
                # this callback as ``op: tokens`` frames carrying their
                # stream OFFSET — the gateway (and a failover replay)
                # de-duplicates by it, and the final completion still
                # carries the full list, so non-streaming peers see no
                # difference (docs/SERVING.md "Front-door scaling").
                def on_tokens(toks, off, _mid=mid):
                    send_partial({"op": "tokens", "id": _mid,
                                  "off": int(off), "tokens": toks})

                req.on_tokens = on_tokens
            if raw:
                prefilled = serving_mod.unpack_prefilled(head, msg.body)
                batcher.validate(Prefilled(req, prefilled))
            elif staged_body is not None:
                prefilled = serving_mod.unpack_prefilled(head,
                                                         staged_body)
                batcher.validate(Prefilled(req, prefilled))
            else:
                # Reject un-servable requests NOW with an explicit
                # error — run()'s own invalid-request path raises only
                # after the stream drains, which would take the whole
                # replica down.
                batcher.validate(req)
        except (TypeError, ValueError, KeyError) as e:
            reply(_attach_trace(
                {"op": "error", "id": mid, "kind": "bad_request",
                 "error": str(e)}, tr, failed=True))
            return

        ckey = (getattr(reply, "conn_key", None), mid)
        with _inflight_lock:
            _inflight[ckey] = req

        def on_done(comp, err) -> None:
            with _inflight_lock:
                _inflight.pop(ckey, None)
            if comp is None:
                reply(_attach_trace(
                    {"op": "error", "id": mid, "kind": "internal",
                     "error": err or "request dropped"}, tr,
                    failed=True))
                return
            if isinstance(comp, Expired):
                # The batcher cancelled the row (deadline passed):
                # explicit, deterministic, and never retried — the
                # router treats deadline_exceeded as final.
                reply(_attach_trace(
                    {"op": "error", "id": mid,
                     "kind": "deadline_exceeded",
                     "error": "request deadline expired in the "
                              "batcher; row cancelled"}, tr,
                    failed=True))
                return
            if isinstance(comp, Suspended):
                # Model-catalog identity on the export: the router may
                # only resume this mid-stream KV on a replica serving
                # the SAME model and adapter delta.
                model_id = (model_state or {}).get("model_id") or ""
                adapter = getattr(batcher, "adapter_version", "")
                if comp.artifact is None:
                    out = {"op": "suspended", "id": mid, "requeue": True,
                           "gen": generation,
                           "weights_version": weights_version}
                    if model_id:
                        out["model_id"] = model_id
                    reply(_attach_trace(out, tr, failed=True))
                    return
                meta, body = serving_mod.pack_prefilled(comp.artifact)
                meta.update(op="suspended", id=mid, gen=generation,
                            weights_version=weights_version,
                            adapter_version=adapter)
                if model_id:
                    meta["model_id"] = model_id
                pt = _push_state["to"]
                if pt:
                    # Drain migration with a brokered survivor: stream
                    # the artifact peer-to-peer and hand the router only
                    # a small reference.  One bounded attempt — a failed
                    # push falls back to the relay frame below, so the
                    # fast path never costs correctness.
                    xfer = f"mig-{mid}"
                    smeta = dict(meta)
                    smeta.update(op="kv_stage", xfer=xfer)
                    ack = None
                    try:
                        ack = _push_stage(pt, smeta, body)
                    except (OSError, wire.WireError) as e:
                        log.warning("direct KV push of %s to %s failed:"
                                    " %s; relaying through the router",
                                    xfer, pt, e)
                    if isinstance(ack, dict) \
                            and ack.get("op") == "kv_staged":
                        out = {"op": "suspended", "id": mid,
                               "pushed": True, "xfer": xfer,
                               "push_to": pt, "bytes": len(body),
                               "gen": generation,
                               "weights_version": weights_version,
                               "adapter_version": adapter}
                        if model_id:
                            out["model_id"] = model_id
                        reply(_attach_trace(out, tr, failed=True))
                        return
                # A migration hop's spans always piggyback (failed=True
                # here just means "always export"): the router stitches
                # the victim's suspend into the one waterfall.
                _attach_trace(meta, tr, failed=True)
                reply(wire.RawFrame(meta, body))
                return
            reply(_attach_trace(
                {"op": "completion", "id": mid,
                 "tokens": [int(t) for t in comp.tokens],
                 "queue_ms": round(comp.queue_s * 1000.0, 3),
                 "ttft_ms": round(comp.ttft_s * 1000.0, 3),
                 "total_ms": round(comp.total_s * 1000.0, 3)}, tr))

        serving.submit(req, on_done, prefilled=prefilled)

    return handler


def prefill_handler(batcher, max_queue: int = 8, token: str = "",
                    self_addr: Optional[Callable[[], str]] = None
                    ) -> Callable:
    """The prefill-role ``ReplicaServer`` handler: run the prompt
    through prefill only (``export_kv``) and stream the KV artifact
    back as ONE raw binary frame.  Prefill runs off the connection's
    reader thread so a mux peer can pipeline requests; admitted work
    drains through ONE worker thread off a bounded FIFO queue (exports
    serialize inside the batcher anyway, so extra threads would only
    pile up on its lock in unspecified wakeup order), and a full queue
    answers ``overloaded`` immediately — the router treats that as
    transient and retries another prefill replica or falls back.
    ``generate`` is refused — a prefill-role replica never decodes,
    which is what keeps its tier's admission latency flat."""
    import queue as _queue
    import time as _time

    import numpy as np

    from tfmesos_tpu import serving as serving_mod
    from tfmesos_tpu.serving import Request

    log = get_logger("tfmesos_tpu.fleet.replica")
    work_q: "_queue.Queue" = _queue.Queue(maxsize=max_queue)

    def drain() -> None:
        while True:
            req, mid, reply, t_enq, push = work_q.get()
            tr = getattr(req, "trace", None)
            if tr is not None:
                tr.add("replica", "prefill_queue", tr.rel_ms(t_enq),
                       (_time.perf_counter() - t_enq) * 1000.0)
            if req.expired:
                # The deadline passed while queued: shed without
                # burning a prompt's worth of prefill compute.
                batcher.deadline_cancels += 1
                reply(_attach_trace(
                    {"op": "error", "id": mid,
                     "kind": "deadline_exceeded",
                     "error": "request deadline expired in the "
                              "prefill queue"}, tr, failed=True))
                continue
            try:
                t0 = _time.perf_counter()
                art = batcher.export_kv(req)
                meta, body = serving_mod.pack_prefilled(art)
                prefill_ms = round(
                    (_time.perf_counter() - t0) * 1000.0, 3)
                meta.update(op="prefilled", id=mid,
                            prefill_ms=prefill_ms)
                if tr is not None:
                    tr.add("replica", "prefill_export", tr.rel_ms(t0),
                           prefill_ms)
                    _attach_trace(meta, tr)
                if push is not None:
                    # Direct disagg streaming: the router already
                    # picked the decode replica and brokered its addr;
                    # land the KV there as one kv_stage frame and hand
                    # the router only a small reference.  One bounded
                    # attempt — on any failure the full raw frame
                    # relays through the router exactly as before.
                    daddr, xfer = push
                    smeta = dict(meta)
                    smeta.update(op="kv_stage", xfer=xfer)
                    ack = None
                    try:
                        from tfmesos_tpu.fleet.kvtier import fabric_rpc

                        ack = fabric_rpc(
                            daddr, smeta, body, token=token,
                            timeout=30.0,
                            self_addr=self_addr() if self_addr else "")
                    except (OSError, wire.WireError) as e:
                        log.warning("direct KV push of %s to %s "
                                    "failed: %s; relaying through the "
                                    "router", xfer, daddr, e)
                    if isinstance(ack, dict) \
                            and ack.get("op") == "kv_staged":
                        out = {"op": "prefilled", "id": mid,
                               "pushed": True, "xfer": xfer,
                               "bytes": len(body),
                               "prefill_ms": prefill_ms}
                        if tr is not None:
                            _attach_trace(out, tr)
                        reply(out)
                        continue
                reply(wire.RawFrame(meta, body))
            except Exception as e:
                log.exception("prefill failed: %s", e)
                reply(_attach_trace(
                    {"op": "error", "id": mid, "kind": "internal",
                     "error": repr(e)}, tr, failed=True))

    threading.Thread(target=drain, name="replica-prefill",
                     daemon=True).start()

    def handler(msg, reply: Callable) -> None:
        raw = isinstance(msg, wire.RawFrame)
        head = msg.meta if raw else msg
        mid = head.get("id")
        if not raw and head.get("op") == "migrate":
            # Exports are synchronous — a prefill replica holds no
            # resident rows to suspend; ack so a tier-blind drain can
            # migrate every member the same way.
            reply({"op": "migrated", "id": mid})
            return
        if head.get("op") == "swap_adapter":
            # Prefill replicas compute KV with the weights too: an
            # adapter swap must land tier-wide.  No serve loop here,
            # so the fold applies synchronously under the export lock
            # (exports queue behind it).
            _handle_swap_adapter(batcher, msg, reply)
            return
        if raw or head.get("op") != "prefill":
            reply({"op": "error", "id": mid, "kind": "bad_request",
                   "error": "this replica serves only the prefill op "
                            "(role: prefill); route generate to a "
                            "decode or unified replica"})
            return
        tr = _hop_trace(head)
        if tr is not None:
            tr.event("replica", "recv", op="prefill")
        try:
            prio = head.get("priority")
            req = Request(
                prompt=np.asarray(head.get("prompt"), np.int32),
                max_new_tokens=int(head.get("max_new_tokens") or 0),
                stop_token=head.get("stop_token"),
                priority=int(prio) if prio is not None else 0,
                deadline_ms=_deadline_ms(head))
            req.trace = tr
            batcher.validate(req)
        except (TypeError, ValueError) as e:
            reply(_attach_trace(
                {"op": "error", "id": mid, "kind": "bad_request",
                 "error": str(e)}, tr, failed=True))
            return
        push = None
        pt, xf = head.get("push_to"), head.get("xfer")
        if isinstance(pt, str) and pt and isinstance(xf, str) and xf:
            push = (pt, xf)
        try:
            work_q.put_nowait((req, mid, reply, _time.perf_counter(),
                               push))
        except _queue.Full:
            reply(_attach_trace(
                {"op": "error", "id": mid, "kind": "overloaded",
                 "error": f"prefill queue full ({max_queue} pending)"},
                tr, failed=True))

    return handler


def fabric_handler(fabric, inner: Optional[Callable] = None) -> Callable:
    """Wrap a replica handler with the KV fabric's wire surface
    (docs/SERVING.md "Cross-host KV fabric"): ``kv_put`` lands a peer's
    replicated park, ``kv_fetch`` serves a peer's resume from this
    host's tier.  Everything else delegates to ``inner``; with no
    ``inner`` (a dedicated ``--role kv`` replica) other ops are refused
    — a KV holder never decodes.  Jax-free by construction, so the
    dedicated holder process never imports the model stack."""

    def handler(msg, reply: Callable) -> None:
        raw = isinstance(msg, wire.RawFrame)
        head = msg.meta if raw else msg
        op = head.get("op")
        mid = head.get("id")
        if op == "kv_put":
            if not raw:
                reply({"op": "error", "id": mid, "kind": "bad_request",
                       "error": "kv_put ships its artifact as a raw "
                                "frame"})
                return
            out = fabric.handle_put(msg)
            if isinstance(out, dict):
                out.setdefault("id", mid)
            reply(out)
            return
        if op == "kv_fetch":
            out = fabric.handle_fetch(head)
            if isinstance(out, wire.RawFrame):
                out.meta.setdefault("id", mid)
            elif isinstance(out, dict):
                out.setdefault("id", mid)
            reply(out)
            return
        if inner is not None:
            inner(msg, reply)
            return
        if op == "migrate":
            # A KV holder has no rows to suspend; ack so a tier-blind
            # drain completes the same way everywhere.
            reply({"op": "migrated", "id": mid})
            return
        reply({"op": "error", "id": mid, "kind": "bad_request",
               "error": f"this replica holds KV state only (role: "
                        f"kv); it does not serve {op!r}"})

    return handler


# -- model presets ----------------------------------------------------------


def tiny_model(seed: int = 0):
    """The CI model: deterministic from ``seed``, so a test (or a peer
    replica) can reproduce a replica's exact greedy outputs locally."""
    import jax
    import jax.numpy as jnp

    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=128, dtype=jnp.float32)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(seed))


def flagship_model(seed: int = 0, max_len: int = 1024):
    """The flagship serving config (a 34M d512 transformer)."""
    import jax
    import jax.numpy as jnp

    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=8192, d_model=512, n_layers=8, n_heads=8, d_ff=1408,
        max_seq_len=max_len, dtype=jnp.bfloat16)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(seed))


def tiny_draft_model(seed: int = 5, max_len: int = 128, n_draft: int = 4):
    """The tiny model's DRAFT companion (speculative decoding):
    deterministic from ``seed`` with the tiny vocab, its max_seq_len
    covering the verify overshoot (max_len + n_draft + 1)."""
    import jax
    import jax.numpy as jnp

    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=max_len + n_draft + 1, dtype=jnp.float32)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(seed))


def flagship_draft_model(seed: int = 1, max_len: int = 1024,
                         n_draft: int = 4):
    """The flagship's DRAFT companion: a ~16x-smaller transformer on
    the flagship vocab — cheap enough that a speculative round's k
    draft steps cost less than the target tokens they replace."""
    import jax
    import jax.numpy as jnp

    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=8192, d_model=128, n_layers=2, n_heads=4, d_ff=352,
        max_seq_len=max_len + n_draft + 1, dtype=jnp.bfloat16)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(seed))


# -- batcher assembly --------------------------------------------------------


def rid_seed_for_node(node: str) -> int:
    """Per-replica request-id stream base, derived from the fleet node
    id ("job:index").  Sampled draws are pure (rid, step) key folds, so
    two replicas whose rids collide would draw IDENTICAL sampling
    streams — cross-exporter sampled artifacts must never share one
    (the PR 4 caveat, now closed).  A 20-bit CRC of the node id shifted
    10 bits gives distinct nodes disjoint 1024-rid blocks, stays int32-
    safe with ~2^30 of increment headroom, and leaves the node-less
    (direct/test) replica at the historical 0 base."""
    if not node:
        return 0
    import zlib

    return (zlib.crc32(node.encode("utf-8")) & 0xFFFFF) << 10


def build_batcher(args, token: str, generation: int, node: str = "",
                  with_kv_tier: bool = True):
    """Assemble the model + ContinuousBatcher one serving process runs —
    shared by the single-process replica, the gang LEADER (which owns
    the gang's batcher), and gang MEMBERS (which mirror-execute with an
    identical build, minus the KV tier: parking a session N times over
    would corrupt the economy's accounting).  Split out of ``main()``
    so one process == one replica is an entry-point choice, not a
    structural assumption."""
    from tfmesos_tpu.serving import ContinuousBatcher

    build_seed = args.model_seed if args.model_seed is not None \
        else args.seed
    if args.tiny:
        cfg, params = tiny_model(build_seed)
    else:
        cfg, params = flagship_model(build_seed,
                                     max_len=args.max_len or 1024)
    draft_cfg = draft_params = None
    if args.draft:
        max_len = args.max_len or int(cfg.max_seq_len)
        if args.tiny:
            draft_cfg, draft_params = tiny_draft_model(
                max_len=max_len, n_draft=args.n_draft)
        else:
            draft_cfg, draft_params = flagship_draft_model(
                seed=args.seed + 1, max_len=max_len,
                n_draft=args.n_draft)
    kv_tier = None
    if with_kv_tier and (args.kv_tier_mb > 0 or args.kv_tier_dir):
        from tfmesos_tpu.fleet.kvtier import KVTierStore

        # The store is stamped with this replica's rollout identity:
        # a parked artifact from another weights_version (a pre-rollout
        # entry in a shared disk dir) reads as a miss, never stale KV.
        # The MODEL composes into the stamp — two models' replicas may
        # share one host disk tier, and a session parked by model A
        # must read as a version miss to model B, never as its KV.
        wv_stamp = args.weights_version
        if args.model_id:
            wv_stamp = f"{args.weights_version or 'v0'}@{args.model_id}"
        kv_tier = KVTierStore(
            ram_bytes=int(max(0.0, args.kv_tier_mb) * 1e6),
            disk_dir=args.kv_tier_dir, token=token,
            stamp={"weights_version": wv_stamp,
                   "gen": generation})
    return ContinuousBatcher(
        cfg, params, rows=args.rows, max_len=args.max_len,
        page_size=args.page_size, prefill_bucket=args.prefill_bucket,
        multi_step=args.multi_step,
        prefix_cache_pages=args.prefix_cache_pages,
        pipeline_depth=args.pipeline_depth, kv_tier=kv_tier,
        # Fused scheduling serves in chunked mode (the bucket doubles
        # as the chunk width — the batcher couples them anyway).
        prefill_chunk=(args.prefill_bucket if getattr(
            args, "fused_prefill", False) else None),
        fused_prefill=getattr(args, "fused_prefill", False),
        tokens_per_tick=getattr(args, "tokens_per_tick", None),
        draft_cfg=draft_cfg, draft_params=draft_params,
        n_draft=args.n_draft, rid_seed=rid_seed_for_node(node))


def _gang_member_main(args, token: str, spec, generation: int) -> int:
    """A gang MEMBER process (rank >= 1): no serve socket, no registry
    heartbeat — its whole life is the leader's dispatch loop (see
    :mod:`tfmesos_tpu.fleet.gang`).  Mirror-executes each dispatched
    request on an identical batcher build and acks the token digest;
    exits when the leader does (a gang lives and dies whole)."""
    from tfmesos_tpu.fleet import gang as gang_mod

    gid, size, rank = spec
    log = get_logger("tfmesos_tpu.fleet.gang")
    if not args.registry:
        print("gang member needs --registry for leader rendezvous",
              file=sys.stderr)
        return 2
    batcher = build_batcher(args, token, generation,
                            with_kv_tier=False)

    import numpy as np

    from tfmesos_tpu.serving import Request

    def execute(head) -> List[int]:
        req = Request(
            prompt=np.asarray(head.get("prompt"), np.int32),
            max_new_tokens=int(head.get("max_new_tokens") or 0),
            stop_token=head.get("stop_token"))
        comps = list(batcher.run([req]))
        return [int(t) for t in comps[0].tokens] if comps else []

    if args.warmup:
        info = batcher.warmup(decode=True, prefill=True)
        log.info("gang member rank %d warmed in %.1fs", rank,
                 info["seconds"])
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda s, f: stop.set())
    signal.signal(signal.SIGINT, lambda s, f: stop.set())
    member = gang_mod.GangMember(gid, size, rank, generation,
                                 args.registry, token=token,
                                 execute=execute)
    print(f"gang member rank {rank}/{size} serving gang {gid}",
          flush=True)
    reason = member.run(stop)
    log.info("gang member rank %d exiting: %s (%d served)", rank,
             reason, member.served)
    return 0 if reason == "stopped" else 1


def _kv_holder_main(args, token: str, generation: int,
                    node: str = "") -> int:
    """A dedicated ``--role kv`` replica: a bare KV tier behind the
    replica wire surface — no model, no batcher, no JAX import.  Its
    whole job is holding other replicas' parked artifacts (fabric
    pushes land here first, and resumes fetch from here), so a fleet
    can scale its serving replicas to zero without losing one parked
    session (docs/SERVING.md "Cross-host KV fabric")."""
    from tfmesos_tpu.fleet.kvtier import KVFabric, KVTierStore

    log = get_logger("tfmesos_tpu.fleet.replica")
    if args.kv_tier_mb <= 0 and not args.kv_tier_dir:
        print("--role kv needs a tier to hold (--kv-tier-mb and/or "
              "--kv-tier-dir)", file=sys.stderr)
        return 2
    # An EMPTY stamp on purpose: the holder stores many replicas'
    # artifacts verbatim (kv_put installs without re-stamping) and must
    # never fence a read by its OWN identity — fencing belongs to the
    # importer, which judges the original writer's stamp.
    store = KVTierStore(ram_bytes=int(max(0.0, args.kv_tier_mb) * 1e6),
                        disk_dir=args.kv_tier_dir, token=token,
                        stamp={})
    fabric = KVFabric(store, token=token, registry_addr=args.registry,
                      replication=1, placement=args.kv_placement)
    handler = fabric_handler(fabric)

    def extra() -> Dict[str, Any]:
        beat: Dict[str, Any] = {"role": "kv", "gen": generation,
                                "kv_tier": store.summary()}
        if args.weights_version:
            beat["weights_version"] = args.weights_version
        if node:
            beat["node"] = node
        return beat

    server = ReplicaServer(
        handler, token=token, capacity=0, host=args.host,
        port=args.port, registry_addr=args.registry,
        heartbeat_interval=args.heartbeat_interval, extra_info=extra)
    server.start()
    fabric.self_addr = server.addr or ""
    print(f"replica serving on {server.addr} (role kv)", flush=True)
    stop = threading.Event()

    def on_signal(signum, frame) -> None:
        log.info("signal %d: draining", signum)
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    stop.wait()
    server.stop()
    return 0


# -- process entry ----------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tfmesos_tpu.fleet.replica",
        description="One fleet serving replica: a ContinuousBatcher "
                    "behind an authenticated TCP server.")
    p.add_argument("--registry", type=str, default=None,
                   help="registry host:port to heartbeat (none = serve "
                        "unregistered, for direct testing)")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = OS-assigned)")
    p.add_argument("--rows", type=int, default=4,
                   help="concurrent decode rows (= advertised capacity)")
    p.add_argument("--max-len", type=int, default=None)
    p.add_argument("--page-size", type=int, default=64)
    p.add_argument("--prefill-bucket", type=int, default=64)
    p.add_argument("--multi-step", type=int, default=1)
    p.add_argument("--fused-prefill", action="store_true",
                   dest="fused_prefill",
                   help="stall-free fused scheduling: serve in chunked-"
                        "prefill mode (chunk width = --prefill-bucket) "
                        "with each tick's chunk slots fused into the "
                        "SAME device dispatch as the decode block, so "
                        "decoding rows never stall behind a long "
                        "prompt's prefill (docs/SERVING.md 'Stall-free "
                        "fused scheduling'); modes the fused program "
                        "cannot cover fall back with a recorded "
                        "bypass reason")
    p.add_argument("--tokens-per-tick", type=int, default=None,
                   dest="tokens_per_tick",
                   help="fused tick token budget (default: rows x "
                        "multi_step + one chunk): decode rows spend "
                        "multi_step each, the leftover coalesces "
                        "still-filling rows' chunks into the dispatch")
    p.add_argument("--prefix-cache-pages", type=int, default=0,
                   help="cross-request prefix cache budget in pool pages "
                        "per mesh data shard (0 disables); cached "
                        "summaries are advertised on registry heartbeats "
                        "for prefix-affinity routing")
    p.add_argument("--kv-tier-mb", type=float, default=0.0,
                   dest="kv_tier_mb",
                   help="host-RAM KV tier budget in MB (0 disables): "
                        "prefix pages evicted from the device pool "
                        "spill here (promoting back on the next hit) "
                        "and session-labeled requests park their KV "
                        "between turns (docs/SERVING.md 'KV tiering & "
                        "sessions')")
    p.add_argument("--kv-tier-dir", type=str, default=None,
                   dest="kv_tier_dir",
                   help="disk tier directory (default: none — RAM "
                        "only); RAM-evicted entries spill into "
                        "HMAC-framed files, and replicas of one host "
                        "sharing the directory can resume each "
                        "other's parked sessions (bounded at 4x the "
                        "RAM budget)")
    p.add_argument("--role", choices=("unified", "prefill", "decode",
                                      "kv"),
                   default="unified",
                   help="serving role: 'unified' (default) serves whole "
                        "requests; 'prefill' only runs prompts through "
                        "prefill and exports their KV pages; 'decode' "
                        "additionally imports exported KV and enters "
                        "rows straight into decode (disaggregated "
                        "serving, docs/SERVING.md); 'kv' serves NO "
                        "model at all — a jax-free dedicated holder "
                        "for the cross-host KV fabric's replicated "
                        "parks (needs a tier via --kv-tier-mb/-dir)")
    p.add_argument("--kv-replication", type=int, default=1,
                   dest="kv_replication",
                   help="K-way replicated session parking (default 1 = "
                        "local only): a park lands on this replica "
                        "PLUS K-1 fabric peers before it counts as "
                        "replicated, so a parked session survives "
                        "SIGKILL of its parking host and resumes from "
                        "a surviving copy (docs/SERVING.md 'Cross-host "
                        "KV fabric'); needs --registry and a KV tier")
    p.add_argument("--kv-placement", choices=("rendezvous", "loaded"),
                   default="rendezvous", dest="kv_placement",
                   help="fabric peer choice for replicated parks: "
                        "'rendezvous' (default) is pure hash-ordered "
                        "(deterministic, ignores load); 'loaded' "
                        "re-scores the rendezvous candidates by their "
                        "heartbeat KV-tier occupancy so parks avoid "
                        "peers whose tiers are nearly full "
                        "(docs/SERVING.md 'Cross-host KV fabric')")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   choices=(0, 1), dest="pipeline_depth",
                   help="1 pipelines the decode loop with a device-"
                        "resident carry: block N+1 dispatches from the "
                        "previous block's on-device outputs and block "
                        "N's tokens sync one block behind — token "
                        "streams identical to 0 (fully synchronous).  "
                        "Not given, the batcher chooses: 1 for a model "
                        "whose rows keep a recurrent state, else 0 "
                        "(docs/SERVING.md)")
    p.add_argument("--draft", action="store_true",
                   help="serve with a DRAFT model (speculative "
                        "decoding): each tick the draft proposes "
                        "--n-draft tokens and the target verifies them "
                        "in one chunk, so a row commits 1..n+1 tokens "
                        "per dispatch; composes with the prefix cache, "
                        "KV export/import, preemption/migration, and "
                        "the KV tier, and the acceptance rate rides "
                        "heartbeats into the gateway's 'spec' gauge")
    p.add_argument("--n-draft", type=int, default=4, dest="n_draft",
                   help="draft proposals per speculative round "
                        "(with --draft)")
    p.add_argument("--warmup", action="store_true",
                   help="compile every jitted serving entry point at "
                        "boot (ContinuousBatcher.warmup) before taking "
                        "traffic; the replica registers as 'warming' — "
                        "never routed — and flips itself alive when "
                        "warmup returns, so a relaunch re-warms before "
                        "its first request pays a compile")
    p.add_argument("--tiny", action="store_true",
                   help="serve the tiny CI model instead of the flagship")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--heartbeat-interval", type=float, default=0.3)
    p.add_argument("--model-id", type=str, default="",
                   dest="model_id",
                   help="model-catalog identity this replica serves "
                        "(rides every heartbeat — the router's "
                        "per-model tier keys off it); charset-"
                        "validated like --weights-version "
                        "(docs/SERVING.md 'Model catalog')")
    p.add_argument("--model-seed", type=int, default=None,
                   dest="model_seed",
                   help="weight seed of the catalog model (default: "
                        "--seed); two catalog entries with different "
                        "seeds ARE different models")
    p.add_argument("--warm-pool", action="store_true",
                   dest="warm_pool",
                   help="register as an UNDEDICATED warm-pool member: "
                        "pre-warmed and alive but excluded from every "
                        "router pick until the fleet's model trader "
                        "assigns a model via the 'adopt' control op "
                        "(a weight install — no relaunch, no "
                        "recompile)")
    p.add_argument("--weights-version", type=str, default="",
                   dest="weights_version",
                   help="weights version label this replica serves; "
                        "rides the registry hello and every heartbeat "
                        "so the router's version-preference tier and "
                        "the blue-green rollout can tell generations "
                        "of the model apart (docs/SERVING.md)")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    token = wire.load_token()
    log = get_logger("tfmesos_tpu.fleet.replica")

    # Control-plane identity, both from the Mode-B task env contract:
    # the launch generation (PR 3's fencing epoch — the registry drops
    # beats of reaped rollout generations) and the scheduler-side task
    # name ("job:index"), which is how the autoscaler maps this
    # replica's registry entry back to a killable task.
    try:
        generation = int(os.environ.get("TPUMESOS_GENERATION", "0") or 0)
    except ValueError:
        generation = 0
    job = os.environ.get("TPUMESOS_JOB_NAME", "")
    idx = os.environ.get("TPUMESOS_TASK_INDEX", "")
    node = f"{job}:{idx}" if job and idx != "" else ""

    if not 1 <= args.kv_replication <= 8:
        print("replica: --kv-replication must be in [1, 8]",
              file=sys.stderr)
        return 2
    if args.role == "kv":
        # Dedicated fabric holder: jax-free, no batcher build at all.
        return _kv_holder_main(args, token, generation, node)

    # Every model-serving process (single replica, gang leader, gang
    # member) refuses the wrong device before it builds anything: the
    # same check a Mode-A task gets from runtime.initialize().
    from tfmesos_tpu.runtime import ENV_CHIPS, check_platform
    from tfmesos_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    dev = check_platform()
    # The JAX device id is local to the process (0 in every one-chip
    # task); the host chips the backend gave it are what tells co-located
    # replicas apart.
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "id": int(dev.id),
              "chips": os.environ.get(ENV_CHIPS, "")}

    # Gang identity (docs/SERVING.md "Gang replicas"): when this
    # process was launched as one task of an N-task gang, rank 0 is
    # the LEADER — the one process that owns the fleet identity below —
    # and every other rank is a member whose whole life is the leader's
    # dispatch loop.
    from tfmesos_tpu.fleet import gang as gang_mod

    gang_spec = gang_mod.read_gang_env()
    if gang_spec is not None and gang_spec[2] > 0:
        return _gang_member_main(args, token, gang_spec, generation)

    # Model-catalog identity: --model-id names the catalog entry this
    # replica serves (seeded by --model-seed), --warm-pool starts it
    # UNDEDICATED (default weights, adopted later).  The id is
    # charset-validated here too — every ingress is a boundary, and
    # argv arrived through a shell=True command line.
    if args.model_id:
        from tfmesos_tpu.fleet.registry import validate_model_id

        try:
            args.model_id = validate_model_id(args.model_id)
        except ValueError as e:
            print(f"replica: {e}", file=sys.stderr)
            return 2
    model_state: Dict[str, Any] = {
        "model_id": args.model_id or "",
        "warm_pool": bool(args.warm_pool),
        "pool_capable": bool(args.warm_pool),
    }
    batcher = build_batcher(args, token, generation, node=node)

    # The fabric face of the local KV tier (docs/SERVING.md "Cross-host
    # KV fabric"): replicated parks and locate-driven peer fetch on
    # miss.  The batcher's tier reference is swapped for the wrapper —
    # every park/resume from here on goes through the fabric, and the
    # replica additionally serves kv_put/kv_fetch for its peers.
    fabric = None
    srv_cell: List[Any] = []
    if args.registry and batcher.kv_tier is not None \
            and batcher.kv_tier_bypass_reason is None:
        from tfmesos_tpu.fleet.kvtier import KVFabric

        fabric = KVFabric(batcher.kv_tier, token=token,
                          registry_addr=args.registry,
                          replication=args.kv_replication,
                          placement=args.kv_placement)
        batcher.kv_tier = fabric

    def adopt_fn(head, reply) -> None:
        """The ``adopt`` control op: install one catalog model's
        weights on this (pre-warmed, undedicated) replica.  Same
        preset family and max_len as the boot build, so shapes are
        identical and nothing recompiles — the whole point of the
        warm pool."""
        from tfmesos_tpu.fleet.registry import validate_model_id

        mid = head.get("id")
        try:
            model_id = validate_model_id(head.get("model_id"))
            seed = int(head.get("seed") or 0)
        except (TypeError, ValueError) as e:
            reply({"op": "error", "id": mid, "kind": "bad_request",
                   "error": str(e)})
            return
        # Adoption is a WARM-POOL-ONLY transition: a replica already
        # serving (or mid-install for) a model refuses — the trader's
        # pool view is heartbeat-lagged, so two rapid cold starts
        # could otherwise hand one pool member to BOTH models, and
        # reassigning a dedicated replica would serve wrong_model
        # errors until the identity flip rides a beat.  The refusal
        # makes the trader fall through to the next candidate (or a
        # cold launch).
        if model_state["model_id"] or model_state.get("adopting"):
            reply({"op": "error", "id": mid, "kind": "bad_request",
                   "error": f"already serving model "
                            f"{model_state['model_id'] or '(adopting)'!r}"
                            f"; adoption is a warm-pool-only "
                            f"transition"})
            return
        model_state["adopting"] = True
        if args.tiny:
            _, new_params = tiny_model(seed)
        else:
            _, new_params = flagship_model(seed,
                                           max_len=args.max_len or 1024)

        def applied() -> None:
            model_state["model_id"] = model_id
            model_state["warm_pool"] = False
            model_state["adopting"] = False
            log.info("adopted model %s (seed %d)", model_id, seed)
            reply({"op": "adopted", "id": mid, "model_id": model_id})

        batcher.set_weights(
            new_params,
            version=f"{args.weights_version or 'v0'}@{model_id}",
            on_applied=applied)

    def _self_addr() -> str:
        # Late-bound: the server (and its addr) exist only after the
        # handler is built.  Used to tag direct-push sockets so chaos
        # partition faults can match the peer pair.
        return srv_cell[0].addr or "" if srv_cell else ""

    serving = None
    if args.role == "prefill":
        # Prefill-role replicas never decode: no serve loop runs, the
        # handler drives export_kv directly (exports borrow rows).
        handler = prefill_handler(batcher, token=token,
                                  self_addr=_self_addr)
    else:
        # NOT started yet: warmup must run before the serve loop owns
        # the rows; submissions made while warming just queue.
        serving = BatcherServing(batcher)
        handler = batcher_handler(serving, generation=generation,
                                  weights_version=args.weights_version,
                                  model_state=model_state,
                                  adopt_fn=adopt_fn, token=token,
                                  self_addr=_self_addr)

    stop = threading.Event()
    leader = None
    if gang_spec is not None:
        # Rank 0 leads: it owns the batcher, the serve socket, and the
        # registry heartbeat; the gang coordination server fans each
        # generate to the members and verifies their token digests.  A
        # member loss breaks the gang — stop fires, the process exits,
        # and the fleet tears down and re-forms the gang whole.
        if args.role == "prefill":
            print("gang replicas serve the decode/unified path; "
                  "--role prefill cannot lead a gang", file=sys.stderr)
            return 2
        leader = gang_mod.GangLeader(
            gang_spec[0], gang_spec[1], generation=generation,
            token=token, host=args.host,
            on_break=lambda rank: stop.set())
        leader.start()
        handler = gang_mod.leader_handler(handler, leader)
    if fabric is not None:
        # Outside the gang wrap on purpose: a kv_put/kv_fetch is a
        # host-local tier operation, never gang-dispatched.
        handler = fabric_handler(fabric, handler)

    def extra() -> Dict[str, Any]:
        # Heartbeat advert: the tier this replica belongs to and its
        # live KV headroom (decode-tier routing places imports by it),
        # the rollout identity (weights_version + launch generation +
        # task node), plus the prefix-cache summary when one runs.
        beat: Dict[str, Any] = {"role": args.role,
                                "kv_headroom": batcher.kv_headroom(),
                                "gen": generation, "device": device}
        if args.weights_version:
            beat["weights_version"] = args.weights_version
        if node:
            beat["node"] = node
        # Model-catalog identity: the served model (set at launch, or
        # by a later adoption), warm-pool membership (always sent once
        # pool-capable, so an adoption's False overwrites the table's
        # True), and the last adapter delta folded in.
        if model_state["model_id"]:
            beat["model_id"] = model_state["model_id"]
        if model_state["pool_capable"]:
            beat["warm_pool"] = bool(model_state["warm_pool"])
        # Sent even when "" — a fold followed by a full weight swap
        # resets it, and the table must follow, not keep the old label.
        beat["adapter_version"] = getattr(batcher, "adapter_version",
                                          "")
        if batcher.prefix_cache_active:
            beat["prefix_cache"] = batcher.prefix_cache_summary()
        if batcher.kv_tier is not None \
                and batcher.kv_tier_bypass_reason is None:
            # Tier summary: parked session ids (the router's session-
            # affinity key), spilled prefix digests (tier-resident
            # affinity), counters and occupancy for the fleet gauge.
            beat["kv_tier"] = batcher.kv_tier.summary()
        if batcher.d_side is not None:
            # Speculative health: the draft acceptance rate (None
            # before the first round) plus the raw sums the registry's
            # spec_summary() re-aggregates fleet-wide.
            beat["spec"] = {
                "acceptance_rate": batcher.acceptance_rate,
                "rounds": batcher.spec_rounds,
                "row_rounds": batcher.spec_row_rounds,
                "committed": batcher.spec_committed,
                "n_draft": batcher.n_draft,
            }
        if leader is not None:
            # Gang identity + member liveness: what role_summary / the
            # gangs gauge report, and what gang_lookup serves booting
            # members (the registry-mediated rendezvous).
            beat["gang"] = leader.gang_info()
        return beat

    server = ReplicaServer(
        handler, token=token, capacity=args.rows,
        host=args.host, port=args.port, registry_addr=args.registry,
        heartbeat_interval=args.heartbeat_interval, extra_info=extra,
        status="warming" if (args.warmup or leader is not None)
        else None)
    srv_cell.append(server)
    # Register (as warming with --warmup) BEFORE compiling: the fleet's
    # bring-up accounting sees the replica exists while the router
    # cannot yet pick it, and a relaunched replica is visibly re-warming
    # instead of silently absent.
    server.start()
    if fabric is not None:
        fabric.self_addr = server.addr or ""
    if args.warmup:
        # Role replicas warm only the surface they serve: a prefill
        # replica never decodes, a decode replica never prefills (it
        # imports exported KV) — compiling the other role's per-width
        # executables would only lengthen the warming window re-paid on
        # every elastic/Mode-B relaunch.
        info = batcher.warmup(decode=(args.role != "prefill"),
                              prefill=(args.role != "decode"))
        log.info("warmup compiled %s in %.1fs", info["compiled"],
                 info["seconds"])
        print(f"replica warmed in {info['seconds']:.1f}s "
              f"({len(info['compiled'])} entry points)", flush=True)
    if serving is not None:
        serving.start()
    if leader is not None:
        # Never routed while forming: the leader stays 'warming' until
        # every member has joined.  A gang that cannot form exits
        # nonzero — the scheduler reports the death and the fleet
        # re-forms the gang whole rather than serving degraded.
        if not leader.wait_formed(timeout=300.0) or leader.broken:
            log.error("gang %s never formed (%d/%d live); exiting",
                      leader.gang_id, leader.live, leader.size)
            server.stop()
            leader.stop()
            return 1
        print(f"gang {leader.gang_id} formed "
              f"({leader.size} members, generation {generation})",
              flush=True)
    server.set_status(None)     # routable: the next beat drops 'warming'
    print(f"replica serving on {server.addr} (role {args.role}) on "
          f"{device['platform']} {device['kind']!r} device {device['id']} "
          f"(host chips: {device['chips'] or 'none'})", flush=True)

    def on_signal(signum, frame) -> None:
        log.info("signal %d: draining", signum)
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    stop.wait()
    broken = leader is not None and leader.broken
    server.stop()
    if leader is not None:
        leader.stop()
    if serving is not None:
        serving.close()
    # A gang break exits nonzero: the death must read as a failure to
    # the scheduler's dynamic accounting, not a graceful finish.
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main())
