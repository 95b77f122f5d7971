import socket
import threading
import time

import pytest

from tfmesos_tpu import wire


def _pair():
    listener = wire.bind_ephemeral("127.0.0.1")
    addr = wire.sock_addr(listener, advertise_host="127.0.0.1")
    client = wire.connect(addr)
    server, _ = listener.accept()
    listener.close()
    return client, server


def test_roundtrip_plain():
    c, s = _pair()
    wire.send_msg(c, {"op": "register", "x": [1, 2, 3]})
    assert wire.recv_msg(s) == {"op": "register", "x": [1, 2, 3]}
    c.close(); s.close()


def test_roundtrip_token():
    token = wire.new_token()
    c, s = _pair()
    wire.send_msg(c, "hello", token)
    assert wire.recv_msg(s, token) == "hello"
    c.close(); s.close()


def test_bad_token_rejected():
    c, s = _pair()
    wire.send_msg(c, "hello", "right-token")
    with pytest.raises(wire.WireError):
        wire.recv_msg(s, "wrong-token")
    c.close(); s.close()


def test_tampered_body_rejected():
    token = wire.new_token()
    frame = bytearray(wire.encode({"a": 1}, token))
    frame[-1] ^= 0xFF
    framer = wire.Framer(token)
    with pytest.raises(wire.WireError):
        framer.feed(bytes(frame))


def test_framer_incremental_and_coalesced():
    token = wire.new_token()
    msgs = [{"i": i, "data": "x" * i} for i in range(5)]
    stream = b"".join(wire.encode(m, token) for m in msgs)
    framer = wire.Framer(token)
    out = []
    # Feed one byte at a time: exercises partial-frame buffering.
    for b in stream[: len(stream) // 2]:
        out.extend(framer.feed(bytes([b])))
    # Then the rest at once: exercises multiple frames per feed.
    out.extend(framer.feed(stream[len(stream) // 2:]))
    assert out == msgs


def test_oversized_frame_rejected():
    framer = wire.Framer()
    with pytest.raises(wire.WireError):
        framer.feed(b"\xff\xff\xff\xff")


def test_closed_connection_raises():
    c, s = _pair()
    c.close()
    with pytest.raises(wire.WireError):
        wire.recv_msg(s)
    s.close()


def test_concurrent_messages_ordered():
    token = wire.new_token()
    c, s = _pair()

    def sender():
        for i in range(100):
            wire.send_msg(c, i, token)

    t = threading.Thread(target=sender)
    t.start()
    got = [wire.recv_msg(s, token) for _ in range(100)]
    t.join()
    assert got == list(range(100))
    c.close(); s.close()


def test_load_token_prefers_file(tmp_path):
    from tfmesos_tpu.wire import load_token

    p = tmp_path / "tok"
    p.write_text("file-token\n")
    env = {"TPUMESOS_TOKEN": "env-token", "TPUMESOS_TOKEN_FILE": str(p)}
    assert load_token(env) == "file-token"
    assert load_token({"TPUMESOS_TOKEN": "env-token"}) == "env-token"
    assert load_token({}) == ""


# -- fuzz / edge cases (fleet PR: the gateway multiplies the number of
# -- long-lived framed connections, so the decoder's edges get exhaustive
# -- coverage) --------------------------------------------------------------


def test_framer_every_two_part_split_boundary():
    """Partial frames split at EVERY byte boundary must decode
    identically to one contiguous feed."""
    token = wire.new_token()
    msgs = [{"op": "generate", "prompt": [1, 2, 3]}, "x" * 40, [7, [8]]]
    stream = b"".join(wire.encode(m, token) for m in msgs)
    for i in range(1, len(stream)):
        framer = wire.Framer(token)
        out = framer.feed(stream[:i])
        out.extend(framer.feed(stream[i:]))
        assert out == msgs, f"diverged when split at byte {i}"


def test_framer_three_part_splits_around_header():
    """Splits inside the 4-byte length prefix AND inside the tag of the
    same frame (the double-partial case a byte-at-a-time feed can miss
    interacting)."""
    token = wire.new_token()
    msg = {"k": "v" * 17}
    stream = wire.encode(msg, token) * 2
    for i in range(1, 4):
        for j in range(i + 1, min(len(stream), i + 40)):
            framer = wire.Framer(token)
            out = framer.feed(stream[:i])
            out.extend(framer.feed(stream[i:j]))
            out.extend(framer.feed(stream[j:]))
            assert out == [msg, msg], f"diverged at splits ({i}, {j})"


def test_oversized_length_prefix_rejected_before_buffering():
    """A length prefix over MAX_FRAME must raise immediately — both in
    the incremental decoder and the blocking reader — not allocate."""
    import struct

    huge = struct.pack(">I", wire.MAX_FRAME + 1)
    framer = wire.Framer()
    with pytest.raises(wire.WireError, match="exceeds limit"):
        framer.feed(huge)
    c, s = _pair()
    c.sendall(huge + b"\x00" * 64)
    with pytest.raises(wire.WireError, match="exceeds limit"):
        wire.recv_msg(s)
    c.close(); s.close()


def test_frame_shorter_than_tag_rejected():
    """A frame whose payload cannot even hold the 32-byte auth tag is
    malformed, not silently truncated."""
    import struct

    for n in (0, 1, wire.TAG_SIZE - 1):
        frame = struct.pack(">I", n) + b"\x01" * n
        framer = wire.Framer()
        with pytest.raises(wire.WireError, match="shorter than auth tag"):
            framer.feed(frame)


def test_framer_wrong_token_rejected_incrementally():
    """Wrong-token rejection through the incremental path, fed one byte
    at a time — the tag check must fire exactly when the frame
    completes."""
    frame = wire.encode({"a": 1}, "right-token")
    framer = wire.Framer("wrong-token")
    with pytest.raises(wire.WireError, match="bad auth tag"):
        for i in range(len(frame)):
            framer.feed(frame[i:i + 1])


def test_recv_msg_wrong_token_then_socket_reusable_for_framer():
    """recv_msg with the wrong token rejects the frame; a fresh frame
    with the right token on the same socket still decodes (the gateway
    logs-and-drops per connection, so the decoder must not poison
    unrelated state)."""
    token = wire.new_token()
    c, s = _pair()
    wire.send_msg(c, "nope", "other-token")
    with pytest.raises(wire.WireError):
        wire.recv_msg(s, token)
    wire.send_msg(c, "yes", token)
    assert wire.recv_msg(s, token) == "yes"
    c.close(); s.close()


def test_raw_frame_roundtrip_socket_and_framer():
    """A raw frame (JSON meta + binary body) survives the socket
    path and the incremental decoder, body byte-exact."""
    token = wire.new_token()
    meta = {"op": "prefilled", "id": 7, "shape": [2, 3]}
    body = bytes(range(256)) * 64
    c, s = _pair()
    wire.send_raw_msg(c, meta, body, token)
    got = wire.recv_msg(s, token, allow_raw=True)
    assert isinstance(got, wire.RawFrame)
    assert got.meta == meta and got.body == body
    framer = wire.Framer(token, allow_raw=True)
    out = framer.feed(wire.encode_raw(meta, body, token))
    assert len(out) == 1 and out[0].meta == meta and out[0].body == body
    c.close(); s.close()


def test_raw_and_json_frames_interleave_on_one_stream():
    """Raw and JSON frames mixed on one connection decode in order —
    neither framing can mis-frame the other (the raw bit partitions
    the length space)."""
    token = wire.new_token()
    framer = wire.Framer(token, allow_raw=True)
    stream = (wire.encode({"op": "a"}, token)
              + wire.encode_raw({"op": "raw1"}, b"\x00" * 1000, token)
              + wire.encode([1, 2], token)
              + wire.encode_raw({"op": "raw2"}, b"", token)
              + wire.encode("tail", token))
    # Whole stream at once, then byte-at-a-time: identical decodes.
    whole = framer.feed(stream)
    byte_framer = wire.Framer(token, allow_raw=True)
    bywise = []
    for i in range(len(stream)):
        bywise.extend(byte_framer.feed(stream[i:i + 1]))
    for out in (whole, bywise):
        assert [getattr(m, "meta", m) for m in out] == \
            [{"op": "a"}, {"op": "raw1"}, [1, 2], {"op": "raw2"}, "tail"]
        assert out[1].body == b"\x00" * 1000 and out[3].body == b""


def test_raw_frame_truncated_body_never_misframes():
    """A raw frame cut anywhere stays pending in the Framer (no
    partial emit) and fails loudly on the blocking reader when the
    connection dies mid-frame."""
    token = wire.new_token()
    frame = wire.encode_raw({"op": "kv"}, b"\xab" * 512, token)
    for cut in (3, 4, 10, wire.TAG_SIZE + 4, len(frame) - 1):
        framer = wire.Framer(token, allow_raw=True)
        assert framer.feed(frame[:cut]) == []
        out = framer.feed(frame[cut:])     # completing it decodes fine
        assert len(out) == 1 and out[0].body == b"\xab" * 512
    c, s = _pair()
    c.sendall(frame[:len(frame) - 7])
    c.close()
    with pytest.raises(wire.WireError, match="closed mid-frame"):
        wire.recv_msg(s, token, allow_raw=True)
    s.close()


def test_raw_frame_tampered_tag_and_body_rejected():
    token = wire.new_token()
    frame = bytearray(wire.encode_raw({"op": "kv"}, b"payload", token))
    flipped_tag = bytearray(frame)
    flipped_tag[4] ^= 0xFF              # inside the 32B tag
    with pytest.raises(wire.WireError, match="bad auth tag"):
        wire.Framer(token, allow_raw=True).feed(bytes(flipped_tag))
    flipped_body = bytearray(frame)
    flipped_body[-1] ^= 0xFF            # last body byte
    with pytest.raises(wire.WireError, match="bad auth tag"):
        wire.Framer(token, allow_raw=True).feed(bytes(flipped_body))


def test_raw_frame_wrong_token_rejected():
    frame = wire.encode_raw({"op": "kv"}, b"x" * 32, "right-token")
    with pytest.raises(wire.WireError, match="bad auth tag"):
        wire.Framer("wrong-token", allow_raw=True).feed(frame)


def test_raw_frame_oversized_rejected_before_buffering():
    import struct

    huge = struct.pack(">I", wire.RAW_FLAG | (wire.MAX_RAW_FRAME + 1))
    with pytest.raises(wire.WireError, match="exceeds limit"):
        wire.Framer(allow_raw=True).feed(huge)
    c, s = _pair()
    c.sendall(huge + b"\x00" * 64)
    with pytest.raises(wire.WireError, match="exceeds limit"):
        wire.recv_msg(s, allow_raw=True)
    c.close(); s.close()


def test_raw_frame_rejected_on_default_stream():
    """Raw decoding is opt-in per stream: a default Framer/recv_msg
    (gateway, registry, scheduler listeners) rejects the raw bit at
    the 4-byte length prefix — BEFORE buffering any of the claimed
    body, so an unauthenticated peer cannot widen the pre-auth memory
    bound past MAX_FRAME by setting the bit."""
    token = wire.new_token()
    frame = wire.encode_raw({"op": "kv"}, b"x" * 128, token)
    with pytest.raises(wire.WireError, match="not accepted"):
        wire.Framer(token).feed(frame)
    # The prefix alone triggers the rejection — no body needed.
    with pytest.raises(wire.WireError, match="not accepted"):
        wire.Framer(token).feed(frame[:4])
    c, s = _pair()
    c.sendall(frame)
    with pytest.raises(wire.WireError, match="not accepted"):
        wire.recv_msg(s, token)
    c.close(); s.close()


def test_raw_frame_bad_meta_rejected_after_auth():
    """A correctly tagged frame whose meta is not valid JSON is a
    WireError — and the tag is checked FIRST (an unauthenticated frame
    never reaches the meta decoder)."""
    import hashlib
    import hmac as hmac_mod
    import struct

    token = "t"
    inner = struct.pack(">I", 5) + b"\xffnope" + b"body"
    tag = hmac_mod.new(token.encode(), inner, hashlib.sha256).digest()
    frame = struct.pack(
        ">I", wire.RAW_FLAG | (len(tag) + len(inner))) + tag + inner
    with pytest.raises(wire.WireError, match="bad raw meta"):
        wire.Framer(token, allow_raw=True).feed(frame)
    # Same frame, wrong token: rejected at the tag, meta never decoded.
    with pytest.raises(wire.WireError, match="bad auth tag"):
        wire.Framer("other", allow_raw=True).feed(frame)


def test_raw_frame_meta_length_beyond_payload_rejected():
    import hashlib
    import hmac as hmac_mod
    import struct

    token = "t"
    inner = struct.pack(">I", 10_000) + b"short"
    tag = hmac_mod.new(token.encode(), inner, hashlib.sha256).digest()
    frame = struct.pack(
        ">I", wire.RAW_FLAG | (len(tag) + len(inner))) + tag + inner
    with pytest.raises(wire.WireError, match="bad raw meta length"):
        wire.Framer(token, allow_raw=True).feed(frame)


def test_non_utf8_body_rejected():
    """A correct tag over a non-JSON body is still a WireError (never a
    raw UnicodeDecodeError escaping to callers)."""
    import hashlib
    import hmac as hmac_mod
    import struct

    token = "t"
    body = b"\xff\xfe{bad"
    tag = hmac_mod.new(token.encode(), body, hashlib.sha256).digest()
    frame = struct.pack(">I", len(tag) + len(body)) + tag + body
    framer = wire.Framer(token)
    with pytest.raises(wire.WireError, match="bad JSON body"):
        framer.feed(frame)


# -- the event-loop serve core (WireServer) ----------------------------------
#
# One selector thread serves EVERY connection of a listener (the
# front-door scaling core, docs/SERVING.md "Front-door scaling"); these
# tests drive it with plain threaded clients — proving old clients talk
# to the new server unchanged — and with hostile peers (slow-loris,
# half-open, slow readers) that must cost one connection, never the
# loop.


def _echo_server(token, allow_raw=False, **kw):
    def handler(conn, msg):
        if isinstance(msg, wire.RawFrame):
            conn.send_raw(dict(msg.meta, echoed=True), msg.body)
        else:
            conn.send({"echo": msg})

    return wire.WireServer(handler, token=token, allow_raw=allow_raw,
                           **kw).start()


def test_wire_server_echo_smoke():
    """Threaded-client wire compatibility: send_msg/recv_msg against the
    event loop round-trips JSON frames, HMAC discipline intact (a
    wrong-token frame drops the connection, the right-token peer is
    untouched)."""
    token = wire.new_token()
    srv = _echo_server(token)
    try:
        c = wire.connect(srv.addr)
        for i in range(50):
            wire.send_msg(c, {"op": "ping", "i": i}, token)
        for i in range(50):
            assert wire.recv_msg(c, token) == {
                "echo": {"op": "ping", "i": i}}
        # An unauthenticated peer is dropped at its first frame...
        bad = wire.connect(srv.addr)
        wire.send_msg(bad, {"op": "x"}, "wrong-token")
        with pytest.raises((OSError, wire.WireError)):
            for _ in range(10):
                wire.recv_msg(bad, "wrong-token")
        bad.close()
        # ...and the healthy connection never noticed.
        wire.send_msg(c, "still-here", token)
        assert wire.recv_msg(c, token) == {"echo": "still-here"}
        c.close()
    finally:
        srv.stop()


def test_wire_server_slow_loris_partial_frames():
    """A peer dribbling one frame a byte at a time (and stalling
    mid-frame) holds only its own Framer buffer: concurrent clients get
    served at full speed the whole while, and the dribbled frame
    decodes once it completes."""
    token = wire.new_token()
    srv = _echo_server(token)
    try:
        loris = wire.connect(srv.addr)
        frame = wire.encode({"op": "slow"}, token)
        for b in frame[:-1]:
            loris.sendall(bytes([b]))
            # A fast client round-trips BETWEEN the loris bytes.
        fast = wire.connect(srv.addr)
        t0 = time.monotonic()
        wire.send_msg(fast, {"op": "fast"}, token)
        assert wire.recv_msg(fast, token) == {"echo": {"op": "fast"}}
        assert time.monotonic() - t0 < 2.0
        fast.close()
        loris.sendall(frame[-1:])       # frame completes -> decoded
        assert wire.recv_msg(loris, token) == {"echo": {"op": "slow"}}
        loris.close()
    finally:
        srv.stop()


def test_wire_server_half_open_peer_does_not_wedge_loop():
    """A peer that sends half a frame and then goes silent (the
    SIGKILLed-host shape) just sits as one idle connection; an aborted
    peer (RST) is reaped.  Either way the loop keeps serving."""
    token = wire.new_token()
    srv = _echo_server(token)
    try:
        half = wire.connect(srv.addr)
        half.sendall(wire.encode({"op": "never"}, token)[:7])
        # Abortive close (RST instead of FIN): the loop must reap it.
        rst = wire.connect(srv.addr)
        rst.sendall(wire.encode({"op": "x"}, token)[:3])
        rst.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                       __import__("struct").pack("ii", 1, 0))
        rst.close()
        deadline = time.monotonic() + 5.0
        fast = wire.connect(srv.addr)
        wire.send_msg(fast, {"op": "alive"}, token)
        assert wire.recv_msg(fast, token) == {"echo": {"op": "alive"}}
        fast.close()
        while time.monotonic() < deadline:
            if len(srv.connections()) <= 1:     # rst + fast reaped
                break
            time.sleep(0.02)
        assert len(srv.connections()) <= 1
        half.close()
    finally:
        srv.stop()


def test_wire_server_backpressure_drops_slow_reader_only():
    """A peer that never reads its replies fills its bounded write
    buffer and gets DROPPED — the loop and every other client keep
    going (an unbounded buffer would let one slow reader OOM the
    gateway; a blocking send would wedge every connection)."""
    token = wire.new_token()
    payload = "x" * 65536
    srv = _echo_server(token, max_buffer=256 * 1024)
    try:
        slow = wire.connect(srv.addr)
        slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        # Pump requests without ever reading replies: the echo replies
        # accumulate in the server-side buffer past max_buffer.
        dropped = False
        try:
            for _ in range(200):
                wire.send_msg(slow, {"op": "flood", "pad": payload},
                              token)
        except OSError:
            dropped = True      # server closed us mid-pump
        # Either the pump already saw the close, or the next read does.
        if not dropped:
            slow.settimeout(5.0)
            with pytest.raises((OSError, wire.WireError)):
                while True:
                    wire.recv_msg(slow, token)
        slow.close()
        # The loop survived and other clients are unaffected.
        fine = wire.connect(srv.addr)
        wire.send_msg(fine, {"op": "ok"}, token)
        assert wire.recv_msg(fine, token) == {"echo": {"op": "ok"}}
        fine.close()
    finally:
        srv.stop()


def test_wire_server_oversized_preauth_frame_rejected_at_prefix():
    """The 64 MiB pre-auth bound holds on the event loop: a length
    prefix over MAX_FRAME (or the raw bit on a non-allow_raw server)
    drops the connection at the 4-byte prefix — nothing buffers."""
    import struct as struct_mod

    token = wire.new_token()
    srv = _echo_server(token)               # allow_raw=False
    try:
        for prefix in (struct_mod.pack(">I", wire.MAX_FRAME + 1),
                       struct_mod.pack(
                           ">I", wire.RAW_FLAG | (1 << 29))):
            c = wire.connect(srv.addr)
            c.sendall(prefix)
            c.settimeout(5.0)
            with pytest.raises((OSError, wire.WireError)):
                wire.recv_msg(c, token)     # server closed on us
            c.close()
        ok = wire.connect(srv.addr)
        wire.send_msg(ok, "fine", token)
        assert wire.recv_msg(ok, token) == {"echo": "fine"}
        ok.close()
    finally:
        srv.stop()


def test_wire_server_interleaved_raw_and_json_frames():
    """An allow_raw WireServer (replica-link shape) decodes raw and
    JSON frames interleaved on one connection, in order, bodies
    byte-exact — same contract as the threaded reader."""
    token = wire.new_token()
    srv = _echo_server(token, allow_raw=True)
    try:
        c = wire.connect(srv.addr)
        body = bytes(range(256)) * 32
        wire.send_msg(c, {"op": "a"}, token)
        wire.send_raw_msg(c, {"op": "kv", "id": 1}, body, token)
        wire.send_msg(c, {"op": "b"}, token)
        assert wire.recv_msg(c, token, allow_raw=True) == {
            "echo": {"op": "a"}}
        raw = wire.recv_msg(c, token, allow_raw=True)
        assert isinstance(raw, wire.RawFrame)
        assert raw.meta == {"op": "kv", "id": 1, "echoed": True}
        assert raw.body == body
        assert wire.recv_msg(c, token, allow_raw=True) == {
            "echo": {"op": "b"}}
        c.close()
    finally:
        srv.stop()


def test_wire_server_wake_listener_unblocks_selector_on_stop():
    """Regression: wake_listener must still unblock the selector loop
    after the stop flag is set (the fleet-wide stop discipline) — even
    with the internal waker disabled, the accept poke alone gets the
    loop to re-check its flag and exit promptly."""
    token = wire.new_token()
    srv = _echo_server(token)
    try:
        srv._wake = lambda: None            # waker out of the picture
        srv._stop.set()
        t0 = time.monotonic()
        wire.wake_listener(srv._listen)
        srv._thread.join(timeout=3.0)
        assert not srv._thread.is_alive()
        assert time.monotonic() - t0 < 3.0
    finally:
        srv._thread = None
        srv.stop()                          # idempotent cleanup


def test_wire_server_connection_flood():
    """The point of the event loop: hundreds of concurrent client
    connections on ONE serve thread, every request answered.  (The
    full-scale 1000+ figure is scenario_gateway_concurrency's.)"""
    token = wire.new_token()
    srv = _echo_server(token)
    socks = []
    try:
        n = 256
        for i in range(n):
            s = wire.connect(srv.addr, timeout=10.0)
            socks.append(s)
            wire.send_msg(s, {"i": i}, token)
        for i, s in enumerate(socks):
            assert wire.recv_msg(s, token) == {"echo": {"i": i}}
        # Threads in this process stayed O(1): the server side of the
        # flood is the selector loop, not 256 readers.
        server_threads = [t for t in threading.enumerate()
                          if t.name == "wire-server"]
        assert len(server_threads) == 1
    finally:
        for s in socks:
            s.close()
        srv.stop()


def test_wire_server_send_from_many_threads_ordered_per_connection():
    """conn.send is thread-safe: replies queued from many worker
    threads all land, each frame intact (the gateway's worker pool
    replies through exactly this path)."""
    token = wire.new_token()
    got = []

    def handler(conn, msg):
        # Fan the reply work out to threads, like gateway workers.
        def work(k):
            for j in range(10):
                conn.send({"k": k, "j": j})

        for k in range(4):
            threading.Thread(target=work, args=(k,), daemon=True).start()

    srv = wire.WireServer(handler, token=token).start()
    try:
        c = wire.connect(srv.addr)
        wire.send_msg(c, {"op": "go"}, token)
        c.settimeout(10.0)
        for _ in range(40):
            got.append(wire.recv_msg(c, token))
        per_k = {k: [m["j"] for m in got if m["k"] == k]
                 for k in range(4)}
        assert all(v == list(range(10)) for v in per_k.values())
        c.close()
    finally:
        srv.stop()
