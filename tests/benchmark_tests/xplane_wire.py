"""The protobuf wire format, as far as a profiler trace (``XSpace``,
tsl/profiler/protobuf/xplane.proto) needs it: enough to cut a recorded
trace down to the lines the reduction reads (``cut_trace.py``) and to make
a small trace in a test.  A test helper: reading a trace for a measurement
goes through ``jax.profiler.ProfileData`` (``trace_reduce.load``), never
through this.

A message is a list of ``(field, wire_type, value)``: ``value`` is an int
for a varint (wire type 0) and bytes for everything else (1: 8 bytes, 2:
length-delimited, 5: 4 bytes).  Field numbers used here:

    XSpace          1 planes
    XPlane          1 id  2 name  3 lines  4 event_metadata (1 key, 2 value)
    XLine           1 id  2 name  3 timestamp_ns  4 events
    XEvent          1 metadata_id  2 offset_ps  3 duration_ps
    XEventMetadata  1 id  2 name
"""

from __future__ import annotations

from typing import Iterable, List, Tuple, Union

Field = Tuple[int, int, Union[int, bytes]]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def decode(buf: bytes) -> List[Field]:
    out: List[Field] = []
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        out.append((field, wire, value))
    return out


def _enc_varint(value: int) -> bytes:
    value &= (1 << 64) - 1          # a negative int64 is ten bytes
    out = bytearray()
    while value > 0x7F:
        out.append((value & 0x7F) | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def encode(fields: Iterable[Field]) -> bytes:
    out = bytearray()
    for field, wire, value in fields:
        out += _enc_varint(field << 3 | wire)
        if wire == 0:
            out += _enc_varint(value)
        elif wire == 2:
            out += _enc_varint(len(value)) + value
        else:
            out += value
    return bytes(out)


def first(fields: List[Field], field: int, default=None):
    return next((v for f, _, v in fields if f == field), default)


# -- making a trace ----------------------------------------------------------

def line(name: str, events: Iterable[Tuple[int, int, int]],
         line_id: int = 0) -> bytes:
    """An ``XLine`` whose events are ``(metadata_id, start_ps,
    duration_ps)``, its own timestamp 0."""
    fields: List[Field] = [(1, 0, line_id), (2, 2, name.encode())]
    for meta, start_ps, dur_ps in events:
        fields.append((4, 2, encode([(1, 0, meta), (2, 0, start_ps),
                                     (3, 0, dur_ps)])))
    return encode(fields)


def plane(name: str, lines: Iterable[bytes], names: dict,
          plane_id: int = 0) -> bytes:
    """An ``XPlane``; ``names`` maps each metadata id to the event's name."""
    fields: List[Field] = [(1, 0, plane_id), (2, 2, name.encode())]
    fields += [(3, 2, ln) for ln in lines]
    for meta, text in names.items():
        value = encode([(1, 0, meta), (2, 2, text.encode())])
        fields.append((4, 2, encode([(1, 0, meta), (2, 2, value)])))
    return encode(fields)


def space(planes: Iterable[bytes]) -> bytes:
    return encode([(1, 2, p) for p in planes])
