"""What jax.profiler.ProfileData leaves out of an .xplane.pb: the stats of
each plane's EVENT METADATA. On a TPU plane every executed HLO op has one
metadata entry whose `name` is the event's name (the instruction's whole HLO
text) and whose stats carry `tf_op` (JAX's op_name: the jit, transform and
`jax.named_scope` path down to the primitive), `source` (file:line),
`hlo_category`, `flops`, `bytes_accessed`.

Read with a walk of the protobuf wire format, so that nothing heavy is
imported after a traced window (`tensorflow`'s generated classes cost 15 s).
The messages (tsl/profiler/protobuf/xplane.proto), fields by number:

  XSpace          1 planes*
  XPlane          2 name, 4 event_metadata* (map entry: 1 key, 2 value),
                  5 stat_metadata* (map entry: 1 key, 2 value)
  XEventMetadata  1 id, 2 name, 5 stats*
  XStatMetadata   1 id, 2 name
  XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                  6 bytes, 7 ref (the id of a stat metadata whose NAME is
                  the string)
"""
from __future__ import annotations

import struct

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i):
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message's top level: an int
    for varints, the raw bytes for fixed and length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (_FIXED64, _FIXED32):
            size = 8 if wire == _FIXED64 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}: not a "
                             f"protobuf this reader knows")
        yield number, wire, value


def _map_entry(buf):
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict):
    """(stat name, value) of one XStat."""
    name, value = None, None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf) -> dict:
    name, stat_entries, event_entries = "", [], []
    for number, _, v in _fields(buf):
        if number == 2:
            name = bytes(v).decode()
        elif number == 4:
            event_entries.append(v)
        elif number == 5:
            stat_entries.append(v)
    stat_names = {}
    for entry in stat_entries:
        key, value = _map_entry(entry)
        for number, _, v in _fields(value):
            if number == 2:
                stat_names[key] = bytes(v).decode("utf-8", "replace")
    metadata = {}
    for entry in event_entries:
        _, value = _map_entry(entry)
        ev_name, stats = "", {}
        for number, _, v in _fields(value):
            if number == 2:
                ev_name = bytes(v).decode("utf-8", "replace")
            elif number == 5:
                k, val = _stat(v, stat_names)
                stats[k] = val
        metadata[ev_name] = stats
    return {"name": name, "event_metadata": metadata}


def read_planes(path: str) -> list:
    """[{"name": plane name, "event_metadata": {event name: {stat: value}}}]
    of every plane in the file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for number, _, v in _fields(buf) if number == 1]
