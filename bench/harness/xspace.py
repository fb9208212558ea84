"""A reader of the profiler's ``.xplane.pb`` that needs no TensorFlow.

The file is one ``XSpace`` protobuf message.  ``jax.profiler.ProfileData``
gives each event its own stats but not those of its event metadata, and a
device op's ``tf_op`` stat, the JAX name stack of the op
(``jit(stream_call)/while/body/closed_call/vmap(egress)/...``), lives in
the metadata.  ``read`` decodes the protobuf wire format by hand for the
few messages it needs and returns every plane with its lines and events;
an event carries its metadata's stats overlaid with its own.  A plane
also lists every event metadata's stats by name: the ``/host:metadata``
plane holds each compiled module's ``Hlo Proto`` there, with no events.

Field numbers follow ``tsl/profiler/protobuf/xplane.proto``:

* ``XSpace``: planes 1;
* ``XPlane``: name 2, lines 3, event_metadata 4 and stat_metadata 5 (maps
  of id to message, each entry a message with key 1 and value 2);
* ``XLine``: name 2, timestamp_ns 3, events 4;
* ``XEvent``: metadata_id 1, offset_ps 2, duration_ps 3, stats 4;
* ``XEventMetadata``: id 1, name 2, stats 5;
* ``XStatMetadata``: id 1, name 2;
* ``XStat``: metadata_id 1, then one of double 2, uint64 3, int64 4,
  str 5, bytes 6, ref 7 (the id of a stat metadata whose name is the
  value).

``hlo_instructions`` reads a module's ``Hlo Proto`` the same way, after
``xla/service/hlo.proto``: ``HloProto`` hlo_module 1; ``HloModuleProto``
computations 3; ``HloComputationProto`` instructions 2, id 5;
``HloInstructionProto`` name 1, metadata 7 (``OpMetadata`` op_name 2), id
35, operand_ids 36.
"""

from __future__ import annotations

import dataclasses
import gzip
import struct
from typing import NamedTuple


@dataclasses.dataclass
class Event:
    name: str              # the event metadata's name
    start_ns: float        # on the trace's clock
    duration_ns: float
    stats: dict            # stat name -> value


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    metadata: dict         # event metadata name -> its stats


def _varint(buf, i: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of every field of one message: an
    int for a varint, bytes for a fixed-width value, a memoryview for a
    length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            key = _signed(v)
        elif field == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict):
    mid, value = 0, None
    for field, _, v in _fields(buf):
        if field == 1:
            mid = _signed(v)
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = _text(v)
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


def _plane(buf) -> Plane:
    name, raw_lines, raw_events, stat_names = "", [], {}, {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            raw_lines.append(v)
        elif field == 4:
            key, value = _map_entry(v)
            raw_events[key] = value
        elif field == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for f, _, x in _fields(value) if f == 2), "")
    metadata = {}
    for key, value in raw_events.items():
        md_name, md_stats = "", {}
        for field, _, v in _fields(value if value is not None else b""):
            if field == 2:
                md_name = _text(v)
            elif field == 5:
                k, x = _stat(v, stat_names)
                md_stats[k] = x
        metadata[key] = (md_name, md_stats)
    return Plane(name=name,
                 lines=[_line(b, metadata, stat_names) for b in raw_lines],
                 metadata=dict(metadata.values()))


def _line(buf, metadata: dict, stat_names: dict) -> Line:
    name, t0, raw = "", 0, []
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            t0 = _signed(v)
        elif field == 4:
            raw.append(v)
    events = []
    for b in raw:
        mid = offset_ps = duration_ps = 0
        own = {}
        for field, _, v in _fields(b):
            if field == 1:
                mid = _signed(v)
            elif field == 2:
                offset_ps = _signed(v)
            elif field == 3:
                duration_ps = _signed(v)
            elif field == 4:
                k, x = _stat(v, stat_names)
                own[k] = x
        md_name, md_stats = metadata.get(mid, ("", {}))
        events.append(Event(name=md_name, start_ns=t0 + offset_ps / 1000,
                            duration_ns=duration_ps / 1000,
                            stats={**md_stats, **own} if own else md_stats))
    return Line(name=name, events=events)


def _ids(wire: int, v) -> list[int]:
    """A repeated int64 field's values, packed or not."""
    if wire == 0:
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


class Instruction(NamedTuple):
    op_name: str             # "" where the compiler gave it none
    operands: list           # operand instruction names


def hlo_instructions(hlo_proto: bytes) -> dict:
    """Every instruction of a serialized ``HloProto`` by name (names are
    unique in a module)."""
    module = next(v for f, _, v in _fields(memoryview(hlo_proto)) if f == 1)
    raw = []
    for field, _, comp in _fields(module):
        if field != 3:
            continue
        for f, _, v in _fields(comp):
            if f != 2:
                continue
            name, op_name, iid, ops = "", "", None, []
            for g, w, x in _fields(v):
                if g == 1:
                    name = _text(x)
                elif g == 7:
                    op_name = next((_text(y) for h, _, y in _fields(x)
                                    if h == 2), "")
                elif g == 35:
                    iid = x
                elif g == 36:
                    ops += _ids(w, x)
            raw.append((name, op_name, iid, ops))
    by_id = {iid: name for name, _, iid, _ in raw}
    return {name: Instruction(op_name, [by_id[i] for i in ops if i in by_id])
            for name, op_name, _, ops in raw}


def read(path: str) -> list[Plane]:
    """Every plane of the ``.xplane.pb`` at ``path`` (gzip-compressed when
    its name ends in ``.gz``)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v) for field, _, v in _fields(data) if field == 1]
