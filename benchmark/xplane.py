"""A reader for the profiler's ``.xplane.pb`` with nothing but the standard library.

``jax.profiler.ProfileData`` gives planes, lines and events, but not the statistics that
sit on an event's *metadata* — and that is where XLA keeps the framework name of an
operation (``jit(train_step)/.../splash_mha_fwd/...``), the only place a kernel's
``jax.named_scope`` survives to. So this decodes the protobuf wire format directly.
Field numbers are those of tsl/profiler/protobuf/xplane.proto:

    XSpace  planes=1
    XPlane  name=2 lines=3 event_metadata=4(map) stat_metadata=5(map)
    XLine   name=2 timestamp_ns=3 events=4
    XEvent  metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XStat   metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
    XEventMetadata id=1 name=2 display_name=4 stats=5
    XStatMetadata  id=1 name=2
"""

from __future__ import annotations

import gzip
import struct
from dataclasses import dataclass, field


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes):
    """Yield (field number, wire type, value) of one message; length-delimited values are
    memoryview slices."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = buf[pos : pos + 8], pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos : pos + size], pos + size
        elif wire == 5:
            value, pos = buf[pos : pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


@dataclass
class Event:
    name: str
    start_ns: float  # on the trace's clock: the line's timestamp plus the event's offset
    duration_ns: float
    stats: dict = field(default_factory=dict)  # the event's own and its metadata's

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def _stat(buf: bytes, stat_names: dict) -> tuple[str, object]:
    name, value = "?", None
    for number, _, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw, str(raw))
        elif number == 2:
            value = struct.unpack("<d", bytes(raw))[0]
        elif number == 3:
            value = raw
        elif number == 4:
            value = _signed(raw)
        elif number in (5, 6):
            value = bytes(raw).decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(raw, str(raw))
    return name, value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, _, raw in _fields(buf):
        if number == 1:
            key = raw
        elif number == 2:
            value = raw
    return key, value


def _plane(buf: bytes) -> Plane:
    name, raw_lines, raw_events, stat_names = "", [], {}, {}
    for number, _, raw in _fields(buf):
        if number == 2:
            name = bytes(raw).decode()
        elif number == 3:
            raw_lines.append(raw)
        elif number == 4:
            key, value = _map_entry(raw)
            raw_events[key] = value
        elif number == 5:
            key, value = _map_entry(raw)
            for n, _, r in _fields(value):
                if n == 2:
                    stat_names[key] = bytes(r).decode()
    metadata = {}
    for key, value in raw_events.items():
        event_name, display, stats = "", "", {}
        for n, _, r in _fields(value):
            if n == 2:
                event_name = bytes(r).decode("utf-8", "replace")
            elif n == 4:
                display = bytes(r).decode("utf-8", "replace")
            elif n == 5:
                k, v = _stat(r, stat_names)
                stats[k] = v
        if display:
            stats["display_name"] = display
        metadata[key] = (event_name, stats)
    lines = []
    for raw_line in raw_lines:
        line_name, timestamp_ns, events = "", 0, []
        raw_line_events = []
        for n, _, r in _fields(raw_line):
            if n == 2:
                line_name = bytes(r).decode()
            elif n == 3:
                timestamp_ns = _signed(r)
            elif n == 4:
                raw_line_events.append(r)
        for raw_event in raw_line_events:
            meta_id = offset_ps = duration_ps = 0
            stats = {}
            for n, _, r in _fields(raw_event):
                if n == 1:
                    meta_id = r
                elif n == 2:
                    offset_ps = _signed(r)
                elif n == 3:
                    duration_ps = _signed(r)
                elif n == 4:
                    k, v = _stat(r, stat_names)
                    stats[k] = v
            event_name, meta_stats = metadata.get(meta_id, (str(meta_id), {}))
            events.append(
                Event(event_name, timestamp_ns + offset_ps / 1e3, duration_ps / 1e3, {**meta_stats, **stats})
            )
        lines.append(Line(line_name, events))
    return Plane(name, lines)


def read_xplane(path: str) -> list[Plane]:
    """The planes of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(raw) for number, _, raw in _fields(buf) if number == 1]
