"""Decoder of the JAX profiler's dumps (``*.xplane.pb``), standard
library only: what ``trace.py`` reads the ``df2.*`` device scopes with.

``jax.profiler.ProfileData`` shows each event's own stats and hides the
stats of its *event metadata*, which is where the profiler keeps what
is the same for every execution of an operation: the ``tf_op`` path
with the program's ``jax.named_scope`` names in it. So this decodes the
XSpace protobuf wire format itself (tsl/profiler/protobuf/xplane.proto:
planes, lines, events, event and stat metadata, ``ref_value`` stats).

A copy of the program's ``dragonfly2_tpu/utils/xplane.py`` (less its
file finder), because what turns a trace into a metric is the
benchmark's yardstick and lives under its own paths;
``tests/test_scopes.py`` holds the two to the same events on a recorded
trace.

    for plane in read_xspace(path):  # "/device:TPU:0", "/host:CPU", ...
        for line in plane.lines:     # "XLA Ops", a host thread, ...
            for ev in line.events:   # ev.name, ev.start_ns, ev.duration_ns,
                ...                  # ev.stats (own, then metadata's)
"""

from __future__ import annotations

import dataclasses
import struct

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _varint(buf: bytes, pos: int) -> tuple:
    value = buf[pos]
    pos += 1
    if value & 0x80:
        value &= 0x7F
        shift = 7
        while True:
            b = buf[pos]
            pos += 1
            value |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
    return value, pos


def _fields(buf: bytes, pos: int, end: int):
    """(field number, wire type, value) of one message; a
    length-delimited value is its (start, end) in ``buf``."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == _VARINT:
            value, pos = _varint(buf, pos)
        elif kind == _BYTES:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif kind == _FIXED64:
            value = buf[pos:pos + 8]
            pos += 8
        elif kind == _FIXED32:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {kind} at byte {pos}: not an "
                             "XSpace dump")
        yield key >> 3, kind, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: dict):
    """One XStat as (name, value); a ``ref_value`` is the name of the
    stat metadata it points at (how strings are shared)."""
    name, value = None, None
    for field, kind, raw in _fields(buf, *span):
        if field == 1:
            name = stat_names.get(raw, str(raw))
        elif field == 2:
            value = struct.unpack("<d", raw)[0]
        elif field == 3:
            value = raw
        elif field == 4:
            value = _signed(raw)
        elif field == 5:
            value = _text(buf, raw)
        elif field == 6:
            value = buf[raw[0]:raw[1]]
        elif field == 7:
            value = stat_names.get(raw, str(raw))
    return name, value


def _map_entry(buf: bytes, span):
    key, value = 0, None
    for field, _, raw in _fields(buf, *span):
        if field == 1:
            key = raw
        elif field == 2:
            value = raw
    return key, value


def _plane(buf: bytes, span) -> Plane:
    name, line_spans, event_meta, stat_meta = "", [], [], []
    for field, _, raw in _fields(buf, *span):
        if field == 2:
            name = _text(buf, raw)
        elif field == 3:
            line_spans.append(raw)
        elif field == 4:
            event_meta.append(raw)
        elif field == 5:
            stat_meta.append(raw)

    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for field, _, raw in _fields(buf, *value):
            if field == 2:
                stat_names[key] = _text(buf, raw)

    # id -> (name, stats of the metadata)
    metadata = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        meta_name, display, stats = "", "", {}
        for field, _, raw in _fields(buf, *value):
            if field == 2:
                meta_name = _text(buf, raw)
            elif field == 4:
                display = _text(buf, raw)
            elif field == 5:
                stat_name, stat_value = _stat(buf, raw, stat_names)
                stats[stat_name] = stat_value
        metadata[key] = (meta_name or display, stats)

    lines = []
    for line_span in line_spans:
        line_name, timestamp_ns, event_spans = "", 0, []
        for field, _, raw in _fields(buf, *line_span):
            if field == 2:
                line_name = _text(buf, raw)
            elif field == 3:
                timestamp_ns = _signed(raw)
            elif field == 4:
                event_spans.append(raw)
        events = []
        for event_span in event_spans:
            meta_id, offset_ps, duration_ps, own = 0, 0, 0, None
            for field, _, raw in _fields(buf, *event_span):
                if field == 1:
                    meta_id = raw
                elif field == 2:
                    offset_ps = _signed(raw)
                elif field == 3:
                    duration_ps = _signed(raw)
                elif field == 4:
                    stat_name, stat_value = _stat(buf, raw, stat_names)
                    if own is None:
                        own = {}
                    own[stat_name] = stat_value
            event_name, shared = metadata.get(meta_id, (str(meta_id), {}))
            # The event's own stats win over its metadata's.
            stats = {**shared, **own} if own else shared
            events.append(Event(event_name, timestamp_ns + offset_ps * 1e-3,
                                duration_ps * 1e-3, stats))
        lines.append(Line(line_name, events))
    return Plane(name, lines)


def read_xspace(path: str) -> list:
    """Every plane of the dump at ``path``."""
    with open(path, "rb") as fh:
        buf = fh.read()
    return [_plane(buf, raw) for field, kind, raw in _fields(buf, 0, len(buf))
            if field == 1 and kind == _BYTES]
