"""Minimal XSpace protobuf reader — the ``jax.profiler.ProfileData``
fallback for jax builds that don't ship the binding (this container's
0.4.37 exposes only ``device_memory_profile``).

The xplane file on disk is a plain ``tensorflow.profiler.XSpace`` proto;
the handful of fields the tables need (planes → lines → events with
names and times) decode with a ~60-line wire-format walker — no
tensorflow/protobuf dependency. Field numbers from
``tsl/profiler/protobuf/xplane.proto``::

    XSpace   { repeated XPlane planes = 1; }
    XPlane   { int64 id = 1; string name = 2; repeated XLine lines = 3;
               map<int64, XEventMetadata> event_metadata = 4; }
    XLine    { int64 id = 1; string name = 2; int64 timestamp_ns = 3;
               repeated XEvent events = 4; }
    XEvent   { int64 metadata_id = 1; int64 offset_ps = 2;
               int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; }

The facade classes mirror the ``ProfileData`` attribute surface the
table builders consume (``planes[].lines[].events[]`` with ``name`` /
``start_ns`` / ``duration_ns``).

``op_names`` reads what ``ProfileData`` does not expose at all: the stats
of an event's METADATA. On a TPU trace that is where an op's HLO
``op_name`` lives (stat ``tf_op``, as ``<op_name>:<op type>``; seen by
hand on a v5e trace, PR 25) — the ``jax.named_scope`` path the per-scope
table is built from::

    XPlane   { map<int64, XStatMetadata> stat_metadata = 5; }
    XEventMetadata { repeated XStat stats = 5; }
    XStat    { int64 metadata_id = 1; string str_value = 5;
               uint64 ref_value = 7; }   # ref: a stat_metadata id
    XStatMetadata { int64 id = 1; string name = 2; }
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

__all__ = ["XSpaceData", "op_names"]


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = 0
    val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field_number, wire_type, value) for every top-level field."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if wt == 0:                      # varint
            val, i = _read_varint(buf, i)
        elif wt == 1:                    # fixed64
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 2:                    # length-delimited
            ln, i = _read_varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:                    # fixed32
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield field, wt, val


class _Event:
    __slots__ = ("name", "start_ns", "duration_ns")

    def __init__(self, name: str, start_ns: float, duration_ns: float):
        self.name = name
        self.start_ns = start_ns
        self.duration_ns = duration_ns


class _Line:
    __slots__ = ("name", "events")

    def __init__(self, name: str, events: List[_Event]):
        self.name = name
        self.events = events


class _Plane:
    __slots__ = ("name", "lines")

    def __init__(self, name: str, lines: List[_Line]):
        self.name = name
        self.lines = lines


def _map_value(buf: bytes) -> bytes:
    """The value (field 2) of one protobuf map entry."""
    for field, _wt, val in _fields(buf):
        if field == 2:
            return val
    return b""


def _parse_event_metadata(buf: bytes) -> Tuple[int, str]:
    mid, name = 0, ""
    for field, _wt, val in _fields(buf):
        if field == 1:
            mid = val
        elif field == 2:
            name = val.decode("utf-8", "replace")
    return mid, name


def _parse_event(buf: bytes) -> Tuple[int, int, int]:
    mid, offset_ps, duration_ps = 0, 0, 0
    for field, _wt, val in _fields(buf):
        if field == 1:
            mid = val
        elif field == 2:
            offset_ps = val
        elif field == 3:
            duration_ps = val
    return mid, offset_ps, duration_ps


def _parse_line(buf: bytes, meta: Dict[int, str]) -> _Line:
    name = ""
    timestamp_ns = 0
    raw_events: List[Tuple[int, int, int]] = []
    for field, _wt, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3:
            timestamp_ns = val
        elif field == 4:
            raw_events.append(_parse_event(val))
    events = [_Event(meta.get(mid, f"#{mid}"),
                     timestamp_ns + offset_ps / 1e3,
                     duration_ps / 1e3)
              for mid, offset_ps, duration_ps in raw_events]
    return _Line(name, events)


def _parse_plane(buf: bytes) -> _Plane:
    name = ""
    meta: Dict[int, str] = {}
    line_bufs: List[bytes] = []
    for field, _wt, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3:
            line_bufs.append(val)
        elif field == 4:
            # map entry { key = 1 (varint), value = 2 (XEventMetadata) }
            mid, mname = _parse_event_metadata(_map_value(val))
            meta[mid] = mname
    return _Plane(name, [_parse_line(b, meta) for b in line_bufs])


class XSpaceData:
    """``ProfileData``-shaped facade over one raw xplane.pb file."""

    def __init__(self, planes: List[_Plane]):
        self.planes = planes

    @classmethod
    def from_file(cls, path: str) -> "XSpaceData":
        with open(path, "rb") as f:
            buf = f.read()
        planes = [_parse_plane(val) for field, _wt, val in _fields(buf)
                  if field == 1]
        return cls(planes)


def op_names(path: str, stat: str = "tf_op") -> Dict[str, str]:
    """event name -> the ``stat`` of its metadata (the HLO ``op_name``,
    without the ``:<type>`` tail), over the device planes of one xplane
    file. Events whose metadata has no such stat are left out."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, str] = {}
    for field, _wt, plane in _fields(buf):
        if field != 1:
            continue
        name, stat_names, metas = "", {}, []
        for f2, _w2, v2 in _fields(plane):
            if f2 == 2:
                name = v2.decode("utf-8", "replace")
            elif f2 == 4:
                metas.append(_map_value(v2))
            elif f2 == 5:
                sid, sname = _parse_event_metadata(_map_value(v2))
                stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        for meta in metas:
            ev_name, op = "", None
            for f3, _w3, v3 in _fields(meta):
                if f3 == 2:
                    ev_name = v3.decode("utf-8", "replace")
                elif f3 == 5:
                    sid, val = 0, None
                    for f4, _w4, v4 in _fields(v3):
                        if f4 == 1:
                            sid = v4
                        elif f4 == 5:
                            val = v4.decode("utf-8", "replace")
                        elif f4 == 7:
                            val = stat_names.get(v4)
                    if stat_names.get(sid) == stat and val:
                        op = val
            if op:
                out[ev_name] = op.rsplit(":", 1)[0]
    return out
