"""A reader of the profiler's trace file (``.xplane.pb``, an ``XSpace``
protobuf) from its wire format, in the standard library alone.

``jax.profiler.ProfileData`` gives each event's name, times and own stats,
but not its event metadata: the stats the compiler attached to the
operation, among them ``tf_op``, the operation's name-scope path
(``jit(step)/while/body/attention/dot_general``). This reader gives the
planes, their lines and events (metadata id, start and end in ns on
``ProfileData``'s clock) and each plane's event metadata with its stats.

Only the messages and fields it reads are decoded (tsl's ``xplane.proto``):

    XSpace          planes 1
    XPlane          name 2, lines 3, event_metadata 4 (map),
                    stat_metadata 5 (map)
    XLine           name 2, timestamp_ns 3, events 4
    XEvent          metadata_id 1, offset_ps 2, duration_ps 3
    XEventMetadata  id 1, name 2, stats 5
    XStatMetadata   id 1, name 2
    XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                    bytes 6, ref 7 (the id of a stat metadata naming a string)
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from pathlib import Path


def _varint(buf, i):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, lo, hi):
    """(field number, wire type, value) of a message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, v


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


@dataclass
class Line:
    name: str
    timestamp_ns: int
    # (metadata id, start ns, end ns), as ProfileData times them
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)
    # metadata id -> (name, {stat name: value})
    event_metadata: dict = field(default_factory=dict)


def _events(buf, spans, t0_ns):
    out = []
    for lo, hi in spans:
        mid = off = dur = 0
        i = lo
        while i < hi:
            key, i = _varint(buf, i)
            wt = key & 7
            if wt == 0:
                v, i = _varint(buf, i)
                num = key >> 3
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
            elif wt == 2:
                n, i = _varint(buf, i)
                i += n
            elif wt == 1:
                i += 8
            elif wt == 5:
                i += 4
            else:
                raise ValueError(f"xplane: wire type {wt} at byte {i}")
        s = t0_ns + off // 1000
        out.append((mid, s, s + dur // 1000))
    return out


def _line(buf, lo, hi, want_events):
    name, ts, evs = "", 0, []
    for num, _wt, v in _fields(buf, lo, hi):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            ts = _signed(v)
        elif num == 4:
            evs.append(v)
    ln = Line(name, ts)
    if want_events(name):
        ln.events = _events(buf, evs, ts)
    return ln


def _stat(buf, lo, hi):
    """(stat metadata id, value); a ``ref`` value comes back as ("ref", id)."""
    mid, val = 0, None
    for num, _wt, v in _fields(buf, lo, hi):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = _str(buf, v)
        elif num == 6:
            val = bytes(buf[v[0]:v[1]])
        elif num == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf, lo, hi):
    key, span = 0, None
    for num, _wt, v in _fields(buf, lo, hi):
        if num == 1:
            key = v
        elif num == 2:
            span = v
    return key, span


def _plane(buf, lo, hi, want_events):
    name, lines, ev_meta, st_meta = "", [], [], {}
    for num, _wt, v in _fields(buf, lo, hi):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(v)
        elif num == 5:
            k, span = _map_entry(buf, *v)
            if span is not None:
                st_meta[k] = next((_str(buf, x) for n, _w, x
                                   in _fields(buf, *span) if n == 2), "")
    pl = Plane(name)
    pl.lines = [_line(buf, a, b, lambda ln: want_events(name, ln))
                for a, b in lines]
    for a, b in ev_meta:
        k, span = _map_entry(buf, a, b)
        if span is None:
            continue
        ename, stats = "", {}
        for n, _w, x in _fields(buf, *span):
            if n == 2:
                ename = _str(buf, x)
            elif n == 5:
                sid, val = _stat(buf, *x)
                if isinstance(val, tuple):
                    val = st_meta.get(val[1], "")
                stats[st_meta.get(sid, str(sid))] = val
        pl.event_metadata[k] = (ename, stats)
    return pl


def read(path, want_plane=lambda name: True,
         want_events=lambda plane, line: True) -> list:
    """The planes of the trace file at ``path`` that ``want_plane`` takes
    (by name), each line's events decoded where ``want_events(plane name,
    line name)``."""
    buf = memoryview(Path(path).read_bytes())
    out = []
    for num, _wt, v in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name = next((_str(buf, x) for n, _w, x in _fields(buf, *v)
                     if n == 2), "")
        if want_plane(name):
            out.append(_plane(buf, v[0], v[1], want_events))
    return out
