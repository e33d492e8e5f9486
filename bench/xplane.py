"""Read a ``jax.profiler`` trace (``.xplane.pb``) with each device op's
source path.

``trace_reduce.load_events`` reads the trace through
``jax.profiler.ProfileData``, which gives an event's name, start and
duration but not its metadata's stats. The stat ``tf_op`` of an ``XLA
Ops`` event's metadata is JAX's name stack for the op (``jit(<program>)/
.../<scope>/.../<primitive>``): the program's named scopes in order. This
module decodes the few XPlane fields that carry it straight from the
protobuf wire format, skipping the rest, so it needs nothing beyond the
standard library.

The fields read (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes (1);
XPlane.name (2), lines (3), event_metadata (4), stat_metadata (5);
XLine.name (2), timestamp_ns (3), events (4); XEvent.metadata_id (1),
offset_ps (2), duration_ps (3); XEventMetadata.id (1), name (2), stats (5);
XStatMetadata.id (1), name (2); XStat.metadata_id (1), str_value (5),
ref_value (7).
"""
from __future__ import annotations

import re

from bench.trace_reduce import kernel_of

_DEVICE = re.compile(r"/device:TPU:(\d+)")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field; fixed-width fields
    are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _str(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _plane(buf: bytes, s: int, e: int) -> tuple[str, list, dict, dict]:
    name, lines, ev_meta, stat_meta = "", [], {}, {}
    for num, v in _fields(buf, s, e):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines.append(v)
        elif num in (4, 5):                 # map entry: key 1, value 2
            val = None
            for k, kv in _fields(buf, *v):
                if k == 2:
                    val = kv
            if val is None:
                continue
            mid, mname, stats = 0, "", []
            for k, kv in _fields(buf, *val):
                if k == 1:
                    mid = kv
                elif k == 2:
                    mname = _str(buf, kv)
                elif k == 5 and num == 4:
                    stats.append(kv)
            if num == 4:
                ev_meta[mid] = (mname, stats)
            else:
                stat_meta[mid] = mname
    return name, lines, ev_meta, stat_meta


def _tf_op(buf: bytes, stats: list, stat_meta: dict, tf_op_id) -> str:
    for s, e in stats:
        mid, val = None, ""
        for k, v in _fields(buf, s, e):
            if k == 1:
                mid = v
            elif k == 5:
                val = _str(buf, v)
            elif k == 7:
                val = stat_meta.get(v, "")
        if mid == tf_op_id:
            return val
    return ""


def _events(buf: bytes, line: tuple[int, int], want) -> tuple[str, list]:
    """(line name, [(metadata id, start ns, end ns)]) of one line, or
    (name, None) when ``want(name)`` is false."""
    name, t0, spans = "", 0, []
    for num, v in _fields(buf, *line):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            t0 = _int64(v)
        elif num == 4:
            spans.append(v)
    if not want(name):
        return name, None
    out = []
    for s, e in spans:
        mid = off = dur = 0
        for k, v in _fields(buf, s, e):
            if k == 1:
                mid = v
            elif k == 2:
                off = _int64(v)
            elif k == 3:
                dur = v
        start = float(t0 + off // 1000)      # whole ns, as ProfileData
        out.append((mid, start, start + dur // 1000))
    return name, out


def load_ops(path) -> dict:
    """What ``trace_reduce.load_events`` returns, with each device op's
    source path appended: {'host': [(name, start, end)], 'devices': {id:
    {'ops': [(name, start, end, kernel, tf_op)], 'modules': [(name, start,
    end)]}}}, times in ns on the profiler's clock. An op whose metadata
    carries no ``tf_op`` has the path ``""``."""
    with open(path, "rb") as f:
        buf = f.read()
    host, devices = [], {}
    for num, (s, e) in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        pname, lines, ev_meta, stat_meta = _plane(buf, s, e)
        if pname.startswith("/host:"):
            for line in lines:
                _, evs = _events(buf, line, lambda n: True)
                host += [(ev_meta.get(m, ("",))[0], a, b) for m, a, b in evs]
            continue
        m = _DEVICE.match(pname)
        if not m:
            continue
        dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        tf_op_id = next((k for k, n in stat_meta.items() if n == "tf_op"),
                        None)
        paths: dict = {}
        for line in lines:
            lname, evs = _events(buf, line,
                                 lambda n: n in ("XLA Ops", "XLA Modules"))
            if evs is None:
                continue
            for mid, a, b in evs:
                name, stats = ev_meta.get(mid, ("", []))
                if lname == "XLA Modules":
                    dev["modules"].append((name, a, b))
                    continue
                if mid not in paths:
                    paths[mid] = (kernel_of(name),
                                  _tf_op(buf, stats, stat_meta, tf_op_id))
                k, path = paths[mid]
                dev["ops"].append((name, a, b, k, path))
    return {"host": host, "devices": devices}
