"""What ``jax.profiler.ProfileData`` leaves out of an ``.xplane.pb`` file:
the stats of each event's metadata, read straight from the protobuf wire
format of ``XSpace`` (tsl/profiler/protobuf/xplane.proto), with nothing
but the standard library.

Only the fields the benchmark reads are decoded:

    XSpace.planes = 1            XPlane.name = 2, .lines = 3,
    XPlane.event_metadata = 4    (map<int64, XEventMetadata>)
    XPlane.stat_metadata = 5     (map<int64, XStatMetadata>)
    XLine.name = 2, .events = 4  XEvent.metadata_id = 1
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
"""
from __future__ import annotations

import re

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint or fixed field, the ``(start,
    end)`` of its bytes for a length-delimited one."""
    i = lo
    hi = len(buf) if hi is None else hi
    while i < hi:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == _FIXED64:
            value = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == _FIXED32:
            value = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span):
    key, value = 0, (span[0], span[0])
    for f, v in fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf: bytes, span, names: dict[int, str], want: str) -> str | None:
    """The value of a stat named ``want``, as a string, else None.  A
    ``ref_value`` names a stat metadata entry whose name is the string."""
    mid, value = None, None
    for f, v in fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 5:
            value = _text(buf, v)
        elif f == 7:
            value = names.get(v, "")
    return value if names.get(mid) == want else None


def _plane(buf: bytes, span, line: str, stat: str):
    """``(name, [(event name, stat value or "") for each event of the
    line named ``line``])`` of one plane."""
    name, lines, metas, stat_meta = "", [], [], []
    for f, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            stat_meta.append(v)
    strings = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for f, v in fields(buf, *value):
            if f == 2:
                strings[key] = _text(buf, v)
    events = None
    for ln in lines:
        ids, found = [], False
        for f, v in fields(buf, *ln):
            if f == 2:
                found = _text(buf, v) == line
            elif f == 4:
                ids.append(v)
        if found:
            events = ids
            break
    if events is None:
        return name, None
    meta = {}
    for entry in metas:
        key, value = _map_entry(buf, entry)
        mname, sval = "", ""
        for f, v in fields(buf, *value):
            if f == 2:
                mname = _text(buf, v)
            elif f == 5:
                got = _stat(buf, v, strings, stat)
                if got is not None:
                    sval = got
        meta[key] = (mname, sval)
    out = []
    for span_e in events:
        mid = 0
        for f, v in fields(buf, *span_e):
            if f == 1:
                mid = v
                break
        out.append(meta.get(mid, ("", "")))
    return name, out


def event_stats(path: str, plane: str, line: str, stat: str) -> dict:
    """For each plane whose name matches the regular expression ``plane``
    and that has a line named ``line``: the name of each event of that
    line, in the file's order, with the string value of its metadata's
    stat ``stat`` ("" where it has none).  Keyed by plane name."""
    with open(path, "rb") as f:
        buf = f.read()
    pat = re.compile(plane)
    out = {}
    for field_no, span in fields(buf):
        if field_no != 1:
            continue
        # the plane's name decides whether its lines are read
        pname = ""
        for f, v in fields(buf, *span):
            if f == 2:
                pname = _text(buf, v)
                break
        if not pat.match(pname):
            continue
        pname, events = _plane(buf, span, line, stat)
        if events is not None:
            out[pname] = events
    return out
