"""Read a profiler trace's `XSpace` message itself — the five fields the
benchmark needs that `jax.profiler.ProfileData` does not hand out.

`ProfileData` yields each event's name, start, duration and the event's OWN
stats.  What the compiler knows of a device op — the HLO `op_name` it was
lowered from (`tf_op`, where `jax.named_scope` sections live), the program
it belongs to (`program_id`, one per compiled executable), its
`hlo_category` — is a stat of the op's EVENT METADATA, the plane-level table
`event_metadata` keyed by the event's `metadata_id`, and is not reachable
through it.  So this is a wire-format reader of the protobuf (tsl's
`xplane.proto`), with nothing imported but the standard library:

    XSpace        planes = 1
    XPlane        name = 2, lines = 3, event_metadata = 4 (map),
                  stat_metadata = 5 (map)
    XLine         name = 2, timestamp_ns = 3, events = 4
    XEvent        metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4
    XEventMetadata  id = 1, name = 2, stats = 5
    XStatMetadata   id = 1, name = 2
    XStat         metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                  str = 5, bytes = 6, ref = 7 (a stat_metadata id whose
                  name is the string)

Lazy where it matters: a plane's lines are located, not parsed, until
`Line.events()` is asked for, so a reader that wants only the host plane
never walks the device's million events.  Times come out as ProfileData
gives them — whole ns, `timestamp_ns + offset_ps // 1000` and a duration of
`duration_ps // 1000` — so sums made here and sums made from ProfileData
(`trace_reduce`) agree to the last digit.
`benchmarks/tests/test_bench_step_sections.py` holds this reader to a
generated `xplane_pb2` wherever one can be imported.
"""
from __future__ import annotations

import gzip
import struct


def _varint(buf, i):
    """(value, next index) of the varint at `i`."""
    v = buf[i]
    i += 1
    if v < 0x80:
        return v, i
    v &= 0x7F
    shift = 7
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, i, end):
    """(field number, wire type, value) over one message's bytes [i, end):
    a varint's value, a length-delimited field's (start, end), the raw
    eight or four bytes' (start, end) of a fixed one."""
    while i < end:
        tag, i = _varint(buf, i)
        wt = tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = (i, i + 8)
            i += 8
        elif wt == 5:
            v = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"xspace: wire type {wt} at byte {i}")
        yield tag >> 3, wt, v


def _after(buf, i) -> int:
    """The index behind the field that starts at `i`."""
    tag, i = _varint(buf, i)
    wt = tag & 7
    if wt == 0:
        return _varint(buf, i)[1]
    if wt == 2:
        n, i = _varint(buf, i)
        return i + n
    return i + (8 if wt == 1 else 4)


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """(stat's name, value) of one XStat."""
    mid, value = 0, None
    for f, _wt, v in _fields(buf, *span):
        if f == 1:
            mid = _signed(v)
        elif f == 2:
            value = struct.unpack("<d", bytes(buf[v[0]:v[1]]))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = bytes(buf[v[0]:v[1]])
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


class Line:
    """One XLine: `name`, `timestamp_ns`, and its events on demand (the
    line's head is read up to its first event; the events — serialized
    after the name and the timestamp — only by `events()`)."""

    __slots__ = ("plane", "name", "timestamp_ns", "_rest")

    def __init__(self, plane, span):
        self.plane, self.name, self.timestamp_ns = plane, "", 0
        buf, (i, end) = plane.buf, span
        while i < end and buf[i] != 34:          # events = 4, a message
            head = _after(buf, i)
            for f, _wt, v in _fields(buf, i, head):
                if f == 2:
                    self.name = _text(buf, v)
                elif f == 3:
                    self.timestamp_ns = _signed(v)
            i = head
        self._rest = (i, end)

    def events(self, stats: bool = False):
        """(metadata_id, start ns, end ns[, {stat: value}]) of every event,
        in file order.  The event's own stats are parsed only when asked
        for (a device op's are its three timing stats; a host span's are
        what the program said of it)."""
        buf, t0 = self.plane.buf, float(self.timestamp_ns)
        names = self.plane.stat_names
        at, line_end = self._rest
        while at < line_end:
            if buf[at] != 34:                    # a field after the events
                at = _after(buf, at)
                continue
            n, i = _varint(buf, at + 1)
            end = at = i + n
            mid = off = dur = 0
            own = []
            while i < end:
                tag = buf[i]
                i += 1
                wt = tag & 7
                if wt == 0:
                    v = buf[i]
                    i += 1
                    if v >= 0x80:
                        v, i = _varint(buf, i - 1)
                    if tag == 8:
                        mid = _signed(v) if v >= 0x80 else v
                    elif tag == 16:
                        off = v
                    elif tag == 24:
                        dur = v
                elif wt == 2:
                    n = buf[i]
                    i += 1
                    if n >= 0x80:
                        n, i = _varint(buf, i - 1)
                    if stats and tag == 34:
                        own.append((i, i + n))
                    i += n
                elif wt == 1:
                    i += 8
                else:
                    i += 4
            if off >= 1 << 63:
                off -= 1 << 64
            s = t0 + off // 1000
            if stats:
                yield mid, s, s + dur // 1000, dict(
                    _stat(buf, sp, names) for sp in own)
            else:
                yield mid, s, s + dur // 1000


class Plane:
    """One XPlane: `name`, `lines`, `stat_names` {id: name} and
    `metadata` {event metadata id: (name, {stat: value})}."""

    __slots__ = ("buf", "name", "lines", "stat_names", "_metadata",
                 "_metadata_spans")

    def __init__(self, buf, span):
        self.buf, self.name = buf, ""
        self.stat_names, self._metadata = {}, None
        lines, self._metadata_spans, stat_spans = [], [], []
        for f, _wt, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                self._metadata_spans.append(v)
            elif f == 5:
                stat_spans.append(v)
        for span in stat_spans:              # map entry: key = 1, value = 2
            for f, _wt, v in _fields(buf, *span):
                if f == 2:
                    sid, name = 0, ""
                    for g, _w, x in _fields(buf, *v):
                        if g == 1:
                            sid = _signed(x)
                        elif g == 2:
                            name = _text(buf, x)
                    self.stat_names[sid] = name
        self.lines = [Line(self, span) for span in lines]

    @property
    def metadata(self) -> dict:
        if self._metadata is None:
            self._metadata = {}
            buf = self.buf
            for span in self._metadata_spans:
                for f, _wt, v in _fields(buf, *span):
                    if f != 2:
                        continue
                    mid, name, stats = 0, "", {}
                    for g, _w, x in _fields(buf, *v):
                        if g == 1:
                            mid = _signed(x)
                        elif g == 2:
                            name = _text(buf, x)
                        elif g == 5:
                            k, val = _stat(buf, x, self.stat_names)
                            stats[k] = val
                    self._metadata[mid] = (name, stats)
        return self._metadata


def read(path: str) -> list:
    """The planes of the XSpace in `path` (`.xplane.pb`, or gzipped)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        buf = fh.read()
    return [Plane(buf, v) for f, _wt, v in _fields(buf, 0, len(buf))
            if f == 1]
