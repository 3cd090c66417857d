"""A small reader of the profiler's ``.xplane.pb`` (an XSpace protobuf),
decoding only what the reduction needs: the events of the lines asked for,
each with its name, start and duration.  Pure Python over the wire format;
field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:

XSpace.planes=1; XPlane: name=2, lines=3, event_metadata(map)=4; XLine:
name=2, timestamp_ns=3, events=4, display_name=11; XEvent: metadata_id=1, offset_ps=2, duration_ps=3;
XEventMetadata: id=1, name=2, stats=5; XStat: metadata_id=1, bytes_value=6.

The device's op events carry the instruction's HLO text and nothing of the
name stack it came from.  The named scopes are in the compiled programs the
profiler stores beside the events: plane ``/host:metadata`` keeps one
``HloProto`` per program as a bytes stat of an event metadata entry.  Of
``xla/service/hlo.proto``: HloProto.hlo_module=1; HloModuleProto:
computations=3; HloComputationProto: instructions=2; HloInstructionProto:
name=1, metadata=7; OpMetadata: op_name=2.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) pairs of one message: ints for varints and
    fixed widths, bytes for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, val


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_name(buf: bytes) -> str:
    for f, v in _fields(buf):
        if f == 2:
            return _text(v)
    return ""


def _line_name(buf: bytes) -> str:
    name = ""
    for f, v in _fields(buf):
        if f == 2 and not name:
            name = _text(v)
        elif f == 11:
            name = _text(v)
    return name


def read_events(path: str, want_plane: Callable[[str], bool],
                want_line: Callable[[str, str], bool]) -> List[Dict]:
    """[{"plane", "line", "events": [[name, start_ns, dur_ns]]}] of the
    lines asked for; an event's name is that of its metadata entry."""
    with open(path, "rb") as f:
        space = f.read()
    out = []
    for field, plane in _fields(space):
        if field != 1:
            continue
        pname = _plane_name(plane)
        if not want_plane(pname):
            continue
        lines, names = [], {}
        for f, v in _fields(plane):
            if f == 3:
                lines.append(v)
            elif f == 4:
                mid, md = _map_entry(v)
                names[mid] = next(
                    (_text(x) for g, x in _fields(md) if g == 2), "")
        for line in lines:
            lname = _line_name(line)
            if not want_line(pname, lname):
                continue
            t0 = next((v for f, v in _fields(line) if f == 3), 0)
            events = []
            for f, v in _fields(line):
                if f != 4:
                    continue
                mid = off = dur = 0
                for g, x in _fields(v):
                    if g == 1:
                        mid = x
                    elif g == 2:
                        off = x
                    elif g == 3:
                        dur = x
                events.append([names.get(mid, ""), t0 + off * 1e-3,
                               dur * 1e-3])
            out.append({"plane": pname, "line": lname, "events": events})
    return out


def read_hlo_programs(path: str) -> List[Dict[str, str]]:
    """One {instruction name: op_name (the JAX name stack, named scopes in
    it)} for each compiled program stored in the trace.  Instruction names
    repeat from program to program (``fusion.3``), so the caller picks the
    program its events come from."""
    with open(path, "rb") as f:
        space = f.read()
    programs: List[Dict[str, str]] = []
    for field, plane in _fields(space):
        if field != 1 or _plane_name(plane) != "/host:metadata":
            continue
        for f, entry in _fields(plane):
            if f != 4:
                continue
            _, md = _map_entry(entry)
            for g, stat in _fields(md):
                if g != 5:
                    continue
                for h, proto in _fields(stat):
                    if h == 6:
                        programs.append(_hlo_names(proto))
    return programs


def _hlo_names(proto: bytes) -> Dict[str, str]:
    names = {}
    for f, module in _fields(proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, instr in _fields(comp):
                if h != 2:
                    continue
                name = op_name = ""
                for k, v in _fields(instr):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for m, x in _fields(v)
                                        if m == 2), "")
                if name:
                    names[name] = op_name
    return names
