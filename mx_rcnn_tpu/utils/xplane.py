"""Minimal XSpace (``*.xplane.pb``) reader for profiler trace analysis.

Reference: none — the reference has no profiler tooling (SURVEY.md §5.1).
``jax.profiler.trace`` writes a TensorBoard profile whose ground truth is
the XSpace protobuf (per-op device events with full metadata); the
side-car ``*.trace.json.gz`` chrome trace is lossy (no scope/source
stats).  TensorFlow isn't a dependency of this framework, so this module
hand-decodes the protobuf wire format for exactly the message subset the
profiler needs — pure Python, no schema compiler.

Field numbers follow ``tensorflow/core/profiler/protobuf/xplane.proto``
(stable since 2020):

* XSpace.planes = 1
* XPlane: id=1, name=2, lines=3, event_metadata(map)=4, stat_metadata=5,
  stats=6
* XLine: id=1, name=2, timestamp_ns=3, events=4, display_name=11
* XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4
* XEventMetadata: id=1, name=2, display_name=4
* XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6, ref=7
* XStatMetadata: id=1, name=2

The decoded form is plain dicts/lists; ``summarize_device_time`` rolls
per-op durations up by ``jax.named_scope`` component (extracted from the
op metadata's source scope stats), which is what
``obs/profiler.py::rollup`` puts into a run record.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = 0
    out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, bytes]]:
    """Yield (field_number, wire_type, payload) over a message buffer.
    Varints yield their value encoded back as int in payload position."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if wt == 0:  # varint
            val, i = _read_varint(buf, i)
            yield field, wt, val
        elif wt == 1:  # fixed64
            yield field, wt, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 2:  # length-delimited
            ln, i = _read_varint(buf, i)
            yield field, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:  # fixed32
            yield field, wt, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:  # groups (3/4) never appear in xplane
            raise ValueError(f"unsupported wire type {wt}")


def _zigzag_ok(v: int) -> int:
    """xplane int64s are plain varints (no zigzag); keep as-is but fold
    Python's unbounded two's-complement back to signed 64-bit."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _parse_stat(buf: bytes) -> Dict:
    st: Dict = {}
    for f, wt, v in _fields(buf):
        if f == 1:
            st["metadata_id"] = v
        elif f == 2:
            import struct

            st["value"] = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            st["value"] = v
        elif f == 7:
            # interned string: ref into the plane's stat_metadata table —
            # resolved to the referenced entry's name in event_rows
            st["ref"] = v
        elif f == 4:
            st["value"] = _zigzag_ok(v)
        elif f == 5:
            st["value"] = v.decode("utf-8", "replace")
        elif f == 6:
            st["value"] = bytes(v)
    return st


def _parse_event(buf: bytes) -> Dict:
    ev: Dict = {"stats": []}
    for f, wt, v in _fields(buf):
        if f == 1:
            ev["metadata_id"] = v
        elif f == 2:
            ev["offset_ps"] = _zigzag_ok(v)
        elif f == 3:
            ev["duration_ps"] = _zigzag_ok(v)
        elif f == 4:
            ev["stats"].append(_parse_stat(v))
    return ev


def _parse_line(buf: bytes) -> Dict:
    line: Dict = {"events": []}
    for f, wt, v in _fields(buf):
        if f == 2:
            line["name"] = v.decode("utf-8", "replace")
        elif f == 11:
            line["display_name"] = v.decode("utf-8", "replace")
        elif f == 3:
            line["timestamp_ns"] = _zigzag_ok(v)
        elif f == 4:
            line["events"].append(_parse_event(v))
    return line


def _parse_map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for f, wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _parse_named_metadata(buf: bytes) -> Dict:
    md: Dict = {}
    for f, wt, v in _fields(buf):
        if f == 1:
            md["id"] = v
        elif f == 2:
            md["name"] = v.decode("utf-8", "replace")
        elif f == 4:
            md["display_name"] = v.decode("utf-8", "replace")
    return md


def _parse_plane(buf: bytes) -> Dict:
    plane: Dict = {"lines": [], "event_metadata": {}, "stat_metadata": {},
                   "stats": []}
    for f, wt, v in _fields(buf):
        if f == 6:
            plane["stats"].append(_parse_stat(v))
            continue
        if f == 2:
            plane["name"] = v.decode("utf-8", "replace")
        elif f == 3:
            plane["lines"].append(_parse_line(v))
        elif f == 4:
            k, mv = _parse_map_entry(v)
            plane["event_metadata"][k] = _parse_named_metadata(mv)
        elif f == 5:
            k, mv = _parse_map_entry(v)
            plane["stat_metadata"][k] = _parse_named_metadata(mv)
    return plane


def parse_xspace(path: str) -> List[Dict]:
    """Parse an ``*.xplane.pb`` file into a list of plane dicts."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f_, wt, v in _fields(buf):
        if f_ == 1:
            planes.append(_parse_plane(v))
    return planes


def plane_stats(plane: Dict) -> Dict:
    """A plane's own stats by name (the ``Task Environment`` plane states
    the profiler session's ``profile_start_time`` / ``profile_stop_time``
    there, in unix-epoch ns)."""
    smd = plane.get("stat_metadata", {})
    return {smd.get(s.get("metadata_id"), {}).get("name",
                                                  str(s.get("metadata_id"))):
            s.get("value") for s in plane.get("stats", [])}


def event_rows(plane: Dict) -> Iterator[Dict]:
    """Flatten a plane into per-event rows with resolved names/stats."""
    emd = plane.get("event_metadata", {})
    smd = plane.get("stat_metadata", {})
    for line in plane["lines"]:
        for ev in line["events"]:
            md = emd.get(ev.get("metadata_id"), {})
            stats = {}
            for s in ev["stats"]:
                k = smd.get(s.get("metadata_id"), {}).get(
                    "name", str(s.get("metadata_id")))
                if "ref" in s:  # interned string stat
                    stats[k] = smd.get(s["ref"], {}).get("name", "")
                else:
                    stats[k] = s.get("value")
            yield {
                "line": line.get("display_name") or line.get("name", ""),
                "name": md.get("display_name") or md.get("name", ""),
                "duration_ps": ev.get("duration_ps", 0),
                "stats": stats,
            }


def device_planes(planes: List[Dict]) -> List[Dict]:
    """Planes that carry accelerator (or XLA-CPU op) timelines."""
    out = []
    for p in planes:
        name = p.get("name", "")
        if name.startswith("/device:") or "TPU" in name or "GPU" in name \
                or name == "/host:CPU":
            out.append(p)
    return out


def scope_of(row: Dict, depth: int = 1) -> str:
    """The ``jax.named_scope`` path component of an op row.

    XLA op metadata carries the jaxpr scope in the ``tf_op`` stat (TPU) or
    in the event name itself as ``jit(fn)/scope/.../op`` — take the first
    ``depth`` scope components after the jit frame; ops with no scope
    group under '(unscoped)'."""
    src = row["stats"].get("tf_op") or row["name"]
    if not isinstance(src, str) or "/" not in src:
        return "(unscoped)"
    parts = [p for p in src.split("/") if p]
    # drop leading jit(...) / main frames
    while parts and (parts[0].startswith("jit(") or parts[0] in
                     ("main", "xla_computation")):
        parts = parts[1:]
    if not parts or len(parts) < 2:
        # bare op name (no scope component)
        return "(unscoped)"
    return "/".join(parts[:depth])


def category_of(row: Dict) -> str:
    """HLO op category: the op name with its SSA/clone suffixes stripped
    (``fusion.123`` → ``fusion``, ``fusion.3.clone`` → ``fusion``) —
    available on every backend even when scope stats are absent, so
    op-class attribution (convs vs sorts vs scatters) always works.
    Anchored regex, not rstrip: ops legitimately ending in digits
    (``atan2``) must keep their name."""
    name = row["stats"].get("hlo_op") or row["name"] or "?"
    if not isinstance(name, str):
        return "?"
    base = name.split("/")[-1]
    return re.sub(r"(\.\d+|\.clone|\.remat)*$", "", base) or base


def summarize_device_time(source, depth: int = 1, key=None
                          ) -> Dict[str, Dict[str, float]]:
    """Total device time (ms) per group, per device plane.

    ``source``: an ``*.xplane.pb`` path, or pre-parsed planes from
    :func:`parse_xspace` (pass those when summarizing the same trace more
    than once — the pure-Python protobuf walk is the expensive part).
    ``key``: row → group name; defaults to :func:`scope_of` (named-scope
    attribution).  Pass :func:`category_of` for HLO-op-class grouping.
    Returns {plane_name: {group: ms}} sorted descending by time."""
    if key is None:
        def key(row):
            return scope_of(row, depth)
    planes = parse_xspace(source) if isinstance(source, str) else source
    out: Dict[str, Dict[str, float]] = {}
    for plane in device_planes(planes):
        groups: Dict[str, float] = {}
        for row in event_rows(plane):
            # only XLA op executions: Python/runtime host events on the
            # same plane (tracing scaffolding, fetches) carry no hlo_op
            # stat and would swamp the op timeline
            if "hlo_op" not in row["stats"]:
                continue
            g = key(row)
            groups[g] = groups.get(g, 0.0) + row["duration_ps"] / 1e9
        out[plane.get("name", "?")] = dict(
            sorted(groups.items(), key=lambda kv: -kv[1]))
    return out
