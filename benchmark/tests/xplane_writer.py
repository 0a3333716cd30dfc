"""A minimal writer of the profiler's ``XSpace`` protobuf (tsl/profiler/
protobuf/xplane.proto): planes, lines, events with a name, a start and a
duration.  Enough to trim a recorded trace to the lines the reduction
reads, and to build a small trace whose numbers can be worked out by
hand.  Wire format only; no protobuf package needed.

    XSpace  { repeated XPlane planes = 1; }
    XPlane  { int64 id = 1; string name = 2; repeated XLine lines = 3;
              map<int64, XEventMetadata> event_metadata = 4; }
    XLine   { int64 id = 1; string name = 2; int64 timestamp_ns = 3;
              repeated XEvent events = 4; }
    XEvent  { int64 metadata_id = 1; int64 offset_ps = 2;
              int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; }
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
Plane = Tuple[str, Dict[str, Sequence[Event]]]   # name, {line: events}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(int(value))


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def encode(planes: List[Plane]) -> bytes:
    space = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        ids: Dict[str, int] = {}
        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, events) in enumerate(lines.items(), 1):
            line = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, 0)
            for name, start_ns, dur_ns in events:
                mid = ids.setdefault(name, len(ids) + 1)
                line += _bytes(4, _int(1, mid)
                               + _int(2, round(start_ns * 1000))
                               + _int(3, round(dur_ns * 1000)))
            body += _bytes(3, line)
        for name, mid in ids.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        space += _bytes(1, body)
    return space


def trim(src: str, dest: str, device_lines=("XLA Modules", "XLA Ops"),
         host_names=()) -> None:
    """Copy a recorded trace keeping only the device planes' given lines
    and the named host events."""
    from jax.profiler import ProfileData

    planes: List[Plane] = []
    for plane in ProfileData.from_file(src).planes:
        lines = {}
        for line in plane.lines:
            if plane.name.startswith("/device:") \
                    and line.name in device_lines:
                lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
            elif plane.name.startswith("/host:"):
                kept = [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events if e.name in host_names]
                if kept:
                    lines[line.name] = kept
        if lines:
            planes.append((plane.name, lines))
    with open(dest, "wb") as f:
        f.write(encode(planes))
