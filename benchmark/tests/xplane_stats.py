"""``xplane_writer.py`` with stats: an event's own (a host annotation's
tags, XEvent.stats = 4) and its metadata's (an operation's ``tf_op``,
XEventMetadata.stats = 5), which is where a v5e trace carries the
``jax.named_scope`` path.  Wire format only.

    XPlane  { ...; map<int64, XStatMetadata> stat_metadata = 5; }
    XEvent  { ...; repeated XStat stats = 4; }
    XEventMetadata { int64 id = 1; string name = 2; repeated XStat stats = 5; }
    XStat   { int64 metadata_id = 1; double double_value = 2;
              int64 int64_value = 4; string str_value = 5; }
    XStatMetadata { int64 id = 1; string name = 2; }

An event is (name, start_ns, duration_ns) or (name, start_ns, duration_ns,
{stat: value}); ``meta_stats`` is {event name: {stat: value}}.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, List, Optional, Sequence

from xplane_writer import _bytes, _int, _varint


def _stat(ids: Dict[str, int], name: str, value: Any) -> bytes:
    sid = ids.setdefault(name, len(ids) + 1)
    if isinstance(value, str):
        body = _bytes(5, value.encode())
    elif isinstance(value, float):
        body = _varint(2 << 3 | 1) + struct.pack("<d", value)
    else:
        body = _int(4, int(value))
    return _int(1, sid) + body


def encode(planes: List[tuple],
           meta_stats: Optional[Dict[str, Dict[str, Any]]] = None) -> bytes:
    space = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        ids: Dict[str, int] = {}
        stat_ids: Dict[str, int] = {}
        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, events) in enumerate(lines.items(), 1):
            line = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, 0)
            for name, start_ns, dur_ns, *rest in events:
                mid = ids.setdefault(name, len(ids) + 1)
                ev = (_int(1, mid) + _int(2, round(start_ns * 1000))
                      + _int(3, round(dur_ns * 1000)))
                for key, value in (rest[0] if rest else {}).items():
                    ev += _bytes(4, _stat(stat_ids, key, value))
                line += _bytes(4, ev)
            body += _bytes(3, line)
        for name, mid in ids.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            for key, value in (meta_stats or {}).get(name, {}).items():
                meta += _bytes(5, _stat(stat_ids, key, value))
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        for name, sid in stat_ids.items():
            body += _bytes(5, _int(1, sid) + _bytes(
                2, _int(1, sid) + _bytes(2, name.encode())))
        space += _bytes(1, body)
    return space


def trim(src: str, dest: str, host_names: Sequence[str],
         device_lines=("XLA Modules", "XLA Ops")) -> None:
    """Copy a recorded trace keeping the device planes' given lines with
    each operation's ``tf_op``, and the named host events with their
    tags."""
    from jax.profiler import ProfileData

    from benchmark.harness import phases

    planes = []
    for plane in ProfileData.from_file(src).planes:
        lines = {}
        for line in plane.lines:
            if plane.name.startswith("/device:") \
                    and line.name in device_lines:
                lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
            elif plane.name.startswith("/host:"):
                kept = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events if e.name in host_names]
                if kept:        # threads' lines may share a name
                    lines[f"{line.name}/{len(lines)}"] = kept
        if lines:
            planes.append((plane.name, lines))
    scopes = {name: {"tf_op": scope}
              for name, scope in phases.op_scopes(src).items()}
    with open(dest, "wb") as f:
        f.write(encode(planes, scopes))


if __name__ == "__main__":
    import sys

    from benchmark.harness import phases

    trim(sys.argv[1], sys.argv[2],
         phases.LLM_SPANS + phases.TRAIN_SPANS + ("bench_window",))
