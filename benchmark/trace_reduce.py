#!/usr/bin/env python3
"""Reduce one profiler trace (``*.xplane.pb``) to a small JSON summary.

Run as a child pinned to ``JAX_PLATFORMS=cpu`` after the server has
exited (only ``jax.profiler.ProfileData`` is used; the chip is not
touched):

    python3 benchmark/trace_reduce.py <profile dir> [device plane regex]

Per device plane: the union of the intervals in which an operation ran
(busy), seconds by operation name, and the idle gaps between busy
intervals, named by the operation that ended each gap (no host span can
name them yet).  The traced window is the span from the first to the last
event of the device planes: the host's planes also hold the seconds the
profiler itself takes to start and to stop, in which no step is traced.
``capture_wall`` places that span on the wall clock (seconds since the
epoch, as ``time.time()`` reads them on the same machine): an event's
``start_ns`` counts from the profiler session's start, which the file's
``Task Environment`` plane states as ``profile_start_time``, nanoseconds
since the epoch (PERF.md section 6, PR 44).  A file without that plane
or without a device event places nothing: None.
An operation is named by its HLO instruction without the number, and its
kind, so the forty layers' calls of one kernel add up under one name.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = r"^/device:TPU:\d+$"
#: the plane whose stats say when the profiler session began and ended
SESSION_PLANE = "Task Environment"
SESSION_START = "profile_start_time"
#: the line of a device plane that holds one event per operation
OPS_LINE = "XLA Ops"
TOP = 10


_HLO = re.compile(
    r"^%?([\w\-]+?)(?:\.(?:\d+|remat\d*|clone))* = .*?\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """``%closed_call.501 = bf16[...] custom-call(...), custom_call_target=
    "tpu_custom_call"...`` -> ``closed_call custom-call:tpu_custom_call``;
    a name that is not an HLO instruction is cut to 80 characters."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    target = _TARGET.search(name)
    kind = m.group(2) + (f":{target.group(1)}" if target else "")
    return f"{m.group(1)} {kind}"


def union_and_gaps(intervals: list[tuple[float, float, str]]):
    """(busy ns, [(gap ns, name of the operation that ended it)]) of
    (start, end, name) intervals that may overlap or nest."""
    busy, gaps, reach = 0.0, [], None
    for start, end, name in sorted(intervals):
        if reach is None:
            reach = start
        if start > reach:
            gaps.append((start - reach, name))
            reach = start
        if end > reach:
            busy += end - reach
            reach = end
    return busy, gaps


def plane_pattern(on_cpu: bool) -> str:
    """The planes that count as the device's: the chip's, or in a
    rehearsal on the CPU the host's own, which exercises the code only."""
    return r"^/host:CPU$" if on_cpu else DEVICE_PLANE


def names_device_plane(path: Path, device_plane: str = DEVICE_PLANE) -> bool:
    """Whether the file names a plane that matches, without parsing it (a
    plane's name stands in the file as plain bytes): what the load
    generator can afford to look at while its window runs.  A capture of
    seconds in which the engine stood still has no device plane at all."""
    name = device_plane.strip("^$").encode()
    return re.search(rb"(?<![\w/:])" + name + rb"(?![\w/:])",
                     path.read_bytes()) is not None


def reduce_planes(planes, device_plane: str = DEVICE_PLANE,
                  session_start_ns: int | None = None) -> dict:
    """``planes``: [(plane name, [(line name, [(name, start_ns,
    duration_ns)])])], as ``ProfileData`` gives them; ``session_start_ns``:
    the wall-clock instant their ``start_ns`` count from."""
    lo, hi = None, None
    devices = []
    for plane_name, lines in planes:
        if not re.match(device_plane, plane_name):
            continue
        for _, events in lines:
            for _, start, duration in events:
                lo = start if lo is None else min(lo, start)
                hi = start + duration if hi is None else max(
                    hi, start + duration)
        lines = [(line, [(short_name(n), s, d) for n, s, d in events])
                 for line, events in lines]
        by_name = dict(lines)
        ops = by_name.get(OPS_LINE)
        if ops is None and lines:
            ops = max((events for _, events in lines), key=len)
        intervals = [(s, s + d, n) for n, s, d in ops or [] if d > 0]
        busy, gaps = union_and_gaps(intervals)
        seconds: dict[str, float] = {}
        calls: dict[str, int] = {}
        for n, _, d in ops or []:
            seconds[n] = seconds.get(n, 0.0) + d / 1e9
            calls[n] = calls.get(n, 0) + 1
        gap_seconds: dict[str, float] = {}
        for g, n in gaps:
            key = f"before:{n}"
            gap_seconds[key] = gap_seconds.get(key, 0.0) + g / 1e9
        devices.append({
            "plane": plane_name, "busy_s": busy / 1e9,
            "op_seconds": seconds, "op_calls": calls,
            "gap_seconds": gap_seconds,
            "lines": [(name, len(events)) for name, events in lines],
        })
    window_s = 0.0 if lo is None else (hi - lo) / 1e9
    placed = lo is not None and session_start_ns is not None
    n = max(1, len(devices))

    def mean_top(key: str) -> list:
        total: dict[str, float] = {}
        for dev in devices:
            for name, s in dev[key].items():
                total[name] = total.get(name, 0.0) + s / n
        return sorted(total.items(), key=lambda kv: -kv[1])

    return {
        "window_s": window_s,
        "capture_wall": [(session_start_ns + lo) / 1e9,
                         (session_start_ns + hi) / 1e9] if placed else None,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "device_planes": [d["plane"] for d in devices],
        "all_planes": [name for name, _ in planes],
        "lines": devices[0]["lines"] if devices else [],
        "op_seconds": dict(mean_top("op_seconds")[:200]),
        "op_calls": {
            name: sum(d["op_calls"].get(name, 0) for d in devices) // n
            for name, _ in mean_top("op_seconds")[:200]
        },
        "device_ops": [list(kv) for kv in mean_top("op_seconds")[:TOP]],
        "idle_gaps": [list(kv) for kv in mean_top("gap_seconds")[:TOP]],
    }


def read_planes(path: Path):
    """(the planes as ``reduce_planes`` takes them, the session's start in
    nanoseconds since the epoch or None)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes, session_start_ns = [], None
    for plane in data.planes:
        if plane.name == SESSION_PLANE:
            session_start_ns = dict(plane.stats).get(SESSION_START)
        planes.append((plane.name, [
            (line.name, [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ])
            for line in plane.lines
        ]))
    return planes, session_start_ns


def main() -> None:
    root = Path(sys.argv[1])
    pattern = sys.argv[2] if len(sys.argv) > 2 else DEVICE_PLANE
    found = sorted(root.rglob("*.xplane.pb"))
    if not found:
        raise SystemExit(f"no *.xplane.pb under {root}")
    planes, session_start_ns = read_planes(found[-1])
    print(json.dumps(reduce_planes(planes, pattern, session_start_ns)))


if __name__ == "__main__":
    main()
