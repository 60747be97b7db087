"""Reduction of a profiler trace to device busy time, kernel time and the
idle gaps with what the host was doing in them.

The reduction works on plain data — planes of lines of events
``[name, start_ns, duration_ns]`` — so that a recorded trace kept as a
fixture (``benchmark/tests/fixtures``) is reduced by the same code as a
live one.  :func:`load_xplane` makes that data from the ``.xplane.pb``
file that ``jax.profiler`` writes.

* Busy time is the union of the intervals of the device's op events
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, or ``XLA
  Modules`` where a plane has no op line), clipped to the window; never
  their sum, since events nest and overlap.
* The window is the host annotation named ``window`` that the harness
  puts around the measured cycles.
* Device time per program (the ``XLA Modules`` line, named by the
  jitted function, which is stable across refactors of its body).
* Idle time is split by what the host was doing: the innermost harness
  annotation over each stretch of a gap, on any host line.
* Host time per annotation (``span_s``): the summed length of each
  harness annotation inside the window.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
WINDOW = "window"


def load_xplane(trace_dir: str) -> dict:
    """Plain data from the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """An op event's name without its HLO text: ``%while.18 = (s32[]...``
    is ``while.18``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_events(plane: dict) -> List[list]:
    lines = {line["name"]: line["events"] for line in plane["lines"]}
    for names in (OP_LINES, MODULE_LINES):
        events = [e for n in names for e in lines.get(n, [])]
        if events:
            return events
    return []


def host_spans(data: dict) -> List[list]:
    """Every event on a non-device plane: ``[name, start, end]``."""
    out = []
    for plane in data["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend([n, s, s + d] for n, s, d in line["events"])
    return out


def host_segments(spans, lo: int, hi: int) -> List[Tuple[int, int, str]]:
    """``[lo, hi)`` cut where any span starts or ends, each piece named
    after the innermost span over it (latest start, then earliest
    end), or "(no host span)"."""
    points = sorted(
        {lo, hi}
        | {x[1] for x in spans if lo < x[1] < hi}
        | {x[2] for x in spans if lo < x[2] < hi}
    )
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        cover = [x for x in spans if x[1] <= mid < x[2]]
        label = max(cover, key=lambda x: (x[1], -x[2]))[0] if cover else "(no host span)"
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def reduce(data: dict, labels: Optional[set] = None) -> dict:
    """Window, busy time, device time and calls per op name, and the
    breakdown.  ``labels`` are the host annotation names to attribute
    idle gaps to (None: any host event).  Raises ValueError when the
    trace has no window annotation or no device plane."""
    spans = host_spans(data)
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError("trace has no 'window' annotation")
    lo, hi = min(s[1] for s in windows), max(s[2] for s in windows)
    devices = [p for p in data["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("trace has no /device:TPU plane")

    busy_ns = []
    module_ns: Dict[str, int] = {}
    op_ns: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    busy_all = []
    for plane in devices:
        events = [e for e in _device_events(plane) if e[1] + e[2] > lo and e[1] < hi]
        merged = union([(s, s + d) for _n, s, d in events])
        merged = clip(merged, lo, hi)
        busy_all.append(merged)
        busy_ns.append(sum(e - s for s, e in merged))
        for full, s, d in events:
            name = op_name(full)
            s2, e2 = max(s, lo), min(s + d, hi)
            op_ns[name] = op_ns.get(name, 0) + (e2 - s2)
            op_calls[name] = op_calls.get(name, 0) + 1
        for line in plane["lines"]:
            if line["name"] in MODULE_LINES:
                for name, s, d in line["events"]:
                    if s + d > lo and s < hi:
                        key = name.split("(")[0]
                        module_ns[key] = module_ns.get(key, 0) + min(s + d, hi) - max(s, lo)

    # idle gaps of the first device, split by what the host was doing:
    # the innermost harness span over each stretch of time
    gaps = []
    cursor = lo
    for s, e in busy_all[0] + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = [
        x for x in spans
        if x[0] != WINDOW and (labels is None or x[0] in labels)
    ]
    span_ns: Dict[str, int] = {}
    for name, s, e in named:
        if e > lo and s < hi:
            span_ns[name] = span_ns.get(name, 0) + min(e, hi) - max(s, lo)
    idle_by: Dict[str, int] = {}
    segments = host_segments(named, lo, hi)
    i = 0
    for s, e in gaps:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, label = segments[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                idle_by[label] = idle_by.get(label, 0) + overlap
            j += 1

    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "module_s": {k: v / n_dev / 1e9 for k, v in module_ns.items()},
        "op_s": {k: v / n_dev / 1e9 for k, v in op_ns.items()},
        "op_calls": {k: v / n_dev for k, v in op_calls.items()},
        "span_s": {k: v / 1e9 for k, v in span_ns.items()},
        "device_ops": [
            [name, ns / n_dev / 1e9]
            for name, ns in sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [
            [name, ns / 1e9]
            for name, ns in sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
        ],
    }
