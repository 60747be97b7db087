#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, broken
down by the program's own spans (``vtpu/…``, the names
``volcano_tpu.trace.SPAN_NAMES`` lists) as well as the harness's
annotations:

    python3 benchmark/spans.py --workload <cell> --seed <n> --seconds <s> [--save <path>]

The last line of stdout is the run's result object, as run.py prints
it.  The line before it, ``bench spans {...}``, holds the window's
length and device busy time; per cycle, the host time and count of each
program span; the device's idle time split by the innermost host span
over it, of any name; and the idle time under no host span at all.
``--save`` writes the window's raw events (the window, the harness's
annotations, the program's spans, the device's ops) and each cycle's
phase stats as JSON, in the form of ``tests/fixtures``.
"""

import heapq
import json
import os
import sys
import time
from typing import Dict, List, Tuple

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import core, trace  # noqa: E402

PREFIX = "vtpu/"
NO_SPAN = "(no host span)"


def innermost(spans, lo: int, hi: int) -> List[Tuple[int, int, str]]:
    """What ``harness.trace.host_segments`` gives, by one sweep over the
    span boundaries instead of a scan of every span per piece: ``[lo,
    hi)`` cut where any span starts or ends, each piece named after the
    innermost span over it (latest start, then earliest end)."""
    bounds = sorted({lo, hi} | {x for _n, s, e in spans for x in (s, e) if lo < x < hi})
    starts = sorted(((s, e, n) for n, s, e in spans if e > s), key=lambda x: x[0])
    heap: list = []  # (-start, end, name) of the spans begun so far
    out: List[Tuple[int, int, str]] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        mid = (a + b) / 2
        while i < len(starts) and starts[i][0] <= mid:
            s, e, n = starts[i]
            heapq.heappush(heap, (-s, e, n))
            i += 1
        # the top is the latest start, then the earliest end: once the
        # ended spans on top are dropped it is the innermost live span
        # (an ended one below it began earlier and no longer matters)
        while heap and heap[0][1] <= mid:
            heapq.heappop(heap)
        label = heap[0][2] if heap else NO_SPAN
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def breakdown(data: dict, cycles: int) -> dict:
    """Per-cycle program span times and the idle split by innermost span
    of any name, over the ``window`` annotation of ``data``."""
    spans = trace.host_spans(data)
    windows = [s for s in spans if s[0] == trace.WINDOW]
    lo, hi = min(s[1] for s in windows), max(s[2] for s in windows)
    devices = [p for p in data["planes"] if trace.DEVICE_PLANE.match(p["name"])]
    ops = trace._device_events(devices[0])
    busy = trace.clip(trace.union([(s, s + d) for _n, s, d in ops]), lo, hi)
    named = [x for x in spans if x[0] != trace.WINDOW]
    program: Dict[str, List[float]] = {}
    for name, s, e in named:
        if name.startswith(PREFIX) and e > lo and s < hi:
            entry = program.setdefault(name, [0.0, 0])
            entry[0] += (min(e, hi) - max(s, lo)) / 1e6
            entry[1] += 1
    idle: Dict[str, int] = {}
    segments = innermost(named, lo, hi)
    cursor, j = lo, 0
    for s, e in busy + [(hi, hi)]:
        if s > cursor:  # an idle gap [cursor, s)
            while j < len(segments) and segments[j][1] <= cursor:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < s:
                a, b, label = segments[k]
                idle[label] = idle.get(label, 0) + min(b, s) - max(a, cursor)
                k += 1
        cursor = max(cursor, e)
    n = max(cycles, 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "cycles": cycles,
        "per_cycle": {
            name: {"ms": ms / n, "count": count / n}
            for name, (ms, count) in sorted(program.items(), key=lambda kv: -kv[1][0])
        },
        "idle_s": {k: v / 1e9 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "no_host_span_s": idle.get(NO_SPAN, 0) / 1e9,
    }


def window_events(data: dict, labels) -> dict:
    """``data`` cut to what a breakdown reads: the window, the harness's
    annotations and the program's spans on the host, the op and module
    lines of the devices."""
    keep = set(labels) | {trace.WINDOW}
    planes = []
    for plane in data["planes"]:
        device = trace.DEVICE_PLANE.match(plane["name"])
        lines = []
        for line in plane["lines"]:
            if device:
                if line["name"] in trace.OP_LINES + trace.MODULE_LINES:
                    lines.append(line)
                continue
            events = [e for e in line["events"] if e[0] in keep or e[0].startswith(PREFIX)]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--save", default="")
    args = p.parse_args(argv)

    seen = {}
    load_xplane = trace.load_xplane

    def load(trace_dir):
        seen["data"] = load_xplane(trace_dir)
        return seen["data"]

    def keep(traffic):
        seen["traffic"] = traffic

    trace.load_xplane = load
    try:
        result = core.run(args.workload, args.seed, args.seconds, True, T_START, hook=keep)
    except core.Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    traffic = seen["traffic"]
    data = window_events(seen["data"], traffic.span_labels())
    core.log("spans", **breakdown(data, len(traffic.cycles)))
    if args.save:
        data["cycles"] = [{"index": c.index, "wall_ms": c.wall_ms, "phases": c.phases}
                          for c in traffic.cycles]
        with open(args.save, "w") as f:
            json.dump(data, f)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
