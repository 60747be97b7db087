"""What a per-layer metric reader (``benchmark/metrics/<name>.py``) is
handed after a traced run, and the arithmetic the readers share."""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import List, Optional


@dataclass
class RunView:
    cycles: list  # harness.cluster.Cycle, the window's
    trace: dict  # harness.trace.reduce
    device: dict  # harness.device.describe + memory


def mean_ms(run: RunView, get) -> Optional[float]:
    values = [get(c) for c in run.cycles]
    return sum(values) / len(values) if values else None


def action_ms(run: RunView, action: str) -> Optional[float]:
    values = [c.action_ms[action] for c in run.cycles if action in c.action_ms]
    return sum(values) / len(values) if values else None


def kernel_seconds(run: RunView, pattern: str) -> float:
    """Device seconds of the programs whose jitted name matches."""
    rx = re.compile(pattern)
    return sum(s for name, s in run.trace["module_s"].items() if rx.search(name))


def kernel_ms_per_cycle(run: RunView, pattern: str) -> Optional[float]:
    """Device time of the kernel's ops over the window, per cycle; None
    when the trace holds none of them."""
    total = kernel_seconds(run, pattern)
    if total <= 0 or not run.cycles:
        return None
    return total * 1e3 / len(run.cycles)


def roofline_pct(run: RunView, pattern: str, works: List[dict]) -> Optional[float]:
    """Least time by the peaks — for each kernel call, the larger of its
    operations over peak FLOP/s and its bytes over peak HBM bandwidth —
    summed over the window's calls, over the kernel's device time in the
    window, in percent.  ``works`` are ``{"ops", "bytes"}`` of each call.
    None when the trace holds no event of the kernel."""
    from harness.device import peaks

    total = kernel_seconds(run, pattern)
    if total <= 0 or not works:
        return None
    peak = peaks(run.device["kind"])
    least = sum(
        max(w["ops"] / peak["flops_per_s"], w["bytes"] / peak["hbm_bytes_per_s"])
        for w in works
    )
    return 100.0 * least / total


def span_ms_per_cycle(run: RunView, label: str) -> Optional[float]:
    """Host time inside the harness annotation ``label`` over the
    window, per cycle; None when the trace holds none."""
    total = run.trace["span_s"].get(label, 0.0)
    if total <= 0 or not run.cycles:
        return None
    return total * 1e3 / len(run.cycles)


def idle_pct(run: RunView) -> float:
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
