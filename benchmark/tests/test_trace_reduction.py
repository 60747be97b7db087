"""The trace reduction on recorded traces kept as fixtures."""

import json
import os

import pytest

from harness import trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def test_busy_is_the_union_not_the_sum():
    out = trace.reduce(load("trace_small.json"))
    # op intervals 1100-1200, 1150-1300, 1500-1600 inside the window
    # 1000-2000 (the copy at 2500 lies outside): union 300 ns, sum 350
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert sum(out["op_s"].values()) == pytest.approx(350e-9)


def test_kernel_found_by_name():
    from harness.view import RunView, kernel_ms_per_cycle

    out = trace.reduce(load("trace_small.json"))
    # programs by jitted name, the hash in parentheses dropped
    assert out["module_s"]["jit_schedule_kernel"] == pytest.approx(100e-9)
    assert out["module_s"]["jit_step"] == pytest.approx(610e-9)
    run = RunView(cycles=[object()], trace=out, device={"kind": "TPU v5 lite"})
    assert kernel_ms_per_cycle(run, r"^jit_schedule_kernel") == pytest.approx(100e-6)
    assert kernel_ms_per_cycle(run, r"no_such_kernel") is None


def test_idle_split_by_innermost_host_span():
    out = trace.reduce(load("trace_small.json"), labels={"cycle", "session_open", "allocate:apply", "restore"})
    gaps = dict(out["idle_gaps"])
    # gaps 1000-1100 (session_open, inside cycle), 1300-1500
    # (allocate:apply), 1600-2000: 1600-1700 cycle, 1700-2000 restore
    assert gaps["session_open"] == pytest.approx(100e-9)
    assert gaps["allocate:apply"] == pytest.approx(200e-9)
    assert gaps["cycle"] == pytest.approx(100e-9)
    assert gaps["restore"] == pytest.approx(300e-9)
    assert out["span_s"] == pytest.approx(
        {"cycle": 700e-9, "session_open": 100e-9, "allocate:apply": 200e-9, "restore": 300e-9})
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])


def test_no_window_or_device_raises():
    data = load("trace_small.json")
    data["planes"][0]["lines"][0]["events"] = [e for e in data["planes"][0]["lines"][0]["events"] if e[0] != "window"]
    with pytest.raises(ValueError):
        trace.reduce(data)
    data = load("trace_small.json")
    data["planes"] = data["planes"][:1]
    with pytest.raises(ValueError):
        trace.reduce(data)


def test_recorded_v5e_trace():
    """A trace recorded on the chip (one preempt-full-cycle window,
    PR 22): the op events nest (a while loop around the kernel's custom
    call), so their sum exceeds the busy union; the preempt kernel is
    found by its program's name; the host was in jax-allocate's APPLY
    for most of the idle time."""
    from harness.view import RunView, kernel_ms_per_cycle

    out = trace.reduce(load("trace_preempt_v5e.json"), {
        "cycle", "restore", "session_open", "session_close", "action:enqueue",
        "action:jax-allocate", "allocate:order", "allocate:pack+kernel",
        "allocate:apply", "action:jax-preempt", "preempt:pack", "preempt:kernel",
    })
    assert out["window_s"] == pytest.approx(38.832237235)
    assert out["busy_s"] == pytest.approx(0.028197686)
    assert sum(out["op_s"].values()) > out["busy_s"]
    run = RunView(cycles=[object()], trace=out, device={"kind": "TPU v5 lite"})
    assert kernel_ms_per_cycle(run, r"^jit__preempt_call") == pytest.approx(25.509985)
    assert out["idle_gaps"][0][0] == "allocate:apply"
    # host time per annotation, clipped to the window: APPLY held the
    # cycle (the jax-allocate host sweep, PERF.md section 5)
    assert out["span_s"]["allocate:apply"] == pytest.approx(36.806218158)
    assert out["span_s"]["cycle"] == pytest.approx(38.68837382)
    # ops by their short HLO names, not the instruction's text
    assert "_preempt_call.1" in out["op_s"]
    assert all(" " not in name for name, _s in out["device_ops"])
    idle = out["window_s"] - out["busy_s"]
    assert 0.99 * idle < sum(s for _n, s in out["idle_gaps"]) <= idle  # the ten largest
