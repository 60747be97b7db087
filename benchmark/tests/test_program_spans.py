"""The program's own spans (``vtpu/…``) and APPLY counters beside the
harness's annotations: no name collides, the reduction and the readers
of the trace give what they gave without program spans, and the APPLY
readers read the program's counts."""

import copy

import pytest
from test_rehearsal import small_run
from test_trace_reduction import load

from harness import core, trace
from harness.view import RunView
from traffic import full_cycle

APPLY = ("alloc_host_sweep_ms.full", "alloc_host_sweep_tasks.full",
         "alloc_device_resolved_pct.full")
BEFORE = ("session_open_ms.full", "session_close_ms.full", "alloc_action_ms.full", "alloc_apply_ms.full",
          "preempt_action_ms.full", "preempt_kernel_ms.full", "device_idle_pct.full")


def reader(name):
    return core.load_module("metrics", name).read


def span_names():
    """The program's span names with the cell's actions and plugins
    filled in."""
    import volcano_tpu.trace as program_trace

    cfg = core.load_json("configs", "preemption-basic.json")["scheduler"]
    actions = [a.strip() for a in cfg["actions"].split(",")]
    plugins = [p for tier in cfg["tiers"] for p in tier]
    out = set()
    for n in program_trace.SPAN_NAMES:
        if "{action}" in n:
            out.update(n.format(action=a) for a in actions)
        elif "{plugin}" in n:
            out.update(n.format(plugin=p) for p in plugins)
        else:
            out.add(n)
    return out


def test_no_program_span_takes_a_harness_label():
    names = span_names()
    assert all(n.startswith("vtpu/") for n in names)
    assert not names & full_cycle.LABELS
    assert not names & {trace.WINDOW}


def with_program_spans(data):
    """``data`` with a ``vtpu/`` twin of every harness annotation (the
    same interval) and a host sweep nested inside each."""
    data = copy.deepcopy(data)
    for plane in data["planes"]:
        if trace.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            extra = []
            for name, s, d in line["events"]:
                if name in full_cycle.LABELS:
                    extra.append([f"vtpu/{name}", s, d])
                    extra.append(["vtpu/allocate.host_sweep", s + d // 4, d // 2])
            line["events"].extend(extra)
    assert any(n.startswith("vtpu/") for n, _s, _e in trace.host_spans(data))
    return data


@pytest.mark.parametrize("fixture", ["trace_small.json", "trace_preempt_v5e.json"])
def test_program_spans_leave_the_reduction_and_readers_unchanged(fixture):
    data = load(fixture)
    before = trace.reduce(data, full_cycle.LABELS)
    after = trace.reduce(with_program_spans(data), full_cycle.LABELS)
    assert after == before
    cycles = [core_cycle(i) for i in range(2)]
    for name in BEFORE:
        run_a = RunView(cycles, before, {"kind": "TPU v5 lite"})
        run_b = RunView(cycles, after, {"kind": "TPU v5 lite"})
        assert reader(name)(run_b) == reader(name)(run_a)


def core_cycle(index, phases=None):
    from harness.cluster import Cycle

    return Cycle(index=index, start=0.0, wall_ms=1.0, open_ms=2.0,
                 action_ms={"jax-allocate": 3.0, "jax-preempt": 4.0},
                 phases=dict(phases or {}))


def test_apply_readers_read_the_phase_stats():
    phases = [
        {"apply_fast_apply": 0, "apply_proposal": 0, "apply_explained": 134,
         "apply_host_sweep": 866, "host_sweep_ms": 13000.0},
        {"apply_fast_apply": 10, "apply_proposal": 0, "apply_explained": 124,
         "apply_host_sweep": 866, "host_sweep_ms": 14000.0},
    ]
    run = RunView([core_cycle(i, p) for i, p in enumerate(phases)], {}, {})
    assert reader("alloc_host_sweep_ms.full")(run) == 13500.0
    assert reader("alloc_host_sweep_tasks.full")(run) == 866.0
    assert reader("alloc_device_resolved_pct.full")(run) == pytest.approx(100 * 268 / 2000)


def test_apply_readers_are_silent_on_a_program_without_the_stats():
    """The parent program reports none of these stats: the readers
    return None and the result line leaves the metrics out."""
    run = RunView([core_cycle(0, {"order_ms": 30.0, "pack_ms": 5.0})], {}, {})
    assert all(reader(n)(run) is None for n in APPLY)


def test_apply_readers_on_a_small_cpu_run():
    """The tiny preemption cluster on the CPU: every pending preemptor
    takes one APPLY path each cycle, and the readers give the counts'
    own arithmetic."""
    seen = {}

    def keep(traffic):
        seen["traffic"] = traffic

    out = small_run("preempt-full-cycle", hook=keep)
    assert out["correct"], out["checks"]
    cycles = seen["traffic"].cycles
    paths = ("apply_fast_apply", "apply_proposal", "apply_explained", "apply_host_sweep")
    for c in cycles:
        assert sum(c.phases[k] for k in paths) == 100  # high_pods
        assert c.phases["host_sweep_nodes"] == 100 * c.phases["apply_host_sweep"]
        assert (c.phases["host_sweep_ms"] > 0) == (c.phases["apply_host_sweep"] > 0)
    run = RunView(cycles, {}, {})
    n = len(cycles)
    assert reader("alloc_host_sweep_tasks.full")(run) == sum(
        c.phases["apply_host_sweep"] for c in cycles) / n
    assert reader("alloc_host_sweep_ms.full")(run) == pytest.approx(
        sum(c.phases["host_sweep_ms"] for c in cycles) / n)
    assert reader("alloc_device_resolved_pct.full")(run) == pytest.approx(
        100 - sum(c.phases["apply_host_sweep"] for c in cycles) / n)


def test_sweep_names_the_pieces_as_host_segments_does():
    """``spans.innermost`` is ``harness.trace.host_segments`` by a sweep:
    the same pieces on every recorded trace, program spans included."""
    import spans

    for fixture in ("trace_small.json", "trace_preempt_v5e.json", "trace_spans_v5e.json"):
        data = load(fixture)
        host = trace.host_spans(data)
        lo = min(s for n, s, _e in host if n == trace.WINDOW)
        hi = max(e for n, _s, e in host if n == trace.WINDOW)
        named = [x for x in host if x[0] != trace.WINDOW]
        if len(named) > 1000:  # host_segments scans every span per piece
            named = [x for x in named if x[0] != "vtpu/statement.commit"]
        assert spans.innermost(named, lo, hi) == trace.host_segments(named, lo, hi)


def test_recorded_v5e_cycle_with_program_spans():
    """One cycle of a traced preempt-full-cycle window recorded on the
    chip (seed 5230000011), cut to that cycle: the fixture's ``window``
    is the cycle's own annotation, and ``cycles`` holds the phase stats
    the program reported for it.  The readers give the counts by hand;
    the program's 866 host-sweep spans on the profiler's clock add up to
    the ``host_sweep_ms`` it reported from the same spans; session close
    reads the harness's annotation around ``close_session``; and the idle
    time falls under program spans, the sweep first."""
    import spans

    data = load("trace_spans_v5e.json")
    (cycle,) = data["cycles"]
    phases = cycle["phases"]
    assert {k: phases[k] for k in ("apply_fast_apply", "apply_proposal", "apply_explained",
                                   "apply_host_sweep", "apply_rejected")} == {
        "apply_fast_apply": 0, "apply_proposal": 0, "apply_explained": 134,
        "apply_host_sweep": 866, "apply_rejected": 0}
    assert phases["host_sweep_nodes"] == 866 * 1000
    out = trace.reduce(data, full_cycle.LABELS)
    run = RunView([core_cycle(cycle["index"], phases)], out, {"kind": "TPU v5 lite"})
    assert reader("alloc_host_sweep_tasks.full")(run) == 866
    assert reader("alloc_device_resolved_pct.full")(run) == pytest.approx(13.4)
    sweep_ms = reader("alloc_host_sweep_ms.full")(run)
    assert sweep_ms == phases["host_sweep_ms"] == pytest.approx(12477.759966)
    assert sweep_ms <= reader("alloc_apply_ms.full")(run)
    # the harness's annotation around close_session holds the program's
    # own vtpu/session.close span (169.066685 ms) and little else
    assert reader("session_close_ms.full")(run) == pytest.approx(169.075245)

    sweeps = [(s, e) for n, s, e in trace.host_spans(data) if n == "vtpu/allocate.host_sweep"]
    assert len(sweeps) == 866
    assert sum(e - s for s, e in sweeps) / 1e6 == pytest.approx(sweep_ms, rel=1e-3)

    bd = spans.breakdown(data, 1)
    assert bd["per_cycle"]["vtpu/allocate.host_sweep"]["count"] == 866
    assert list(bd["idle_s"])[:2] == ["vtpu/allocate.host_sweep", "vtpu/gc"]
    assert bd["no_host_span_s"] < 0.05 * bd["window_s"]
    assert sum(bd["idle_s"].values()) == pytest.approx(bd["window_s"] - bd["busy_s"])
