"""Program spans on the profiler's clock (``volcano_tpu.trace.span``),
the counters of jax-allocate's APPLY paths, and the gc pause hook."""

from __future__ import annotations

import gc
import glob
import os
import re
import subprocess
import sys
import time
import tracemalloc
import warnings

import jax
import pytest

from volcano_tpu import trace
from volcano_tpu.actions import jax_allocate
from volcano_tpu.actions.jax_allocate import JaxAllocateAction
from volcano_tpu.apis import core
from volcano_tpu.metrics import metrics
from volcano_tpu.obs import spans as obs_spans
from volcano_tpu.trace import TraceRecorder
from volcano_tpu.utils import gcutil

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, run_actions, tiers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _profile(tmp_path, body):
    """Run ``body`` under a CPU profiler session; every host event as
    ``(name, duration_ns, stats)``."""
    d = str(tmp_path / "profile")
    jax.profiler.start_trace(d)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = {}
                if e.name.startswith("vtpu/"):
                    with warnings.catch_warnings():  # the stat type's own warning
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                out.append((e.name, e.duration_ns, stats))
    return out


def _span_patterns():
    """SPAN_NAMES as regexes, each ``{…}`` matching one registered name."""
    return [
        re.compile("^" + re.sub(r"\\\{\w+\\\}", r"[\\w-]+", re.escape(n)) + "$")
        for n in trace.SPAN_NAMES
    ]


class _Exporter:
    """A flight-recorder exporter that keeps every span it is handed."""

    token = "test"

    def __init__(self):
        self.records = []

    def keep(self, trace_id):
        return True

    def emit(self, record):
        self.records.append(record)


@pytest.fixture
def recorder():
    rec = TraceRecorder()
    trace.set_recorder(rec)
    try:
        yield rec
    finally:
        trace.set_recorder(None)


@pytest.fixture
def exporter():
    exp = _Exporter()
    obs_spans._set_exporter(exp)
    try:
        yield exp
    finally:
        obs_spans._set_exporter(None)


# ---- the primitive ----


def test_span_is_a_host_event_on_the_profiler_clock(tmp_path):
    spans = []

    def body():
        with trace.span("vtpu/allocate.host_sweep", "allocate", task="t-1") as sp:
            time.sleep(0.02)
            sp.set(nodes=7)
        spans.append(sp)

    events = [e for e in _profile(tmp_path, body) if e[0] == "vtpu/allocate.host_sweep"]
    assert len(events) == 1
    _name, duration_ns, stats = events[0]
    assert stats == {"task": "t-1", "nodes": 7}
    # the annotation opens before the span's clock reads and closes after
    assert spans[0].seconds >= 0.02
    assert spans[0].seconds <= duration_ns / 1e9 < spans[0].seconds + 0.01


def test_span_feeds_an_enabled_recorder_one_event_of_its_duration(recorder):
    recorder.begin_cycle()
    with trace.span("vtpu/session.close", "framework", label="close_session", jobs=3) as sp:
        sp.set(nodes=2)
    recorder.end_cycle()
    (event,) = recorder.last_cycle()["events"]
    assert event["name"] == "close_session"
    assert event["cat"] == "framework"
    assert event["dur"] == sp.seconds * 1e6
    assert event["args"] == {"jobs": 3, "nodes": 2}


def test_span_feeds_the_flight_recorder_with_its_nesting(exporter):
    with trace.span("vtpu/cycle", "scheduler", label="cycle:full", cycle=4) as outer:
        with trace.span("vtpu/action.enqueue", "action", label="action:enqueue") as inner:
            pass
    assert [r["name"] for r in exporter.records] == ["action:enqueue", "cycle:full"]
    child, parent = exporter.records
    assert child["p"] == parent["s"]
    assert parent["args"] == {"cycle": 4}
    assert child["dur"] == inner.seconds * 1e6
    assert parent["dur"] == outer.seconds * 1e6
    assert obs_spans.current() is None  # both popped


def test_span_with_every_sink_off_leaves_no_trace():
    """No recorder, no flight recorder, no profiler session: the span
    only times.  In a process that never imported JAX it does not
    import it either."""
    assert trace.get_recorder().enabled is False
    assert obs_spans.get_exporter() is None
    with trace.span("vtpu/cycle", "scheduler") as sp:
        pass
    assert sp.seconds >= 0.0
    assert trace.get_recorder().last_cycle() is None
    code = (
        "import sys\n"
        "from volcano_tpu import trace\n"
        "with trace.span('vtpu/cycle', 'scheduler', cycle=1) as sp:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
        "assert trace.annotation_class() is None and sp._ann is None\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ---- the cycle's spans ----


def _conf(tmp_path) -> str:
    path = tmp_path / "scheduler.conf"
    path.write_text(
        'actions: "enqueue, jax-allocate, jax-preempt, backfill"\n'
        "tiers:\n"
        "- plugins:\n  - name: priority\n  - name: gang\n  - name: conformance\n"
        "- plugins:\n  - name: drf\n  - name: predicates\n  - name: proportion\n"
        "  - name: nodeorder\n  - name: binpack\n"
    )
    return str(path)


def _small_cluster():
    nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "8Gi"}) for i in range(3)]
    pods = [
        build_pod("ns", f"p{i}", "", {"cpu": "1", "memory": "1Gi"}, group="pg")
        for i in range(4)
    ]
    pods.append(build_pod("ns", "big", "", {"cpu": "16", "memory": "1Gi"}, group="pg-big"))
    # a node affinity richer than the packed bitsets keeps the session on
    # the Statement loop, whose host sweep and commit spans are checked
    rich = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
        "nodeSelectorTerms": [{"matchExpressions": [
            {"key": "accel", "operator": "In", "values": ["tpu", "gpu"]}]}]}}}
    pods.append(build_pod("ns", "rich", "", {"cpu": "1", "memory": "1Gi"},
                          group="pg-rich", affinity=rich))
    pgs = [build_pod_group("ns", "pg", 4, queue="q1"),
           build_pod_group("ns", "pg-big", 1, queue="q1"),
           build_pod_group("ns", "pg-rich", 1, queue="q1")]
    return make_cache(nodes=nodes, pods=pods, pod_groups=pgs,
                      queues=[build_queue("q1", weight=1)])


def test_cycle_spans_are_all_named_in_span_names(tmp_path):
    from volcano_tpu.scheduler.scheduler import Scheduler

    cache = _small_cluster()
    sched = Scheduler(cache, scheduler_conf_path=_conf(tmp_path), period=0.0)

    def body():
        sched.run_once()
        cache.flush()

    names = {n for n, _d, _s in _profile(tmp_path, body) if n.startswith("vtpu/")}
    patterns = _span_patterns()
    assert names and all(any(p.match(n) for p in patterns) for n in names), names
    assert {
        "vtpu/cycle", "vtpu/cache.snapshot", "vtpu/session.open",
        "vtpu/plugin.gang.open", "vtpu/session.close", "vtpu/plugin.gang.close",
        "vtpu/session.job_updater", "vtpu/session.job_writeback", "vtpu/action.jax-allocate",
        "vtpu/action.backfill", "vtpu/allocate.order", "vtpu/allocate.pack",
        "vtpu/allocate.dispatch", "vtpu/allocate.apply",
        "vtpu/allocate.host_sweep", "vtpu/statement.commit", "vtpu/cache.flush",
        "vtpu/cache.bind_batch",
    } <= names


def test_action_histogram_is_fed_from_the_span(tmp_path, recorder):
    """One duration for every sink: the action histogram's sum grows by
    exactly the recorded span's duration."""
    from volcano_tpu.scheduler.scheduler import Scheduler

    name = "volcano_action_scheduling_latency_microseconds"
    labels = {"action": "jax-allocate"}
    before = (metrics.registry.histogram_snapshot(name, labels) or {"sum": 0.0})["sum"]
    Scheduler(_small_cluster(), scheduler_conf_path=_conf(tmp_path), period=0.0).run_once()
    after = metrics.registry.histogram_snapshot(name, labels)["sum"]
    (event,) = [e for e in recorder.last_cycle()["events"]
                if e["name"] == "action:jax-allocate"]
    assert after - before == pytest.approx(event["dur"], rel=1e-12)


# ---- jax-allocate's APPLY paths ----


def _counter(name, labels=None) -> float:
    key = (name, tuple(sorted((labels or {}).items())))
    with metrics.registry._lock:
        return metrics.registry._counters.get(key, 0.0)


def _paths_cluster():
    """Five nodes, each failing a different predicate for a 4-cpu
    ``accel=tpu`` pod, and three one-pod jobs in creation order:

    * ``stuck-exact`` — selector only: the device's reason counts prove
      it fits nowhere, so APPLY takes the explanation;
    * ``stuck-rich`` — a two-value node affinity, richer than the
      packed bitsets: the kernel, blind to it, proposes ``n-other``,
      validation rejects that, and the row is not exactly encoded, so
      the host sweeps all five nodes;
    * ``easy`` — fits ``n-other``: the kernel's proposal validates.

    The rich row makes the session need host validation, so the bulk
    commit refuses and every task goes through the per-task chooser."""
    nodes = [
        build_node("n-small", {"cpu": "1", "memory": "1Gi"}, labels={"accel": "tpu"}),
        build_node("n-full", {"cpu": "32", "memory": "32Gi", "pods": 0},
                   labels={"accel": "tpu"}),
        build_node("n-cordon", {"cpu": "32", "memory": "32Gi"},
                   labels={"accel": "tpu"}, unschedulable=True),
        build_node("n-other", {"cpu": "32", "memory": "32Gi"}),
        build_node("n-taint", {"cpu": "32", "memory": "32Gi"}, labels={"accel": "tpu"},
                   taints=[core.Taint(key="dedicated", value="x", effect="NoSchedule")]),
    ]
    rich = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
        "nodeSelectorTerms": [{"matchExpressions": [
            {"key": "accel", "operator": "In", "values": ["tpu", "gpu"]}]}]}}}
    pods = [
        build_pod("ns", "stuck-exact", "", {"cpu": "4", "memory": "4Gi"},
                  group="pg-exact", selector={"accel": "tpu"}),
        build_pod("ns", "stuck-rich", "", {"cpu": "4", "memory": "4Gi"},
                  group="pg-rich", affinity=rich),
        build_pod("ns", "easy", "", {"cpu": "1", "memory": "1Gi"}, group="pg-easy"),
    ]
    pgs = [build_pod_group("ns", g, 1, queue="q1")
           for g in ("pg-exact", "pg-rich", "pg-easy")]
    return make_cache(nodes, pods, pgs, [build_queue("q1", weight=1)])


TIERS = tiers(
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)


def test_apply_path_counters_match_hand_counts(recorder):
    paths = ("fast_apply", "proposal", "explained", "host_sweep")
    apply_name = "volcano_allocate_apply_tasks_total"
    before = {p: _counter(apply_name, {"path": p}) for p in paths}
    rejected0 = _counter("volcano_allocate_proposals_rejected_total")
    nodes0 = _counter("volcano_allocate_host_sweep_nodes_total")

    recorder.begin_cycle()
    cache = _paths_cluster()
    run_actions(cache, [JaxAllocateAction(explain=True)], TIERS)
    recorder.end_cycle()

    delta = {p: _counter(apply_name, {"path": p}) - before[p] for p in paths}
    assert delta == {"fast_apply": 0, "proposal": 1, "explained": 1, "host_sweep": 1}
    assert _counter("volcano_allocate_proposals_rejected_total") - rejected0 == 1
    assert _counter("volcano_allocate_host_sweep_nodes_total") - nodes0 == 5
    assert cache.binder.binds == {"ns/easy": "n-other"}
    stats = jax_allocate.last_phase_stats
    assert {k: stats[k] for k in ("apply_fast_apply", "apply_proposal", "apply_explained",
                                  "apply_host_sweep", "apply_rejected",
                                  "host_sweep_nodes")} == {
        "apply_fast_apply": 0, "apply_proposal": 1, "apply_explained": 1,
        "apply_host_sweep": 1, "apply_rejected": 1, "host_sweep_nodes": 5,
    }
    sweeps = [e for e in recorder.last_cycle()["events"]
              if e["name"] == "vtpu/allocate.host_sweep"]
    assert len(sweeps) == 1 and sweeps[0]["args"]["nodes"] == 5
    assert stats["host_sweep_ms"] == pytest.approx(sweeps[0]["dur"] / 1e3, rel=1e-9)


def test_rejected_proposal_falls_through_and_is_counted(monkeypatch):
    """Proposals that fail host validation are counted as rejected and
    fall through: ``easy`` (sent to the cordoned node) takes the host
    sweep, which places it; ``stuck-exact`` is still explained."""
    rejected0 = _counter("volcano_allocate_proposals_rejected_total")
    sweep0 = _counter("volcano_allocate_apply_tasks_total", {"path": "host_sweep"})
    orig = JaxAllocateAction._kernel_proposals

    def wrong_node(self, ssn, ordered, nodes=None, pack_cache=None):
        proposals, snap = orig(self, ssn, ordered, nodes, pack_cache)
        return {uid: "n-cordon" for uid in proposals}, snap

    monkeypatch.setattr(JaxAllocateAction, "_kernel_proposals", wrong_node)
    cache = _paths_cluster()
    run_actions(cache, [JaxAllocateAction(explain=True)], TIERS)
    assert _counter("volcano_allocate_proposals_rejected_total") - rejected0 == 2
    assert _counter("volcano_allocate_apply_tasks_total", {"path": "host_sweep"}) - sweep0 == 2
    assert cache.binder.binds == {"ns/easy": "n-other"}


# ---- the gc hook ----


def _gc2_count() -> float:
    snap = metrics.registry.histogram_snapshot(
        "volcano_gc_pause_seconds", {"generation": "2"})
    return snap["count"] if snap else 0.0


def test_gc_hook_records_a_forced_collection(tmp_path):
    gcutil.install_gc_hook()
    gcutil.install_gc_hook()  # once per process
    assert sum(cb is gcutil._hook for cb in gc.callbacks) == 1
    before = _gc2_count()
    events = _profile(tmp_path, lambda: gc.collect(2))
    assert _gc2_count() >= before + 1
    assert any(name == gcutil.GC_SPAN for name, _d, _s in events)


def test_gc_pause_waits_out_a_held_registry_lock():
    """A collection that starts while this thread holds the registry's
    lock records its pause without waiting for the lock."""
    gcutil.install_gc_hook()
    before = _gc2_count()
    with metrics.registry._lock:
        gc.collect(2)
    assert _gc2_count() >= before + 1


def test_gc_pauses_unread_keep_memory_bounded():
    """Thousands of collections that nothing scrapes leave the hook's
    memory where it was: each pause lands in a fixed bucket array."""
    gcutil.install_gc_hook()
    for _ in range(200):
        gc.collect(0)
    c0 = metrics.registry.histogram_snapshot(
        "volcano_gc_pause_seconds", {"generation": "0"})["count"]
    here = [tracemalloc.Filter(True, metrics.__file__), tracemalloc.Filter(True, gcutil.__file__)]
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot().filter_traces(here)
        for _ in range(5000):
            gc.collect(0)
        after = tracemalloc.take_snapshot().filter_traces(here)
    finally:
        tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename"))
    assert grown < 1024
    c1 = metrics.registry.histogram_snapshot(
        "volcano_gc_pause_seconds", {"generation": "0"})["count"]
    assert c1 >= c0 + 5000
