"""Session close's job status writeback (framework/job_updater).

Every job's status is derived on the closing thread; only the writes that
are due leave it, by a path picked from what the cache shows.  These pin
the writes and the attempt counts to the per-job fan-out the updater had
before, and each path to the cache that selects it."""

from __future__ import annotations

import collections
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from volcano_tpu import trace
from volcano_tpu.actions.allocate import AllocateAction
from volcano_tpu.apis import scheduling
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.cache.interface import StatusUpdater
from volcano_tpu.framework import job_updater, open_session
from volcano_tpu.framework.job_updater import JobUpdater, is_pod_group_status_updated
from volcano_tpu.incremental.subgraph import _ShadowCache
from volcano_tpu.metrics import metrics

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.fakes import FakeBinder, FakeEvictor
from tests.scheduler_helpers import tiers

TIERS = tiers(["priority", "gang"], ["drf", "predicates", "proportion", "nodeorder"])
RESULTS = ("scheduled", "unschedulable", "error")
PATHS = ("inline", "pool", "plane")


class RecordingWriter(StatusUpdater):
    """Logs every status write as it is issued; raises for the PodGroups
    named in ``fail``."""

    def __init__(self, fail=()):
        self.lock = threading.Lock()
        self.fail = set(fail)
        self.writes = []

    def update_pod_condition(self, task, reason, message):
        with self.lock:
            self.writes.append(("condition", f"{task.namespace}/{task.name}", reason, message))

    def update_pod_group(self, pg):
        st = pg.status
        conds = tuple(sorted((c.type, c.status, c.reason, c.message) for c in st.conditions))
        with self.lock:
            self.writes.append(("pod_group", pg.metadata.name, st.phase, st.running,
                                st.succeeded, st.failed, conds))
        if pg.metadata.name in self.fail:
            raise RuntimeError(f"write of {pg.metadata.name} refused")
        return pg


def _cluster(writer=None, pipelined=False):
    """Two nodes of 4 cpu.  Jobs: two Running since before the session
    (``run-*``), one Running that carries a condition it keeps
    (``kept``), two placed this cycle (``new-*``), one too big for any
    node (``big``), one too big that already carried an Unschedulable
    condition at open (``stuck``)."""
    cache = SchedulerCache(binder=FakeBinder(), evictor=FakeEvictor(),
                           status_updater=writer, pipelined_commit=pipelined)
    for n in ("n0", "n1"):
        cache.add_node(build_node(n, {"cpu": "4", "memory": "16Gi"}))
    cache.add_queue(build_queue("q", weight=1))
    one = {"cpu": "1", "memory": "1Gi"}
    running = scheduling.POD_GROUP_RUNNING
    groups = {
        "run-a": (2, running, [("n0", "Running"), ("n0", "Running")]),
        "run-b": (1, running, [("n1", "Running")]),
        "kept": (1, running, [("n1", "Running")]),
        "new-a": (2, scheduling.POD_GROUP_INQUEUE, [("", "Pending")] * 2),
        "new-b": (1, scheduling.POD_GROUP_INQUEUE, [("", "Pending")]),
        "big": (1, scheduling.POD_GROUP_INQUEUE, [("", "Pending")]),
        "stuck": (1, scheduling.POD_GROUP_INQUEUE, [("", "Pending")]),
    }
    for name, (min_member, phase, pods) in groups.items():
        pg = build_pod_group("ns", name, min_member, queue="q", phase=phase)
        if name == "kept":
            pg.status.running = 1
            pg.status.conditions.append(scheduling.PodGroupCondition(
                type="Scheduled", status="True", reason="tasks-ready"))
        if name == "stuck":
            pg.status.conditions.append(scheduling.PodGroupCondition(
                type=scheduling.POD_GROUP_UNSCHEDULABLE_TYPE, status="True",
                reason="NotEnoughResources", message="1/1 tasks in gang unschedulable"))
        cache.add_pod_group(pg)
        req = {"cpu": "64", "memory": "1Gi"} if name in ("big", "stuck") else one
        for i, (node, pod_phase) in enumerate(pods):
            cache.add_pod(build_pod("ns", f"{name}-{i}", node, req, phase=pod_phase, group=name))
    return cache


def _closing_session(cache):
    """A session over ``cache`` after allocate and the plugins' close
    (gang's marks the jobs it could not start), ready for its job
    updater."""
    ssn = open_session(cache, TIERS, [])
    AllocateAction().execute(ssn)
    for plugin in ssn.plugins.values():
        plugin.on_session_close(ssn)
    return ssn


def _pooled_reference(ssn) -> None:
    """The updater as it was: derivation and write of each job together,
    every job through a pool of 16."""

    def update(job):
        if job.pod_group is None:
            return
        was_running = ssn.pod_group_phase0.get(job.uid) == scheduling.POD_GROUP_RUNNING
        job.pod_group.status = ssn.job_status(job)
        phase = job.pod_group.status.phase
        attempt = None
        if phase == scheduling.POD_GROUP_RUNNING:
            if not was_running:
                attempt = "scheduled"
        elif job.job_fit_errors or phase == scheduling.POD_GROUP_UNKNOWN:
            attempt = "unschedulable"
        try:
            if is_pod_group_status_updated(ssn.pod_group_status.get(job.uid),
                                           job.pod_group.status):
                ssn.cache.update_job_status_async(job)
        except Exception:  # noqa: BLE001
            attempt = "error"
        if attempt is not None:
            metrics.register_schedule_attempt(attempt)

    with ThreadPoolExecutor(max_workers=16) as pool:
        list(pool.map(update, list(ssn.jobs.values())))


def _counter(name, labels):
    with metrics.registry._lock:
        return metrics.registry._counters.get((name, tuple(sorted(labels.items()))), 0.0)


def _attempts():
    return {r: _counter("volcano_schedule_attempts_total", {"result": r}) for r in RESULTS}


def _writes_by_path():
    return {p: _counter("volcano_job_status_writes_total", {"path": p}) for p in PATHS}


def _close(ssn, update):
    """Run ``update`` on the session; (status by job, attempt deltas)."""
    before = _attempts()
    update(ssn)
    after = _attempts()
    status = {
        job.name: (job.pod_group.status.phase, job.pod_group.status.running,
                   job.pod_group.status.succeeded, job.pod_group.status.failed,
                   sorted((c.type, c.status, c.reason, c.message)
                          for c in job.pod_group.status.conditions))
        for job in ssn.jobs.values()
    }
    return status, {r: after[r] - before[r] for r in RESULTS}


@pytest.mark.parametrize("fail", [(), ("run-a", "new-b", "big")], ids=["ok", "failing"])
def test_writes_and_attempts_equal_the_pooled_fan_out(fail):
    ref_writer, new_writer = RecordingWriter(fail), RecordingWriter(fail)
    ref = _close(_closing_session(_cluster(ref_writer)), _pooled_reference)
    new = _close(_closing_session(_cluster(new_writer)), lambda s: JobUpdater(s).update_all())

    assert collections.Counter(new_writer.writes) == collections.Counter(ref_writer.writes)
    assert new == ref
    status, attempts = new
    # the scenario reaches every branch: placed, stuck, steady, untouched
    assert status["new-a"][0] == status["new-b"][0] == scheduling.POD_GROUP_RUNNING
    assert status["big"][0] == scheduling.POD_GROUP_INQUEUE
    written = {w[1] for w in new_writer.writes if w[0] == "pod_group"}
    assert "kept" not in written and {"run-a", "run-b", "big", "stuck"} <= written
    # a write that raises turns that job's attempt into an error, and
    # only that job's: run-a had none (Running at open), new-b was
    # scheduled, big unschedulable
    assert attempts["error"] == len(fail)
    if fail:
        assert attempts == {"scheduled": 1, "unschedulable": 1, "error": 3}
    else:
        assert attempts == {"scheduled": 2, "unschedulable": 2, "error": 0}


class _PoolProbe:
    """Stands in for ThreadPoolExecutor in job_updater: raises when the
    path must not build a pool, else counts and builds a real one."""

    def __init__(self, allowed):
        self.allowed = allowed
        self.built = 0

    def __call__(self, *args, **kwargs):
        if not self.allowed:
            raise AssertionError("the job updater built a thread pool")
        self.built += 1
        return ThreadPoolExecutor(*args, **kwargs)


def _shadow(ssn):
    # the shadow cache hands unknown attributes to the real cache, whose
    # writer here does I/O as far as the updater can tell
    ssn.cache = _ShadowCache(ssn.cache, {})


@pytest.mark.parametrize("writer,pipelined,wrap,path", [
    (False, False, None, "inline"),
    (True, False, _shadow, "inline"),
    (True, False, None, "pool"),
    (True, True, None, "plane"),
], ids=["no-writer", "shadow-cache", "io-writer", "commit-plane"])
def test_due_writes_take_the_path_the_cache_selects(monkeypatch, writer, pipelined, wrap, path):
    probe = _PoolProbe(allowed=path == "pool")
    monkeypatch.setattr(job_updater, "ThreadPoolExecutor", probe)
    recording = RecordingWriter() if writer else None
    cache = _cluster(recording, pipelined=pipelined)
    try:
        ssn = _closing_session(cache)
        if wrap is not None:
            wrap(ssn)
        before = _writes_by_path()
        JobUpdater(ssn).update_all()
        cache.flush()
        delta = {p: _writes_by_path()[p] - before[p] for p in PATHS}
    finally:
        cache.stop_commit_plane()
    due = 6  # every job but ``kept``
    assert delta == {p: (due if p == path else 0) for p in PATHS}
    assert probe.built == (1 if path == "pool" else 0)
    if recording is not None:
        pod_groups = sorted(w[1] for w in recording.writes if w[0] == "pod_group")
        expect = [] if wrap is _shadow else sorted(
            ["run-a", "run-b", "new-a", "new-b", "big", "stuck"])
        assert pod_groups == expect


def test_writeback_span_is_named_and_holds_the_due_writes():
    assert "vtpu/session.job_writeback" in trace.SPAN_NAMES
    rec = trace.TraceRecorder()
    trace.set_recorder(rec)
    try:
        rec.begin_cycle()
        ssn = _closing_session(_cluster(RecordingWriter()))
        JobUpdater(ssn).update_all()
        rec.end_cycle()
    finally:
        trace.set_recorder(None)
    (event,) = [e for e in rec.last_cycle()["events"]
                if e["name"] == "vtpu/session.job_writeback"]
    assert event["args"] == {"path": "pool", "jobs": 6}
