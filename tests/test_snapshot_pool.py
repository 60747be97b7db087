"""The snapshot clone pool against a cold-cloning cache.

``SchedulerCache.snapshot`` hands out the previous session's clone of
every object that session left untouched and the cache has not mutated
since.  That is exact only while every write to a session's clone marks
``ssn.touched_jobs`` / ``ssn.touched_nodes``.  The harness here drives
two caches fed the same objects through several cycles of every shipped
action list, with churn of every kind between cycles: one keeps its pool,
the other drops it before each snapshot.  Every cycle the two must agree
on the snapshot, the binds, the evictions and the pipelined placements.
"""

from __future__ import annotations

import copy

import pytest

import volcano_tpu.actions  # noqa: F401 — registers actions
from volcano_tpu.api import TaskStatus
from volcano_tpu.apis import core
from volcano_tpu.framework import close_session, get_action, open_session
from volcano_tpu.framework.interface import Action
from volcano_tpu.metrics import metrics

from tests.builders import (
    build_node,
    build_pod,
    build_pod_group,
    build_priority_class,
    build_queue,
)
from tests.scheduler_helpers import make_cache, tiers

FULL_TIERS = lambda: tiers(
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)

ACTION_LISTS = {
    "allocate": "enqueue, allocate, backfill",
    "jax-allocate": "enqueue, jax-allocate, backfill",
    "jax-preempt": "enqueue, jax-allocate, jax-preempt, backfill",
    "preempt": "enqueue, allocate, preempt",
    "reclaim": "reclaim",
    "jax-reclaim": "jax-reclaim",
}


def _world():
    """Four full nodes of priority-0 runners in ``q-a``, two empty
    nodes, pending priority-100 gangs in ``q-a`` that outgrow the free
    room (allocate places some, preempt evicts for the rest), and
    pending pods in an empty ``q-b`` (reclaim evicts ``q-a`` for them)."""
    nodes = [
        build_node(f"n{i}", {"cpu": "4", "memory": "16Gi"},
                   labels={"zone": f"z{i % 2}"})
        for i in range(6)
    ]
    pods, pgs = [], []
    for i in range(4):
        for k in range(4):
            group = f"run{i}-{k // 2}"
            pods.append(build_pod(
                "ns", f"run{i}-{k}", f"n{i}", {"cpu": "1", "memory": "1Gi"},
                phase="Running", group=group, priority=0,
            ))
    pgs += [
        build_pod_group("ns", f"run{i}-{g}", 1, queue="q-a")
        for i in range(4) for g in range(2)
    ]
    for j in range(3):
        pgs.append(build_pod_group("ns", f"hi{j}", 2, queue="q-a",
                                   priority_class_name="high"))
        for k in range(2):
            pods.append(build_pod("ns", f"hi{j}-{k}", "",
                                  {"cpu": "2", "memory": "1Gi"},
                                  group=f"hi{j}", priority=100))
    for j in range(2):
        pgs.append(build_pod_group("ns", f"b{j}", 1, queue="q-b"))
        pods.append(build_pod("ns", f"b{j}-0", "", {"cpu": "1", "memory": "1Gi"},
                              group=f"b{j}"))
    return dict(
        nodes=nodes, pods=pods, pod_groups=pgs,
        queues=[build_queue("q-a"), build_queue("q-b")],
        priority_classes=[build_priority_class("high", 100),
                          build_priority_class("mid", 50)],
    )


def _res(r):
    return (r.milli_cpu, r.memory, sorted((r.scalars or {}).items()))


def _snapshot_state(snap):
    """Everything of a snapshot's jobs and nodes a session reads, in the
    snapshot's own order (ORDER and APPLY tie-break on it)."""
    jobs = []
    for uid, j in snap.jobs.items():
        jobs.append((
            uid, j.priority, j.min_available, j.ready_num,
            _res(j.allocated), _res(j.total_request),
            [(t.uid, t.status.name, t.node_name, t.volume_ready, t.priority)
             for t in j.tasks.values()],
            {s.name: sorted(ts) for s, ts in j.task_status_index.items()},
            sorted(j.nodes_fit_errors), dict(j.nodes_fit_delta),
            j.job_fit_errors,
        ))
    nodes = []
    for name, n in snap.nodes.items():
        nodes.append((
            name, n.phase, _res(n.idle), _res(n.used), _res(n.releasing),
            _res(n.pipelined), _res(n.allocatable),
            [(t.uid, t.status.name, t.node_name) for t in n.tasks.values()],
        ))
    return jobs, nodes


def _clone_counts():
    return {
        (kind, path): metrics.registry._counters.get(
            ("volcano_snapshot_clones_total",
             (("kind", kind), ("path", path))), 0)
        for kind in ("job", "node") for path in ("reused", "cloned")
    }


def _pod(cache, name):
    for job in cache.jobs.values():
        for t in job.tasks.values():
            if t.name == name:
                return t.pod
    return None


def _churn(step):
    """One change of every kind; the returned function applies the same
    change to either cache."""
    pg = build_pod_group("ns", f"late{step}", 1,
                         queue="q-b" if step % 2 else "q-a")
    pod = build_pod("ns", f"late{step}-0", "", {"cpu": "1", "memory": "1Gi"},
                    group=f"late{step}")
    pc = build_priority_class("high", 100 + step)

    def apply(cache, binds):
        # a new pod and its PodGroup
        cache.add_pod_group(copy.deepcopy(pg))
        cache.add_pod(copy.deepcopy(pod))
        # a pod deleted: the first runner still there
        for i in range(16):
            gone = _pod(cache, f"run{i // 4}-{i % 4}")
            if gone is not None:
                cache.delete_pod(gone)
                break
        # a node NotReady, back Ready the step after
        old = cache.nodes["n5"].node
        new = copy.deepcopy(old)
        new.status.conditions = [core.NodeCondition(
            status="False" if step % 2 == 0 else "True")]
        cache.update_node(old, new)
        # a priority class's value
        cache.add_priority_class(copy.deepcopy(pc))
        # a PodGroup update: min member of the last pending gang
        job = cache.jobs.get("ns/hi2")
        if job is not None and job.pod_group is not None:
            upd = copy.deepcopy(job.pod_group)
            upd.spec.min_member = 1 if step % 2 == 0 else 2
            cache.update_pod_group(job.pod_group, upd)
        # bind echoes: the apiserver reports the first binds as Running
        for name, host in sorted(binds.items())[:2]:
            bound = _pod(cache, name.split("/", 1)[1])
            if bound is None or bound.spec.node_name:
                continue
            echo = copy.deepcopy(bound)
            echo.spec.node_name = host
            echo.status.phase = "Running"
            cache.update_pod(bound, echo)

    return apply


def _cycle(cache, actions, tier_conf, cold):
    if cold:
        cache._drop_clone_pool()
    before = _clone_counts()
    snap = cache.snapshot()
    after = _clone_counts()
    moved = {k: after[k] - before[k] for k in after}
    assert moved[("job", "reused")] + moved[("job", "cloned")] == len(snap.jobs)
    assert moved[("node", "reused")] + moved[("node", "cloned")] == len(snap.nodes)
    state = _snapshot_state(snap)
    ssn = open_session(cache, tier_conf(), [], snapshot=snap)
    try:
        for action in actions:
            action.execute(ssn)
        pipelined = {
            t.uid: t.node_name
            for job in ssn.jobs.values()
            for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values()
        }
    finally:
        close_session(ssn)
    return state, pipelined, moved


def run_equivalence(actions, world, cycles, churn, tier_conf=FULL_TIERS):
    """Drive a pooled and a cold-cloning cache through ``cycles`` cycles
    and assert they agree at every one; returns the pooled side's reused
    job and node counts summed over the cycles."""
    pooled = make_cache(**copy.deepcopy(world))
    cold = make_cache(**copy.deepcopy(world))
    reused = {"job": 0, "node": 0}
    for cycle in range(cycles):
        got, got_pipe, moved = _cycle(pooled, actions, tier_conf, cold=False)
        want, want_pipe, moved_cold = _cycle(cold, actions, tier_conf, cold=True)
        assert moved_cold[("job", "reused")] == moved_cold[("node", "reused")] == 0
        assert got == want, f"snapshot differs at cycle {cycle}"
        assert pooled.binder.binds == cold.binder.binds, f"binds, cycle {cycle}"
        assert pooled.evictor.evicts == cold.evictor.evicts, f"evicts, cycle {cycle}"
        assert got_pipe == want_pipe, f"pipelined, cycle {cycle}"
        reused["job"] += moved[("job", "reused")]
        reused["node"] += moved[("node", "reused")]
        apply = churn(cycle)
        for cache in (pooled, cold):
            apply(cache, dict(cold.binder.binds))
    return reused


def late_job(cycle):
    """One pending job arriving in queue ``q``."""
    pg = build_pod_group("ns", f"late{cycle}", 1, queue="q")
    pod = build_pod("ns", f"late{cycle}-t0", "", {"cpu": "1", "memory": "1Gi"},
                    group=f"late{cycle}")

    def apply(cache, _binds):
        cache.add_pod_group(copy.deepcopy(pg))
        cache.add_pod(copy.deepcopy(pod))

    return apply


@pytest.mark.parametrize("name", list(ACTION_LISTS))
def test_pool_matches_cold_snapshot(name):
    actions = [get_action(a.strip()) for a in ACTION_LISTS[name].split(",")]
    assert all(actions)
    reused = run_equivalence(actions, _world(), cycles=6, churn=_churn)
    # the pool engaged: untouched clones came back in later cycles
    assert reused["job"] > 0 and reused["node"] > 0


class _UnmarkedWrite(Action):
    """Test-only: writes to a node clone without marking it touched."""

    def name(self) -> str:
        return "test-unmarked-write"

    def execute(self, ssn) -> None:
        node = ssn.nodes.get("n5")
        if node is not None:
            node.idle.milli_cpu -= 1


def test_unmarked_write_is_caught():
    """A write outside the touched set reaches the next snapshot through
    the pool, and the equivalence harness sees it."""
    actions = [get_action("enqueue"), _UnmarkedWrite()]
    with pytest.raises(AssertionError, match="snapshot differs"):
        run_equivalence(actions, _world(), cycles=3,
                         churn=lambda step: lambda cache, binds: None)


class _Raise(Action):
    """Test-only: allocates through the session, then raises."""

    def name(self) -> str:
        return "test-raise"

    def execute(self, ssn) -> None:
        get_action("allocate").execute(ssn)
        raise RuntimeError("injected action failure")


def test_raising_session_hands_nothing_back(tmp_path, monkeypatch):
    """A cycle whose action raises hands no clone back: the next
    snapshot clones every object."""
    from volcano_tpu.framework import interface
    from volcano_tpu.scheduler.scheduler import Scheduler

    monkeypatch.setitem(interface._action_map, "test-raise", _Raise())
    conf = tmp_path / "conf.yaml"
    conf.write_text(
        'actions: "enqueue, test-raise"\n'
        "tiers:\n- plugins:\n  - name: priority\n  - name: gang\n"
        "- plugins:\n  - name: drf\n  - name: predicates\n"
        "  - name: proportion\n  - name: nodeorder\n"
    )
    cache = make_cache(**_world())
    sched = Scheduler(cache, scheduler_conf_path=str(conf), period=0.0)
    with pytest.raises(RuntimeError, match="injected"):
        sched.run_once()
    assert cache._pool_open
    before = _clone_counts()
    snap = cache.snapshot()
    after = _clone_counts()
    assert after[("job", "reused")] == before[("job", "reused")]
    assert after[("node", "reused")] == before[("node", "reused")]
    assert after[("job", "cloned")] - before[("job", "cloned")] == len(snap.jobs)
    assert after[("node", "cloned")] - before[("node", "cloned")] == len(snap.nodes)
