"""Device-derived scheduling explainability (ISSUE 4).

The acceptance contract: for a snapshot where a task is unschedulable,
the device-derived ``FitErrors.error()`` message is byte-identical to
the host path's message on the same snapshot; the synthesized errors
feed the existing Unschedulable event + pod-condition writeback
unchanged; and the surfaces (``/explain``, ``vtctl describe``, metrics,
trace summaries, the bus correlation id) all render from them.
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from volcano_tpu.actions.allocate import AllocateAction
from volcano_tpu.actions.backfill import BackfillAction
from volcano_tpu.actions import jax_allocate
from volcano_tpu.actions.jax_allocate import JaxAllocateAction
from volcano_tpu.api import FitError
from volcano_tpu.api import unschedule_info as reasons
from volcano_tpu.api.unschedule_info import (
    FitErrors,
    format_fit_errors,
    parse_fit_errors,
)
from volcano_tpu.apis import core, scheduling
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.client import APIServer, SchedulerClient
from volcano_tpu.framework import close_session, open_session

from tests.builders import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
)
from tests.scheduler_helpers import make_cache, run_actions, tiers

TIERS = tiers(
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)


# ---- unschedule_info unit surface ----


class TestFitErrorsFormat:
    def test_histogram_render_matches_per_node_render(self):
        per_node = FitErrors()
        per_node.set_node_error("n1", FitError("t", "n1", reasons.NODE_RESOURCE_FIT_FAILED))
        per_node.set_node_error("n2", FitError("t", "n2", reasons.NODE_TAINT_UNTOLERATED))
        per_node.set_node_error("n3", FitError("t", "n3", reasons.NODE_TAINT_UNTOLERATED))
        synthesized = FitErrors()
        synthesized.set_histogram(3, {
            reasons.NODE_RESOURCE_FIT_FAILED: 1,
            reasons.NODE_TAINT_UNTOLERATED: 2,
        })
        assert per_node.error() == synthesized.error()
        assert per_node.histogram() == synthesized.histogram()

    def test_parse_is_inverse_of_format(self):
        hist = {
            reasons.NODE_SELECTOR_MISMATCH: 4,
            reasons.NODE_POD_NUMBER_EXCEEDED: 2,
        }
        msg = format_fit_errors(6, hist)
        assert parse_fit_errors(msg) == (6, hist)

    def test_parse_rejects_non_aggregate_messages(self):
        assert parse_fit_errors("pod group is not ready, 3 Pending.") is None
        assert parse_fit_errors("") is None


# ---- the equivalence acceptance criterion ----


def _mixed_reason_objects():
    """One stuck task vs five nodes, each failing a DIFFERENT first
    predicate in host order: resource fit, pod count, unschedulable,
    selector, taint."""
    nodes = [
        # too small → resource fit (checked before everything else)
        build_node("n-small", {"cpu": "1", "memory": "1Gi"},
                   labels={"accel": "tpu"}),
        # roomy but zero pod slots → pod number exceeded
        build_node("n-full", {"cpu": "32", "memory": "32Gi", "pods": 0},
                   labels={"accel": "tpu"}),
        # cordoned → unschedulable
        build_node("n-cordon", {"cpu": "32", "memory": "32Gi"},
                   labels={"accel": "tpu"}, unschedulable=True),
        # missing the selector label → selector mismatch
        build_node("n-other", {"cpu": "32", "memory": "32Gi"}),
        # labeled but tainted → taint untolerated
        build_node(
            "n-taint", {"cpu": "32", "memory": "32Gi"},
            labels={"accel": "tpu"},
            taints=[core.Taint(key="dedicated", value="x",
                               effect="NoSchedule")],
        ),
    ]
    pods = [
        build_pod("ns", "stuck-0", "", {"cpu": "4", "memory": "4Gi"},
                  group="pg-stuck", selector={"accel": "tpu"}),
    ]
    pgs = [build_pod_group("ns", "pg-stuck", 1, queue="q1")]
    queues = [build_queue("q1", weight=1)]
    return nodes, pods, pgs, queues


def _fit_error_map(ssn):
    """(namespace/name) → (message, was_synthesized) over all jobs."""
    out = {}
    for job in ssn.jobs.values():
        for uid, fe in job.nodes_fit_errors.items():
            task = job.tasks[uid]
            out[f"{task.namespace}/{task.name}"] = (
                fe.error(), fe._histogram is not None
            )
    return out


def _run_capture(cache, actions, tier_conf):
    """Run the actions and capture the fit-error map BEFORE close_session
    empties the session maps."""
    ssn = open_session(cache, tier_conf, [])
    try:
        for action in actions:
            action.execute(ssn)
        return _fit_error_map(ssn)
    finally:
        close_session(ssn)


class TestDeviceHostEquivalence:
    def test_mixed_reasons_byte_identical(self):
        """The acceptance pin: five nodes, five distinct first-failure
        reasons — the device-synthesized message equals the host sweep's
        byte for byte, and the device path really synthesized (no host
        sweep ran for it)."""
        host = _run_capture(
            make_cache(*_mixed_reason_objects()), [AllocateAction()], TIERS
        )
        dev = _run_capture(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=True)],
            TIERS,
        )

        assert set(host) == set(dev) == {"ns/stuck-0"}
        host_msg, host_synth = host["ns/stuck-0"]
        dev_msg, dev_synth = dev["ns/stuck-0"]
        assert not host_synth and dev_synth
        assert dev_msg == host_msg
        # every reason plane shows up exactly once
        total, hist = parse_fit_errors(dev_msg)
        assert total == 5
        assert hist == {
            reasons.NODE_RESOURCE_FIT_FAILED: 1,
            reasons.NODE_POD_NUMBER_EXCEEDED: 1,
            reasons.NODE_UNSCHEDULABLE: 1,
            reasons.NODE_SELECTOR_MISMATCH: 1,
            reasons.NODE_TAINT_UNTOLERATED: 1,
        }

    def test_randomized_stuck_cluster_equivalence(self):
        """Label/taint-rich synthetic cluster where nothing fits: the
        device path's recorded messages equal the host path's for every
        task, across many tasks and mixed reasons."""
        from volcano_tpu.ops.synthetic import generate_cluster_objects

        def fresh():
            nodes, pods, pgs, queues = generate_cluster_objects(
                n_tasks=48, n_nodes=12, gang_size=4, seed=3,
                label_classes=3, taint_fraction=0.4,
                node_cpu_milli=100, node_mem_mib=64,  # nothing ever fits
            )
            cache = make_cache(nodes=nodes, pods=pods, pod_groups=pgs,
                               queues=queues)
            return cache

        host = _run_capture(fresh(), [AllocateAction()], TIERS)
        dev = _run_capture(fresh(), [JaxAllocateAction(explain=True)], TIERS)
        assert host and set(host) == set(dev)
        for key, (host_msg, _) in host.items():
            dev_msg, dev_synth = dev[key]
            assert dev_msg == host_msg, key
            assert dev_synth, key
        # one queue: ORDER lists every task whatever the queue's share,
        # each gang's failing task is explained at its step of the scan,
        # and nothing sweeps or needs the tail pack
        stats = jax_allocate.last_phase_stats
        assert stats["apply_host_sweep"] == 0
        assert stats["explain_tail_tasks"] == 0
        assert stats["apply_explained"] == len(host)

    def test_explain_off_still_records_via_host_sweep(self):
        fe_map = _run_capture(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=False)],
            TIERS,
        )
        msg, synth = fe_map["ns/stuck-0"]
        assert not synth and "0/5 nodes are available" in msg

    def test_synthesis_refused_after_placements_still_correct(self):
        """A placeable job ahead of the stuck one: placements touch node
        state, so counts taken at the pack would no longer hold; the
        stuck task's counts were taken at its step of the scan, after
        the placement, and synthesize the host sweep's message."""
        def objects():
            nodes, pods, pgs, queues = _mixed_reason_objects()
            pods = pods + [
                build_pod("ns", "easy-0", "", {"cpu": "1", "memory": "1Gi"},
                          group="pg-easy"),
            ]
            pgs = pgs + [build_pod_group("ns", "pg-easy", 1, queue="q1")]
            return nodes, pods, pgs, queues

        host_cache, cache = make_cache(*objects()), make_cache(*objects())
        host = _run_capture(host_cache, [AllocateAction()], TIERS)
        fe_map = _run_capture(cache, [JaxAllocateAction(explain=True)], TIERS)
        assert cache.binder.binds == host_cache.binder.binds  # the easy pod placed
        assert cache.binder.binds
        msg, synth = fe_map["ns/stuck-0"]
        assert synth and jax_allocate.last_phase_stats["apply_host_sweep"] == 0
        assert msg == host["ns/stuck-0"][0]
        assert parse_fit_errors(msg) is not None

    def test_plane_retention_attributes_per_node(self):
        ssn = run_actions(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=True, explain_planes=True)],
            TIERS,
        )
        from volcano_tpu.ops.explain import last_explain

        info = last_explain()
        assert info is not None and len(info["tasks"]) == 1
        (detail,) = info["tasks"].values()
        assert detail["nodes"] == {
            "n-small": reasons.NODE_RESOURCE_FIT_FAILED,
            "n-full": reasons.NODE_POD_NUMBER_EXCEEDED,
            "n-cordon": reasons.NODE_UNSCHEDULABLE,
            "n-other": reasons.NODE_SELECTOR_MISMATCH,
            "n-taint": reasons.NODE_TAINT_UNTOLERATED,
        }

    def test_pressure_predicates_close_the_synthesis_gate(self):
        """Opt-in pressure predicates insert host failure reasons the
        device planes cannot see — synthesis must refuse and take the
        host sweep (still correct messages, just not device-derived)."""
        from volcano_tpu.conf import PluginOption, Tier
        from volcano_tpu.framework.arguments import Arguments

        pressure_tiers = [
            Tier(plugins=[
                PluginOption(name=n)
                for n in ("priority", "gang", "conformance")
            ]),
            Tier(plugins=[
                PluginOption(
                    name="predicates",
                    arguments=Arguments(
                        {"predicate.MemoryPressureEnable": "true"}
                    ),
                ),
                *[PluginOption(name=n)
                  for n in ("drf", "proportion", "nodeorder", "binpack")],
            ]),
        ]
        fe_map = _run_capture(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=True)],
            pressure_tiers,
        )
        msg, synth = fe_map["ns/stuck-0"]
        assert not synth  # gate closed
        assert parse_fit_errors(msg) is not None

    def test_stale_last_explain_cleared(self):
        """A later cycle with nothing to explain clears the /explain
        surface instead of serving the previous cycle's explanation."""
        from volcano_tpu.ops.explain import last_explain

        run_actions(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=True)],
            TIERS,
        )
        assert last_explain() is not None
        # a cycle where everything places
        run_actions(
            make_cache(
                nodes=[build_node("n1", {"cpu": "8", "memory": "8Gi"})],
                pods=[build_pod("ns", "easy-0", "",
                                {"cpu": "1", "memory": "1Gi"}, group="pg1")],
                pod_groups=[build_pod_group("ns", "pg1", 1, queue="q1")],
                queues=[build_queue("q1", weight=1)],
            ),
            [JaxAllocateAction(explain=True)],
            TIERS,
        )
        assert last_explain() is None

    def test_reason_metric_label_cardinality_bounded(self):
        from volcano_tpu.metrics import metrics

        metrics.registry.reset()
        metrics.register_unschedulable_reason(
            'persistentvolumeclaim "ns/claim-42" not found'
        )
        metrics.register_unschedulable_reason(
            'persistentvolumeclaim "ns/claim-43" not found'
        )
        metrics.register_unschedulable_reason(reasons.NODE_NOT_READY)
        text = metrics.registry.render()
        assert 'volcano_unschedulable_task_reasons{reason="other"} 2' in text
        assert "claim-42" not in text

    def test_unschedulable_reason_metric_recorded(self):
        from volcano_tpu.metrics import metrics

        metrics.registry.reset()
        run_actions(
            make_cache(*_mixed_reason_objects()),
            [JaxAllocateAction(explain=True)],
            TIERS,
        )
        text = metrics.registry.render()
        assert (
            'volcano_unschedulable_task_reasons{reason="'
            + reasons.NODE_TAINT_UNTOLERATED + '"} 1'
        ) in text
        # the reasons were counted on the device, at the task's step of
        # the allocate scan: no separate reduction ran
        assert 'volcano_allocate_apply_tasks_total{path="explained"}' in text
        assert "volcano_explain_latency_milliseconds_count" not in text


# ---- the tail: pending tasks ORDER's share cut leaves out ----


def _preemption_basic_mini(extra_nodes=(), extra_pods=(), gang=1):
    """kube-scheduler's ``PreemptionBasic`` in miniature: 12 nodes of 4
    cpu, each holding four Running priority-1 pods of 900m, and 12
    pending priority-10 pods of 3000m in queue ``default``, every pod a
    job of its own.  ORDER's every-task-places simulation finds the
    queue over its deserved 48 cpu after 2 of them (43.2 + 2 × 3), so it
    lists 2; APPLY, where nothing places, reaches all 12.  With
    ``gang`` > 1 the pending pods are jobs of ``gang`` pods (minMember
    ``gang``) and ORDER lists every one.  Extra pods come after the
    others, each a job of its own."""
    nodes = [build_node(f"n{i:02d}", {"cpu": "4", "memory": "32Gi"})
             for i in range(12)]
    nodes += list(extra_nodes)
    pods = []
    for i in range(12):
        pods += [
            build_pod("ns", f"low-{i:02d}-{k}", f"n{i:02d}",
                      {"cpu": "900m", "memory": "500Mi"}, phase="Running",
                      group=f"low-{i:02d}-{k}", priority=1)
            for k in range(4)
        ]
    high = [
        build_pod("ns", f"high-{i:02d}", "", {"cpu": "3", "memory": "500Mi"},
                  group=f"high-{i // gang:02d}", priority=10)
        for i in range(12)
    ]
    pgs = [build_pod_group("ns", p.metadata.name, 1, queue="default")
           for p in pods]
    pgs += [build_pod_group("ns", f"high-{j:02d}", gang, queue="default")
            for j in range(12 // gang)]
    pgs += [build_pod_group("ns", p.metadata.name, 1, queue="default")
            for p in extra_pods]
    return nodes, pods + high + list(extra_pods), pgs, [build_queue("default", weight=1)]


def _tail_counter() -> float:
    from volcano_tpu.metrics import metrics

    key = ("volcano_allocate_explain_tail_tasks_total", ())
    with metrics.registry._lock:
        return metrics.registry._counters.get(key, 0.0)


@pytest.fixture
def recorder():
    from volcano_tpu import trace

    rec = trace.TraceRecorder()
    trace.set_recorder(rec)
    rec.begin_cycle()
    try:
        yield rec
    finally:
        trace.set_recorder(None)


def _tail_spans(rec):
    rec.end_cycle()
    return [e for e in rec.last_cycle()["events"]
            if e["name"] == "vtpu/allocate.explain_tail"]


def _host_and_device(objects):
    """(host fit errors, host binds, device fit errors, device binds)
    for the same cluster under ``AllocateAction`` and jax-allocate."""
    host_cache, dev_cache = make_cache(*objects()), make_cache(*objects())
    host = _run_capture(host_cache, [AllocateAction()], TIERS)
    dev = _run_capture(dev_cache, [JaxAllocateAction(explain=True)], TIERS)
    return host, host_cache.binder.binds, dev, dev_cache.binder.binds


class TestTailExplanation:
    def test_share_cut_tail_explained_on_device(self, recorder):
        """Every preemptor's FitErrors synthesized, byte-equal to the
        host action's, and not one host sweep: ORDER's 2 rows from the
        primary pack, the other 10 from the tail's."""
        before = _tail_counter()
        host, host_binds, dev, dev_binds = _host_and_device(_preemption_basic_mini)
        stats = jax_allocate.last_phase_stats

        assert host_binds == dev_binds == {}
        assert len(host) == 12 and set(host) == set(dev)
        for key, (host_msg, _) in host.items():
            assert dev[key] == (host_msg, True), key
        assert parse_fit_errors(host["ns/high-00"][0]) == (
            12, {reasons.NODE_RESOURCE_FIT_FAILED: 12})
        assert {k: stats[k] for k in ("apply_host_sweep", "apply_explained",
                                      "explain_tail_tasks")} == {
            "apply_host_sweep": 0, "apply_explained": 12, "explain_tail_tasks": 10}
        assert stats["explain_tail_ms"] > 0
        assert _tail_counter() - before == 10
        (span,) = _tail_spans(recorder)
        assert span["args"]["tasks"] == 10

    def test_single_queue_gangs_order_lists_every_task_builds_no_tail(self, recorder):
        """One queue of gangs: ORDER lists every preemptor whatever the
        queue's share, the kernel counts each gang's failing task's
        reasons at its step, and no tail is built and nothing sweeps."""
        before = _tail_counter()
        host, host_binds, dev, dev_binds = _host_and_device(
            lambda: _preemption_basic_mini(gang=2))
        stats = jax_allocate.last_phase_stats
        assert host_binds == dev_binds == {}
        assert len(host) == 6 and dev == {k: (msg, True) for k, (msg, _) in host.items()}
        assert {k: stats[k] for k in ("apply_host_sweep", "apply_explained",
                                      "explain_tail_tasks", "gang_discarded",
                                      "gang_outcomes_device")} == {
            "apply_host_sweep": 0, "apply_explained": 6, "explain_tail_tasks": 0,
            "gang_discarded": 6, "gang_outcomes_device": 6}
        assert _tail_counter() == before
        assert _tail_spans(recorder) == []

    def test_order_lists_every_task_builds_no_tail(self, recorder):
        """No share cut: every pending task is an ORDER row, so the tail
        is never built."""
        before = _tail_counter()
        host, _, dev, _ = _host_and_device(_mixed_reason_objects)
        assert dev == {k: (msg, True) for k, (msg, _) in host.items()}
        stats = jax_allocate.last_phase_stats
        assert stats["explain_tail_tasks"] == 0 and stats["explain_tail_ms"] == 0.0
        assert stats["apply_host_sweep"] == 0
        assert _tail_counter() == before
        assert _tail_spans(recorder) == []

    def test_placement_ahead_closes_the_tail_gate(self, recorder):
        """A placeable pod ahead of the stuck ones places first, so the
        epoch has moved by the first task ORDER did not list: no tail is
        built, the stuck tasks take the host sweep, and the binds equal
        the host action's."""
        def objects():
            nodes, pods, pgs, queues = _preemption_basic_mini(
                extra_nodes=[build_node("n-roomy", {"cpu": "4", "memory": "32Gi"})])
            easy = build_pod("ns", "easy", "", {"cpu": "1", "memory": "500Mi"},
                             group="easy", priority=10)
            return nodes, [easy] + pods, [build_pod_group("ns", "easy", 1)] + pgs, queues

        host, host_binds, dev, dev_binds = _host_and_device(objects)
        stats = jax_allocate.last_phase_stats
        assert dev_binds == host_binds and "ns/easy" in dev_binds
        assert set(host) == set(dev)
        for key, (host_msg, _) in host.items():
            assert dev[key][0] == host_msg, key
        assert stats["explain_tail_tasks"] == 0
        assert stats["apply_host_sweep"] > 0
        assert _tail_spans(recorder) == []

    def test_tail_task_that_fits_is_swept_and_placed(self):
        """A task beyond ORDER's cut that fits a node: the tail's counts
        do not prove it infeasible, so it takes the host sweep and places
        as under the host action; the tasks after it find the gate
        closed and are swept too."""
        def objects():
            roomy = build_node("n-roomy", {"cpu": "4", "memory": "32Gi"})
            fillers = [
                build_pod("ns", f"low-roomy-{k}", "n-roomy",
                          {"cpu": "900m", "memory": "500Mi"}, phase="Running",
                          group=f"low-roomy-{k}", priority=1)
                for k in range(3)
            ]
            small = build_pod("ns", "small", "", {"cpu": "500m", "memory": "500Mi"},
                              group="small", priority=10)
            return _preemption_basic_mini(extra_nodes=[roomy],
                                          extra_pods=fillers + [small])

        host, host_binds, dev, dev_binds = _host_and_device(objects)
        stats = jax_allocate.last_phase_stats
        assert dev_binds == host_binds == {"ns/small": "n-roomy"}
        assert set(host) == set(dev)
        for key, (host_msg, _) in host.items():
            assert dev[key][0] == host_msg, key
        assert stats["explain_tail_tasks"] == 10  # 9 preemptors and ``small``
        assert stats["apply_host_sweep"] >= 1
        assert stats["apply_explained"] + stats["apply_host_sweep"] == 13

    def test_plane_retention_attributes_tail_nodes(self):
        """``explain_planes`` with a tail: the /explain surface names
        every node for the tail's tasks as for ORDER's."""
        from volcano_tpu.ops.explain import last_explain

        run_actions(
            make_cache(*_preemption_basic_mini()),
            [JaxAllocateAction(explain=True, explain_planes=True)],
            TIERS,
        )
        assert jax_allocate.last_phase_stats["explain_tail_tasks"] == 10
        info = last_explain()
        assert info is not None and len(info["tasks"]) == 12
        every_node = {f"n{i:02d}": reasons.NODE_RESOURCE_FIT_FAILED for i in range(12)}
        for detail in info["tasks"].values():
            assert detail["nodes"] == every_node


# ---- no-victim preempt/reclaim explanations ----


class TestNoVictimExplain:
    def test_jax_preempt_no_victim_synthesizes(self):
        from volcano_tpu.actions.jax_preempt import JaxPreemptAction

        cache = make_cache(
            nodes=[build_node("n1", {"cpu": "4", "memory": "4Gi"})],
            pods=[
                build_pod("ns", "victim", "n1", {"cpu": "2", "memory": "2Gi"},
                          phase="Running", group="pg1", priority=0),
                # can never fit, even with every victim evicted
                build_pod("ns", "preemptor", "", {"cpu": "8", "memory": "2Gi"},
                          group="pg2", priority=10),
            ],
            pod_groups=[
                build_pod_group("ns", "pg1", 1, queue="q1"),
                build_pod_group("ns", "pg2", 1, queue="q1"),
            ],
            queues=[build_queue("q1", weight=1)],
        )
        fe_map = _run_capture(cache, [JaxPreemptAction()], TIERS)
        assert cache.evictor.evicts == []
        msg, synth = fe_map["ns/preemptor"]
        assert synth
        assert msg == format_fit_errors(
            1, {reasons.NODE_RESOURCE_FIT_FAILED: 1}
        )


# ---- events + pod conditions writeback (satellite 3) ----


def _writeback_cluster():
    """Cache wired to a real API server so the status writeback records
    Events and pod conditions; one tainted node, one intolerant task."""
    api = APIServer()
    node = build_node(
        "n1", {"cpu": "8", "memory": "8Gi"},
        taints=[core.Taint(key="dedicated", value="x", effect="NoSchedule")],
    )
    pod = build_pod("ns", "pg1-stuck-0", "",
                    {"cpu": "1", "memory": "1Gi"}, group="pg1")
    pg = build_pod_group("ns", "pg1", 1, queue="q1")
    queue = build_queue("q1", weight=1)
    for obj in (node, pod, pg, queue):
        api.create(obj)
    cache = SchedulerCache(client=SchedulerClient(api))
    cache.add_node(node)
    cache.add_pod(pod)
    cache.add_pod_group(pg)
    cache.add_queue(queue)
    return api, cache


EXPECTED_TAINT_MESSAGE = format_fit_errors(
    1, {reasons.NODE_TAINT_UNTOLERATED: 1}
)


class TestUnschedulableWriteback:
    @pytest.mark.parametrize("action_cls", [AllocateAction, JaxAllocateAction])
    def test_one_event_and_condition_per_cycle(self, action_cls):
        api, cache = _writeback_cluster()
        run_actions(cache, [action_cls()], TIERS)

        events = [
            e for e in api.list("Event", "ns")
            if e.reason == "Unschedulable"
        ]
        assert len(events) == 1
        (ev,) = events
        assert ev.type == "Warning" and ev.count == 1
        assert ev.message == EXPECTED_TAINT_MESSAGE
        assert ev.involved_object["name"] == "pg1-stuck-0"

        pod = api.get("Pod", "ns", "pg1-stuck-0")
        conds = [c for c in pod.status.conditions if c.type == "PodScheduled"]
        assert len(conds) == 1
        assert conds[0].status == "False"
        assert conds[0].reason == "Unschedulable"
        assert conds[0].message == EXPECTED_TAINT_MESSAGE

        # a second identical stuck cycle must NOT duplicate anything:
        # the pod-group status is unchanged, so the writeback gate
        # (is_pod_group_status_updated) suppresses a re-record — still
        # exactly one Event row, count untouched, one condition
        run_actions(cache, [action_cls()], TIERS)
        events = [
            e for e in api.list("Event", "ns")
            if e.reason == "Unschedulable"
        ]
        assert len(events) == 1 and events[0].count == 1
        pod = api.get("Pod", "ns", "pg1-stuck-0")
        assert len([c for c in pod.status.conditions
                    if c.type == "PodScheduled"]) == 1

    def test_unschedulable_digest_parked_and_cleared(self):
        api, cache = _writeback_cluster()
        run_actions(cache, [AllocateAction()], TIERS)
        assert len(cache.unschedulable_digest) == 1
        (digest,) = cache.unschedulable_digest.values()
        assert digest["name"] == "pg1" and digest["namespace"] == "ns"
        (task,) = digest["tasks"].values()
        assert task["message"] == EXPECTED_TAINT_MESSAGE

        # untaint the node → task schedules → digest clears
        node = build_node("n2", {"cpu": "8", "memory": "8Gi"})
        api.create(node)
        cache.add_node(node)
        run_actions(cache, [AllocateAction()], TIERS)
        assert cache.unschedulable_digest == {}


# ---- cache event client handling (satellite 2) ----


class TestRecordEventClients:
    def test_remote_api_server_records_events_over_bus(self):
        from volcano_tpu.bus import BusServer, RemoteAPIServer

        api = APIServer()
        server = BusServer(api).start()
        client = RemoteAPIServer(f"tcp://127.0.0.1:{server.port}", timeout=5)
        try:
            assert client.wait_ready(5)
            involved = {"kind": "Pod", "namespace": "ns", "name": "p1"}
            client.record_event("ns", involved, "Warning", "Unschedulable", "m1")
            client.record_event("ns", involved, "Warning", "Unschedulable", "m2")
            events = api.list("Event", "ns")
            assert len(events) == 1
            assert events[0].count == 2 and events[0].message == "m2"

            # the cache path accepts a bare RemoteAPIServer as client
            cache = SchedulerCache(client=client)
            task = next(
                iter(
                    make_cache(
                        pods=[build_pod("ns", "p1", "", {"cpu": "1"},
                                        group="pg1")],
                        pod_groups=[build_pod_group("ns", "pg1", 1)],
                    ).jobs.values()
                )
            ).tasks
            cache._record_event(
                next(iter(task.values())), "Warning", "FailedScheduling", "x"
            )
            assert any(
                e.reason == "FailedScheduling" for e in api.list("Event", "ns")
            )
        finally:
            client.close()
            server.stop()

    def test_capability_less_client_warns_once(self, caplog):
        class NoEvents:
            pass

        cache = SchedulerCache(client=NoEvents())
        pod = build_pod("ns", "p1", "", {"cpu": "1"}, group="pg1")
        cache.add_pod(pod)
        task = next(iter(next(iter(cache.jobs.values())).tasks.values()))
        with caplog.at_level("WARNING"):
            cache._record_event(task, "Warning", "Unschedulable", "m")
            cache._record_event(task, "Warning", "Unschedulable", "m")
        warnings = [
            r for r in caplog.records if "cannot record events" in r.message
        ]
        assert len(warnings) == 1


# ---- backfill reason propagation (satellite 1) ----


class TestBackfillReasons:
    def test_allocate_fit_error_keeps_bare_reasons(self, monkeypatch):
        cache = make_cache(
            nodes=[build_node("n1", {"cpu": "2", "memory": "2Gi"})],
            pods=[build_pod("ns", "be-0", "", {}, group="pg1")],
            pod_groups=[build_pod_group("ns", "pg1", 1, queue="q1")],
            queues=[build_queue("q1", weight=1)],
        )
        ssn = open_session(cache, TIERS, [])
        try:
            def boom(task, hostname):
                raise FitError(task, ssn.nodes[hostname],
                               reasons.NODE_PORT_CONFLICT)

            monkeypatch.setattr(ssn, "allocate", boom)
            BackfillAction().execute(ssn)
            (job,) = [j for j in ssn.jobs.values() if j.nodes_fit_errors]
            (fe,) = job.nodes_fit_errors.values()
            # the bare reason — not "task X on node Y: ..." — lands in
            # the histogram
            assert fe.histogram() == {reasons.NODE_PORT_CONFLICT: 1}
            assert fe.error() == format_fit_errors(
                1, {reasons.NODE_PORT_CONFLICT: 1}
            )
        finally:
            close_session(ssn)


# ---- dispatch plumbing ----


class TestExplainPlumbing:
    def _stuck_snapshot(self):
        from volcano_tpu.ops.synthetic import generate_snapshot

        snap = generate_snapshot(n_tasks=32, n_nodes=8, gang_size=4, seed=5)
        snap.task_resreq[:, 0] = 1e9  # nothing fits anywhere
        return snap

    def test_executor_counts_lazy(self):
        from volcano_tpu.ops import dispatch
        from volcano_tpu.ops.synthetic import generate_snapshot

        placed = generate_snapshot(n_tasks=16, n_nodes=8, gang_size=4, seed=0)
        dispatch.run_allocate(placed, explain=True)
        assert dispatch.last_explain_counts() is None  # everything placed
        assert dispatch.last_explain_ms() is None

        snap = self._stuck_snapshot()
        outcome = dispatch.run_allocate(snap, explain=True)
        counts = dispatch.last_explain_counts()
        # counted at each failing step of the scan: every gang's first
        # task fails and ends it, its other tasks are never tried
        assert dispatch.last_explain_at_step()
        failed = outcome.placed == -1
        assert counts is not None and counts.shape == (snap.n_tasks, 8)
        assert failed.any() and (counts[failed].sum(axis=1) == snap.n_nodes).all()
        assert (counts[~failed] == 0).all()
        assert (outcome.placed[~failed] == -2).all()

        # without the flag nothing is reduced, and nothing stale is kept
        dispatch.run_allocate(snap, explain=False)
        assert dispatch.last_explain_counts() is None

    def test_task_row_subset_matches_full(self):
        from volcano_tpu.ops.explain import run_explain
        from volcano_tpu.ops.synthetic import generate_snapshot

        snap = generate_snapshot(n_tasks=32, n_nodes=8, gang_size=4, seed=7)
        snap.task_resreq[::3, 0] = 1e9
        full = run_explain(snap)
        rows = np.arange(0, snap.n_tasks, 3)
        subset = run_explain(snap, task_rows=rows)
        assert np.array_equal(full.counts[rows], subset.counts[rows])
        off_rows = np.setdiff1d(np.arange(snap.n_tasks), rows)
        assert (subset.counts[off_rows] == 0).all()


# ---- /explain endpoint ----


class TestExplainEndpoint:
    def test_endpoint_serves_digest(self):
        from volcano_tpu.serving.explain import explain_jobs
        from volcano_tpu.serving.http import ServingServer

        api, cache = _writeback_cluster()
        run_actions(cache, [JaxAllocateAction(explain=True)], TIERS)

        server = ServingServer(
            port=0,
            explain_source=lambda ns, job: explain_jobs(cache, ns, job),
        ).start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/explain"
            ) as resp:
                data = json.loads(resp.read())
            assert len(data["jobs"]) == 1
            (job,) = data["jobs"]
            assert job["name"] == "pg1"
            (task,) = job["unschedulable"]
            assert task["message"] == EXPECTED_TAINT_MESSAGE
            assert task["reasons"] == {reasons.NODE_TAINT_UNTOLERATED: 1}
            assert data["last_cycle"]["reasons"] == {
                reasons.NODE_TAINT_UNTOLERATED: 1
            }

            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/explain?namespace=ns&job=pg1"
            ) as resp:
                assert json.loads(resp.read())["jobs"]

            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/explain?job=missing"
                )
            assert e.value.code == 404
            e.value.close()  # the HTTPError holds the response socket
        finally:
            server.stop()

    def test_endpoint_404_without_source(self):
        from volcano_tpu.serving.http import ServingServer

        server = ServingServer(port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/explain"
                )
            assert e.value.code == 404
            e.value.close()  # the HTTPError holds the response socket
        finally:
            server.stop()


# ---- vtctl describe over both backends (acceptance) ----


def _scheduled_api_with_stuck_job():
    """Drive a REAL scheduling cycle against an API server so the
    Unschedulable event/condition/podgroup-condition exist, then
    describe through it."""
    from volcano_tpu.apis import batch

    api, cache = _writeback_cluster()
    # a vcjob whose name matches the podgroup, as the job controller
    # lays them out, so `describe job` joins them
    job = batch.Job(
        metadata=core.ObjectMeta(name="pg1", namespace="ns"),
        spec=batch.JobSpec(
            min_available=1, queue="q1",
            tasks=[batch.TaskSpec(name="stuck", replicas=1)],
        ),
    )
    api.create(job)
    run_actions(cache, [JaxAllocateAction(explain=True)], TIERS)
    return api


class TestVtctlDescribe:
    def _run(self, argv, api):
        import io

        from volcano_tpu.cli.vtctl import main

        out = io.StringIO()
        rc = main(argv, api=api, out=out)
        return rc, out.getvalue()

    def test_describe_podgroup_in_process(self):
        api = _scheduled_api_with_stuck_job()
        rc, text = self._run(
            ["describe", "podgroup", "-N", "pg1", "-n", "ns"], api
        )
        assert rc == 0
        assert "Unschedulable" in text
        assert EXPECTED_TAINT_MESSAGE in text
        assert f"1       {reasons.NODE_TAINT_UNTOLERATED}" in text

    def test_describe_job_both_backends(self):
        from volcano_tpu.bus import BusServer

        api = _scheduled_api_with_stuck_job()
        rc, local = self._run(["describe", "job", "-N", "pg1", "-n", "ns"], api)
        assert rc == 0
        assert "Unschedulable" in local  # the Event row
        assert EXPECTED_TAINT_MESSAGE in local

        server = BusServer(api).start()
        try:
            from volcano_tpu.cli.vtctl import main

            import io

            out = io.StringIO()
            rc = main(
                ["--bus", f"tcp://127.0.0.1:{server.port}",
                 "describe", "job", "-N", "pg1", "-n", "ns"],
                out=out,
            )
            remote = out.getvalue()
            assert rc == 0
            assert remote == local  # byte-identical over the bus
        finally:
            server.stop()

    def test_describe_missing(self):
        rc, text = self._run(
            ["describe", "job", "-N", "nope", "-n", "ns"], APIServer()
        )
        assert rc == 1 and "not found" in text


# ---- trace journal + cross-process correlation ----


class TestExplainTrace:
    def test_explain_summary_journaled(self, tmp_path):
        from volcano_tpu import trace

        rec = trace.enable(str(tmp_path / "journal"), snapshot_every=0)
        try:
            cid = rec.begin_cycle()
            run_actions(
                make_cache(*_mixed_reason_objects()),
                [JaxAllocateAction(explain=True)],
                TIERS,
            )
            rec.end_cycle(duration_s=0.01)
            record = rec.journal.read_cycle(cid)
            (summary,) = [
                e for e in record["events"] if e["name"] == "explain-summary"
            ]
            assert summary["args"]["tasks"] == 1
            assert summary["args"]["reasons"] == {
                reasons.NODE_RESOURCE_FIT_FAILED: 1,
                reasons.NODE_POD_NUMBER_EXCEEDED: 1,
                reasons.NODE_UNSCHEDULABLE: 1,
                reasons.NODE_SELECTOR_MISMATCH: 1,
                reasons.NODE_TAINT_UNTOLERATED: 1,
            }
        finally:
            trace.disable()

    def test_scheduler_sets_cycle_correlation_id(self):
        from volcano_tpu import trace
        from volcano_tpu.scheduler.scheduler import Scheduler

        cache = make_cache(queues=[build_queue("q1", weight=1)])
        sched = Scheduler(cache)
        sched.run_once()
        first = trace.current_cycle()
        sched.run_once()
        assert trace.current_cycle() == first + 1

    def test_bus_request_carries_cycle_id(self):
        from volcano_tpu import trace
        from volcano_tpu.bus import BusServer, RemoteAPIServer

        api = APIServer()
        server = BusServer(api).start()
        client = RemoteAPIServer(f"tcp://127.0.0.1:{server.port}", timeout=5)
        rec = trace.TraceRecorder()
        trace.set_recorder(rec)
        try:
            assert client.wait_ready(5)
            trace.set_current_cycle(41)
            rec.begin_cycle()
            client.create(build_queue("qx", weight=1))
            rec.end_cycle()
            events = [
                e for e in rec.last_cycle()["events"]
                if e["name"] == "bus:create"
            ]
            assert events and events[0]["args"]["cycle"] == 41
        finally:
            trace.set_current_cycle(-1)
            trace.disable()
            client.close()
            server.stop()
