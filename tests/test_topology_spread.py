"""Hard pod topology spread (PodTopologySpread, ``DoNotSchedule``): the
host filter against a brute-force count of the k8s v1.30 semantics, the
device formulations against the host action, the explanation of a
spread-stuck task, and the paths that stay on the host."""

from __future__ import annotations

import functools
import random

import numpy as np
import pytest

from volcano_tpu.actions import jax_allocate
from volcano_tpu.actions.allocate import AllocateAction
from volcano_tpu.actions.jax_allocate import compute_task_order, JaxAllocateAction
from volcano_tpu.api import FitError, TaskStatus
from volcano_tpu.api import unschedule_info as reasons
from volcano_tpu.apis import core
from volcano_tpu.framework import close_session, open_session

from tests.builders import (
    build_node,
    build_pod,
    build_pod_group,
    build_priority_class,
    build_queue,
)
from tests.scheduler_helpers import make_cache, run_actions, tiers

ZONE = "topology.kubernetes.io/zone"
TIERS = lambda: tiers(
    ["priority", "gang"], ["drf", "predicates", "proportion", "nodeorder", "binpack"]
)


def spread(max_skew, app, key=ZONE, when="DoNotSchedule", **kw):
    return core.TopologySpreadConstraint(
        max_skew=max_skew, topology_key=key, when_unsatisfiable=when,
        label_selector={"matchLabels": {"app": app}}, **kw,
    )


TAINT = core.Taint(key="dedicated", value="batch", effect="NoSchedule")
TOLERATE = core.Toleration(key="dedicated", operator="Equal", value="batch", effect="NoSchedule")


def cluster(seed, n_nodes=12, n_pending=24, policies=False):
    """Three zones and a node without the key; ssd nodes for a node
    selector that narrows the eligible domains; matching pods already
    placed, in the pods' namespace and in another; pending pods of two
    apps under maxSkew 1 and 5, some constrained twice (six classes).
    ``policies`` taints every fourth node; the web pods tolerate it and
    count on it (``nodeTaintsPolicy: Honor``), the db pods count
    regardless of their node selector (``nodeAffinityPolicy: Ignore``)."""
    rng = random.Random(seed)
    nodes = []
    for i in range(n_nodes):
        labels = {} if i == 3 else {ZONE: f"z{rng.randrange(3)}"}
        if i % 3 == 0:
            labels["disk"] = "ssd"
        labels["kubernetes.io/hostname"] = f"n{i:02d}"
        taints = [TAINT] if policies and i % 4 == 1 else []
        nodes.append(build_node(f"n{i:02d}", {"cpu": "4", "memory": "8Gi"},
                                labels=labels, taints=taints))
    pods, pgs = [], []

    def job(ns, name, node, labels, cons=(), selector=None, phase="Pending"):
        p = build_pod(ns, name, node, {"cpu": "500m", "memory": "512Mi"}, phase=phase,
                      labels=labels, group=f"pg-{name}", selector=selector)
        p.spec.topology_spread_constraints = list(cons)
        pods.append(p)
        pgs.append(build_pod_group(ns, f"pg-{name}", 1, queue="q"))

    for i in range(5):
        job("ns", f"run{i}", f"n{rng.randrange(n_nodes):02d}",
            {"app": rng.choice(["web", "db"])}, phase="Running")
    job("other", "foreign", "n00", {"app": "web"}, phase="Running")
    for i in range(n_pending):
        app = "web" if i % 3 else "db"
        cons = [spread(1 if app == "web" else 5, app)]
        ssd = i % 7 == 0
        if i % 5 == 4 and app == "web" and not ssd:
            cons.append(spread(2, app, key="kubernetes.io/hostname"))
        if policies:
            for c in cons:
                if app == "web":
                    c.node_taints_policy = "Honor"
                else:
                    c.node_affinity_policy = "Ignore"
        job("ns", f"p{i:02d}", "", {"app": app}, cons,
            selector={"disk": "ssd"} if ssd else None)
        if policies and app == "web":
            pods[-1].spec.tolerations = [TOLERATE]
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])


def brute_force(task, node, nodes) -> str:
    """The first failing predicate of the ones these clusters exercise —
    node selector, taints, then each hard constraint in order — by
    counting every pod of every node afresh."""
    pod = task.pod

    def selected(n):
        return all(n.node.metadata.labels.get(k) == v for k, v in pod.spec.node_selector.items())

    def tolerated(n):
        return not n.node.spec.taints or TOLERATE in pod.spec.tolerations

    labels = node.node.metadata.labels
    if not selected(node):
        return reasons.NODE_SELECTOR_MISMATCH
    if not tolerated(node):
        return reasons.NODE_TAINT_UNTOLERATED
    hard = [c for c in pod.spec.topology_spread_constraints
            if c.when_unsatisfiable == "DoNotSchedule"]
    keys = {c.topology_key for c in hard}

    def matches(c, p):
        return all(p.metadata.labels.get(k) == v
                   for k, v in c.label_selector["matchLabels"].items())

    for c in hard:
        if c.topology_key not in labels:
            return reasons.SPREAD_LABEL_MISSING
        counts = {}
        for m in nodes.values():
            ml = m.node.metadata.labels
            if not keys <= set(ml):
                continue
            if c.node_affinity_policy != "Ignore" and not selected(m):
                continue
            if c.node_taints_policy == "Honor" and not tolerated(m):
                continue
            counts.setdefault(ml[c.topology_key], 0)
            counts[ml[c.topology_key]] += sum(
                1 for t in m.tasks.values()
                if t.status != TaskStatus.Releasing
                and t.pod.metadata.namespace == pod.metadata.namespace
                and matches(c, t.pod)
            )
        least = min(counts.values()) if counts else float("inf")
        if counts.get(labels[c.topology_key], 0) + matches(c, pod) - least > c.max_skew:
            return reasons.SPREAD_CONSTRAINTS_MISMATCH
    return ""


def host_reason(ssn, task, node) -> str:
    try:
        ssn.predicate_fn(task, node)
    except FitError as e:
        return e.reasons[0]
    return ""


def pending(ssn):
    return sorted(
        (t for j in ssn.jobs.values() for t in j.tasks.values()
         if t.status == TaskStatus.Pending),
        key=lambda t: t.uid,
    )


def assert_filter_matches_brute_force(ssn):
    checked = 0
    for task in pending(ssn):
        for node in ssn.nodes.values():
            want = brute_force(task, node, ssn.nodes)
            assert host_reason(ssn, task, node) == want, (task.name, node.name)
            checked += bool(want)
    return checked


@pytest.mark.parametrize("policies", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_host_filter_matches_brute_force(seed, policies):
    """At session open, after placements (the allocate events), after an
    eviction (a Releasing pod stops counting), and after the statement's
    discard unwinds them all."""
    ssn = open_session(make_cache(**cluster(seed, policies=policies)), TIERS(), [])
    try:
        assert assert_filter_matches_brute_force(ssn)
        stmt = ssn.statement()
        for task in pending(ssn)[:8]:
            fits = [n for n in sorted(ssn.nodes) if not brute_force(task, ssn.nodes[n], ssn.nodes)]
            if fits:
                stmt.allocate(task, fits[-1])
        running = next(t for j in ssn.jobs.values() for t in j.tasks.values()
                       if t.status == TaskStatus.Running and t.namespace == "ns")
        stmt.evict(running, "test")
        assert_filter_matches_brute_force(ssn)
        stmt.discard()
        assert_filter_matches_brute_force(ssn)
    finally:
        close_session(ssn)


def _binds(args, action):
    cache = make_cache(**args)
    run_actions(cache, [action], TIERS())
    return dict(cache.binder.binds)


def _strip(args):
    for p in args["pods"]:
        p.spec.topology_spread_constraints = []
    return args


@pytest.fixture(params=["xla-scan", "blocked", "pallas"])
def executor(request, monkeypatch):
    """jax-allocate on one formulation: the plain scan (the reference
    formulation of ops/kernels.py), the blocked rung, or the Pallas
    kernel in interpret mode."""
    from volcano_tpu.ops import dispatch, pallas_session

    monkeypatch.setattr(dispatch, "select_executor", lambda snap, weights=None: request.param)
    monkeypatch.setattr(
        pallas_session, "run_packed_pallas",
        functools.partial(pallas_session.run_packed_pallas, block_size=128, interpret=True),
    )
    return request.param


@pytest.mark.parametrize("policies", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_jax_allocate_matches_host(seed, policies, executor):
    host = _binds(cluster(seed, policies=policies), AllocateAction())
    dev = _binds(cluster(seed, policies=policies), JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["spread_rows_device"] == 24 and stats["spread_rows_host"] == 0
    assert stats["apply_fast_apply"] == len(dev) and stats["apply_host_sweep"] == 0
    # the constraint bites: without it the same cluster binds otherwise
    assert _binds(_strip(cluster(seed, policies=policies)), AllocateAction()) != host


def test_class_overflow_keeps_the_host_action_binds(monkeypatch):
    """Past the class bound, rows from the first unencoded one on take the
    host chooser, and the binds still equal the host action's."""
    from volcano_tpu.ops import packing

    monkeypatch.setattr(packing, "SPREAD_MAX_CLASSES", 3)
    host = _binds(cluster(2), AllocateAction())
    dev = _binds(cluster(2), JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["spread_classes"] == 3 and stats["spread_rows_host"] > 0
    assert stats["apply_host_sweep"] > 0


def test_kernel_formulations_agree_at_width():
    """200 nodes, 150 pending: the scan, the blocked rung and the Pallas
    kernel give one assignment, and the mask moves it."""
    from volcano_tpu.ops.blocked import run_packed_blocked
    from volcano_tpu.ops.kernels import run_packed
    from volcano_tpu.ops.packing import pack_session
    from volcano_tpu.ops.pallas_session import run_packed_pallas

    ssn = open_session(make_cache(**cluster(5, n_nodes=200, n_pending=150)), TIERS(), [])
    try:
        order = compute_task_order(ssn)
        nodes = [ssn.nodes[n] for n in sorted(ssn.nodes)]
        jobs = list({t.job: ssn.jobs[t.job] for t in order}.values())
        snap = pack_session(order, jobs, nodes)
        assert snap.has_spread and not snap.needs_host_validation
        want = run_packed(snap)
        assert (want >= 0).sum() == 150
        assert (run_packed_pallas(snap, block_size=128, interpret=True) == want).all()
        assert (run_packed_blocked(snap) == want).all()
        snap.spread_max_skew = None
        assert (run_packed(snap) != want).any()
    finally:
        close_session(ssn)


def test_warm_pack_equals_cold():
    """The pack cache derives the spread planes afresh on a warm pack."""
    from volcano_tpu.ops.packing import pack_session

    cache = make_cache(**cluster(6))
    ssn = open_session(cache, TIERS(), [])
    try:
        order = compute_task_order(ssn)
        nodes = [ssn.nodes[n] for n in sorted(ssn.nodes)]
        jobs = list({t.job: ssn.jobs[t.job] for t in order}.values())
        cold = pack_session(order, jobs, nodes)
        pc = cache.pack_cache
        pc.pack(order, jobs, nodes, ssn.pack_epoch)
        warm = pc.pack(order, jobs, nodes, ssn.pack_epoch)
        assert pc.last_stats["mode"] == "warm"
        for name in ("spread_key_dom", "spread_class_key", "spread_max_skew",
                     "spread_node_elig", "spread_dom_count", "task_spread_on",
                     "task_spread_self", "task_spread_host"):
            assert np.array_equal(getattr(warm, name), getattr(cold, name)), name
    finally:
        close_session(ssn)


def _stuck():
    """One web pod that fits nowhere: z0's one node is too small, z1's two
    nodes hold two web pods already (skew 2 + 1 - 0 > 1), one node has no
    zone."""
    nodes = [
        build_node("n0", {"cpu": "1", "memory": "8Gi"}, labels={ZONE: "z0"}),
        build_node("n1", {"cpu": "8", "memory": "8Gi"}, labels={ZONE: "z1"}),
        build_node("n2", {"cpu": "8", "memory": "8Gi"}, labels={ZONE: "z1"}),
        build_node("n3", {"cpu": "8", "memory": "8Gi"}),
    ]
    pods, pgs = [], []
    for i, n in enumerate(("n1", "n2")):
        pods.append(build_pod("ns", f"web{i}", n, {"cpu": "1", "memory": "1Gi"},
                              phase="Running", labels={"app": "web"}, group=f"pgw{i}"))
        pgs.append(build_pod_group("ns", f"pgw{i}", 1, queue="q"))
    stuck = build_pod("ns", "stuck", "", {"cpu": "2", "memory": "1Gi"},
                      labels={"app": "web"}, group="pgs")
    stuck.spec.topology_spread_constraints = [spread(1, "web")]
    pods.append(stuck)
    pgs.append(build_pod_group("ns", "pgs", 1, queue="q"))
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])


def _fit_errors(action):
    ssn = open_session(make_cache(**_stuck()), TIERS(), [])
    try:
        action.execute(ssn)
        return {
            job.tasks[uid].name: (fe.error(), fe._histogram is not None)
            for job in ssn.jobs.values() for uid, fe in job.nodes_fit_errors.items()
        }
    finally:
        close_session(ssn)


def test_explain_spread_stuck_task_byte_equal():
    host = _fit_errors(AllocateAction())
    dev = _fit_errors(JaxAllocateAction(explain=True))
    assert set(host) == set(dev) == {"stuck"}
    (host_msg, host_synth), (dev_msg, dev_synth) = host["stuck"], dev["stuck"]
    assert dev_msg == host_msg and dev_synth and not host_synth
    assert f"2 {reasons.SPREAD_CONSTRAINTS_MISMATCH}" in dev_msg
    assert f"1 {reasons.SPREAD_LABEL_MISSING}" in dev_msg
    assert jax_allocate.last_phase_stats["apply_explained"] == 1


def test_soft_and_unencoded_constraints_take_the_host_path():
    """ScheduleAnyway routes to the host chooser; minDomains and
    matchLabelKeys keep host validation and are never explained by the
    device.  Bindings still equal the host action's."""
    args = cluster(7, n_pending=6)
    extra = [
        [spread(1, "web", when="ScheduleAnyway")],
        [spread(1, "web", min_domains=3)],
        [spread(1, "web", match_label_keys=["app"])],
    ]
    pending_pods = [p for p in args["pods"] if not p.spec.node_name]
    for p, cons in zip(pending_pods, extra):
        p.spec.topology_spread_constraints = cons
    host = _binds(args, AllocateAction())
    args = cluster(7, n_pending=6)
    pending_pods = [p for p in args["pods"] if not p.spec.node_name]
    for p, cons in zip(pending_pods, extra):
        p.spec.topology_spread_constraints = cons
    dev = _binds(args, JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["spread_rows_host"] == 3 and stats["spread_rows_device"] == 3
    assert stats["apply_fast_apply"] == 0  # needs_host_validation refuses the bulk commit


def test_spread_preemptors_stay_on_the_host(monkeypatch):
    from volcano_tpu.actions import preempt
    from volcano_tpu.actions.jax_preempt import JaxPreemptAction
    from volcano_tpu.ops.preempt_pack import pack_preempt_session

    def objects():
        """PreemptionBasic in miniature (tests/test_explain.py), the
        preemptors spread over two zones."""
        nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "32Gi"}, labels={ZONE: f"z{i}"})
                 for i in range(2)]
        pods = [
            build_pod("ns", f"low-{i}-{k}", f"n{i}", {"cpu": "900m", "memory": "500Mi"},
                      phase="Running", group=f"low-{i}-{k}", priority=1)
            for i in range(2) for k in range(4)
        ]
        for i in range(2):
            high = build_pod("ns", f"high-{i}", "", {"cpu": "3", "memory": "500Mi"},
                             group=f"high-{i}", labels={"app": "web"}, priority=10)
            high.spec.topology_spread_constraints = [spread(1, "web")]
            pods.append(high)
        pgs = [build_pod_group("ns", p.metadata.name, 1, queue="default",
                               priority_class_name="high" if p.spec.priority == 10 else "low")
               for p in pods]
        return dict(nodes=nodes, pods=pods, pod_groups=pgs,
                    queues=[build_queue("default", weight=1)],
                    priority_classes=[build_priority_class("low", 1),
                                      build_priority_class("high", 10)])

    preempt_tiers = tiers(["priority", "gang", "conformance"],
                          ["drf", "predicates", "proportion", "nodeorder", "binpack"])
    ssn = open_session(make_cache(**objects()), preempt_tiers, [])
    try:
        pk = pack_preempt_session(ssn)
        assert pk.base.needs_host_validation and pk.base.task_spread_host[0]
        assert not pk.base.has_spread
    finally:
        close_session(ssn)
    calls = []
    orig = preempt.PreemptAction.execute
    monkeypatch.setattr(preempt.PreemptAction, "execute",
                        lambda self, s: calls.append(1) or orig(self, s))
    cache = make_cache(**objects())
    run_actions(cache, [JaxPreemptAction()], preempt_tiers)
    assert calls and cache.evictor.evicts
    host = make_cache(**objects())
    run_actions(host, [preempt.PreemptAction()], preempt_tiers)
    assert cache.evictor.evicts == host.evictor.evicts


@pytest.mark.parametrize("codec", ["json", "binary"])
def test_codecs_round_trip_the_constraints(codec):
    from volcano_tpu.bus import protocol

    pod = build_pod("ns", "p", "", {"cpu": "1"}, labels={"app": "web"})
    pod.spec.topology_spread_constraints = [
        spread(5, "web", min_domains=2, node_affinity_policy="Ignore",
               node_taints_policy="Honor", match_label_keys=["rev"]),
        spread(1, "web", key="kubernetes.io/hostname", when="ScheduleAnyway"),
    ]
    data = protocol.encode_obj(pod)
    assert data["spec"]["topologySpreadConstraints"][0]["maxSkew"] == 5
    wire = protocol.encode_payload(data, codec=codec)
    back = protocol.decode_obj(protocol.decode_payload(wire, codec=codec))
    assert back == pod
    record = protocol.decode_record(protocol.encode_record({"pod": data}))
    assert core.Pod.from_dict(record["pod"]) == pod
