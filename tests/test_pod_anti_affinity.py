"""Required pod anti-affinity (InterPodAffinity's required terms): the
host filter in PreFilter form against the plain scan predicate on every
(task, node), the device formulations against the host action on
seeded random clusters, the explanation of a stuck task, the bulk
commit keeping the counts, and the rows that stay on the host."""

from __future__ import annotations

import functools
import random

import numpy as np
import pytest

from volcano_tpu.actions import jax_allocate
from volcano_tpu.actions.allocate import AllocateAction
from volcano_tpu.actions.fast_apply import _find_pod_listers
from volcano_tpu.actions.jax_allocate import compute_task_order, JaxAllocateAction
from volcano_tpu.api import TaskStatus
from volcano_tpu.api import unschedule_info as reasons
from volcano_tpu.apis import core
from volcano_tpu.framework import close_session, open_session
from volcano_tpu.plugins import util as putil

from tests.builders import (
    build_node,
    build_pod,
    build_pod_group,
    build_priority_class,
    build_queue,
)
from tests.scheduler_helpers import make_cache, run_actions, tiers

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
TIERS = lambda: tiers(
    ["priority", "gang"], ["drf", "predicates", "proportion", "nodeorder", "binpack"]
)


def pod_affinity_predicate(pod, node, all_nodes, assigned) -> bool:
    """The plain scan predicate: required pod affinity, required
    anti-affinity and the placed pods' anti-affinity (symmetry), by
    scanning every placed pod (``assigned``: (uid, pod, node name) of
    every pod the nodes hold) on every call; the pod itself is never
    among the others.  The first pod of a series passes its own
    affinity when no other pod matches any of its terms and it matches
    all of them (k8s v1.30 interpodaffinity)."""
    node_obj = node.node
    labels = node_obj.metadata.labels
    uid = next((u for u, p, _n in assigned if p is pod), None)
    others = [(p, nn) for u, p, nn in assigned if u != uid]

    def key(term):
        return term.get("topologyKey") or putil.DEFAULT_TOPOLOGY_KEY

    def domain_pods(k):
        return [ep for ep, nn in others
                if putil._same_topology(node_obj, all_nodes[nn].node, k)]

    aff = putil.required_terms(pod, "podAffinity")
    if aff:
        if any(key(t) not in labels for t in aff):
            return False
        if not all(any(putil._term_matches_pod(t, pod, ep) for ep in domain_pods(key(t)))
                   for t in aff):
            anywhere = any(
                putil._term_matches_pod(t, pod, ep)
                and key(t) in all_nodes[nn].node.metadata.labels
                for t in aff for ep, nn in others
            )
            if anywhere or not all(putil._term_matches_pod(t, pod, pod) for t in aff):
                return False
    for term in putil.required_terms(pod, "podAntiAffinity"):
        if any(putil._term_matches_pod(term, pod, ep) for ep in domain_pods(key(term))):
            return False
    for ep, nn in others:
        for term in putil.required_terms(ep, "podAntiAffinity"):
            if putil._same_topology(node_obj, all_nodes[nn].node, key(term)) and \
                    putil._term_matches_pod(term, ep, pod):
                return False
    return True


def anti(key=HOST, namespaces=None, **match):
    term = {"labelSelector": {"matchLabels": dict(match)}, "topologyKey": key}
    if namespaces is not None:
        term["namespaces"] = list(namespaces)
    return term


def affinity(*anti_terms, required_affinity=()):
    out = {}
    if anti_terms:
        out["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": list(anti_terms)}
    if required_affinity:
        out["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": list(required_affinity)}
    return out


def spread(max_skew, app):
    return core.TopologySpreadConstraint(
        max_skew=max_skew, topology_key=ZONE, when_unsatisfiable="DoNotSchedule",
        label_selector={"matchLabels": {"app": app}},
    )


def cluster(seed, mode="both", n_nodes=14, n_pending=24, with_spread=False):
    """Nodes in three zones, one without a zone label and one without a
    hostname label.  ``mode`` picks which rule binds the pending pods:

    * ``own`` — pending pods hold terms that avoid other pods and match
      none of their own kind, and no placed pod holds a term;
    * ``symmetry`` — placed pods hold terms (hostname and zone keys)
      that pending pods without terms of their own match;
    * ``both`` — the two, and green pending pods under a term they match
      themselves.

    Pods in a namespace of a term's list but with other labels, and pods
    with its labels outside its namespaces, sit beside those that match.
    ``with_spread`` puts a zone spread constraint on every fourth pending
    pod (spread and anti-affinity classes in one session)."""
    rng = random.Random(seed)
    nodes = []
    for i in range(n_nodes):
        labels = {} if i == 3 else {ZONE: f"z{rng.randrange(3)}"}
        if i != 5:
            labels[HOST] = f"n{i:02d}"
        nodes.append(build_node(f"n{i:02d}", {"cpu": "4", "memory": "8Gi"}, labels=labels))
    pods, pgs = [], []

    def job(ns, name, node, labels, aff=None, phase="Pending", cons=()):
        p = build_pod(ns, name, node, {"cpu": "500m", "memory": "512Mi"}, phase=phase,
                      labels=labels, group=f"pg-{name}", affinity=aff)
        p.spec.topology_spread_constraints = list(cons)
        pods.append(p)
        pgs.append(build_pod_group(ns, f"pg-{name}", 1, queue="q"))

    def node():
        return f"n{rng.randrange(n_nodes):02d}"

    # placed pods: matching and non-matching by namespace and by labels
    job("ns", "run-green", node(), {"color": "green", "app": "web"}, phase="Running")
    job("other", "run-green-other", node(), {"color": "green"}, phase="Running")
    job("far", "run-green-far", node(), {"color": "green"}, phase="Running")
    job("ns", "run-blue", node(), {"color": "blue", "app": "db"}, phase="Running")
    if mode in ("symmetry", "both"):
        job("ns", "guard-host", node(), {"role": "guard"},
            affinity(anti(HOST, ["ns", "other"], color="green")), phase="Running")
        job("other", "guard-zone", node(), {"role": "guard"},
            affinity(anti(ZONE, app="db")), phase="Running")
    for i in range(n_pending):
        kind = i % 4
        cons = [spread(2, "web")] if with_spread and i % 4 == 1 else []
        if mode == "own":
            # avoid db pods of ns / green pods of other and far; never matched
            aff = affinity(anti(HOST, app="db")) if kind % 2 else \
                affinity(anti(ZONE, ["other", "far"], color="green"))
            job("ns", f"p{i:02d}", "", {"app": "web"}, aff, cons=cons)
        elif mode == "symmetry":
            labels = ({"color": "green"}, {"app": "db"}, {"color": "blue"}, {"app": "web"})[kind]
            job(("ns", "other", "ns", "ns")[kind], f"p{i:02d}", "", labels, cons=cons)
        else:
            if kind == 0:
                job("ns", f"p{i:02d}", "", {"color": "green", "app": "web"},
                    affinity(anti(HOST, ["ns", "other"], color="green")), cons=cons)
            elif kind == 1:
                job("other", f"p{i:02d}", "", {"app": "db"},
                    affinity(anti(HOST, ["ns"], color="blue")), cons=cons)
            elif kind == 2:
                job("far", f"p{i:02d}", "", {"color": "green"}, cons=cons)
            else:
                job("ns", f"p{i:02d}", "", {"app": "web"}, cons=cons)
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])


def pending(ssn):
    return sorted(
        (t for j in ssn.jobs.values() for t in j.tasks.values()
         if t.status == TaskStatus.Pending),
        key=lambda t: t.uid,
    )


def placed(ssn):
    return [(t.uid, t.pod, node.name) for node in ssn.nodes.values()
            for t in node.tasks.values() if t.pod is not None]


def predicates_lister(ssn):
    return _find_pod_listers(ssn)[0]


def assert_filter_matches_scan(ssn):
    """Every (pending task, node): the PreFilter form's verdict equals the
    scan predicate's; returns how many pairs it refused."""
    state = predicates_lister(ssn).affinity
    assigned = placed(ssn)
    refused = 0
    for task in pending(ssn):
        for node in ssn.nodes.values():
            want = pod_affinity_predicate(task.pod, node, ssn.nodes, assigned)
            got = state.filter(task, node)
            assert (got is None) == want, (task.name, node.name)
            assert got in (None, reasons.POD_AFFINITY_MISMATCH)
            refused += not want
    return refused


@pytest.mark.parametrize("mode", ["own", "symmetry", "both"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_filter_matches_the_scan_predicate(seed, mode):
    """At session open, after placements (the allocate events, some of
    them of pods that hold terms), after an eviction (the pod stays on
    its node, and counted, while it releases), and after the statement's
    discard unwinds them all."""
    ssn = open_session(make_cache(**cluster(seed, mode)), TIERS(), [])
    try:
        assert assert_filter_matches_scan(ssn)
        stmt = ssn.statement()
        assigned = placed(ssn)
        for task in pending(ssn)[:10]:
            fits = [n for n in sorted(ssn.nodes)
                    if task.resreq.less_equal(ssn.nodes[n].idle)
                    and pod_affinity_predicate(task.pod, ssn.nodes[n], ssn.nodes, assigned)]
            if fits:
                stmt.allocate(task, fits[-1])
                assigned = placed(ssn)
        running = next(t for j in ssn.jobs.values() for t in j.tasks.values()
                       if t.status == TaskStatus.Running and t.pod.spec.affinity) \
            if mode != "own" else None
        if running is not None:
            stmt.evict(running, "test")
        assert_filter_matches_scan(ssn)
        stmt.discard()
        assert_filter_matches_scan(ssn)
    finally:
        close_session(ssn)


def test_first_pod_of_an_affinity_series():
    """Required pod affinity to its own kind: the first pod may land on
    any node with the key, the second only beside it; a node without the
    key never passes."""
    term = {"labelSelector": {"matchLabels": {"app": "cache"}}, "topologyKey": ZONE}
    nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "8Gi"},
                        labels={ZONE: f"z{i}"} if i < 2 else {}) for i in range(3)]
    pods = [build_pod("ns", f"c{i}", "", {"cpu": "1", "memory": "1Gi"}, group=f"g{i}",
                      labels={"app": "cache"}, affinity=affinity(required_affinity=[term]))
            for i in range(2)]
    pgs = [build_pod_group("ns", f"g{i}", 1, queue="q") for i in range(2)]
    ssn = open_session(make_cache(nodes=nodes, pods=pods, pod_groups=pgs,
                                  queues=[build_queue("q")]), TIERS(), [])
    try:
        state = predicates_lister(ssn).affinity
        first, second = pending(ssn)
        by = {n: ssn.nodes[n] for n in ("n0", "n1", "n2")}
        assert [state.filter(first, by[n]) is None for n in by] == [True, True, False]
        stmt = ssn.statement()
        stmt.allocate(first, "n1")
        assert [state.filter(second, by[n]) is None for n in by] == [False, True, False]
        assert assert_filter_matches_scan(ssn) == 2
        stmt.discard()
    finally:
        close_session(ssn)


def _binds(args, action):
    cache = make_cache(**args)
    run_actions(cache, [action], TIERS())
    return dict(cache.binder.binds)


def _strip(args):
    for p in args["pods"]:
        p.spec.affinity = {}
    return args


@pytest.fixture(params=["xla-scan", "pallas"])
def executor(request, monkeypatch):
    """jax-allocate on the plain scan or on the Pallas kernel in
    interpret mode."""
    from volcano_tpu.ops import dispatch, pallas_session

    monkeypatch.setattr(dispatch, "select_executor", lambda snap, weights=None: request.param)
    monkeypatch.setattr(
        pallas_session, "run_packed_pallas",
        functools.partial(pallas_session.run_packed_pallas, block_size=128, interpret=True),
    )
    return request.param


@pytest.mark.parametrize("with_spread", [False, True])
@pytest.mark.parametrize("mode", ["own", "symmetry", "both"])
@pytest.mark.parametrize("seed", [1, 2])
def test_jax_allocate_matches_host(seed, mode, with_spread, executor):
    args = functools.partial(cluster, seed, mode, with_spread=with_spread)
    host = _binds(args(), AllocateAction())
    dev = _binds(args(), JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["affinity_rows_host"] == 0 and stats["affinity_rows_device"] > 0
    assert stats["affinity_classes"] > 0
    assert stats["spread_classes"] == int(with_spread)
    assert stats["apply_fast_apply"] == len(dev) and stats["apply_host_sweep"] == 0
    # the terms bite: without them the same cluster binds otherwise
    assert _binds(_strip(args()), AllocateAction()) != host


@pytest.mark.parametrize("mode", ["symmetry", "both"])
def test_bulk_commit_keeps_the_counts(mode):
    """With the counts built before APPLY, the bulk commit moves them as
    the allocate events would: afterwards they equal a recount, and
    every (task, node) verdict equals a fresh state's."""
    ssn = open_session(make_cache(**cluster(4, mode)), TIERS(), [])
    try:
        state = predicates_lister(ssn).affinity
        tasks = pending(ssn)
        for task in tasks:
            state.filter(task, next(iter(ssn.nodes.values())))
        JaxAllocateAction().execute(ssn)
        assert jax_allocate.last_phase_stats["apply_fast_apply"] > 0
        fresh = putil.InterPodAffinity(ssn)
        node = next(iter(ssn.nodes.values()))
        for task in tasks:
            fresh.filter(task, node)
        nonzero = lambda c: {d: n for d, n in c.counts.items() if n}
        assert set(state._where) == set(fresh._where)
        assert {k: nonzero(c) for k, c in state._held.items()} == \
            {k: nonzero(c) for k, c in fresh._held.items()}
        assert {k: nonzero(c) for k, c in state._matched.items()} == \
            {k: nonzero(c) for k, c in fresh._matched.items()}
        for task in tasks:
            for n in ssn.nodes.values():
                assert state.filter(task, n) == fresh.filter(task, n)
    finally:
        close_session(ssn)


def test_class_overflow_goes_to_the_host_rows(monkeypatch):
    """Past the class bound, the rows from the first unencoded one on
    take the host chooser, and the binds still equal the host action's."""
    from volcano_tpu.ops import packing

    monkeypatch.setattr(packing, "SPREAD_MAX_CLASSES", 2)
    args = functools.partial(cluster, 2, "both", with_spread=True)
    host = _binds(args(), AllocateAction())
    dev = _binds(args(), JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["spread_classes"] + stats["affinity_classes"] == 2
    assert stats["affinity_rows_host"] > 0
    assert stats["apply_fast_apply"] == 0 and stats["apply_host_sweep"] > 0


def test_unencoded_terms_stay_on_the_host():
    """Required pod affinity, a namespace selector and label keys keep
    their rows on the host; the binds equal the host action's."""
    def args():
        out = cluster(3, "both", n_pending=8)
        pend = [p for p in out["pods"] if not p.spec.node_name]
        sel = anti(HOST, color="green")
        pend[1].spec.affinity = affinity(required_affinity=[anti(ZONE, app="web")])
        pend[2].spec.affinity = affinity({**sel, "namespaceSelector": {}})
        pend[3].spec.affinity = affinity({**sel, "matchLabelKeys": ["app"]})
        return out

    host = _binds(args(), AllocateAction())
    dev = _binds(args(), JaxAllocateAction())
    assert host == dev
    stats = jax_allocate.last_phase_stats
    assert stats["affinity_rows_host"] >= 3
    assert stats["apply_fast_apply"] == 0


def _stuck():
    """One green web pod that fits nowhere: n0 holds a green pod (its own
    term), n1 and n2 lie in a zone two web pods ahead (spread, checked
    first, though n1 also fails the term), n3 has no hostname and too
    little cpu."""
    nodes = [
        build_node("n0", {"cpu": "8", "memory": "8Gi"}, labels={ZONE: "z0", HOST: "n0"}),
        build_node("n1", {"cpu": "8", "memory": "8Gi"}, labels={ZONE: "z1", HOST: "n1"}),
        build_node("n2", {"cpu": "8", "memory": "8Gi"}, labels={ZONE: "z1", HOST: "n2"}),
        build_node("n3", {"cpu": "1", "memory": "8Gi"}, labels={ZONE: "z0"}),
    ]
    placed_pods = [("g0", "n0", {"color": "green", "app": "web"}),
                   ("g1", "n1", {"color": "green", "app": "web"}),
                   ("w2", "n2", {"app": "web"})]
    pods = [build_pod("ns", name, n, {"cpu": "1", "memory": "1Gi"}, phase="Running",
                      labels=labels, group=f"pg-{name}") for name, n, labels in placed_pods]
    stuck = build_pod("ns", "stuck", "", {"cpu": "2", "memory": "1Gi"}, group="pg-stuck",
                      labels={"color": "green", "app": "web"},
                      affinity=affinity(anti(HOST, color="green")))
    stuck.spec.topology_spread_constraints = [spread(1, "web")]
    pods.append(stuck)
    pgs = [build_pod_group("ns", f"pg-{p.metadata.name}", 1, queue="q") for p in pods]
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


def test_explain_anti_affinity_stuck_task_byte_equal(executor):
    host = _fit_errors(AllocateAction())
    dev = _fit_errors(JaxAllocateAction(explain=True))
    assert set(host) == set(dev) == {"stuck"}
    (host_msg, host_synth), (dev_msg, dev_synth) = host["stuck"], dev["stuck"]
    assert dev_msg == host_msg and dev_synth and not host_synth
    assert f"1 {reasons.POD_AFFINITY_MISMATCH}" in dev_msg
    assert f"2 {reasons.SPREAD_CONSTRAINTS_MISMATCH}" in dev_msg
    assert jax_allocate.last_phase_stats["apply_explained"] == 1


def test_anti_affinity_preemptors_stay_on_the_host():
    """The preempt kernel models no class: a preemptor with required
    anti-affinity is a host row of its pack."""
    from volcano_tpu.ops.preempt_pack import pack_preempt_session

    nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "32Gi"}, labels={HOST: f"n{i}"})
             for i in range(2)]
    pods = [build_pod("ns", f"low-{i}", f"n{i}", {"cpu": "3", "memory": "500Mi"},
                      phase="Running", group=f"low-{i}", priority=1) for i in range(2)]
    pods.append(build_pod("ns", "high", "", {"cpu": "3", "memory": "500Mi"}, group="high",
                          labels={"color": "green"}, priority=10,
                          affinity=affinity(anti(HOST, color="green"))))
    pgs = [build_pod_group("ns", p.metadata.name, 1, queue="default",
                           priority_class_name="high" if p.spec.priority == 10 else "low")
           for p in pods]
    objects = dict(nodes=nodes, pods=pods, pod_groups=pgs,
                   queues=[build_queue("default", weight=1)],
                   priority_classes=[build_priority_class("low", 1),
                                     build_priority_class("high", 10)])
    ssn = open_session(make_cache(**objects), tiers(
        ["priority", "gang", "conformance"],
        ["drf", "predicates", "proportion", "nodeorder", "binpack"]), [])
    try:
        pk = pack_preempt_session(ssn)
        assert pk.base.needs_host_validation and pk.base.task_spread_host[0]
        assert not pk.base.has_spread
    finally:
        close_session(ssn)


def test_pack_planes_and_journal_round_trip(tmp_path):
    """The hostname classes of the benchmark's shape in miniature: one
    class of the pod's own term and one of the symmetry rule, both of
    the absolute kind, counts at the nodes that hold a green pod; the
    journal carries the kind and replays to the same assignment."""
    from volcano_tpu.ops.kernels import run_packed
    from volcano_tpu.ops.packing import (
        load_snapshot,
        pack_session,
        save_snapshot,
        spread_node_planes,
        spread_task_columns,
    )

    term = anti(HOST, ["a", "b"], color="green")
    nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "8Gi"}, labels={HOST: f"n{i}"})
             for i in range(6)]
    pods = [build_pod("a", f"i{i}", f"n{i}", {"cpu": "1", "memory": "1Gi"}, phase="Running",
                      labels={"color": "green"}, group=f"gi{i}", affinity=affinity(term))
            for i in (1, 4)]
    pods += [build_pod("b", f"m{i}", "", {"cpu": "1", "memory": "1Gi"},
                       labels={"color": "green"}, group=f"gm{i}", affinity=affinity(term))
             for i in range(3)]
    pgs = [build_pod_group(p.metadata.namespace, f"g{p.metadata.name}", 1, queue="q")
           for p in pods]
    ssn = open_session(make_cache(nodes=nodes, pods=pods, pod_groups=pgs,
                                  queues=[build_queue("q")]), TIERS(), [])
    try:
        order = compute_task_order(ssn)
        node_list = [ssn.nodes[n] for n in sorted(ssn.nodes)]
        jobs = list({t.job: ssn.jobs[t.job] for t in order}.values())
        snap = pack_session(order, jobs, node_list)
    finally:
        close_session(ssn)
    assert snap.has_spread and not snap.needs_host_validation
    assert snap.spread_absolute.tolist() == [True, True]
    assert snap.affinity_stats["affinity_classes"] == 2
    assert snap.affinity_stats["affinity_rows_device"] == 3
    dom, elig, count = spread_node_planes(snap, 6)
    assert count.tolist() == [[0, 1, 0, 0, 1, 0]] * 2 and (elig == 1).all()
    thr, inc = spread_task_columns(snap, snap.n_tasks)
    assert (thr == 0).all() and (inc == 1).all()
    got = run_packed(snap)
    assert sorted(got.tolist()) == [0, 2, 3]
    path = str(tmp_path / "snap.npz")
    save_snapshot(snap, path)
    loaded, _ = load_snapshot(path)
    np.testing.assert_array_equal(loaded.spread_absolute, snap.spread_absolute)
    np.testing.assert_array_equal(run_packed(loaded), got)
