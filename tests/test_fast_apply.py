"""fast_apply equivalence: the bulk commit must leave session + cache
state identical to the slow drive_allocate_loop/Statement path — exact
floats, dict contents and insertion orders, binder calls, plugin state."""

from __future__ import annotations

import copy

import numpy as np
import pytest

import volcano_tpu.actions.jax_allocate as ja
from volcano_tpu.actions.fast_apply import try_fast_apply
from volcano_tpu.actions.jax_allocate import JaxAllocateAction
from volcano_tpu.api import TaskStatus
from volcano_tpu.framework import close_session, open_session

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, tiers

STANDARD = lambda: tiers(
    ["priority", "gang"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)


def _cluster(n_jobs=6, gang=4, min_avail=None, n_nodes=6, seed=0, queues=None):
    rng = np.random.RandomState(seed)
    nodes = [build_node(f"n{i}", {"cpu": "16", "memory": "64Gi"}) for i in range(n_nodes)]
    pods, pgs = [], []
    qnames = [q.metadata.name for q in (queues or [build_queue("q")])]
    for j in range(n_jobs):
        pgs.append(
            build_pod_group("ns", f"pg{j}", min_avail or gang,
                            queue=qnames[j % len(qnames)])
        )
        for i in range(gang):
            cpu = ["500m", "1", "2"][rng.randint(3)]
            pods.append(
                build_pod("ns", f"j{j}-t{i}", "", {"cpu": cpu, "memory": "1Gi"},
                          group=f"pg{j}")
            )
    return dict(nodes=nodes, pods=pods, pod_groups=pgs,
                queues=queues or [build_queue("q")])


def _run(cluster, force_slow, monkeypatch=None):
    cache = make_cache(**copy.deepcopy(cluster))
    ssn = open_session(cache, STANDARD(), [])
    engaged = {"fast": False}
    if force_slow:
        orig_import = ja.__dict__
        import volcano_tpu.actions.fast_apply as fa

        real = fa.try_fast_apply
        fa.try_fast_apply = lambda *a, **k: False
        try:
            JaxAllocateAction().execute(ssn)
        finally:
            fa.try_fast_apply = real
    else:
        import volcano_tpu.actions.fast_apply as fa

        real = fa.try_fast_apply

        def spy(*a, **k):
            engaged["fast"] = real(*a, **k)
            return engaged["fast"]

        fa.try_fast_apply = spy
        try:
            JaxAllocateAction().execute(ssn)
        finally:
            fa.try_fast_apply = real
    return cache, ssn, engaged["fast"]


def _assert_state_equal(a, b):
    cache_a, ssn_a = a
    cache_b, ssn_b = b
    assert cache_a.binder.binds == cache_b.binder.binds

    assert set(ssn_a.jobs) == set(ssn_b.jobs)
    for uid in ssn_a.jobs:
        ja_, jb = ssn_a.jobs[uid], ssn_b.jobs[uid]
        assert ja_.allocated.milli_cpu == jb.allocated.milli_cpu, uid
        assert ja_.allocated.memory == jb.allocated.memory, uid
        assert ja_.total_request.milli_cpu == jb.total_request.milli_cpu, uid
        assert list(ja_.tasks) == list(jb.tasks), uid  # insertion order
        assert {t: fe.error() for t, fe in ja_.nodes_fit_errors.items()} == {
            t: fe.error() for t, fe in jb.nodes_fit_errors.items()
        }, uid
        assert {
            s: set(ts) for s, ts in ja_.task_status_index.items()
        } == {s: set(ts) for s, ts in jb.task_status_index.items()}, uid
        for t_uid, ta in ja_.tasks.items():
            tb = jb.tasks[t_uid]
            assert ta.status == tb.status
            assert ta.node_name == tb.node_name
            assert ta.volume_ready == tb.volume_ready

    assert set(ssn_a.nodes) == set(ssn_b.nodes)
    for name in ssn_a.nodes:
        na, nb = ssn_a.nodes[name], ssn_b.nodes[name]
        assert na.idle.milli_cpu == nb.idle.milli_cpu, name
        assert na.idle.memory == nb.idle.memory, name
        assert na.used.milli_cpu == nb.used.milli_cpu, name
        assert na.used.memory == nb.used.memory, name
        assert list(na.tasks) == list(nb.tasks), name
        for t_uid, ca in na.tasks.items():
            cb = nb.tasks[t_uid]
            assert ca.status == cb.status and ca.node_name == cb.node_name

    # plugin internal state (consumed by later actions in the session)
    for pname in ("drf", "proportion"):
        pa, pb = ssn_a.plugins[pname], ssn_b.plugins[pname]
        if pname == "drf":
            assert set(pa.job_attrs) == set(pb.job_attrs)
            for uid in pa.job_attrs:
                assert pa.job_attrs[uid].share == pb.job_attrs[uid].share, uid
                assert (
                    pa.job_attrs[uid].allocated.milli_cpu
                    == pb.job_attrs[uid].allocated.milli_cpu
                )
            assert set(pa.namespace_opts) == set(pb.namespace_opts)
            for ns in pa.namespace_opts:
                assert pa.namespace_opts[ns].share == pb.namespace_opts[ns].share
        else:
            assert set(pa.queue_opts) == set(pb.queue_opts)
            for q in pa.queue_opts:
                assert pa.queue_opts[q].share == pb.queue_opts[q].share, q
                assert (
                    pa.queue_opts[q].allocated.milli_cpu
                    == pb.queue_opts[q].allocated.milli_cpu
                )

    # cache-side state
    for uid in cache_a.jobs:
        ca, cb = cache_a.jobs[uid], cache_b.jobs[uid]
        assert {s: set(ts) for s, ts in ca.task_status_index.items()} == {
            s: set(ts) for s, ts in cb.task_status_index.items()
        }
    for name in cache_a.nodes:
        na, nb = cache_a.nodes[name], cache_b.nodes[name]
        assert na.idle.milli_cpu == nb.idle.milli_cpu
        assert na.used.milli_cpu == nb.used.milli_cpu
        assert list(na.tasks) == list(nb.tasks)


@pytest.mark.parametrize("kwargs", [
    dict(),                                   # simple gangs, one queue
    dict(min_avail=2, gang=5),                # post-ready single-task episodes
    dict(n_jobs=9, gang=3,
         queues=[build_queue("qa", weight=3), build_queue("qb", weight=1)]),
])
def test_fast_apply_matches_slow_path(kwargs):
    cluster = _cluster(**kwargs)
    cache_f, ssn_f, engaged = _run(cluster, force_slow=False)
    assert engaged, "fast apply did not engage on an exact fully-placed session"
    cache_s, ssn_s, _ = _run(cluster, force_slow=True)
    _assert_state_equal((cache_f, ssn_f), (cache_s, ssn_s))
    close_session(ssn_f)
    close_session(ssn_s)
    # post-close status writeback must agree too
    assert {
        (uid, j.pod_group.status.phase)
        for uid, j in cache_f.jobs.items()
        if j.pod_group is not None
    } == {
        (uid, j.pod_group.status.phase)
        for uid, j in cache_s.jobs.items()
        if j.pod_group is not None
    }


def test_fast_apply_fractional_cpu_bit_identity():
    """Fractional cpu milli-values make the per-lane float sequences
    round-sensitive: the bulk path must follow the slow path's EPISODE
    op structure (all allocates then all commits per gang episode), not a
    per-task interleave, for job.allocated/total_request to stay
    bit-identical."""
    rng = np.random.RandomState(7)
    nodes = [build_node(f"n{i}", {"cpu": "16", "memory": "64Gi"}) for i in range(4)]
    pods, pgs = [], []
    cpus = ["0.1003", "0.2507", "0.4701"]
    for j in range(5):
        pgs.append(build_pod_group("ns", f"pg{j}", 3, queue="q"))
        for i in range(3):
            pods.append(
                build_pod("ns", f"j{j}-t{i}", "",
                          {"cpu": cpus[rng.randint(3)], "memory": "1Gi"},
                          group=f"pg{j}")
            )
    cluster = dict(nodes=nodes, pods=pods, pod_groups=pgs,
                   queues=[build_queue("q")])
    cache_f, ssn_f, engaged = _run(cluster, force_slow=False)
    cache_s, ssn_s, _ = _run(cluster, force_slow=True)
    if engaged:  # identical bindings required for a meaningful comparison
        _assert_state_equal((cache_f, ssn_f), (cache_s, ssn_s))
    close_session(ssn_f)
    close_session(ssn_s)


def test_fast_apply_refuses_partial_placement():
    # one tiny node: most gangs cannot place — the kernel discards them,
    # and the bulk path applies the discards as the slow path would
    cluster = _cluster(n_jobs=6, gang=4, n_nodes=1)
    cluster["nodes"] = [build_node("n0", {"cpu": "4", "memory": "8Gi"})]
    cache_f, ssn_f, engaged = _run(cluster, force_slow=False)
    assert engaged
    cache_s, ssn_s, _ = _run(cluster, force_slow=True)
    _assert_state_equal((cache_f, ssn_f), (cache_s, ssn_s))
    close_session(ssn_f)
    close_session(ssn_s)


def test_fast_apply_refuses_pvc_pods():
    cluster = _cluster(n_jobs=2, gang=2)
    pod = cluster["pods"][0]
    from volcano_tpu.apis import core

    pod.spec.volumes = [
        core.Volume(name="v", source={"persistentVolumeClaim": {"claimName": "c"}})
    ]
    cache, ssn, engaged = _run(cluster, force_slow=False)
    assert not engaged
    close_session(ssn)


def _guarded_cluster(term_extra=None):
    """Two nodes; a RUNNING pod on n0 whose required anti-affinity term
    matches both pending pods."""
    anti = {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": {"app": "x"}},
         "topologyKey": "kubernetes.io/hostname", **(term_extra or {})}]}}
    nodes = [
        build_node(f"n{i}", {"cpu": "8", "memory": "16Gi"},
                   labels={"kubernetes.io/hostname": f"n{i}"})
        for i in range(2)
    ]
    pods = [
        build_pod("ns", "guard", "n0", {"cpu": "1", "memory": "1Gi"},
                  phase="Running", group="pgr", affinity=anti),
        build_pod("ns", "t0", "", {"cpu": "1", "memory": "1Gi"}, group="pg",
                  labels={"app": "x"}),
        build_pod("ns", "t1", "", {"cpu": "1", "memory": "1Gi"}, group="pg",
                  labels={"app": "x"}),
    ]
    pgs = [build_pod_group("ns", "pgr", 1, queue="q"),
           build_pod_group("ns", "pg", 1, queue="q")]
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])


def test_fast_apply_refuses_preassigned_anti_affinity():
    """A RUNNING pod's required anti-affinity term that the pack cannot
    encode (a namespace selector) sends the pending pods it matches to
    the host, so the bulk path must refuse and the slow path must keep
    them off the guard's node."""
    cluster = _guarded_cluster({"namespaceSelector": {}})
    cache, ssn, engaged = _run(cluster, force_slow=False)
    assert not engaged
    binds = dict(cache.binder.binds)
    # slow path places the app=x pods only away from the guard's node
    assert all(v != "n0" for k, v in binds.items() if k in ("ns/t0", "ns/t1"))
    close_session(ssn)


def test_fast_apply_commits_encoded_anti_affinity():
    """The same guard with a term the pack encodes (a class of the
    symmetry rule): the bulk path commits and the pods land away from
    the guard's node."""
    cache, ssn, engaged = _run(_guarded_cluster(), force_slow=False)
    assert engaged
    binds = dict(cache.binder.binds)
    assert {"ns/t0", "ns/t1"} <= set(binds)
    assert all(v != "n0" for k, v in binds.items() if k in ("ns/t0", "ns/t1"))
    close_session(ssn)


def test_fast_apply_refuses_unknown_plugin():
    cluster = _cluster(n_jobs=2, gang=2)
    cache = make_cache(**copy.deepcopy(cluster))
    ssn = open_session(cache, STANDARD(), [])
    try:
        ssn.plugins["mystery"] = object()
        ordered = ja.compute_task_order(ssn)
        proposals, snap = JaxAllocateAction()._kernel_proposals(ssn, ordered)
        assert snap is None or not try_fast_apply(ssn, ordered, proposals, snap)
    finally:
        del ssn.plugins["mystery"]
        close_session(ssn)


def test_ready_counter_invariant_through_fast_apply():
    """job.ready_num (the O(1) counter behind ready_task_num) must equal
    the recomputed bucket sum after the bulk path's direct status-index
    surgery, session- and cache-side."""
    from volcano_tpu.api.job_info import _READY_STATUSES

    def recount(job):
        return sum(
            len(tasks)
            for status, tasks in job.task_status_index.items()
            if status in _READY_STATUSES
        )

    cluster = _cluster()
    cache, ssn, engaged = _run(cluster, force_slow=False)
    assert engaged
    for job in list(ssn.jobs.values()) + list(cache.jobs.values()):
        assert job.ready_task_num() == recount(job), job.uid
        assert job.ready_task_num() > 0  # the session placed everything
    close_session(ssn)


def test_ready_counter_immune_to_double_add():
    """A watch-echo double add (cache._add_task racing its own bind echo)
    must not inflate ready_num: the bucket write is idempotent, so the
    counter has to be as well."""
    from volcano_tpu.api import JobInfo, Resource, TaskInfo, TaskStatus

    job = JobInfo("j1")
    t = TaskInfo(uid="t1", job="j1", name="t1", namespace="ns",
                 resreq=Resource(), status=TaskStatus.Running)
    job.add_task_info(t)
    job.add_task_info(t)  # echo
    assert job.ready_task_num() == 1
    job.delete_task_info(t)
    assert job.ready_task_num() == 0


def _residual_cluster(kind: str):
    """Clean gang jobs plus ONE residual job (created last → processed
    last by the drive loop's creation-timestamp order, so bulk-then-slow
    equals the pure-slow processing order)."""
    cluster = _cluster(n_jobs=4, gang=3)
    if kind == "preference":
        extra = build_pod(
            "ns", "odd-t0", "", {"cpu": "1", "memory": "1Gi"}, group="pgodd",
            affinity={"nodeAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 1, "preference": {"matchExpressions": [
                        {"key": "zone", "operator": "In", "values": ["z1"]}]}}]}},
        )
    else:  # pvc
        from volcano_tpu.apis import core

        extra = build_pod("ns", "odd-t0", "", {"cpu": "1", "memory": "1Gi"},
                          group="pgodd")
        extra.spec.volumes = [
            core.Volume(name="v",
                        source={"persistentVolumeClaim": {"claimName": "c"}})
        ]
    cluster["pods"].append(extra)
    cluster["pod_groups"].append(build_pod_group("ns", "pgodd", 1, queue="q"))
    if kind == "pvc":
        from volcano_tpu.apis import core

        cluster["pvcs"] = [core.PersistentVolumeClaim(
            metadata=core.ObjectMeta(name="c", namespace="ns"),
            spec={"storageClassName": "std"},
            status={"phase": "Bound"},
        )]
    return cluster


def _make_cache_with_pvcs(cluster):
    pvcs = cluster.pop("pvcs", [])
    cache = make_cache(**copy.deepcopy(cluster))
    for pvc in pvcs:
        cache.add_pvc(pvc)
    cluster["pvcs"] = pvcs
    return cache


@pytest.mark.parametrize("kind", ["preference", "pvc"])
def test_partial_bulk_apply_matches_slow_path(kind):
    """One odd task (preference terms / PVC volume) no longer forces the
    whole session onto the Statement loop: clean jobs bulk-commit, the
    residual runs host-side, and the final session + cache state equals
    the pure-slow path's."""
    import volcano_tpu.actions.fast_apply as fa

    cluster = _residual_cluster(kind)

    # fast (partial) run, counting what the bulk path actually committed
    cache_f = _make_cache_with_pvcs(cluster)
    ssn_f = open_session(cache_f, STANDARD(), [])
    batches = []
    orig_bind_batch = cache_f.bind_batch
    cache_f.bind_batch = lambda pairs: (batches.append(len(pairs)),
                                        orig_bind_batch(pairs))[1]
    engaged = {}
    real = fa.try_fast_apply
    fa.try_fast_apply = lambda *a, **k: engaged.setdefault("r", real(*a, **k))
    try:
        JaxAllocateAction().execute(ssn_f)
    finally:
        fa.try_fast_apply = real
        cache_f.bind_batch = orig_bind_batch
    assert engaged["r"] is False  # residual present → not fully applied
    assert batches and batches[0] == 12  # the 4 clean gangs bulk-committed

    # pure slow run
    cache_s = _make_cache_with_pvcs(cluster)
    ssn_s = open_session(cache_s, STANDARD(), [])
    fa.try_fast_apply = lambda *a, **k: False
    try:
        JaxAllocateAction().execute(ssn_s)
    finally:
        fa.try_fast_apply = real

    # everything — including the residual task — got placed identically
    assert dict(cache_f.binder.binds) == dict(cache_s.binder.binds)
    assert len(cache_f.binder.binds) == 13
    _assert_state_equal((cache_f, ssn_f), (cache_s, ssn_s))
    close_session(ssn_f)
    close_session(ssn_s)
