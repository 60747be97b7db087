"""Preempt dense-formulation equivalence: the packed numpy reference
(ops/preempt_pack.py) must reproduce the host PreemptAction's evictions
and pipelined placements exactly on identical sessions — the same
bindings-equivalence discipline the allocate kernel has."""

from __future__ import annotations

import numpy as np
import pytest

from volcano_tpu.actions.preempt import PreemptAction
from volcano_tpu.api import TaskStatus
from volcano_tpu.framework.framework import close_session, open_session
from volcano_tpu.ops.preempt_pack import pack_preempt_session, preempt_dense

from tests.builders import (
    build_node,
    build_pod,
    build_pod_group,
    build_priority_class,
    build_queue,
)
from tests.scheduler_helpers import make_cache, tiers


FULL_TIERS = tiers(
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)


def _run_host(cache):
    """Host action → (evicted uid set, {preemptor uid: node}) read from
    the session before close."""
    ssn = open_session(cache, FULL_TIERS, [])
    # pack BEFORE the action mutates session state
    pk = pack_preempt_session(ssn)
    PreemptAction().execute(ssn)
    pipelined = {}
    for job in ssn.jobs.values():
        for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values():
            pipelined[t.uid] = t.node_name
    close_session(ssn)
    return set(cache.evictor.evicts), pipelined, pk


def _dense_outcome(pk):
    evicted, pnode = preempt_dense(pk)
    ev_names = {pk.vic_names[i] for i in np.nonzero(evicted)[0]}
    pipelined = {
        pk.ptask_uids[p]: pk.node_names[pnode[p]]
        for p in range(pk.base.n_tasks)
        if pnode[p] >= 0
    }
    return ev_names, pipelined


def _pallas_outcome(pk):
    """Interpret-mode Pallas replay → same (evicted names, pipelined map)
    shape as _dense_outcome, so every case below proves host ≡ dense ≡
    pallas on identical sessions."""
    from volcano_tpu.ops.preempt_pallas import run_preempt_pallas

    evicted, pnode = run_preempt_pallas(pk, interpret=True)
    ev_names = {pk.vic_names[i] for i in np.nonzero(evicted)[0]}
    pipelined = {
        pk.ptask_uids[p]: pk.node_names[pnode[p]]
        for p in range(pk.base.n_tasks)
        if pnode[p] >= 0
    }
    return ev_names, pipelined


def _case_saturated(n_nodes=4, gangs=2, gang_size=2, seed=0):
    """Nodes saturated with low-priority runners; pending high-priority
    gangs that must preempt."""
    rng = np.random.RandomState(seed)
    nodes = [
        build_node(f"n{i:03d}", {"cpu": "4", "memory": "8G"}) for i in range(n_nodes)
    ]
    pods, pgs, queues = [], [], [build_queue("q1", weight=1)]
    # fillers: one job per node pair, priority 0, saturate cpu
    fid = 0
    for i in range(n_nodes):
        for k in range(4):
            pods.append(
                build_pod(
                    "ns", f"filler-{fid:03d}", f"n{i:03d}",
                    {"cpu": "1", "memory": str(1 + int(rng.randint(0, 2))) + "G"},
                    phase="Running", group=f"fpg{fid % 3}", priority=0,
                )
            )
            fid += 1
    for g in range(3):
        pgs.append(build_pod_group("ns", f"fpg{g}", 1, queue="q1"))
    # preemptors: high-priority gangs
    for g in range(gangs):
        pgs.append(build_pod_group("ns", f"hpg{g}", gang_size, queue="q1",
                                   priority_class_name="high"))
        for m in range(gang_size):
            pods.append(
                build_pod(
                    "ns", f"high-{g}-{m}", "",
                    {"cpu": "2", "memory": "2G"},
                    group=f"hpg{g}", priority=100,
                )
            )
    return make_cache(
        nodes=nodes, pods=pods, pod_groups=pgs, queues=queues,
        priority_classes=[build_priority_class("high", 100)],
    )


def _assert_case(cache):
    """host ≡ dense ≡ pallas on one session; returns the host outcome."""
    host_ev, host_pipe, pk = _run_host(cache)
    dense_ev, dense_pipe = _dense_outcome(pk)
    assert dense_ev == host_ev
    assert dense_pipe == host_pipe
    pallas_ev, pallas_pipe = _pallas_outcome(pk)
    assert pallas_ev == host_ev
    assert pallas_pipe == host_pipe
    return host_ev, host_pipe


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_matches_host_saturated(seed):
    cache = _case_saturated(seed=seed)
    host_ev, host_pipe = _assert_case(cache)
    assert host_ev  # the scenario actually preempts


def test_dense_matches_host_idle_sufficient():
    """Enough idle resources → no evictions either way... but preempt
    still pipelines nothing (allocate would place them)."""
    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "10", "memory": "10G"})],
        pods=[
            build_pod("ns", "r1", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 1, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q1",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)


def test_dense_matches_host_gang_guard():
    """Victim job at its minAvailable floor → gang vetoes, no preemption."""
    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "2", "memory": "2G"})],
        pods=[
            build_pod("ns", "r1", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "r2", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 2, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q1",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert host_ev == set()
    assert host_pipe == {}


def test_dense_matches_host_two_queues():
    """Preempt is in-queue only: victims in another queue are untouchable."""
    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "2", "memory": "2G"})],
        pods=[
            build_pod("ns", "r1", "n000", {"cpu": "2", "memory": "2G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 1, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q2",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1), build_queue("q2", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert host_ev == set()
    assert host_pipe == {}


def test_dense_matches_host_mixed_priorities():
    """Victims with mixed priorities: eviction order must pick the
    lowest-priority ones first on the chosen node."""
    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "3", "memory": "3G"})],
        pods=[
            build_pod("ns", "lo", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "mid", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=10),
            build_pod("ns", "mid2", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=10),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 1, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q1",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert host_ev == {"ns/lo"}


def test_dense_matches_host_equal_priority_tie():
    """Equal-priority victims: both paths evict the youngest victim
    first (inverse task order — the task-order fallback is creation/uid
    ascending, so its inversion prefers the latest-created)."""
    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "2", "memory": "2G"})],
        pods=[
            build_pod("ns", "va", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "vb", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 1, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q1",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert host_ev == {"ns/vb"}


def test_dense_matches_host_pod_count_limit():
    """A node at its pod-count limit is rejected by the predicates
    plugin in both paths, even when resources would fit."""
    node = build_node("n000", {"cpu": "4", "memory": "4G"})
    node.status.allocatable["pods"] = "1"
    node.status.capacity["pods"] = "1"
    cache = make_cache(
        nodes=[node],
        pods=[
            build_pod("ns", "v1", "n000", {"cpu": "1", "memory": "1G"},
                      phase="Running", group="pg1", priority=0),
            build_pod("ns", "h1", "", {"cpu": "1", "memory": "1G"},
                      group="pg2", priority=100),
        ],
        pod_groups=[
            build_pod_group("ns", "pg1", 1, queue="q1"),
            build_pod_group("ns", "pg2", 1, queue="q1",
                            priority_class_name="high"),
        ],
        queues=[build_queue("q1", weight=1)],
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert host_pipe == {}


# ---- JaxPreemptAction: device-dispatched action ≡ host action ----


def _run_action(cache, action):
    """Run an action on a fresh session → (evicted set, pipelined map).
    Pipelined keys are ns/name (uids are a global counter, so they
    differ between two identically-built caches)."""
    ssn = open_session(cache, FULL_TIERS, [])
    action.execute(ssn)
    pipelined = {}
    for job in ssn.jobs.values():
        for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values():
            pipelined[f"{t.namespace}/{t.name}"] = t.node_name
    close_session(ssn)
    return set(cache.evictor.evicts), pipelined


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_jax_preempt_action_matches_host(seed):
    """JaxPreemptAction on one cache ≡ PreemptAction on an identical
    cache: same evictions, same pipelined placements."""
    from volcano_tpu.actions.jax_preempt import JaxPreemptAction

    host_ev, host_pipe = _run_action(_case_saturated(seed=seed), PreemptAction())
    dev_ev, dev_pipe = _run_action(_case_saturated(seed=seed), JaxPreemptAction())
    assert dev_ev == host_ev
    assert dev_pipe == host_pipe
    assert host_ev  # scenario actually preempts


def test_jax_preempt_action_noop_when_nothing_starves():
    from volcano_tpu.actions.jax_preempt import JaxPreemptAction

    cache = make_cache(
        nodes=[build_node("n000", {"cpu": "4", "memory": "4G"})],
        pods=[build_pod("ns", "r1", "n000", {"cpu": "1", "memory": "1G"},
                        phase="Running", group="pg1", priority=0)],
        pod_groups=[build_pod_group("ns", "pg1", 1, queue="q1")],
        queues=[build_queue("q1", weight=1)],
    )
    ev, pipe = _run_action(cache, JaxPreemptAction())
    assert ev == set() and pipe == {}


def test_jax_preempt_action_tier_fallback():
    """A session whose preemptable tier differs from the supported
    intersection routes to the host action (pack refuses loudly)."""
    from volcano_tpu.actions.jax_preempt import JaxPreemptAction

    bad_tiers = tiers(["priority", "gang", "conformance", "drf"], [])
    cache = _case_saturated(seed=0)
    ssn = open_session(cache, bad_tiers, [])
    JaxPreemptAction().execute(ssn)  # must not raise
    close_session(ssn)
    host_cache = _case_saturated(seed=0)
    hssn = open_session(host_cache, bad_tiers, [])
    PreemptAction().execute(hssn)
    close_session(hssn)
    assert set(cache.evictor.evicts) == set(host_cache.evictor.evicts)


def _case_starving_victim_source():
    """Queue with TWO starving jobs where one of them also has Running
    tasks: evicting its task mid-phase flips its DRF share against the
    other starving job — the frozen pack-time job order cannot see that."""
    nodes = [build_node(f"n{i:03d}", {"cpu": "4", "memory": "8G"})
             for i in range(3)]
    pods, pgs = [], []
    queues = [build_queue("q1", weight=1)]
    # mixed job: running tasks (victim source) + pending (starving)
    pgs.append(build_pod_group("ns", "mixed", 4, queue="q1"))
    for i in range(3):
        pods.append(build_pod("ns", f"mix-r{i}", f"n{i:03d}",
                              {"cpu": "1", "memory": "1G"},
                              phase="Running", group="mixed", priority=0))
    for i in range(2):
        pods.append(build_pod("ns", f"mix-p{i}", "",
                              {"cpu": "2", "memory": "2G"},
                              group="mixed", priority=0))
    # filler job: pure victim source (low priority, min_available 1) so
    # the session really evicts through whatever path runs it
    pgs.append(build_pod_group("ns", "filler", 1, queue="q1"))
    for i in range(3):
        pods.append(build_pod("ns", f"fil-r{i}", f"n{i:03d}",
                              {"cpu": "2", "memory": "2G"},
                              phase="Running", group="filler", priority=0))
    # second starving job in the same queue, higher priority
    pgs.append(build_pod_group("ns", "hungry", 2, queue="q1",
                               priority_class_name="high"))
    for i in range(2):
        pods.append(build_pod("ns", f"hun-{i}", "",
                              {"cpu": "2", "memory": "2G"},
                              group="hungry", priority=100))
    return make_cache(
        nodes=nodes, pods=pods, pod_groups=pgs, queues=queues,
        priority_classes=[build_priority_class("high", 100)],
    )


def test_pack_refuses_starving_victim_source():
    """ADVICE r3 medium: the frozen starving-job order is unsound when a
    victim's job is itself a starving preemptor in a multi-job queue —
    pack must refuse (mirroring reclaim_pack's guard)."""
    cache = _case_starving_victim_source()
    ssn = open_session(cache, FULL_TIERS, [])
    with pytest.raises(ValueError, match="starving preemptor and victim"):
        pack_preempt_session(ssn)
    close_session(ssn)


def test_jax_preempt_action_starving_victim_fallback():
    """The refused session must route through the host action with
    identical evictions/placements."""
    from volcano_tpu.actions.jax_preempt import JaxPreemptAction

    cache = _case_starving_victim_source()
    ssn = open_session(cache, FULL_TIERS, [])
    JaxPreemptAction().execute(ssn)  # must not raise
    jax_pipe = {
        f"{t.namespace}/{t.name}": t.node_name
        for job in ssn.jobs.values()
        for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values()
    }
    close_session(ssn)

    host_cache = _case_starving_victim_source()
    hssn = open_session(host_cache, FULL_TIERS, [])
    PreemptAction().execute(hssn)
    host_pipe = {
        f"{t.namespace}/{t.name}": t.node_name
        for job in hssn.jobs.values()
        for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values()
    }
    close_session(hssn)

    assert set(cache.evictor.evicts) == set(host_cache.evictor.evicts)
    assert jax_pipe == host_pipe


def test_preempt_f32_gate_covers_victims_and_future_idle():
    """ADVICE r3: the pallas-eligibility exactness gate must examine the
    preempt-specific lanes (vic_resreq, node_fi0), not just pk.base."""
    from volcano_tpu.ops.dispatch import preempt_f32_exact
    from volcano_tpu.ops.synthetic import generate_preempt_packed

    pk = generate_preempt_packed(n_victims=100, n_nodes=10, n_preemptors=10)
    assert preempt_f32_exact(pk)
    big = 2**24  # beyond the f32 floor-division envelope
    saved = pk.vic_resreq[0, 0]
    pk.vic_resreq[0, 0] = big
    assert not preempt_f32_exact(pk)
    pk.vic_resreq[0, 0] = saved
    assert preempt_f32_exact(pk)
    pk.node_fi0[0, 0] = big
    assert not preempt_f32_exact(pk)


def test_sensitive_gang_allowance_flips_mid_pass():
    """A victim job with 1 < minAvailable < running-count loses victims
    until the gang floor, then its remaining victims become protected —
    the allowance refresh fires mid-pass and must invalidate the
    kernel's cached masked plane (identical gang-replica preemptor rows
    keep the incremental fast path active around the flip)."""
    nodes = [build_node(f"n{i:03d}", {"cpu": "4", "memory": "8G"})
             for i in range(4)]
    pods, pgs = [], []
    queues = [build_queue("q1", weight=1)]
    # victim job: 4 running tasks, minAvailable 2 -> exactly 2 evictable
    pgs.append(build_pod_group("ns", "vic", 2, queue="q1"))
    for i in range(4):
        pods.append(build_pod("ns", f"vic-r{i}", f"n{i:03d}",
                              {"cpu": "3", "memory": "3G"},
                              phase="Running", group="vic", priority=0))
    # preemptor gang: 4 identical tasks (fast-path rows) wanting 3 nodes
    pgs.append(build_pod_group("ns", "pre", 2, queue="q1",
                               priority_class_name="high"))
    for i in range(4):
        pods.append(build_pod("ns", f"pre-{i}", "",
                              {"cpu": "2", "memory": "2G"},
                              group="pre", priority=100))
    cache = make_cache(
        nodes=nodes, pods=pods, pod_groups=pgs, queues=queues,
        priority_classes=[build_priority_class("high", 100)],
    )
    host_ev, host_pipe = _assert_case(cache)
    assert len(host_ev) == 2, host_ev  # gang floor protects the other two


DRF_TIERS = tiers(
    ["drf", "gang", "conformance"],
    ["priority", "predicates", "proportion", "nodeorder", "binpack"],
)


def _run_host_tiers(cache, tier_conf):
    ssn = open_session(cache, tier_conf, [])
    pk = pack_preempt_session(ssn)
    PreemptAction().execute(ssn)
    pipelined = {}
    for job in ssn.jobs.values():
        for t in job.task_status_index.get(TaskStatus.Pipelined, {}).values():
            pipelined[t.uid] = t.node_name
    close_session(ssn)
    return set(cache.evictor.evicts), pipelined, pk


def _case_drf_imbalance(seed=0):
    """A fat job hogging the cluster vs a starving skinny job in the
    same queue: DRF admits the fat job's tasks as victims (victim share
    stays above the preemptor's), without any PriorityClass involved.
    ``seed`` offsets the object uid/ts counters (builders are global),
    exercising different tie-break landscapes."""
    nodes = [build_node(f"n{i:03d}", {"cpu": "8", "memory": "16G"})
             for i in range(4)]
    pods, pgs = [], []
    queues = [build_queue("q1", weight=1)]
    # fat job: 12 running tasks saturating the cluster
    pgs.append(build_pod_group("ns", "fat", 1, queue="q1"))
    for i in range(12):
        pods.append(build_pod("ns", f"fat-r{i:02d}", f"n{i % 4:03d}",
                              {"cpu": "2", "memory": "2G"},
                              phase="Running", group="fat", priority=0))
    # skinny pending gang
    pgs.append(build_pod_group("ns", "skinny", 2, queue="q1"))
    for i in range(3):
        pods.append(build_pod("ns", f"skin-{i}", "",
                              {"cpu": "2", "memory": "2G"},
                              group="skinny", priority=0))
    return make_cache(nodes=nodes, pods=pods, pod_groups=pgs, queues=queues)


def test_drf_preemptable_dense_matches_host():
    """VERDICT r4 item 7: DRF-preemptable tiers run the dense
    formulation (not host fallback) with evictions/placements identical
    to the host action."""
    cache = _case_drf_imbalance()
    host_ev, host_pipe, pk = _run_host_tiers(cache, DRF_TIERS)
    assert pk.use_drf and not pk.use_prio
    dense_ev, dense_pipe = _dense_outcome(pk)
    assert dense_ev == host_ev
    assert dense_pipe == host_pipe
    assert host_ev, "scenario must actually evict through DRF"


def test_drf_preemptable_mixed_with_priority():
    """priority+drf in one tier: both filters intersect."""
    both = tiers(
        ["priority", "drf", "gang", "conformance"],
        ["predicates", "proportion", "nodeorder", "binpack"],
    )
    cache = _case_drf_imbalance(seed=2)
    host_ev, host_pipe, pk = _run_host_tiers(cache, both)
    assert pk.use_drf and pk.use_prio
    dense_ev, dense_pipe = _dense_outcome(pk)
    assert dense_ev == host_ev
    assert dense_pipe == host_pipe


def test_drf_preemptable_routes_dense_not_pallas():
    from volcano_tpu.ops.dispatch import select_preempt_executor

    cache = _case_drf_imbalance()
    ssn = open_session(cache, DRF_TIERS, [])
    pk = pack_preempt_session(ssn)
    close_session(ssn)
    # force past the small-area gate by checking the flag logic directly
    pk.base.n_tasks, pk.base.n_nodes = 10_000, 10_000
    assert select_preempt_executor(pk) == "dense"


def test_drf_critical_victims_participate_in_subtraction():
    """Conformance removes critical tasks from the EVICTION intersection
    but the host's DRF plugin still subtracts them in its running
    share arithmetic (each plugin scans the full preemptees list) —
    the dense replay must match: host and dense agree even when the
    critical task's subtraction flips a DRF admission."""
    nodes = [build_node("n000", {"cpu": "8", "memory": "16G"})]
    pods, pgs = [], []
    queues = [build_queue("q1", weight=1)]
    pgs.append(build_pod_group("ns", "fat", 1, queue="q1"))
    pods.append(build_pod("ns", "fat-a-crit", "n000",
                          {"cpu": "4", "memory": "4G"},
                          phase="Running", group="fat", priority=0,
                          labels={}))
    # mark critical via the annotation conformance checks
    pods[-1].metadata.annotations["scheduler.alpha.kubernetes.io/critical-pod"] = ""
    pods.append(build_pod("ns", "fat-b", "n000", {"cpu": "4", "memory": "4G"},
                          phase="Running", group="fat", priority=0))
    pgs.append(build_pod_group("ns", "skinny", 1, queue="q1"))
    pods.append(build_pod("ns", "skin-0", "", {"cpu": "2", "memory": "2G"},
                          group="skinny", priority=0))
    cache = make_cache(nodes=nodes, pods=pods, pod_groups=pgs, queues=queues)
    host_ev, host_pipe, pk = _run_host_tiers(cache, DRF_TIERS)
    dense_ev, dense_pipe = _dense_outcome(pk)
    assert dense_ev == host_ev
    assert dense_pipe == host_pipe


def test_make_preempt_dispatch_prestaged_matches_wrapper():
    # bench compute probe path: prestaged dispatch ≡ run_preempt_pallas
    import numpy as np

    from volcano_tpu.ops.preempt_pallas import (
        make_preempt_dispatch,
        run_preempt_pallas,
    )
    from volcano_tpu.ops.synthetic import generate_preempt_packed

    pk = generate_preempt_packed(n_victims=300, n_nodes=64, n_preemptors=64)
    want_ev, want_pipe = run_preempt_pallas(pk, interpret=True)

    made = make_preempt_dispatch(pk, interpret=True, prestage=True)
    assert made is not None
    dispatch, dims, vic_slot = made
    out = np.asarray(dispatch())
    out2 = np.asarray(dispatch())
    assert (out == out2).all()

    # unpack exactly like run_preempt_pallas
    from volcano_tpu.ops.preempt_pallas import LANES

    K, NS = dims["K"], dims["NS"]
    ev_planes = out[: K * NS].reshape(K, NS, LANES)
    pipe_flat = out[K * NS:].reshape(-1)
    V, P = pk.n_victims, pk.base.n_tasks
    sub = pk.vic_node[:V] // LANES
    lane = pk.vic_node[:V] % LANES
    got_ev = ev_planes[vic_slot[:V], sub, lane] > 0
    got_pipe = pipe_flat[:P].astype(np.int32)
    assert (want_ev == got_ev).all()
    assert (want_pipe == got_pipe).all()
