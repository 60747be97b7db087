"""Device kernel tests: score math goldens vs host plugins, and
bindings-equivalence of the packed session kernel vs the host allocate
path on identical snapshots (the north-star contract)."""

from __future__ import annotations

import numpy as np
import pytest

import volcano_tpu.scheduler.util as sched_util
from volcano_tpu.api import new_task_info, NodeInfo, TaskStatus
from volcano_tpu.ops import pack_session, run_packed, ScoreWeights
from volcano_tpu.ops.kernels import (
    balanced_resource_score,
    binpack_score,
    least_requested_score,
)
from volcano_tpu.plugins.binpack import bin_packing_score, PriorityWeight
from volcano_tpu.plugins.nodeorder import (
    balanced_resource_priority,
    least_requested_priority,
)

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, run_actions, tiers


def _host_score_inputs(ncpu, nmem, used_cpu, used_mem, req_cpu, req_mem):
    node = NodeInfo(build_node("n", {"cpu": str(ncpu), "memory": str(int(nmem))}))
    pod = build_pod("ns", "p", "", {"cpu": str(req_cpu), "memory": str(int(req_mem))})
    task = new_task_info(pod)
    if used_cpu or used_mem:
        filler = new_task_info(
            build_pod(
                "ns", "filler", "n",
                {"cpu": str(used_cpu), "memory": str(int(used_mem))},
                phase="Running",
            )
        )
        node.add_task(filler)
    return task, node


GI = 1024**3
MI = 1024**2


@pytest.mark.parametrize(
    "ncpu,nmem,used_cpu,used_mem,req_cpu,req_mem",
    [
        (4, 8 * GI, 0, 0, 1, 1 * GI),
        (4, 8 * GI, 2, 2 * GI, 1, 1 * GI),
        (16, 64 * GI, 7, 40 * GI, 3, 10 * GI),
        (2, 4 * GI, 1, 3 * GI, 1, 1 * GI),
        (8, 33 * GI + 512 * MI, 3, 7 * GI + 256 * MI, 1, 2 * GI + 128 * MI),
    ],
)
def test_score_goldens_match_host_plugins(ncpu, nmem, used_cpu, used_mem, req_cpu, req_mem):
    """Device closed-form scores == host plugin math on the same state.
    Device memory lanes are MiB-quantized (ops/packing.py), so the
    exactness contract covers MiB-aligned quantities."""
    task, node = _host_score_inputs(ncpu, nmem, used_cpu, used_mem, req_cpu, req_mem)

    resreq = np.array([[task.resreq.milli_cpu, task.resreq.memory / MI]], dtype=np.float32)
    used = np.array([[node.used.milli_cpu, node.used.memory / MI]], dtype=np.float32)
    alloc = np.array([[node.allocatable.milli_cpu, node.allocatable.memory / MI]], dtype=np.float32)

    host_bp = bin_packing_score(task, node, PriorityWeight())
    dev_bp = float(binpack_score(resreq, used, alloc, ScoreWeights())[0, 0])
    assert dev_bp == pytest.approx(host_bp, rel=1e-5)

    host_lr = least_requested_priority(
        node.used.milli_cpu + task.resreq.milli_cpu,
        node.used.memory + task.resreq.memory,
        node.allocatable.milli_cpu,
        node.allocatable.memory,
    )
    dev_lr = float(least_requested_score(resreq, used, alloc)[0, 0])
    assert dev_lr == host_lr

    host_ba = balanced_resource_priority(
        node.used.milli_cpu + task.resreq.milli_cpu,
        node.used.memory + task.resreq.memory,
        node.allocatable.milli_cpu,
        node.allocatable.memory,
    )
    dev_ba = float(balanced_resource_score(resreq, used, alloc)[0, 0])
    assert dev_ba == host_ba


def _host_bindings(cache):
    """Run the host allocate on the cache; return {task_key: node}."""
    from volcano_tpu.actions.allocate import AllocateAction

    sched_util._last_processed_node_index = 0
    run_actions(
        cache, [AllocateAction()], tiers(["gang"], ["drf", "predicates", "proportion", "nodeorder", "binpack"])
    )
    return dict(cache.binder.binds)


def _device_bindings(cache):
    """Pack the same snapshot, run the kernel, return {task_key: node}."""
    snapshot = cache.snapshot()
    jobs = sorted(snapshot.jobs.values(), key=lambda j: j.uid)
    tasks = []
    for job in jobs:
        # the host's task order (creation, then uid): uids come from a
        # per-process counter, so their string order alone can put a
        # later pod first
        pending = sorted(
            job.task_status_index.get(TaskStatus.Pending, {}).values(),
            key=lambda t: (t.creation_timestamp, t.uid),
        )
        tasks.extend(t for t in pending if not t.resreq.is_empty())
    nodes = [snapshot.nodes[name] for name in sorted(snapshot.nodes)]
    snap = pack_session(tasks, jobs, nodes)
    assignment = run_packed(snap)
    out = {}
    for i, t in enumerate(tasks):
        if assignment[i] >= 0:
            out[f"{t.namespace}/{t.name}"] = nodes[assignment[i]].name
    return out


def _mk_case(nodes, pods, pod_groups, queues):
    return make_cache(nodes=nodes, pods=pods, pod_groups=pod_groups, queues=queues)


def test_kernel_matches_host_simple_fill():
    args = dict(
        nodes=[
            build_node("n1", {"cpu": "4", "memory": "8G"}),
            build_node("n2", {"cpu": "4", "memory": "8G"}),
        ],
        pods=[
            build_pod("ns", f"p{i}", "", {"cpu": "1", "memory": "1G"}, group="pg1")
            for i in range(4)
        ],
        pod_groups=[build_pod_group("ns", "pg1", 0, queue="q")],
        queues=[build_queue("q")],
    )
    host = _host_bindings(_mk_case(**args))
    dev = _device_bindings(_mk_case(**args))
    assert host == dev
    assert len(host) == 4


def test_kernel_matches_host_gang_discard():
    """Gang job that cannot fully fit must bind nothing on both paths."""
    args = dict(
        nodes=[build_node("n1", {"cpu": "2", "memory": "4G"})],
        pods=[
            build_pod("ns", f"p{i}", "", {"cpu": "1", "memory": "1G"}, group="pg1")
            for i in range(3)
        ],
        pod_groups=[build_pod_group("ns", "pg1", 3, queue="q")],
        queues=[build_queue("q")],
    )
    host = _host_bindings(_mk_case(**args))
    dev = _device_bindings(_mk_case(**args))
    assert host == dev == {}


def test_kernel_matches_host_selector_and_taints():
    from volcano_tpu.apis import core

    def mk():
        return _mk_case(
            nodes=[
                build_node("n1", {"cpu": "8", "memory": "16G"}, labels={"disk": "ssd"}),
                build_node(
                    "n2", {"cpu": "8", "memory": "16G"},
                    taints=[core.Taint(key="gpu", value="yes", effect="NoSchedule")],
                ),
                build_node("n3", {"cpu": "8", "memory": "16G"}),
            ],
            pods=[
                build_pod("ns", "pssd", "", {"cpu": "1", "memory": "1G"},
                          group="pg1", selector={"disk": "ssd"}),
                build_pod("ns", "ptol", "", {"cpu": "1", "memory": "1G"}, group="pg1",
                          tolerations=[core.Toleration(key="gpu", value="yes", effect="NoSchedule")]),
                build_pod("ns", "plain", "", {"cpu": "1", "memory": "1G"}, group="pg1"),
            ],
            pod_groups=[build_pod_group("ns", "pg1", 0, queue="q")],
            queues=[build_queue("q")],
        )

    host = _host_bindings(mk())
    dev = _device_bindings(mk())
    assert host == dev
    assert host["ns/pssd"] == "n1"


def test_kernel_matches_host_single_job_heterogeneous():
    """One job over heterogeneous nodes: static kernel order == host order.
    (Multi-job dynamic interleave equivalence is covered through the
    jax-allocate action in tests/test_jax_allocate.py, which feeds the
    kernel the replayed host order.)"""
    nodes = [
        build_node(f"n{i}", {"cpu": str(4 + (i % 3) * 2), "memory": "16G"})
        for i in range(8)
    ]
    pods = [
        build_pod("ns", f"t{i}", "", {"cpu": "2", "memory": "2G"}, group="pg0")
        for i in range(9)
    ]
    args = dict(
        nodes=nodes,
        pods=pods,
        pod_groups=[build_pod_group("ns", "pg0", 2, queue="q")],
        queues=[build_queue("q")],
    )
    host = _host_bindings(_mk_case(**args))
    dev = _device_bindings(_mk_case(**args))
    assert host == dev


def test_kernel_respects_existing_usage():
    """Nodes with running pods: used/idle packed correctly."""
    def mk():
        cache = _mk_case(
            nodes=[
                build_node("n1", {"cpu": "4", "memory": "8G"}),
                build_node("n2", {"cpu": "4", "memory": "8G"}),
            ],
            pods=[
                build_pod("ns", "running", "n1", {"cpu": "3", "memory": "6G"},
                          phase="Running", group="pg0"),
                build_pod("ns", "new1", "", {"cpu": "2", "memory": "2G"}, group="pg1"),
            ],
            pod_groups=[
                build_pod_group("ns", "pg0", 1, queue="q"),
                build_pod_group("ns", "pg1", 1, queue="q"),
            ],
            queues=[build_queue("q")],
        )
        return cache

    host = _host_bindings(mk())
    dev = _device_bindings(mk())
    assert host == dev == {"ns/new1": "n2"}


# ---- Volcano's per-job commit/discard in the scan ----


def _episode_snapshot(resreq_cpu, task_job, min_available, n_nodes=2):
    """Rows of ``resreq_cpu`` milli-cpu (2 GiB each) in ORDER, their job
    rows, each job's minAvailable, over ``n_nodes`` identical nodes of
    1000m/2 GiB — one row a node (ties break to the lowest index)."""
    from volcano_tpu.ops.packing import PackedSnapshot

    T, J = len(resreq_cpu), len(min_available)
    snap = PackedSnapshot()
    snap.resource_names = ["cpu", "memory"]
    snap.tolerance = np.array([10.0, 10.0], dtype=np.float32)
    snap.n_tasks, snap.n_nodes, snap.n_jobs = T, n_nodes, J
    snap.task_resreq = np.array([[c, 2048.0] for c in resreq_cpu], dtype=np.float32)
    snap.task_job = np.array(task_job, dtype=np.int32)
    snap.task_sel_bits = np.zeros((T, 2), dtype=np.uint32)
    snap.task_tol_bits = np.zeros((T, 2), dtype=np.uint32)
    snap.node_idle = np.tile(
        np.array([[1000.0, 2048.0]], dtype=np.float32), (n_nodes, 1)
    )
    snap.node_used = np.zeros((n_nodes, 2), dtype=np.float32)
    snap.node_alloc = snap.node_idle.copy()
    snap.node_label_bits = np.zeros((n_nodes, 2), dtype=np.uint32)
    snap.node_taint_bits = np.zeros((n_nodes, 2), dtype=np.uint32)
    snap.node_ok = np.ones(n_nodes, dtype=bool)
    snap.node_task_count = np.zeros(n_nodes, dtype=np.int32)
    snap.node_max_tasks = np.full(n_nodes, 110, dtype=np.int32)
    snap.job_min_available = np.array(min_available, dtype=np.int32)
    snap.job_ready_count = np.zeros(J, dtype=np.int32)
    snap.task_has_preferences = np.zeros(T, dtype=bool)
    return snap


#: (rows' cpu, rows' jobs, minAvailable, nodes) → (assignment, placed)
EPISODES = {
    # b1 fits nowhere: gang B (min 2) rolls b0 back, and a0 takes the
    # node b0 held — the room a discarded gang frees reaches the next job
    "rollback_frees_room": (([1000, 50000, 1000], [1, 1, 0], [1, 2], 2),
                            ([-1, -1, 0], [0, -1, 0])),
    # a gang's first task fits nowhere: its other task is never tried
    "first_task_fails": (([50000, 1000, 1000], [1, 1, 0], [1, 2], 2),
                         ([-1, -1, 0], [-1, -2, 0])),
    # a ready job's task fails: the job ends, its later task (which would
    # fit) is skipped, and the next job still places
    "post_ready_rows_skipped": (([1000, 50000, 1000, 1000], [0, 0, 0, 1], [1, 1], 3),
                                ([0, -1, -1, 1], [0, -1, -2, 1])),
    # a gang's tasks run out before minAvailable: rolled back at its end
    "tasks_run_out_short": (([1000, 1000, 1000], [0, 0, 1], [3, 1], 2),
                            ([-1, -1, 0], [0, 1, 0])),
}


@pytest.mark.parametrize("formulation", ["plain", "native", "pallas"])
@pytest.mark.parametrize("case", sorted(EPISODES))
def test_episode_semantics(case, formulation):
    """Each formulation that takes multi-task gangs commits or discards
    per job in one pass, as drive_allocate_loop + gang_end_job do."""
    from volcano_tpu.ops.kernels import run_outcome

    args, (want_assignment, want_placed) = EPISODES[case]
    snap = _episode_snapshot(*args)
    if formulation == "native":
        from volcano_tpu import native

        if native.load() is None:
            pytest.skip("native executor unavailable (no g++)")
        out = native.baseline_outcome(snap)
    elif formulation == "pallas":
        from volcano_tpu.ops.pallas_session import run_outcome_pallas

        out = run_outcome_pallas(snap, block_size=128, interpret=True)
    else:
        out = run_outcome(snap)
    np.testing.assert_array_equal(out.assignment, want_assignment)
    np.testing.assert_array_equal(out.placed, want_placed)
    failed = np.asarray(want_placed) == -1
    # the failing step's reasons: every node short of cpu
    assert (out.reasons[failed][:, 0] == snap.n_nodes).all()
    assert (out.reasons[~failed] == 0).all()


# ---- score divisions rounded to nearest on every backend ----


def _division_operands():
    """Every (req + used, capacity) pair of the gang-backlog cell's node
    states, and random integer-valued pairs of the widths node lanes
    take (milli-cpu, MiB)."""
    rng = np.random.default_rng(7)
    used = np.arange(0, 4001, 500, dtype=np.float32)
    x = np.concatenate([
        (used[:, None] + np.array([500, 1000, 2000], np.float32)).ravel(),
        (np.arange(0, 32769, 1024, dtype=np.float32)[:, None]
         + np.array([2048, 4096, 8192], np.float32)).ravel(),
        rng.integers(0, 2**17, 4096).astype(np.float32),
    ])
    y = np.concatenate([
        np.full(27, 4000, np.float32), np.full(99, 32768, np.float32),
        rng.integers(1, 2**17, 4096).astype(np.float32),
    ])
    return x, y


@pytest.mark.parametrize("approx", ["reciprocal", "ulp_below", "ulp_above"])
def test_rn_correct_restores_ieee_division(approx):
    """The TPU divides by a reciprocal and a multiply, a few ulps off
    IEEE's quotient for the binpack lanes and balanced fractions
    (benchmark/tests/score_division_check.py counted 264 and 330 of 891
    cell states off on a v5e); rn_correct brings such a quotient back to
    the round-to-nearest one the CPU computes."""
    import jax.numpy as jnp

    from volcano_tpu.ops.kernels import div_rn, rn_correct

    x, y = _division_operands()
    want = x / y  # numpy float32: IEEE round to nearest
    q = {
        "reciprocal": x * (np.float32(1.0) / y),
        "ulp_below": np.nextafter(want, np.float32(-np.inf)),
        "ulp_above": np.nextafter(want, np.float32(np.inf)),
    }[approx].astype(np.float32)
    assert (q != want).any()
    got = np.asarray(rn_correct(jnp.asarray(x), jnp.asarray(y), jnp.asarray(q)))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    ieee = np.asarray(div_rn(jnp.asarray(x), jnp.asarray(y)))
    np.testing.assert_array_equal(ieee.view(np.uint32), want.view(np.uint32))
