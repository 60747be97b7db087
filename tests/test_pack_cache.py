"""Warm-pack equivalence: N random cache mutations followed by a delta
pack must produce tensors (and kernel bindings) identical to a cold
``pack_session`` seeded with the same bit registries — the PackCache's
correctness contract (ISSUE 2 tentpole).

Also covers: the delta metadata (previous snapshot + delta rows
reconstruct the new snapshot), the device stager (staged buffers match
the numpy planes), dirty-tracking granularity (status churn keeps task
rows clean; spec changes don't), and the snapshot clone pool.
"""

from __future__ import annotations

import copy

import numpy as np
import pytest

from volcano_tpu.actions.jax_allocate import (
    compute_task_order,
    JaxAllocateAction,
)
from volcano_tpu.apis import core
from volcano_tpu.framework import close_session, open_session
from volcano_tpu.ops.pack_cache import (
    JOB_PLANES,
    NODE_DYNAMIC_PLANES,
    NODE_STATIC_PLANES,
    PackCache,
    TASK_PLANES,
)
from volcano_tpu.ops.packing import BitRegistry, pack_session

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, tiers

STANDARD = lambda: tiers(
    ["priority", "gang"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)

ALL_PLANES = (
    TASK_PLANES
    + NODE_DYNAMIC_PLANES
    + NODE_STATIC_PLANES
    + JOB_PLANES
    + ("tolerance",)
)

META_FIELDS = (
    "n_tasks",
    "n_nodes",
    "n_jobs",
    "task_uids",
    "node_names",
    "job_uids",
    "resource_names",
    "needs_host_validation",
    "memory_exact",
)


def _copy_reg(reg: BitRegistry) -> BitRegistry:
    c = BitRegistry(reg.words)
    c.index = dict(reg.index)
    c.overflow = reg.overflow
    return c


def _session_inputs(ssn):
    ordered = compute_task_order(ssn)
    jobs = {}
    for t in ordered:
        j = ssn.jobs.get(t.job)
        if j is not None and j.uid not in jobs:
            jobs[j.uid] = j
    nodes = [ssn.nodes[name] for name in sorted(ssn.nodes)]
    return ordered, list(jobs.values()), nodes


def _pack_both(cache, pc):
    """One cycle: warm pack through the PackCache, then a cold pack
    seeded with the resulting registry dictionaries; returns (ssn, warm,
    cold).  Post-pack seeding makes the contract well-defined even when
    a cycle registers new pairs from both a dirty task and a dirty node
    (warm packs nodes first for relay overlap, cold packs tasks first —
    FIRST-registration order differs, the dictionary does not)."""
    ssn = open_session(cache, STANDARD(), [])
    ordered, jobs, nodes = _session_inputs(ssn)
    warm = pc.pack(ordered, jobs, nodes, ssn.pack_epoch, enforce_pod_count=True)
    cold = pack_session(
        ordered,
        jobs,
        nodes,
        label_registry=_copy_reg(pc.label_reg),
        taint_registry=_copy_reg(pc.taint_reg),
    )
    return ssn, warm, cold


def _assert_identical(warm, cold, ctx=""):
    for name in ALL_PLANES:
        a, b = getattr(warm, name), getattr(cold, name)
        assert np.array_equal(a, b), f"{ctx}: plane {name} diverged"
    for f in META_FIELDS:
        assert getattr(warm, f) == getattr(cold, f), f"{ctx}: {f}"


def _base_cluster(rng, n_jobs=8, gang=4, n_nodes=10):
    nodes = []
    for i in range(n_nodes):
        labels = {"zone": f"z{i % 3}"}
        if i % 4 == 0:
            labels["disk"] = "ssd"
        taints = (
            [core.Taint(key="dedicated", value="batch", effect="NoSchedule")]
            if i % 5 == 0
            else []
        )
        nodes.append(
            build_node(f"n{i:03d}", {"cpu": "32", "memory": "64Gi"},
                       labels=labels, taints=taints)
        )
    pods, pgs = [], []
    for j in range(n_jobs):
        pgs.append(build_pod_group("ns", f"pg{j}", gang, queue="q"))
        for i in range(gang):
            kwargs = {}
            if j % 3 == 0:
                kwargs["selector"] = {"zone": f"z{j % 3}"}
            if j % 4 == 0:
                kwargs["tolerations"] = [
                    core.Toleration(key="dedicated", operator="Exists")
                ]
            pods.append(
                build_pod("ns", f"j{j}-t{i}", "",
                          {"cpu": ["500m", "1", "2"][int(rng.randint(3))],
                           "memory": "1Gi"},
                          group=f"pg{j}", **kwargs)
            )
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])


def _mutate(cache, rng, step):
    """One random pack-relevant mutation through the cache event API."""
    kind = rng.randint(7)
    if kind == 0:
        # new gang job, selector may introduce a NEW label pair that
        # existing nodes already carry (back-patch coupling)
        j = f"new{step}"
        cache.add_pod_group(build_pod_group("ns", f"pg-{j}", 2, queue="q"))
        sel = {"disk": "ssd"} if step % 2 else {"zone": "z1"}
        for i in range(2):
            cache.add_pod(
                build_pod("ns", f"{j}-t{i}", "", {"cpu": "1", "memory": "1Gi"},
                          group=f"pg-{j}", selector=sel)
            )
    elif kind == 1:
        # spec-relevant pod update: bump a pending pod's request
        for job in cache.jobs.values():
            for t in job.tasks.values():
                if t.pod is not None and not t.node_name:
                    new = copy.deepcopy(t.pod)
                    new.spec.containers[0].resources = {
                        "requests": {"cpu": "3", "memory": "2Gi"}
                    }
                    cache.update_pod(t.pod, new)
                    return
    elif kind == 2:
        # status-only pod update (the warm path must keep the row clean)
        for job in cache.jobs.values():
            for t in job.tasks.values():
                if t.pod is not None and not t.node_name:
                    new = copy.deepcopy(t.pod)
                    new.status.phase = "Pending"
                    cache.update_pod(t.pod, new)
                    return
    elif kind == 3:
        # node update: new taint (keyed-Exists re-resolution coupling)
        name = sorted(cache.nodes)[int(rng.randint(len(cache.nodes)))]
        node = cache.nodes[name].node
        if node is None:
            return
        new = copy.deepcopy(node)
        new.spec.taints = [
            core.Taint(key="dedicated", value=f"v{step}", effect="NoSchedule")
        ]
        cache.update_node(node, new)
    elif kind == 4:
        # node update: label flip
        name = sorted(cache.nodes)[int(rng.randint(len(cache.nodes)))]
        node = cache.nodes[name].node
        if node is None:
            return
        new = copy.deepcopy(node)
        new.metadata.labels = dict(new.metadata.labels)
        new.metadata.labels["zone"] = f"z{int(rng.randint(4))}"
        cache.update_node(node, new)
    elif kind == 5:
        # bind a pending task (node accounting changes, task row clean)
        for job in cache.jobs.values():
            for t in list(job.tasks.values()):
                if not t.node_name:
                    host = sorted(cache.nodes)[int(rng.randint(len(cache.nodes)))]
                    try:
                        cache.bind(t, host)
                    except Exception:
                        pass
                    return
    else:
        # topology change: a brand-new node (wholesale node invalidation)
        cache.add_node(
            build_node(f"nx{step}", {"cpu": "16", "memory": "32Gi"},
                       labels={"zone": "z9"})
        )


def test_pack_cache_property_random_mutations():
    """The headline contract: after every mutation batch, the delta pack
    is bit-identical to a seeded cold pack, and the kernel bindings are
    identical on both."""
    rng = np.random.RandomState(7)
    cache = make_cache(**_base_cluster(rng))
    pc = PackCache(cache)

    ssn, warm, cold = _pack_both(cache, pc)
    _assert_identical(warm, cold, "cycle 0 (cold)")
    close_session(ssn)

    from volcano_tpu.ops.kernels import run_packed

    for cycle in range(1, 9):
        for _ in range(int(rng.randint(1, 4))):
            _mutate(cache, rng, cycle * 10 + int(rng.randint(10)))
        ssn, warm, cold = _pack_both(cache, pc)
        _assert_identical(warm, cold, f"cycle {cycle}")
        if cycle in (3, 8) and warm.n_tasks:
            assert np.array_equal(run_packed(warm), run_packed(cold))
        close_session(ssn)


def test_pack_cache_warm_reuses_rows_after_bind_churn():
    """Bind + status-only revert churn: node planes go dirty, task rows
    stay cached — the steady-state warm cycle."""
    rng = np.random.RandomState(3)
    cache = make_cache(**_base_cluster(rng, n_jobs=4, gang=3, n_nodes=6))
    pc = PackCache(cache)
    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    assert pc.last_stats["mode"] == "cold"

    # status-only churn on every pending pod
    for job in list(cache.jobs.values()):
        for t in list(job.tasks.values()):
            if t.pod is not None and not t.node_name:
                new = copy.deepcopy(t.pod)
                cache.update_pod(t.pod, new)

    ssn, warm, cold = _pack_both(cache, pc)
    _assert_identical(warm, cold, "status churn")
    close_session(ssn)
    assert pc.last_stats["mode"] == "warm"
    assert pc.last_stats["repacked_tasks"] == 0
    assert pc.last_stats["reused_tasks"] == warm.n_tasks


def test_delta_reconstructs_snapshot():
    """prev snapshot + PackDelta rows == new snapshot, plane by plane —
    the contract the device stager relies on."""
    rng = np.random.RandomState(11)
    cache = make_cache(**_base_cluster(rng))
    pc = PackCache(cache)
    ssn, warm0, _ = _pack_both(cache, pc)
    close_session(ssn)
    prev = {name: np.copy(getattr(warm0, name)) for name in ALL_PLANES}

    _mutate(cache, rng, 1)  # kind varies with seed; any non-topology works
    for job in list(cache.jobs.values()):
        for t in list(job.tasks.values()):
            if not t.node_name:
                try:
                    cache.bind(t, sorted(cache.nodes)[0])
                except Exception:
                    pass
                break
        break

    ssn, warm1, _ = _pack_both(cache, pc)
    close_session(ssn)
    if warm1.delta is None:
        pytest.skip("mutation forced a wholesale pack on this seed")
    for name in ALL_PLANES:
        new = getattr(warm1, name)
        if name not in warm1.delta.planes:
            assert np.array_equal(prev[name], new), name
            continue
        rows = warm1.delta.planes[name]
        if rows is None:
            continue  # wholesale plane — nothing to reconstruct
        rebuilt = prev[name].copy()
        rebuilt[rows] = new[rows]
        assert np.array_equal(rebuilt, new), name


def test_device_stager_matches_numpy_planes():
    from volcano_tpu.ops.device_stage import STAGED_PLANES, get_stager

    rng = np.random.RandomState(5)
    cache = make_cache(**_base_cluster(rng, n_jobs=3, gang=2, n_nodes=5))
    pc = PackCache(cache)
    for cycle in range(3):
        if cycle:
            _mutate(cache, rng, cycle)
        ssn, warm, _ = _pack_both(cache, pc)
        close_session(ssn)
        staged = get_stager(pc.key).stage(warm)
        for name in STAGED_PLANES:
            assert np.array_equal(np.asarray(staged[name]), getattr(warm, name)), (
                cycle,
                name,
            )


def test_out_of_order_epoch_packs_one_shot():
    rng = np.random.RandomState(9)
    cache = make_cache(**_base_cluster(rng, n_jobs=2, gang=2, n_nodes=4))
    pc = PackCache(cache)
    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    consumed = pc._consumed_rev

    class StaleEpoch:
        rev = consumed - 1
        topology_rev = 0
        dirty_tasks = set()
        dirty_nodes = set()

    ssn = open_session(cache, STANDARD(), [])
    ordered, jobs, nodes = _session_inputs(ssn)
    snap = pc.pack(ordered, jobs, nodes, StaleEpoch())
    close_session(ssn)
    assert snap.cache_key is None  # one-shot: not cacheable downstream
    assert pc._consumed_rev == consumed  # state untouched


def test_dirty_tracking_granularity():
    """Status-only churn keeps task rows clean; spec changes dirty them;
    binds dirty nodes; node adds bump the topology revision."""
    rng = np.random.RandomState(2)
    cache = make_cache(**_base_cluster(rng, n_jobs=2, gang=2, n_nodes=3))
    task = next(
        t
        for job in cache.jobs.values()
        for t in job.tasks.values()
        if t.pod is not None and not t.node_name
    )

    cache.clear_dirty_through(cache.snapshot().pack_epoch)
    new = copy.deepcopy(task.pod)
    new.status.phase = "Pending"
    cache.update_pod(task.pod, new)
    assert task.uid not in cache._dirty_tasks

    stored = cache.jobs[task.job].tasks[task.uid]
    new2 = copy.deepcopy(stored.pod)
    new2.spec.containers[0].resources = {"requests": {"cpu": "7", "memory": "1Gi"}}
    cache.update_pod(stored.pod, new2)
    assert task.uid in cache._dirty_tasks

    topo0 = cache._topology_rev
    stored = cache.jobs[task.job].tasks[task.uid]
    cache.bind(stored, sorted(cache.nodes)[0])
    assert sorted(cache.nodes)[0] in cache._dirty_nodes
    assert cache._topology_rev == topo0

    cache.add_node(build_node("late", {"cpu": "4", "memory": "8Gi"}))
    assert cache._topology_rev > topo0


def test_snapshot_clone_reuse_equivalence():
    """A pooled cache must produce snapshots identical to a cold-cloning
    cache across jax-allocate cycles with binds and churn: one case of
    the clone pool's harness (tests/test_snapshot_pool.py)."""
    from tests.test_snapshot_pool import late_job, run_equivalence

    rng = np.random.RandomState(4)
    cluster = _base_cluster(rng, n_jobs=5, gang=3, n_nodes=6)
    run_equivalence([JaxAllocateAction()], cluster, cycles=4,
                    churn=late_job, tier_conf=STANDARD)


def test_kernels_identical_with_staged_planes():
    """run_packed / run_packed_blocked consume staged device planes and
    must produce the same assignment as the pure-numpy path."""
    from volcano_tpu.ops.blocked import run_packed_blocked
    from volcano_tpu.ops.device_stage import get_stager
    from volcano_tpu.ops.kernels import run_packed

    rng = np.random.RandomState(13)
    cache = make_cache(**_base_cluster(rng, n_jobs=6, gang=3, n_nodes=8))
    pc = PackCache(cache)
    ssn, warm, _ = _pack_both(cache, pc)
    close_session(ssn)

    plain_scan = run_packed(warm)
    plain_blocked = run_packed_blocked(warm)
    warm.device_planes = get_stager(pc.key).stage(warm)
    np.testing.assert_array_equal(run_packed(warm), plain_scan)
    np.testing.assert_array_equal(run_packed_blocked(warm), plain_blocked)


def test_device_stage_failure_falls_back_to_numpy_planes(monkeypatch):
    """A device stager that fails mid-cycle leaves jax-allocate on the
    numpy planes: the demotion counts as device-stage → host, and the
    binds equal those of a cycle whose staging worked."""
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops import device_stage

    key = ("volcano_executor_fallbacks_total",
           (("cause", "error"), ("from", "device-stage"), ("to", "host")))
    real = device_stage.get_stager
    staged = []

    class Broken:
        def __init__(self, cache_key):
            self.inner = real(cache_key)

        def prestage(self, *args):
            return self.inner.prestage(*args)

        def stage(self, snap):
            staged.append(snap.cache_key)
            raise RuntimeError("injected staging failure")

    def binds():
        rng = np.random.RandomState(21)
        cache = make_cache(**_base_cluster(rng, n_jobs=6, gang=3, n_nodes=8))
        ssn = open_session(cache, STANDARD(), [])
        try:
            assert ssn.pack_epoch is not None
            JaxAllocateAction().execute(ssn)
        finally:
            close_session(ssn)
        return dict(cache.binder.binds)

    want = binds()
    before = metrics.registry._counters.get(key, 0)
    monkeypatch.setattr(device_stage, "get_stager", Broken)
    got = binds()
    assert staged  # the stager was reached, and failed
    assert metrics.registry._counters.get(key, 0) == before + 1
    assert got and got == want


def test_new_label_pair_back_patches_clean_nodes():
    """A dirty task registering a NEW selector pair must set the bit on
    every CLEAN node carrying that label — the cold pack's task-pass →
    node-pass ordering, reproduced via the inverted label index."""
    rng = np.random.RandomState(0)
    cluster = _base_cluster(rng, n_jobs=2, gang=2, n_nodes=8)
    cache = make_cache(**cluster)
    pc = PackCache(cache)
    ssn, _, _ = _pack_both(cache, pc)
    close_session(ssn)
    assert ("disk", "ssd") not in pc.label_reg.index  # nothing references it yet

    cache.add_pod_group(build_pod_group("ns", "ssdjob", 1, queue="q"))
    cache.add_pod(
        build_pod("ns", "ssdjob-t0", "", {"cpu": "1", "memory": "1Gi"},
                  group="ssdjob", selector={"disk": "ssd"})
    )
    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    _assert_identical(warm, cold, "label back-patch")
    assert pc.last_stats["mode"] == "warm"
    idx = pc.label_reg.index[("disk", "ssd")]
    word, bit = idx // 32, np.uint32(1 << (idx % 32))
    ssd_rows = [i for i, name in enumerate(warm.node_names) if i % 4 == 0]
    assert ssd_rows and all(
        warm.node_label_bits[i, word] & bit for i in ssd_rows
    )
    # and the patch is visible in the delta so device copies heal
    assert warm.delta is not None
    rows = warm.delta.planes.get("node_label_bits")
    assert rows is None or set(ssd_rows) <= set(rows.tolist())


def test_new_taint_reresolves_clean_exists_tolerations():
    """A dirty node registering a NEW taint pair must reach CLEAN tasks
    holding keyed-Exists tolerations on that key."""
    rng = np.random.RandomState(0)
    cluster = _base_cluster(rng, n_jobs=4, gang=2, n_nodes=6)
    cache = make_cache(**cluster)
    pc = PackCache(cache)
    ssn, _, _ = _pack_both(cache, pc)
    close_session(ssn)

    node = cache.nodes[sorted(cache.nodes)[1]].node
    new = copy.deepcopy(node)
    new.spec.taints = [
        core.Taint(key="dedicated", value="fresh", effect="NoSchedule")
    ]
    cache.update_node(node, new)

    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    _assert_identical(warm, cold, "taint re-resolve")
    assert pc.last_stats["mode"] == "warm"
    idx = pc.taint_reg.index[("dedicated", "fresh", "NoSchedule")]
    word, bit = idx // 32, np.uint32(1 << (idx % 32))
    # every j%4==0 task tolerates Exists "dedicated" → bit must be set
    exists_rows = [
        i for i, uid in enumerate(warm.task_uids)
        if uid in pc._exists_uids
    ]
    assert exists_rows and all(
        warm.task_tol_bits[i, word] & bit for i in exists_rows
    )


def test_registry_overflow_recovers_via_cold_rebuild():
    """Pair churn across the cache lifetime must not permanently latch
    needs_host_validation: an overflowed registry forces one cold pack
    that rebuilds fresh registries from the live session."""
    rng = np.random.RandomState(17)
    cache = make_cache(**_base_cluster(rng, n_jobs=2, gang=2, n_nodes=4))
    pc = PackCache(cache)
    ssn, warm, _ = _pack_both(cache, pc)
    close_session(ssn)
    assert not warm.needs_host_validation

    # poison: registry saturated by pairs no live object references
    for i in range(pc.label_reg.words * 32 + 5):
        pc.label_reg.bit(("ghost", str(i)))
    assert pc.label_reg.overflow

    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    assert pc.last_stats["mode"] == "cold"  # overflow forced the rebuild
    assert not pc.label_reg.overflow
    assert not warm.needs_host_validation
    _assert_identical(warm, cold, "post-overflow rebuild")


def test_micro_pack_on_task_bucket_change():
    """Task-bucket crossings no longer force a cold pack: the micro
    path rebuilds only the task planes fresh (warm node planes,
    persistent registries) and stays bit-identical to a seeded cold
    pack — the subset-pack half of the event-driven micro-cycle
    (ISSUE 8)."""
    from volcano_tpu.ops.kernels import run_packed

    rng = np.random.RandomState(11)
    cache = make_cache(**_base_cluster(rng, n_jobs=8, gang=4))  # 32 pending
    pc = PackCache(cache)
    ssn, warm, cold = _pack_both(cache, pc)
    close_session(ssn)
    assert pc.last_stats["mode"] == "cold"
    assert pc.last_stats["cold_cause"] == "first-pack"

    # grow the pending set past the 64-row bucket: + 20 two-task jobs,
    # some with NEW label pairs (the back-patch coupling must reach the
    # warm node planes)
    for k in range(20):
        cache.add_pod_group(build_pod_group("ns", f"burst{k}", 2, queue="q"))
        sel = {"disk": "ssd"} if k % 3 == 0 else None
        for i in range(2):
            cache.add_pod(
                build_pod("ns", f"burst{k}-t{i}", "",
                          {"cpu": "1", "memory": "1Gi"},
                          group=f"burst{k}", selector=sel)
            )
    ssn, micro, cold = _pack_both(cache, pc)
    _assert_identical(micro, cold, "bucket grow (micro)")
    assert pc.last_stats["mode"] == "micro"
    assert micro.task_resreq.shape[0] == 128
    assert np.array_equal(run_packed(micro), run_packed(cold))
    close_session(ssn)

    # shrink back under the bucket (delete the burst) — micro again,
    # and the NEXT unchanged cycle is a plain warm pack over the
    # micro-produced base
    burst_pods = [
        t.pod
        for j in list(cache.jobs.values())
        for t in list(j.tasks.values())
        if t.name.startswith("burst") and t.pod is not None
    ]
    for pod in burst_pods:
        cache.delete_pod(pod)
    ssn, micro2, cold2 = _pack_both(cache, pc)
    _assert_identical(micro2, cold2, "bucket shrink (micro)")
    assert pc.last_stats["mode"] == "micro"
    assert micro2.task_resreq.shape[0] == 64
    close_session(ssn)

    ssn, warm2, cold3 = _pack_both(cache, pc)
    _assert_identical(warm2, cold3, "steady (warm over micro base)")
    assert pc.last_stats["mode"] == "warm"
    close_session(ssn)


def test_micro_pack_device_stager_consistency():
    """Staged device planes equal the numpy planes across a micro pack
    (task planes restaged wholesale at the new bucket, node planes
    delta-scattered through the padded-bucket scatter)."""
    import jax.numpy as jnp

    from volcano_tpu.ops.device_stage import get_stager, STAGED_PLANES

    rng = np.random.RandomState(13)
    cache = make_cache(**_base_cluster(rng, n_jobs=6, gang=4))
    pc = PackCache(cache)
    ssn, warm, _cold = _pack_both(cache, pc)
    stager = get_stager(pc.key)
    stager.stage(warm)
    close_session(ssn)

    for k in range(24):
        cache.add_pod_group(build_pod_group("ns", f"m{k}", 2, queue="q"))
        for i in range(2):
            cache.add_pod(
                build_pod("ns", f"m{k}-t{i}", "",
                          {"cpu": "1", "memory": "1Gi"}, group=f"m{k}")
            )
    ssn, micro, _cold = _pack_both(cache, pc)
    assert pc.last_stats["mode"] == "micro"
    planes = stager.stage(micro)
    for name in STAGED_PLANES:
        arr = getattr(micro, name)
        if arr is None:
            continue
        assert np.array_equal(np.asarray(planes[name]), arr), (
            f"staged plane {name} diverged after micro pack"
        )
    close_session(ssn)


def test_cold_cause_recorded():
    """PackCache.last_stats names why a pack went cold — the label the
    micro-cycle fallback counter attributes."""
    rng = np.random.RandomState(17)
    cache = make_cache(**_base_cluster(rng, n_jobs=4, gang=3, n_nodes=6))
    pc = PackCache(cache)
    ssn, _, _ = _pack_both(cache, pc)
    close_session(ssn)
    assert pc.last_stats["cold_cause"] == "first-pack"

    # registry overflow → cold with the overflow cause
    pc.label_reg.overflow = True
    ssn, warm, cold = _pack_both(cache, pc)
    _assert_identical(warm, cold, "overflow recovery")
    assert pc.last_stats["mode"] == "cold"
    assert pc.last_stats["cold_cause"] == "registry-overflow"
    close_session(ssn)

    # node topology change → cold with the topology cause
    cache.add_node(build_node("fresh-node", {"cpu": "8", "memory": "16Gi"}))
    ssn, warm, cold = _pack_both(cache, pc)
    _assert_identical(warm, cold, "topology rebuild")
    assert pc.last_stats["mode"] == "cold"
    assert pc.last_stats["cold_cause"] == "topology"
    close_session(ssn)
