"""SchedulerCache.bind_batch marks each job and node once per batch.

A batch applies the same per-task mutations as per-task ``bind`` and
then marks every distinct job and node it touched, so the share
ledger's totals must come out exactly what per-task binds, and a fresh
sweep of the cache, give — equality, not tolerance."""

from __future__ import annotations

import copy
import itertools

import pytest

import volcano_tpu.actions.jax_allocate as ja
from volcano_tpu import trace
from volcano_tpu.actions.jax_allocate import JaxAllocateAction
from volcano_tpu.api import TaskStatus
from volcano_tpu.framework import open_session
from volcano_tpu.incremental.shares import ShareLedger
from volcano_tpu.trace import TraceRecorder

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, tiers

GPU = "nvidia.com/gpu"


def _cluster(sizes, n_nodes=8):
    """Gangs of ``sizes`` workers, pending; job ``j`` in queue ``shared``
    when even and in a queue of its own when odd (a one-job queue is
    retracted and re-added by every observe), in one of two namespaces.
    Job 0 also has a Running worker, so its allocated total starts above
    zero.  Every odd worker asks a GPU, so the scalar sums move too."""
    nodes = [build_node(f"n{i}", {"cpu": "64", "memory": "256Gi", GPU: "8"})
             for i in range(n_nodes)]
    queues = [build_queue("shared")]
    pods, pgs = [], []
    for j, size in enumerate(sizes):
        queue = "shared" if j % 2 == 0 else f"solo{j}"
        if j % 2:
            queues.append(build_queue(queue))
        ns = f"ns{j % 2}"
        pgs.append(build_pod_group(ns, f"pg{j}", size, queue=queue))
        for i in range(size):
            req = {"cpu": ["250m", "1", "1500m"][i % 3], "memory": f"{i + 1}Gi"}
            if i % 2:
                req[GPU] = "1"
            pods.append(build_pod(ns, f"j{j}-w{i}", "", req, group=f"pg{j}"))
        if j == 0:
            pods.append(build_pod(ns, "j0-running", "n0", {"cpu": "2", "memory": "2Gi"},
                                  phase="Running", group="pg0"))
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=queues)


def _pairs(cache, n_nodes=8, interleave=False):
    """(task, hostname) for every pending task, job by job in creation
    order, or round-robin over the jobs with ``interleave``."""
    per_job = [
        [t for t in job.tasks.values() if not t.node_name]
        for job in sorted(cache.jobs.values(), key=lambda j: j.name)
    ]
    if interleave:
        rows = itertools.zip_longest(*per_job)
        tasks = [t for row in rows for t in row if t is not None]
    else:
        tasks = [t for row in per_job for t in row]
    return [(t, f"n{i % n_nodes}") for i, t in enumerate(tasks)]


def _bits(r):
    """A Resource as exact bits: cpu, memory and every scalar key."""
    return (r.milli_cpu.hex(), r.memory.hex(),
            tuple((k, v.hex()) for k, v in r.scalars.items()))


def _seed_bits(ledger):
    """The ledger's seed, ordered and bit-exact."""
    seed = ledger.seed()
    return (
        [(q, _bits(a), _bits(r)) for q, (a, r) in seed.queues.items()],
        [(ns, _bits(a)) for ns, a in seed.namespaces.items()],
    )


def _assert_ledger_is_a_fresh_sweep(cache):
    """The ledger equals one that observed every cached job once."""
    fresh = ShareLedger()
    for uid, job in cache.jobs.items():
        fresh.observe(job, uid)
    got, want = cache.share_ledger.seed(), fresh.seed()
    assert got.queues == want.queues
    assert got.namespaces == want.namespaces
    assert cache.share_ledger.schedulable_uids() == fresh.schedulable_uids()
    assert cache.share_ledger.resident_count == fresh.resident_count


@pytest.mark.parametrize("sizes,interleave", [
    ((1,), False),
    ((4,), False),
    ((8,), False),
    ((16,), False),
    ((16, 16, 8, 4, 1, 1, 4), False),
    ((16, 16, 8, 4, 1, 1, 4), True),
], ids=["gang1", "gang4", "gang8", "gang16", "mixed", "mixed-interleaved"])
def test_bind_batch_ledger_equals_sweep_and_per_task_binds(sizes, interleave):
    cluster = _cluster(sizes)
    cache = make_cache(**copy.deepcopy(cluster))
    twin = make_cache(**copy.deepcopy(cluster))
    pairs = _pairs(cache, interleave=interleave)
    jobs = {t.job for t, _h in pairs}
    hosts = {h for _t, h in pairs}
    rev0 = cache._rev

    assert cache.bind_batch(pairs) == (len(pairs), len(jobs), len(hosts))
    for task, host in pairs:
        twin.bind(twin.jobs[task.job].tasks[task.uid], host)
    cache.flush()
    twin.flush()

    _assert_ledger_is_a_fresh_sweep(cache)
    # per-task binds leave the same totals, bit for bit and in the same
    # queue and namespace order
    assert _seed_bits(cache.share_ledger) == _seed_bits(twin.share_ledger)
    assert cache.share_ledger.schedulable_uids() == twin.share_ledger.schedulable_uids()
    assert cache.binder.binds == twin.binder.binds
    for name, node in cache.nodes.items():
        assert _bits(node.idle) == _bits(twin.nodes[name].idle), name
        assert list(node.tasks) == list(twin.nodes[name].tasks), name
    # every job and node of the batch is stamped past the revision the
    # batch started from, which the pack cache and clone pool compare to
    assert all(cache._job_mut_rev[uid] > rev0 for uid in jobs)
    assert all(cache._dirty_nodes[h] > rev0 for h in hosts)
    assert all(cache._node_mut_rev[h] > rev0 for h in hosts)


def test_bind_batch_span_counts_distinct_jobs_and_nodes():
    """The span carries the batch's binds and the jobs and nodes it
    marked: a gang of 16 on 8 nodes is 16 binds, 1 job, 8 nodes."""
    cache = make_cache(**_cluster((16, 4)))
    pairs = _pairs(cache)
    rec = TraceRecorder()
    trace.set_recorder(rec)
    try:
        rec.begin_cycle()
        cache.bind_batch(pairs)
        rec.end_cycle()
    finally:
        trace.set_recorder(None)
    (event,) = [e for e in rec.last_cycle()["events"]
                if e["name"] == "vtpu/cache.bind_batch"]
    assert event["args"] == {"binds": 20, "jobs": 2, "nodes": 8}


def test_bulk_commit_reports_marks_in_phase_stats():
    """jax-allocate's bulk commit reports the batch's counts: one mark
    per gang, however many workers it binds."""
    cluster = _cluster((4, 8, 1, 4), n_nodes=4)
    cache = make_cache(**cluster)
    ssn = open_session(cache, tiers(["priority", "gang"],
                                    ["drf", "predicates", "proportion", "nodeorder"]), [])
    JaxAllocateAction().execute(ssn)
    binding = [t for job in cache.jobs.values() for t in job.tasks.values()
               if t.status == TaskStatus.Binding]
    stats = ja.last_phase_stats
    assert len(binding) == stats["commit_binds"] == 17
    assert stats["commit_jobs_marked"] == 4
    assert stats["commit_nodes_marked"] == len({t.node_name for t in binding})
    _assert_ledger_is_a_fresh_sweep(cache)


def test_bind_batch_raise_part_way_marks_what_it_mutated():
    """A node out of sync with its tasks makes ``add_task`` raise in
    the middle of the loop: the error propagates, every job and node the
    loop touched is marked (the ledger equals a fresh sweep of the cache
    as it stands), the rest is not, and no binder effect is dispatched."""
    cluster = _cluster((4, 8, 4))
    cache = make_cache(**cluster)
    pairs = _pairs(cache)
    bad = 6  # job 1's third worker
    # the node's idle no longer covers the task it is handed
    cache.nodes[pairs[bad][1]].idle.milli_cpu = 0.0
    rev0 = cache._rev

    with pytest.raises(ValueError, match="NotReady"):
        cache.bind_batch(pairs)
    cache.flush()

    assert cache.binder.binds == {}
    touched = pairs[: bad + 1]
    for task, host in touched:
        assert cache._job_mut_rev[task.job] > rev0, task.job
        assert cache._dirty_nodes[host] > rev0, host
    untouched_job = pairs[-1][0].job
    assert untouched_job not in {t.job for t, _h in touched}
    assert cache._job_mut_rev.get(untouched_job, rev0) <= rev0
    _assert_ledger_is_a_fresh_sweep(cache)
