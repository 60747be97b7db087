"""Compute-plane boundary: wire round-trips, sidecar-served sessions
identical to in-process, and fallback-to-in-process when the sidecar
dies (the north-star process separation, SURVEY §7)."""

from __future__ import annotations

import os

import numpy as np
import pytest

from volcano_tpu.ops import executor as executor_mod
from volcano_tpu.ops.dispatch import run_packed_auto
from volcano_tpu.ops.synthetic import generate_preempt_packed, generate_snapshot
from volcano_tpu.serving.compute_plane import (
    ComputePlaneClient,
    ComputePlaneServer,
    deserialize_preempt,
    deserialize_snapshot,
    serialize_preempt,
    serialize_snapshot,
)


@pytest.fixture
def sock_path(tmp_path):
    return str(tmp_path / "cp.sock")


@pytest.fixture
def sidecar(sock_path):
    server = ComputePlaneServer(sock_path).start()
    yield server
    server.stop()


@pytest.fixture(autouse=True)
def _reset_executor():
    yield
    executor_mod.configure(None)


def test_snapshot_serialization_roundtrip():
    snap = generate_snapshot(n_tasks=200, n_nodes=50, gang_size=4, seed=1,
                             label_classes=3, taint_fraction=0.2)
    back, _ = deserialize_snapshot(serialize_snapshot(snap))
    assert back.n_tasks == snap.n_tasks and back.n_jobs == snap.n_jobs
    assert back.resource_names == snap.resource_names
    np.testing.assert_array_equal(back.task_resreq, snap.task_resreq)
    np.testing.assert_array_equal(back.node_taint_bits, snap.node_taint_bits)
    assert (run_packed_auto(back) == run_packed_auto(snap)).all()


def test_preempt_serialization_roundtrip():
    from volcano_tpu.ops.preempt_pack import preempt_dense

    pk = generate_preempt_packed(n_victims=400, n_nodes=40, n_preemptors=60)
    back = deserialize_preempt(serialize_preempt(pk))
    ev_a, pipe_a = preempt_dense(pk)
    ev_b, pipe_b = preempt_dense(back)
    np.testing.assert_array_equal(ev_a, ev_b)
    np.testing.assert_array_equal(pipe_a, pipe_b)


def test_sidecar_allocate_identical(sidecar, sock_path):
    client = ComputePlaneClient(sock_path)
    assert client.health()
    snap = generate_snapshot(n_tasks=300, n_nodes=60, gang_size=4, seed=2)
    remote = client.allocate(snap)
    local = run_packed_auto(snap)
    np.testing.assert_array_equal(remote, local)
    client.close()


def test_sidecar_preempt_identical(sidecar, sock_path):
    from volcano_tpu.ops.preempt_pack import preempt_dense

    client = ComputePlaneClient(sock_path)
    pk = generate_preempt_packed(n_victims=300, n_nodes=30, n_preemptors=50)
    ev_r, pipe_r = client.preempt(pk)
    ev_l, pipe_l = preempt_dense(pk)
    np.testing.assert_array_equal(ev_r, ev_l)
    np.testing.assert_array_equal(pipe_r, pipe_l)
    client.close()


def test_executor_uses_sidecar_then_falls_back(sidecar, sock_path):
    """The e2e fallback contract: sessions flow through the sidecar while
    it lives; killing it degrades to in-process with identical results
    and NO error escaping the action."""
    executor_mod.configure(sock_path)
    snap = generate_snapshot(n_tasks=256, n_nodes=40, gang_size=4, seed=3)
    via_sidecar = executor_mod.execute_allocate(snap)
    local = run_packed_auto(snap)
    np.testing.assert_array_equal(via_sidecar, local)

    sidecar.stop()  # sidecar dies mid-life
    after_death = executor_mod.execute_allocate(snap)
    np.testing.assert_array_equal(after_death, local)


def test_scheduler_behind_sidecar_falls_back_on_host(sock_path, monkeypatch):
    """The sidecar holds the chip: a vtpu-scheduler started with
    VTPU_COMPUTE_PLANE and no explicit JAX_PLATFORMS pins itself to the
    CPU before touching JAX, stages nothing on a device, and with the
    sidecar down still runs its exact in-process fallback on the host."""
    import jax

    from volcano_tpu.cmd import scheduler as sched_cmd
    from volcano_tpu.faults.breaker import reset_breakers

    monkeypatch.setenv("VTPU_COMPUTE_PLANE", sock_path)  # nothing listens
    monkeypatch.setattr(sched_cmd, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(sched_cmd, "SchedulerDaemon", lambda *a, **k: None)
    monkeypatch.setattr(sched_cmd, "serve_forever", lambda daemon: 0)
    jax.config.update("jax_platforms", "tpu,cpu")  # as if unpinned
    try:
        assert sched_cmd.main([]) == 0
        assert jax.config.jax_platforms == "cpu"
        executor_mod.configure(sock_path)
        assert not executor_mod.owns_device()
        snap = generate_snapshot(n_tasks=64, n_nodes=16, gang_size=4, seed=4)
        np.testing.assert_array_equal(
            executor_mod.execute_allocate(snap), run_packed_auto(snap)
        )
        assert executor_mod.last_allocate_executor() != "auto"  # local
    finally:
        jax.config.update("jax_platforms", "cpu")
        reset_breakers()  # the failed probe opened compute-plane's
        executor_mod.configure(None)
    assert executor_mod.owns_device()


def test_action_through_sidecar_binds_identically(sidecar, sock_path, tmp_path):
    """Full framework path over the boundary: the jax-allocate action
    with the kernel executed in the SIDECAR process boundary produces
    bindings identical to the in-process run."""
    import copy
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    from tests.builders import build_node, build_pod, build_pod_group, build_queue
    from tests.scheduler_helpers import make_cache, run_actions, tiers
    from volcano_tpu.actions.jax_allocate import JaxAllocateAction

    def cluster():
        nodes = [build_node(f"n{i}", {"cpu": "8", "memory": "32Gi"}) for i in range(4)]
        pods, pgs = [], []
        for j in range(5):
            pgs.append(build_pod_group("ns", f"pg{j}", 3, queue="q"))
            for i in range(3):
                pods.append(
                    build_pod("ns", f"j{j}-t{i}", "", {"cpu": "1", "memory": "2Gi"}, group=f"pg{j}")
                )
        return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=[build_queue("q")])

    c = cluster()
    tier_conf = tiers(["priority", "gang"],
                      ["drf", "predicates", "proportion", "nodeorder", "binpack"])

    executor_mod.configure(sock_path)
    cache_remote = make_cache(**copy.deepcopy(c))
    run_actions(cache_remote, [JaxAllocateAction()], tier_conf)

    executor_mod.configure(None)
    cache_local = make_cache(**copy.deepcopy(c))
    run_actions(cache_local, [JaxAllocateAction()], tier_conf)

    assert dict(cache_remote.binder.binds) == dict(cache_local.binder.binds)
    assert len(cache_remote.binder.binds) == 15


def test_delta_serialize_apply_roundtrip():
    """serialize_delta → apply_delta reproduces the new snapshot from the
    server-held base, plane by plane (no socket involved)."""
    import copy as _copy

    from volcano_tpu.ops.pack_cache import PackCache
    from volcano_tpu.serving.compute_plane import (
        _unpack_arrays,
        apply_delta,
        serialize_delta,
    )
    from tests.test_pack_cache import _base_cluster, _pack_both
    from tests.scheduler_helpers import make_cache
    from volcano_tpu.framework import close_session

    rng = np.random.RandomState(21)
    cache = make_cache(**_base_cluster(rng, n_jobs=4, gang=2, n_nodes=5))
    pc = PackCache(cache)
    ssn, snap1, _ = _pack_both(cache, pc)
    close_session(ssn)
    base = _copy.deepcopy(snap1)

    # churn: bind one task (node delta) + a spec change (task delta)
    for job in cache.jobs.values():
        for t in list(job.tasks.values()):
            if not t.node_name:
                cache.bind(t, sorted(cache.nodes)[0])
                break
        break
    ssn, snap2, _ = _pack_both(cache, pc)
    close_session(ssn)
    assert snap2.delta is not None and snap2.delta.base_rev == snap1.rev

    meta, arrays = _unpack_arrays(serialize_delta(snap2))
    rebuilt = apply_delta(base, meta, arrays)
    from volcano_tpu.serving.compute_plane import _SNAP_ARRAYS

    for name in _SNAP_ARRAYS:
        np.testing.assert_array_equal(
            getattr(rebuilt, name), getattr(snap2, name), err_msg=name
        )
    assert rebuilt.needs_host_validation == snap2.needs_host_validation
    assert rebuilt.memory_exact == snap2.memory_exact


def test_sidecar_delta_frames_identical(sidecar, sock_path):
    """Warm sessions ship delta frames: the sidecar applies the scatter
    to its held snapshot and returns assignments identical to the local
    kernel; a revision mismatch degrades to a full frame (T_NEED_FULL),
    never a wrong answer."""
    from volcano_tpu.framework import close_session
    from volcano_tpu.ops.pack_cache import PackCache
    from tests.test_pack_cache import _base_cluster, _pack_both
    from tests.scheduler_helpers import make_cache

    rng = np.random.RandomState(22)
    cache = make_cache(**_base_cluster(rng, n_jobs=5, gang=3, n_nodes=6))
    pc = PackCache(cache)
    client = ComputePlaneClient(sock_path)

    ssn, snap1, _ = _pack_both(cache, pc)
    close_session(ssn)
    np.testing.assert_array_equal(client.allocate(snap1), run_packed_auto(snap1))
    assert client._acked[pc.key] == snap1.rev  # server seeded

    # warm cycle: churn then delta frame
    for job in cache.jobs.values():
        for t in list(job.tasks.values()):
            if not t.node_name:
                cache.bind(t, sorted(cache.nodes)[1])
                break
        break
    ssn, snap2, _ = _pack_both(cache, pc)
    close_session(ssn)
    assert snap2.delta is not None
    np.testing.assert_array_equal(client.allocate(snap2), run_packed_auto(snap2))
    assert client._acked[pc.key] == snap2.rev

    # revision-mismatch path: claim a base the server does not hold
    ssn, snap3, _ = _pack_both(cache, pc)
    close_session(ssn)
    client._acked[pc.key] = snap3.delta.base_rev + 1000  # force skew...
    # ...which suppresses the delta attempt; instead, force a delta send
    # against a wrong server-side revision:
    client._acked[pc.key] = snap3.delta.base_rev
    from volcano_tpu.serving import compute_plane as cp

    cp._session_store.put(pc.key, snap3.delta.base_rev - 1, snap2)
    np.testing.assert_array_equal(client.allocate(snap3), run_packed_auto(snap3))
    assert client._acked[pc.key] == snap3.rev
    client.close()
