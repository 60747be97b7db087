"""volcano_tpu/trace — recorder, journal, replay, export, endpoint, CLI.

Fast (tier-1) coverage of the cycle record/replay subsystem:
  * NullRecorder really is a no-op (and cheap);
  * journal JSONL + npz snapshot round-trips exactly;
  * replay.verify reproduces recorded bindings for the jax (and, when
    the toolchain is present, native) executors and flags an injected
    perturbation;
  * Chrome trace export emits schema-valid trace_event JSON;
  * /trace/last serves the last cycle; 404 before any cycle;
  * vtctl trace record|replay|diff|export end-to-end;
  * a live Scheduler.run_once journals its decision set.
"""

from __future__ import annotations

import io
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from volcano_tpu import trace
from volcano_tpu.ops.packing import load_snapshot, save_snapshot
from volcano_tpu.ops.synthetic import generate_snapshot
from volcano_tpu.trace.journal import Journal
from volcano_tpu.trace.recorder import NullRecorder, TraceRecorder
from volcano_tpu.trace.replay import run_snapshot, verify

from tests.builders import build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache


@pytest.fixture(autouse=True)
def _reset_global_recorder():
    yield
    trace.disable()


# ---- recorder ----


def test_default_recorder_is_null():
    rec = trace.get_recorder()
    assert isinstance(rec, NullRecorder)
    assert not rec.enabled
    assert rec.begin_cycle() == -1
    with rec.span("x", "y"):
        pass
    rec.event("x")
    rec.decision("bind", "t0", "n0")
    rec.end_cycle()
    assert rec.last_cycle() is None


def test_null_recorder_overhead_is_negligible():
    """The disabled path must stay cheap enough that instrumented hot
    loops never notice it: 100k guarded emissions well under a second."""
    rec = NullRecorder()
    t0 = time.perf_counter()
    for _ in range(100_000):
        if rec.enabled:
            rec.event("never")
    assert time.perf_counter() - t0 < 1.0


def test_recorder_cycle_assembly():
    rec = TraceRecorder()
    assert rec.begin_cycle() == 0
    rec.event("hello", "cat", answer=42)
    with rec.span("work", "action"):
        pass
    rec.decision("bind", "task-1", "node-1")
    rec.end_cycle(duration_s=0.5)

    record = rec.last_cycle()
    assert record["cycle"] == 0
    assert record["duration_ms"] == pytest.approx(500.0)
    names = [e["name"] for e in record["events"]]
    assert names == ["hello", "work"]
    span = record["events"][1]
    assert span["ph"] == "X" and span["dur"] >= 0
    (decision,) = record["decisions"]
    assert decision["kind"] == "bind"
    assert decision["task"] == "task-1"
    assert decision["node"] == "node-1"
    assert decision["ts"] >= record["start_us"]
    # next cycle starts clean
    assert rec.begin_cycle() == 1
    rec.end_cycle()
    assert rec.last_cycle()["events"] == []


# ---- journal ----


def test_journal_roundtrip_and_ring(tmp_path):
    journal = Journal(str(tmp_path), keep=3)
    rec = TraceRecorder(journal=journal)
    for i in range(5):
        rec.begin_cycle()
        rec.event("e", "c", i=i)
        rec.decision("bind", f"t{i}", f"n{i}")
        rec.end_cycle(duration_s=0.001 * (i + 1))
    # ring keeps only the newest 3 cycles
    assert journal.cycles() == [2, 3, 4]
    record = journal.read_cycle(4)
    assert record["cycle"] == 4
    assert record["events"][0]["args"] == {"i": 4}
    (decision,) = record["decisions"]
    assert (decision["kind"], decision["task"], decision["node"]) == (
        "bind", "t4", "n4",
    )
    assert record["duration_ms"] == pytest.approx(5.0)


def test_journal_ignores_foreign_files(tmp_path):
    """Non-numeric cycle-*.npz names (a user-renamed backup) must be
    ignored by the strict filename match, not crash every caller."""
    (tmp_path / "cycle-keep.npz").write_bytes(b"")
    (tmp_path / "cycle-00000002.npz").write_bytes(b"")
    journal = Journal(str(tmp_path))
    assert journal.snapshot_cycles() == [2]
    rec = TraceRecorder(journal=journal)
    rec.begin_cycle()
    rec.end_cycle()  # _prune walks snapshot_cycles; must not raise
    assert rec.last_cycle()["cycle"] == 3


def test_recorder_resumes_cycle_ids_from_journal(tmp_path):
    """A second recorder over the same journal directory appends after
    the newest recorded cycle instead of overwriting from 0."""
    journal = Journal(str(tmp_path))
    rec = TraceRecorder(journal=journal)
    for _ in range(3):
        rec.begin_cycle()
        rec.end_cycle()
    assert journal.cycles() == [0, 1, 2]

    rec2 = TraceRecorder(journal=Journal(str(tmp_path)))
    assert rec2.begin_cycle() == 3
    rec2.end_cycle()
    assert journal.cycles() == [0, 1, 2, 3]


def test_recorder_resumes_past_orphan_snapshot(tmp_path):
    """A crash between snapshot capture and end_cycle leaves an .npz
    with no .jsonl; the next run must not reuse that cycle id (replay
    would pair the stale snapshot with the new run's event log)."""
    journal = Journal(str(tmp_path))
    snap = generate_snapshot(n_tasks=8, n_nodes=4, seed=0)
    journal.write_snapshot(5, snap, np.zeros(8, dtype=np.int32))
    assert journal.last_cycle() is None  # no event logs at all

    rec = TraceRecorder(journal=journal)
    assert rec.begin_cycle() == 6


def test_journal_write_failure_does_not_raise(tmp_path):
    """Forensics must never break scheduling: a failing journal write
    (here: the root path is a file) is logged and swallowed, and the
    in-memory last_cycle record survives.  Same for snapshot capture,
    which runs inside the allocate action."""
    blocked = tmp_path / "not-a-dir"
    blocked.write_text("")
    rec = TraceRecorder(journal=Journal(str(blocked)), snapshot_every=1)
    rec.begin_cycle()
    rec.event("x")
    snap = generate_snapshot(n_tasks=8, n_nodes=4, seed=0)
    rec.capture(snap, np.zeros(8, dtype=np.int32))  # OSError swallowed
    rec.end_cycle(0.01)
    assert rec.last_cycle()["cycle"] == 0


def test_event_cap_bounds_buffer():
    """Events past max_events_per_cycle are dropped and counted — bounds
    memory when a process emits events without running the cycle loop."""
    rec = TraceRecorder()
    rec.max_events_per_cycle = 5
    rec.begin_cycle()
    for i in range(9):
        rec.event(f"e{i}")
    rec.end_cycle()
    record = rec.last_cycle()
    assert len(record["events"]) == 5
    assert record["n_dropped"] == 4


def test_crashed_open_session_cycle_is_journaled(tmp_path, monkeypatch):
    """A cycle that dies in open_session (plugin on_session_open is the
    likeliest site) still lands in the journal instead of leaving a
    cycle-id gap."""
    import volcano_tpu.scheduler.scheduler as sched_mod
    from volcano_tpu.scheduler.scheduler import Scheduler

    trace.enable(str(tmp_path))

    def boom(*args, **kwargs):
        raise RuntimeError("plugin open crashed")

    monkeypatch.setattr(sched_mod, "open_session", boom)
    with pytest.raises(RuntimeError, match="plugin open crashed"):
        Scheduler(_tiny_cluster_cache()).run_once()
    assert Journal(str(tmp_path)).cycles() == [0]


def _spread_snapshot():
    """A packed session carrying hard topology spread planes."""
    from tests.test_topology_spread import TIERS, cluster
    from volcano_tpu.actions.jax_allocate import compute_task_order
    from volcano_tpu.framework import close_session, open_session
    from volcano_tpu.ops.packing import pack_session

    ssn = open_session(make_cache(**cluster(1)), TIERS(), [])
    try:
        order = compute_task_order(ssn)
        nodes = [ssn.nodes[n] for n in sorted(ssn.nodes)]
        jobs = list({t.job: ssn.jobs[t.job] for t in order}.values())
        snap = pack_session(order, jobs, nodes)
    finally:
        close_session(ssn)
    assert snap.has_spread
    return snap


@pytest.mark.parametrize("kind", ["plain", "spread"])
def test_snapshot_npz_roundtrip(tmp_path, kind):
    """The journal is the one serialized form of a PackedSnapshot: every
    array, the spread planes included, comes back exactly and replays
    to the same assignment."""
    from volcano_tpu.ops.kernels import run_packed
    from volcano_tpu.ops.packing import _SNAPSHOT_ARRAYS

    if kind == "plain":
        snap = generate_snapshot(n_tasks=64, n_nodes=16, gang_size=4, seed=3)
    else:
        snap = _spread_snapshot()
    path = str(tmp_path / "snap.npz")
    assignment = np.arange(snap.n_tasks, dtype=np.int32)
    save_snapshot(snap, path, assignment=assignment)
    loaded, extras = load_snapshot(path)

    assert loaded.n_tasks == snap.n_tasks
    assert loaded.n_nodes == snap.n_nodes
    assert loaded.n_jobs == snap.n_jobs
    assert loaded.resource_names == snap.resource_names
    assert loaded.task_uids == snap.task_uids
    assert loaded.node_names == snap.node_names
    assert loaded.memory_exact == snap.memory_exact
    for name in _SNAPSHOT_ARRAYS:
        want, got = getattr(snap, name), getattr(loaded, name)
        if want is None:
            assert got is None, name
        else:
            np.testing.assert_array_equal(got, want, err_msg=name)
    assert loaded.has_spread == snap.has_spread
    np.testing.assert_array_equal(extras["assignment"], assignment)
    np.testing.assert_array_equal(run_packed(loaded), run_packed(snap))


# ---- replay ----


def _record_one_cycle(tmp_path, executor="jax", n_tasks=128, n_nodes=32):
    journal = Journal(str(tmp_path))
    rec = TraceRecorder(journal=journal, snapshot_every=1)
    snap = generate_snapshot(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, seed=7
    )
    rec.begin_cycle()
    assignment = run_snapshot(snap, executor=executor)
    rec.capture(snap, assignment, executor=executor)
    rec.end_cycle(duration_s=0.01)
    return journal, snap, assignment


def test_replay_verify_identical_jax(tmp_path):
    journal, _, _ = _record_one_cycle(tmp_path, executor="jax")
    result = verify(journal, executor="jax")
    assert result.match
    assert result.n_diffs == 0
    assert result.n_tasks == 128
    assert result.recorded_executor == "jax"
    assert "IDENTICAL" in result.summary()


def test_replay_verify_native_matches_recorded_jax(tmp_path):
    from volcano_tpu import native

    if native.load() is None:
        pytest.skip("native executor unavailable")
    journal, _, _ = _record_one_cycle(tmp_path, executor="jax")
    result = verify(journal, executor="native")
    assert result.match, result.diffs[:5]


def test_replay_flags_perturbed_snapshot(tmp_path):
    journal, snap, assignment = _record_one_cycle(tmp_path, executor="jax")
    # inject a perturbation: claim a different binding for one placed task
    tampered = np.asarray(assignment, dtype=np.int32).copy()
    placed = np.nonzero(tampered[: snap.n_tasks] >= 0)[0]
    idx = int(placed[0])
    tampered[idx] = (tampered[idx] + 1) % snap.n_nodes
    journal.write_snapshot(0, snap, tampered, executor="jax")

    result = verify(journal, executor="jax")
    assert not result.match
    assert result.n_diffs == 1
    task_idx, recorded_node, replayed_node = result.diffs[0]
    assert task_idx == idx
    assert recorded_node != replayed_node
    assert "DIFF" in result.summary()


def test_replay_uses_recorded_kernel_params(tmp_path):
    """A capture made with non-default weights must replay with those
    same weights, not the defaults."""
    from volcano_tpu.ops.kernels import ScoreWeights

    weights = ScoreWeights(binpack_weight=3.0, least_requested_weight=0.25)
    journal = Journal(str(tmp_path))
    rec = TraceRecorder(journal=journal, snapshot_every=1)
    snap = generate_snapshot(n_tasks=96, n_nodes=24, gang_size=4, seed=11)
    rec.begin_cycle()
    assignment = run_snapshot(snap, executor="jax", weights=weights)
    rec.capture(snap, assignment, executor="jax", weights=weights)
    rec.end_cycle()

    _, extras = journal.read_snapshot(0)
    lanes = [float(v) for v in np.asarray(extras["weights"]).ravel()]
    assert lanes[: len(ScoreWeights._fields) - 1] == [
        float(v) for v in tuple(weights)[:-1]
    ]
    assert "gang_rounds" not in extras
    assert verify(journal, executor="jax").match


def test_replay_accepts_directory_path(tmp_path):
    _record_one_cycle(tmp_path, executor="jax")
    assert verify(str(tmp_path), executor="jax").match


def test_replay_without_snapshot_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        verify(str(tmp_path))


# ---- chrome export ----


def test_chrome_trace_schema(tmp_path):
    journal = Journal(str(tmp_path))
    rec = TraceRecorder(journal=journal)
    rec.begin_cycle()
    rec.event("instant", "cat")
    with rec.span("region", "action", detail="x"):
        pass
    rec.decision("bind", "t0", "n0")
    rec.end_cycle(duration_s=0.002)

    from volcano_tpu.trace.export import export_chrome_trace

    text = export_chrome_trace(journal, cycle=0)
    obj = json.loads(text)
    assert set(obj) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert obj["metadata"]["cycle"] == 0
    assert obj["metadata"]["n_decisions"] == 1
    phases = {}
    for e in obj["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        phases.setdefault(e["ph"], []).append(e)
    assert len(phases["X"]) == 1  # the span, with a duration
    assert "dur" in phases["X"][0]
    assert any(e["cat"] == "decision" for e in phases["i"])

    out = tmp_path / "trace.json"
    export_chrome_trace(journal, cycle=0, path=str(out))
    assert json.loads(out.read_text()) == obj


# ---- live scheduler cycle ----


def _tiny_cluster_cache():
    from tests.builders import build_node

    nodes = [build_node(f"n{i}", {"cpu": "8", "memory": "16Gi"}) for i in range(2)]
    pods = [
        build_pod("ns1", f"p{i}", "", {"cpu": "1", "memory": "1Gi"}, group="pg1")
        for i in range(3)
    ]
    pg = build_pod_group("ns1", "pg1", min_member=3, queue="q1")
    queue = build_queue("q1", weight=1)
    return make_cache(nodes=nodes, pods=pods, pod_groups=[pg], queues=[queue])


def test_scheduler_cycle_records_decisions(tmp_path):
    from volcano_tpu.scheduler.scheduler import Scheduler

    rec = trace.enable(str(tmp_path), snapshot_every=0)
    cache = _tiny_cluster_cache()
    Scheduler(cache).run_once()

    record = rec.last_cycle()
    assert record is not None and record["cycle"] == 0
    names = [e["name"] for e in record["events"]]
    assert "open_session" in names
    assert "close_session" in names
    assert any(n.startswith("action:") for n in names)
    assert any(n.startswith("plugin:") for n in names)
    binds = [d for d in record["decisions"] if d["kind"] == "bind"]
    assert len(binds) == 3  # the whole gang placed
    assert {d["node"] for d in binds} <= {"n0", "n1"}
    # journaled too
    assert Journal(str(tmp_path)).read_cycle(0)["decisions"]


def test_disabled_recording_changes_nothing():
    from volcano_tpu.scheduler.scheduler import Scheduler

    cache = _tiny_cluster_cache()
    Scheduler(cache).run_once()
    assert trace.get_recorder().last_cycle() is None
    assert len(cache.binder.binds) == 3


# ---- /trace/last endpoint ----


def test_trace_last_endpoint(tmp_path):
    from volcano_tpu.scheduler.scheduler import Scheduler
    from volcano_tpu.serving.http import ServingServer

    server = ServingServer().start()
    try:
        url = f"http://127.0.0.1:{server.port}/trace/last"
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url)
        assert err.value.code == 404
        err.value.close()  # the HTTPError holds the response socket

        trace.enable(str(tmp_path))
        Scheduler(_tiny_cluster_cache()).run_once()

        with urllib.request.urlopen(url) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            obj = json.loads(resp.read())
        assert obj["metadata"]["cycle"] == 0
        assert obj["metadata"]["n_decisions"] == 3
        assert any(
            e["name"].startswith("action:") for e in obj["traceEvents"]
        )
    finally:
        server.stop()


# ---- vtctl trace CLI ----


def _vtctl(args):
    from volcano_tpu.cli.vtctl import main

    out = io.StringIO()
    rc = main(args, out=out)
    return rc, out.getvalue()


def test_vtctl_trace_end_to_end(tmp_path):
    d = str(tmp_path / "journal")
    rc, text = _vtctl(
        ["trace", "record", "--dir", d, "--tasks", "64", "--nodes", "16",
         "--cycles", "2", "--snapshot-every", "1"]
    )
    assert rc == 0, text
    assert "recorded 2 cycle(s)" in text

    rc, text = _vtctl(["trace", "replay", "--dir", d, "--executor", "jax"])
    assert rc == 0, text
    assert "IDENTICAL" in text

    rc, text = _vtctl(["trace", "diff", "--dir", d, "--cycle", "0"])
    assert rc == 0, text

    out_file = str(tmp_path / "chrome.json")
    rc, text = _vtctl(["trace", "export", "--dir", d, "--out", out_file])
    assert rc == 0, text
    obj = json.loads(open(out_file).read())
    assert obj["traceEvents"]


def test_vtctl_trace_diff_reports_perturbation(tmp_path):
    d = str(tmp_path / "journal")
    rc, _ = _vtctl(
        ["trace", "record", "--dir", d, "--tasks", "64", "--nodes", "16"]
    )
    assert rc == 0
    journal = Journal(d)
    snap, extras = journal.read_snapshot(0)
    tampered = np.asarray(extras["assignment"], dtype=np.int32).copy()
    tampered[0] = (tampered[0] + 1) % snap.n_nodes
    journal.write_snapshot(0, snap, tampered, executor="jax")

    rc, text = _vtctl(["trace", "diff", "--dir", d])
    assert rc == 1
    assert "task[0]" in text


# ---- satellite regressions (this PR) ----


def test_cascade_delete_spares_recreated_child():
    """apiserver cascade must re-verify ownership: a child deleted and
    re-created under the same key with a different controller must
    survive the old owner's cascade (the k8s GC matches by UID)."""
    from volcano_tpu.apis import core
    from volcano_tpu.client import APIServer

    api = APIServer()

    def make_job(uid):
        return core.ConfigMap(  # any kinded object works; use two kinds
            metadata=core.ObjectMeta(name="owner", namespace="d", uid=uid)
        )

    def make_child(owner_uid):
        return core.Pod(
            metadata=core.ObjectMeta(
                name="child",
                namespace="d",
                uid=f"pod-of-{owner_uid}",
                owner_references=[
                    core.OwnerReference(
                        kind="ConfigMap", name="owner", uid=owner_uid,
                        controller=True,
                    )
                ],
            )
        )

    api.create(make_job("uid-1"))
    api.create(make_child("uid-1"))
    # child deleted directly, then re-created under the SAME key but
    # owned by a NEW incarnation of the owner
    api.delete("Pod", "d", "child")
    api.create(make_child("uid-2"))
    api.delete("ConfigMap", "d", "owner")
    # stale _owned entry must not take the new child down
    assert api.get("Pod", "d", "child") is not None
    # the new incarnation's cascade still works
    api.create(make_job("uid-2"))
    api.delete("ConfigMap", "d", "owner")
    assert api.get("Pod", "d", "child") is None


def test_frozen_resource_rejects_inplace_mutation():
    from volcano_tpu.api.job_info import new_task_info
    from volcano_tpu.api.resource import Resource

    pod = build_pod("ns1", "p0", "", {"cpu": "1", "memory": "1Gi"})
    task = new_task_info(pod)
    delta = Resource(milli_cpu=100.0)
    for mutator in (task.resreq.add, task.resreq.sub_unchecked,
                    task.resreq.set_max, task.init_resreq.add):
        with pytest.raises(AssertionError):
            mutator(delta)
    # a clone is mutable again, and aliases stay shared across task clones
    task.resreq.clone().add(delta)
    assert task.clone().resreq is task.resreq


def test_admission_volume_names():
    from volcano_tpu.admission.jobs import _validate_task_template
    from volcano_tpu.apis import batch, core

    def job_task(volumes, mounts=()):
        return batch.TaskSpec(
            name="t",
            replicas=1,
            template=core.PodTemplateSpec(
                spec=core.PodSpec(
                    containers=[
                        core.Container(
                            name="c",
                            volume_mounts=[
                                core.VolumeMount(name=n, mount_path=f"/m{i}")
                                for i, n in enumerate(mounts)
                            ],
                        )
                    ],
                    volumes=[core.Volume(name=n) for n in volumes],
                )
            ),
        )

    # two unnamed volumes: flagged once each as invalid, NOT as duplicates
    msgs = _validate_task_template(job_task(["", ""]), 0)
    assert sum("DNS-1123" in m for m in msgs) == 2
    assert not any("duplicate volume name" in m for m in msgs)
    # a mount referencing the invalid name is NOT treated as declared
    msgs = _validate_task_template(job_task([""], mounts=[""]), 0)
    assert any("not declared" in m for m in msgs)
    # valid duplicates still flagged exactly once
    msgs = _validate_task_template(job_task(["vol", "vol"]), 0)
    assert sum("duplicate volume name" in m for m in msgs) == 1
