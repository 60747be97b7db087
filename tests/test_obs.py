"""Cluster-wide flight recorder (ISSUE 12): span contexts, the
drop-not-block telemetry channel, cross-process propagation over VBUS,
the vtctl trace/top surfaces, telemetry-under-faults, the MTR metric-
hygiene pass, identity labels, and the merged multi-process Chrome
export.

The tier-1 cross-process test runs the scheduler in THIS process
against a real persistent ``vtpu-apiserver`` OS process and a real
``vtpu-controllers`` OS process — three processes, one waterfall.  The
slow test runs the full federated topology (2 scheduler shards, a
2-replica apiserver group, controllers) and pins the cross-shard gang's
txn_commit / WAL-fsync / quorum-wait span chain."""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time

import pytest

from volcano_tpu import faults, obs
from volcano_tpu.apis import core
from volcano_tpu.client import APIServer, KubeClient, VolcanoClient
from volcano_tpu.metrics import metrics
from volcano_tpu.metrics import scrape as mscrape
from volcano_tpu.obs.channel import SpanExporter

from tests.builders import build_node, build_queue


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    metrics.registry.reset()
    yield
    obs.disable()
    metrics.registry.reset()
    faults.configure(None)


def _wait(pred, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ---- span contexts ----

class TestSpanContext:
    def test_trace_ids_stable_and_distinct(self):
        a = obs.trace_id_for_pod("ns", "p1")
        assert a == obs.trace_id_for_pod("ns", "p1")
        assert a != obs.trace_id_for_pod("ns", "p2")
        assert a != obs.trace_id_for_gang("ns2", "p1")
        assert obs.trace_id_for_gang("ns", "g") == obs.trace_id_for("ns", "g")

    def test_disabled_is_null_and_costless(self):
        assert not obs.enabled()
        with obs.span("x") as s:
            assert s.span_id == ""
            assert obs.current_wire() is None
        obs.complete("y", 0.1)  # no-op, no error

    def test_nesting_parents_and_wire(self):
        api = APIServer()
        exp = obs.enable(api, identity="t", flush_interval=3600)
        with obs.span("outer") as outer:
            with obs.span("inner",
                          trace_id=obs.trace_id_for_pod("ns", "p")) as inner:
                w = obs.current_wire()
                assert w == {"t": obs.trace_id_for_pod("ns", "p"),
                             "s": inner.span_id}
            assert obs.current_wire()["s"] == outer.span_id
        assert obs.current_wire() is None
        exp.flush_all()
        spans = {s["name"]: s for s in obs.collect_spans(api)}
        assert spans["inner"]["p"] == spans["outer"]["s"]
        # explicit trace_id re-roots the trace, keeps the parent link
        assert spans["inner"]["t"] == obs.trace_id_for_pod("ns", "p")
        assert spans["outer"]["t"] == ""

    def test_adopt_parents_to_remote_context(self):
        api = APIServer()
        exp = obs.enable(api, identity="t", flush_interval=3600)
        with obs.adopt({"t": "abcd1234", "s": "peer-7"}, "bus:create"):
            pass
        with obs.adopt(None, "local"):  # degraded: plain local span
            pass
        exp.flush_all()
        spans = {s["name"]: s for s in obs.collect_spans(api)}
        assert spans["bus:create"]["p"] == "peer-7"
        assert spans["bus:create"]["t"] == "abcd1234"
        assert spans["local"]["p"] == ""

    def test_suppression_blocks_emission(self):
        api = APIServer()
        exp = obs.enable(api, identity="t", flush_interval=3600)
        with obs.suppressed():
            assert not obs.enabled()
            with obs.span("hidden"):
                obs.complete("also-hidden", 0.01)
        assert exp.flush_all() == 0


# ---- telemetry channel ----

class TestChannel:
    def test_segments_land_and_rotate_bounded(self):
        api = APIServer()
        exp = SpanExporter(api, "d0", batch=2, segments=3,
                           flush_interval=3600)
        for i in range(10):
            exp.emit({"t": "", "s": f"s{i}", "p": "", "name": f"n{i}",
                      "ts": float(i), "dur": 1.0})
        exp.flush_all()
        segs = [cm for cm in api.list("ConfigMap", obs.NAMESPACE)]
        # 5 batches over 3 slots: retention is the slot ring, honestly
        assert len(segs) == 3
        spans = obs.collect_spans(api)
        assert 0 < len(spans) <= 10
        assert exp.exported == 10 and exp.dropped == 0

    def test_ring_full_drops_not_blocks(self):
        api = APIServer()
        exp = SpanExporter(api, "d0", ring=4, flush_interval=3600)
        t0 = time.perf_counter()
        for i in range(100):
            exp.emit({"s": f"s{i}", "name": "n", "ts": 0.0})
        assert time.perf_counter() - t0 < 1.0  # never blocked
        assert exp.dropped == 96
        r = metrics.registry.render()
        assert 'volcano_telemetry_dropped_total{reason="ring-full"} 96' in r

    def test_sampling_keeps_or_drops_whole_traces(self):
        api = APIServer()
        exp = SpanExporter(api, "d0", sample=0.5, flush_interval=3600)
        ids = [obs.trace_id_for_pod("ns", f"p{i}") for i in range(200)]
        kept = [t for t in ids if exp.keep(t)]
        assert 0 < len(kept) < len(ids)  # some of each
        # decision is a pure function of the id — every process agrees
        assert all(exp.keep(t) for t in kept)
        assert exp.keep("")  # process-scope spans always kept
        none = SpanExporter(api, "d1", sample=0.0, flush_interval=3600)
        assert not none.keep(ids[0]) and none.keep("")

    def test_sampled_out_trace_drops_whole_subtree(self):
        """Keep-or-drop-whole-traces: a sampled-out span still pushes
        its (dropped) context, so descendants inherit the dropped
        trace id and drop with it — on BOTH sides of the wire —
        instead of leaking into the enclosing process-scope trace."""
        api = APIServer()
        exp = obs.enable(api, identity="t", flush_interval=3600)
        dropped_tid = next(
            t for t in (obs.trace_id_for_pod("ns", f"g{i}")
                        for i in range(1000))
            if not SpanExporter(api, "x", sample=0.5,
                                flush_interval=3600).keep(t)
        )
        exp.sample = 0.5
        assert not exp.keep(dropped_tid)
        with obs.span("cycle"):  # kept: process scope
            with obs.span("gang:assemble", trace_id=dropped_tid):
                # descendants inherit the DROPPED id, not the cycle's
                w = obs.current_wire()
                assert w is not None and w["t"] == dropped_tid
                with obs.span("gang:txn_commit"):
                    obs.complete("wal:fsync", 0.001)
                # server side: adopting the dropped context drops too
                with obs.adopt(w, "bus:txn_commit"):
                    obs.complete("repl:quorum_wait", 0.001)
        exp.flush_all()
        names = {s["name"] for s in obs.collect_spans(api)}
        assert names == {"cycle"}, names

    def test_export_error_drops_and_counts(self):
        class DeadApi:
            def create(self, obj):
                raise RuntimeError("bus down")

        exp = SpanExporter(DeadApi(), "d0", flush_interval=3600)
        exp.emit({"s": "s1", "name": "n", "ts": 0.0})
        assert exp.flush() == 0  # dropped, never raised
        assert exp.dropped == 1
        r = metrics.registry.render()
        assert ('volcano_telemetry_dropped_total{reason="export-error"} 1'
                in r)


# ---- selection + rendering ----

def _mk(name, sid, parent="", trace="", daemon="d", pid=1, ts=0.0, dur=1.0,
        args=None):
    s = {"name": name, "s": sid, "p": parent, "t": trace, "daemon": daemon,
         "pid": pid, "ts": ts, "dur": dur, "tid": 1}
    if args:
        s["args"] = args
    return s


class TestSelectTrace:
    def test_closure_up_and_process_scope_down(self):
        t_p1 = obs.trace_id_for_pod("ns", "p1")
        t_p2 = obs.trace_id_for_pod("ns", "p2")
        spans = [
            _mk("cycle", "c1", ts=0.0, dur=10.0),
            _mk("kernel:execute", "k1", parent="c1", ts=1.0),
            _mk("bind:landed", "b1", parent="c1", trace=t_p1, ts=5.0),
            _mk("bind:landed", "b2", parent="c1", trace=t_p2, ts=6.0),
            _mk("unrelated", "u1", ts=7.0),
        ]
        sel = obs.select_trace(spans, "ns", "p1")
        names = {s["s"] for s in sel}
        # own span + ancestor cycle + the cycle's process-scope kernel —
        # but NOT the other pod's bind, and not the unrelated root
        assert names == {"c1", "k1", "b1"}

    def test_gang_arg_matches(self):
        tg = obs.trace_id_for_gang("ns", "g1")
        spans = [
            _mk("gang:assemble", "a1", trace=tg, args={"gang": "ns/g1"}),
            _mk("bind:landed", "b1", trace=obs.trace_id_for_pod("ns", "m0"),
                args={"gang": "ns/g1"}),
        ]
        sel = obs.select_trace(spans, "ns", "g1")
        assert {s["s"] for s in sel} == {"a1", "b1"}

    def test_waterfall_and_chrome_multiprocess(self):
        spans = [
            _mk("cycle", "c1", daemon="sched", pid=11, ts=0.0, dur=10.0),
            _mk("bus:create", "x1", parent="c1", daemon="apiserver",
                pid=22, ts=2.0, dur=3.0),
        ]
        out = io.StringIO()
        obs.render_waterfall(spans, out)
        text = out.getvalue()
        assert "cycle" in text and "bus:create" in text
        assert "2 daemon(s) / 2 process(es)" in text
        ch = obs.chrome_export(spans)
        pids = {e["pid"] for e in ch["traceEvents"] if e.get("ph") == "X"}
        assert len(pids) == 2
        names = {e["args"]["name"] for e in ch["traceEvents"]
                 if e.get("ph") == "M"}
        assert names == {"sched", "apiserver"}


# ---- cross-process: 3 OS processes, one waterfall (tier-1) ----

def _spawn(module, *args):
    return subprocess.Popen(
        [sys.executable, "-m", module, *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestCrossProcessWaterfall:
    def test_waterfall_spans_three_os_processes(self, tmp_path):
        """Scheduler (this process) + persistent vtpu-apiserver +
        vtpu-controllers, each a real OS process with the flight
        recorder on: `vtctl trace pod` renders one submit→bind
        waterfall whose spans come from all three, with the bus op and
        WAL fsync parented under the scheduler's cycle."""
        from volcano_tpu.bus import connect_bus
        from volcano_tpu.cache import SchedulerCache
        from volcano_tpu.cli.vtctl import main as vtctl_main
        from volcano_tpu.client import SchedulerClient
        from volcano_tpu.cmd.local_up import seed_cluster
        from volcano_tpu.scheduler.scheduler import Scheduler

        port = _free_port()
        bus_url = f"tcp://127.0.0.1:{port}"
        procs = [_spawn(
            "volcano_tpu.cmd.apiserver",
            "--port", str(port), "--listen-port", "0",
            "--data-dir", str(tmp_path / "wal"),
            "--flight-recorder",
        )]
        api = sched_remote = None
        cache = None
        try:
            api = connect_bus(bus_url, wait=30.0)
            seed_cluster(api, nodes=2, node_cpu="8", node_mem="16Gi")
            procs.append(_spawn(
                "volcano_tpu.cmd.controllers",
                "--bus", bus_url, "--listen-port", "0",
                "--period", "0.05", "--flight-recorder",
                "--leader-elect-id", "ctrl-0",
            ))
            sched_remote = connect_bus(bus_url, wait=10.0)
            obs.enable(sched_remote, identity="sched-0",
                       flush_interval=0.05)
            cache = SchedulerCache(client=SchedulerClient(sched_remote),
                                   scheduler_name="volcano-tpu")
            scheduler = Scheduler(cache, period=0.05)
            cache.run()
            cache.wait_for_cache_sync()

            from volcano_tpu.apis import batch

            VolcanoClient(api).create_job(batch.Job(
                metadata=core.ObjectMeta(name="wf", namespace="default"),
                spec=batch.JobSpec(
                    min_available=1, queue="default",
                    scheduler_name="volcano-tpu",
                    tasks=[batch.TaskSpec(
                        name="t", replicas=1,
                        template=core.PodTemplateSpec(spec=core.PodSpec(
                            containers=[core.Container(
                                name="c", image="busybox",
                                resources={"requests": {"cpu": "1",
                                                        "memory": "1Gi"}},
                            )],
                        )),
                    )],
                ),
            ))

            def pod_bound():
                scheduler.run_once()
                pod = api.get("Pod", "default", "wf-t-0")
                return pod is not None and bool(pod.spec.node_name)

            assert _wait(pod_bound, timeout=60.0, interval=0.1), (
                "pod never bound over the 3-process topology"
            )
            obs.get_exporter().flush_all()
            # controllers flush on their own interval; wait for their
            # spans to land as durable segments
            def _select(spans):
                return obs.select_union(
                    spans,
                    obs.related_identities(api, "default", "wf-t-0"),
                )

            def has_three_daemons():
                sel = _select(obs.collect_spans(api))
                return len({s.get("daemon") for s in sel}) >= 3

            assert _wait(has_three_daemons, timeout=20.0, interval=0.25), (
                "waterfall never spanned 3 daemons: "
                + str(sorted({s.get('daemon')
                              for s in obs.collect_spans(api)}))
            )

            spans = obs.collect_spans(api)
            sel = _select(spans)
            daemons = {s.get("daemon") for s in sel}
            pids = {s.get("pid") for s in sel}
            assert len(daemons) >= 3, daemons
            assert len(pids) >= 3, pids
            names = {s["name"] for s in sel}
            assert "bind:landed" in names
            assert any(n.startswith("cycle:") for n in names)
            assert any(n.startswith("bus:") for n in names)
            assert "wal:fsync" in names
            assert "controller:status" in names
            by_id = {s["s"]: s for s in sel}
            # the fsync parents into a bus op, the bus op into a span
            # recorded by ANOTHER process (the cross-process stitch)
            fsync = next(s for s in sel if s["name"] == "wal:fsync")
            busop = by_id[fsync["p"]]
            assert busop["name"].startswith("bus:")
            assert by_id[busop["p"]].get("daemon") != busop.get("daemon")

            # the vtctl surface renders it, over the bus backend
            out = io.StringIO()
            rc = vtctl_main(
                ["trace", "pod", "-n", "default", "-N", "wf-t-0"],
                api=api, out=out,
            )
            text = out.getvalue()
            assert rc == 0
            assert "bind:landed" in text and "wal:fsync" in text
            chrome_path = str(tmp_path / "merged.json")
            out = io.StringIO()
            rc = vtctl_main(
                ["trace", "pod", "-n", "default", "-N", "wf-t-0",
                 "--chrome", chrome_path],
                api=api, out=out,
            )
            assert rc == 0
            ch = json.load(open(chrome_path))
            chrome_pids = {e["pid"] for e in ch["traceEvents"]
                           if e.get("ph") == "X"}
            assert len(chrome_pids) >= 3
        finally:
            obs.disable()
            if cache is not None:
                cache.stop_commit_plane()
            if sched_remote is not None:
                sched_remote.close()
            if api is not None:
                api.close()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


class TestFederatedGangWaterfall:
    def test_cross_shard_gang_txn_chain(self, tmp_path):
        """The acceptance waterfall: 2 scheduler-shard processes + a
        2-replica persistent apiserver group + controllers — a gang
        larger than any one shard binds via txn_commit, and its trace
        carries gang:txn_commit → bus:txn_commit → wal:fsync and the
        repl:quorum_wait span, correctly parented, across ≥3 OS
        processes."""
        from volcano_tpu.bus import connect_bus
        from volcano_tpu.bus.protocol import NotLeaderError

        ports = [_free_port(), _free_port()]
        endpoints = ",".join(f"tcp://127.0.0.1:{p}" for p in ports)
        procs = []
        api = None
        try:
            for i, port in enumerate(ports):
                procs.append(_spawn(
                    "volcano_tpu.cmd.apiserver",
                    "--port", str(port), "--listen-port", "0",
                    "--data-dir", str(tmp_path / f"r{i}"),
                    "--replicas", endpoints, "--replica-index", str(i),
                    "--repl-lease-ttl", "1.0",
                    "--flight-recorder",
                ))
            api = connect_bus(endpoints, wait=60.0)
            kube = KubeClient(api)
            vc = VolcanoClient(api)
            # reachable is not elected: the first write waits out the
            # replicas' first election
            deadline = time.monotonic() + 30.0
            while True:
                try:
                    vc.create_queue(build_queue("default"))
                    break
                except NotLeaderError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            # n0-n3 hash to shard 0, n4-n7 to shard 1 (crc32 % 2): four
            # single-gang-task nodes per shard
            for i in range(8):
                kube.create_node(build_node(f"n{i}", {"cpu": "4",
                                                      "memory": "16Gi"}))
            procs.append(_spawn(
                "volcano_tpu.cmd.controllers",
                "--bus", endpoints, "--listen-port", "0",
                "--period", "0.05", "--flight-recorder",
                "--leader-elect-id", "ctrl-0",
            ))
            for i in range(2):
                procs.append(_spawn(
                    "volcano_tpu.cmd.scheduler",
                    "--bus", endpoints, "--listen-port", "0",
                    "--shards", "2", "--shard-identity", f"shard-{i}",
                    "--shard-lease-duration", "1.5",
                    "--schedule-period", "0.2", "--micro-cycles",
                    "--gang-broker", "on", "--flight-recorder",
                ))

            # the federation must actually FORM first (two distinct
            # holders): a lone early member absorbs both shards and
            # would bind the gang locally, bypassing the broker
            from volcano_tpu.federation import read_shard_map

            def two_holders():
                rec = read_shard_map(api)
                if not rec:
                    return False
                holders = {
                    e.get("holder")
                    for e in rec.get("shards", {}).values()
                }
                holders.discard("")
                return len(holders) == 2

            assert _wait(two_holders, timeout=60.0, interval=0.25), (
                "federation never formed two distinct shard holders"
            )

            # a 5-member gang Job of node-sized tasks with 4 nodes per
            # shard: no shard can host it alone, so binding it
            # REQUIRES the cross-shard txn_commit assembly.  Submitted
            # as a Job so the CONTROLLERS process creates the PodGroup
            # and pods and writes the status back — its spans share
            # the "ns/gang" identity (the PodGroup is named after the
            # job), putting all three daemon kinds in one waterfall.
            from volcano_tpu.apis import batch

            vc.create_job(batch.Job(
                metadata=core.ObjectMeta(name="gang", namespace="ns"),
                spec=batch.JobSpec(
                    min_available=5, queue="default",
                    scheduler_name="volcano-tpu",
                    tasks=[batch.TaskSpec(
                        name="t", replicas=5,
                        template=core.PodTemplateSpec(spec=core.PodSpec(
                            containers=[core.Container(
                                name="c", image="busybox",
                                resources={"requests": {
                                    "cpu": "4", "memory": "1Gi"}},
                            )],
                        )),
                    )],
                ),
            ))

            def all_bound():
                pods = kube.list_pods("ns")
                return len(pods) == 5 and all(
                    p.spec.node_name for p in pods
                )

            assert _wait(all_bound, timeout=120.0, interval=0.25), (
                "gang never assembled across shards"
            )

            def chain_present():
                spans = obs.collect_spans(api)
                sel = obs.select_trace(spans, "ns", "gang")
                names = {s["name"] for s in sel}
                return {"gang:txn_commit", "bus:txn_commit",
                        "wal:fsync"} <= names
            assert _wait(chain_present, timeout=30.0, interval=0.5), (
                "txn span chain never landed: "
                + str({s['name'] for s in obs.select_trace(
                    obs.collect_spans(api), 'ns', 'gang')})
            )
            spans = obs.collect_spans(api)
            sel = obs.select_trace(spans, "ns", "gang")
            by_id = {s["s"]: s for s in sel}
            names = {s["name"] for s in sel}
            assert "repl:quorum_wait" in names, names
            bus_txn = next(s for s in sel if s["name"] == "bus:txn_commit")
            gang_txn = by_id[bus_txn["p"]]
            assert gang_txn["name"] == "gang:txn_commit"
            fsync = next(s for s in sel if s["name"] == "wal:fsync")
            assert by_id[fsync["p"]]["name"].startswith("bus:")
            quorum = next(s for s in sel
                          if s["name"] == "repl:quorum_wait")
            assert by_id[quorum["p"]]["name"].startswith("bus:")
            assert len({s.get("pid") for s in sel}) >= 3

            # CI artifact hook (the VTPU_CHAOS_JOURNAL_DIR discipline):
            # the merged multi-process timeline ships as the
            # `flight-recorder` artifact next to gang-slo
            art = os.environ.get("VTPU_FLIGHT_RECORDER_ARTIFACT")
            if art:
                os.makedirs(art, exist_ok=True)
                with open(os.path.join(art, "gang-waterfall.json"),
                          "w") as f:
                    json.dump(obs.chrome_export(sel), f, indent=1)
                out = io.StringIO()
                obs.render_waterfall(sel, out)
                with open(os.path.join(art, "gang-waterfall.txt"),
                          "w") as f:
                    f.write(out.getvalue())
        finally:
            if api is not None:
                api.close()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


# ---- telemetry under faults (satellite) ----

class TestTelemetryUnderFaults:
    def test_bus_faults_drop_never_raise(self):
        """bus.disconnect / bus.delay against the export path: spans
        are dropped and counted, emission never raises and never
        blocks."""
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.bus.server import BusServer

        store = APIServer()
        srv = BusServer(store).start()
        remote = RemoteAPIServer(f"tcp://127.0.0.1:{srv.port}", timeout=1.0)
        try:
            assert remote.wait_ready(5)
            exp = SpanExporter(remote, "d0", flush_interval=3600)
            faults.configure("seed=3;bus.client_drop=1:count=50")
            for i in range(8):
                exp.emit({"s": f"s{i}", "name": "n", "ts": 0.0})
            t0 = time.perf_counter()
            exp.flush_all()
            assert time.perf_counter() - t0 < 5.0
            assert exp.dropped == 8 and exp.exported == 0
            r = metrics.registry.render()
            assert 'reason="export-error"' in r
        finally:
            faults.configure(None)
            remote.close()
            srv.stop()

    def test_wal_write_fail_drops_never_raises(self, tmp_path):
        from volcano_tpu.bus.wal import PersistentAPIServer

        api = PersistentAPIServer(str(tmp_path / "wal"))
        try:
            exp = SpanExporter(api, "d0", flush_interval=3600)
            faults.configure("seed=5;wal.write_fail=1:count=50")
            exp.emit({"s": "s1", "name": "n", "ts": 0.0})
            assert exp.flush() == 0
            assert exp.dropped == 1
        finally:
            faults.configure(None)
            api.close()

    def test_chaos_smoke_bit_identical_with_tracing_on(self, tmp_path):
        """The chaos twin with the flight recorder ON both sides: the
        pinned workload's binding map stays bit-identical, and the
        faulted run's telemetry dropped-never-blocked."""
        from tests.test_chaos import ChaosCluster, _submit_mixed_workload

        maps = {}
        for label, spec in (
            ("faulty", "seed=77;bus.disconnect=0.05:count=3;"
                       "bus.delay=0.08:count=5:ms=5;"
                       "bus.client_drop=0.05:count=4;"
                       "cache.bind_fail=0.1:count=3"),
            ("clean", None),
        ):
            cluster = ChaosCluster(tmp_path, f"obs-{label}")
            try:
                # the recorder rides the REMOTE client — exactly the
                # path the bus faults hit
                obs.enable(cluster.remote, identity=f"sched-{label}",
                           flush_interval=0.05)
                _submit_mixed_workload(cluster)
                faults.configure(spec)
                cluster.run_cycles(10)
                faults.configure(None)
                assert _wait(
                    lambda: (cluster.cycle() or True)
                    and cluster.all_placed(),
                    timeout=30.0, interval=0.05,
                ), f"{label}: pods still unplaced with tracing on"
                cluster.assert_no_duplicate_binds()
                assert cluster.cycle_errors == 0, (
                    "telemetry must never raise into the scheduler"
                )
                maps[label] = cluster.binding_map()
            finally:
                obs.disable()
                cluster.close()
                faults.configure(None)
                faults.reset_breakers()
        pinned = {k: v for k, v in maps["faulty"].items() if "pinned" in k}
        pinned_clean = {k: v for k, v in maps["clean"].items()
                        if "pinned" in k}
        assert pinned == pinned_clean and len(pinned) == 4
        assert set(maps["faulty"]) == set(maps["clean"])


# ---- vtctl top (federated metrics) ----

class TestVtctlTop:
    def test_aggregates_discovered_members(self):
        from volcano_tpu.cli.vtctl import main as vtctl_main
        from volcano_tpu.metrics.metrics import _Registry
        from volcano_tpu.serving.http import ServingServer

        # two fake members with their own registries and identities
        regs = []
        servers = []
        for i, ident in enumerate(("shard-a", "shard-b")):
            reg = _Registry()
            reg.set_identity(daemon="scheduler", shard=ident)
            h = reg.histogram(
                "volcano_submit_to_bind_latency_milliseconds", {},
                buckets=[5.0, 10.0, 20.0],
            )
            for v in (4.0, 8.0, 16.0 + i * 2):
                h.observe(v)
            reg.inc("volcano_pod_schedule_successes", {}, 3)
            regs.append(reg)
            servers.append(ServingServer(registry=reg).start())
        api = APIServer()
        # a shard map advertising both members' metrics addrs
        from volcano_tpu.federation.leases import (
            NAMESPACE as SM_NS,
            SHARD_MAP_KEY,
            SHARD_MAP_NAME,
        )

        rec = {
            "nShards": 2, "members": {}, "shards": {},
            "stats": {
                "shard-a": {"metricsAddr":
                            f"127.0.0.1:{servers[0].port}"},
                "shard-b": {"metricsAddr":
                            f"127.0.0.1:{servers[1].port}"},
            },
        }
        api.create(core.ConfigMap(
            metadata=core.ObjectMeta(name=SHARD_MAP_NAME, namespace=SM_NS),
            data={SHARD_MAP_KEY: json.dumps(rec)},
        ))
        try:
            out = io.StringIO()
            rc = vtctl_main(["top"], api=api, out=out)
            text = out.getvalue()
            assert rc == 0, text
            assert "shard-a" in text and "shard-b" in text
            assert "CLUSTER" in text
            # cluster BINDS column sums both members
            cluster_line = next(
                line for line in text.splitlines()
                if line.strip().startswith("CLUSTER")
            )
            assert " 6 " in " ".join(cluster_line.split()) + " "
        finally:
            for s in servers:
                s.stop()

    def test_no_targets_is_an_error(self):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        out = io.StringIO()
        rc = vtctl_main(["top"], api=APIServer(), out=out)
        assert rc == 1
        assert "no scrape targets" in out.getvalue()


class TestScrapeParsing:
    def test_round_trip_and_quantile(self):
        reg_render = metrics.registry
        reg_render.reset()
        h = reg_render.histogram("volcano_x_milliseconds", {},
                                 buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.5, 3.0, 3.5):
            h.observe(v)
        reg_render.inc("volcano_y_total", {"k": "a"}, 2)
        s = mscrape.parse_metrics(reg_render.render())
        assert s.value("volcano_y_total", k="a") == 2
        hist = s.histogram("volcano_x_milliseconds")
        assert hist["count"] == 4
        q = mscrape.histogram_quantile(hist, 0.5)
        assert 1.0 <= q <= 4.0
        d = mscrape.delta(s, s)
        assert d.value("volcano_y_total", k="a") == 0
        assert d.histogram("volcano_x_milliseconds")["count"] == 0


# ---- identity labels + build info (satellite) ----

class TestIdentityLabels:
    def test_identity_injected_into_every_series(self):
        metrics.registry.reset()
        metrics.registry.inc("volcano_things_total", {"kind": "a"})
        before = metrics.registry.render()
        assert 'daemon=' not in before  # unset: output unchanged
        metrics.set_identity(daemon="scheduler", shard="s0",
                             role="scheduler")
        after = metrics.registry.render()
        assert ('volcano_things_total{daemon="scheduler",kind="a",'
                'role="scheduler",shard="s0"} 1') in after
        assert 'volcano_build_info{' in after and 'version=' in after
        metrics.registry.reset()
        assert 'daemon=' not in metrics.registry.render()

    def test_role_vocabulary_bounded(self):
        metrics.set_identity(daemon="x", role="not-a-role")
        assert 'role="other"' in metrics.registry.render()

    def test_role_follows_replication_both_directions(self):
        """update_repl_role retags the identity role on promotion AND
        demotion — a deposed leader's series must stop claiming
        role="leader"."""
        metrics.set_identity(daemon="apiserver", replica_index="0",
                             role="follower")
        metrics.registry.inc("volcano_things_total", {})
        metrics.update_repl_role("leader")
        assert 'volcano_things_total{daemon="apiserver",' \
               'replica_index="0",role="leader"}' in \
               metrics.registry.render()
        metrics.update_repl_role("follower")  # deposed
        line = next(
            ln for ln in metrics.registry.render().splitlines()
            if ln.startswith("volcano_things_total")
        )
        assert 'role="follower"' in line and 'role="leader"' not in line

    def test_identity_unset_ignores_role_refresh(self):
        metrics.update_repl_role("leader")  # no identity installed
        assert "daemon=" not in metrics.registry.render().split(
            "volcano_repl_role"
        )[0]

    def test_bounded_label_caps_cardinality(self):
        from volcano_tpu.metrics.metrics import (
            _LABEL_CARDINALITY_CAP,
            bounded_label,
        )

        for i in range(_LABEL_CARDINALITY_CAP):
            assert bounded_label("m", "job", f"j{i}") == f"j{i}"
        assert bounded_label("m", "job", "overflow") == "other"
        assert bounded_label("m", "job", "j0") == "j0"  # seen: kept
        r = metrics.registry.render()
        assert 'volcano_metric_label_overflow_total{metric="m"} 1' in r


# ---- the MTR analysis pass (satellite) ----

_MTR_OK = '''
def register_result(result):
    """result ∈ {ok, error}."""
    registry.inc("volcano_r_total", {"result": result})


def register_kind(kind):
    # label-vocab: kind — the KINDS registry, a static set
    registry.inc("volcano_k_total", {"kind": kind})


def register_fixed():
    registry.inc("volcano_f_total", {"kind": "fixed"})
'''

_MTR_BAD = '''
def register_job(job_name):
    """No vocabulary declared anywhere."""
    registry.inc("volcano_j_total", {"job": job_name})
'''


class TestMetricHygienePass:
    def _run(self, tmp_path, body, fname="volcano_tpu/m.py"):
        from volcano_tpu.analysis import metric_hygiene

        path = tmp_path / fname
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(body)
        return metric_hygiene.run(str(tmp_path))

    def test_declared_vocabularies_pass(self, tmp_path):
        assert self._run(tmp_path, _MTR_OK) == []

    def test_undeclared_dynamic_label_flagged(self, tmp_path):
        findings = self._run(tmp_path, _MTR_BAD)
        assert len(findings) == 1
        f = findings[0]
        assert f.code == "MTR001" and f.symbol == "register_job.job"

    def test_inline_waiver(self, tmp_path):
        body = _MTR_BAD.replace(
            '{"job": job_name})',
            '{"job": job_name})  # mtr: fixture-only, reviewed',
        )
        assert self._run(tmp_path, body) == []

    def test_orphaned_helper_flagged(self, tmp_path):
        helper = (
            "registry = None\n\n\n"
            "def update_never_called(seconds):\n"
            "    registry.histogram('volcano_dead_ms', {}).observe(seconds)\n"
        )
        caller = "def other():\n    pass\n"
        root = tmp_path
        (root / "volcano_tpu/metrics").mkdir(parents=True)
        (root / "volcano_tpu/metrics/metrics.py").write_text(helper)
        (root / "volcano_tpu/product.py").write_text(caller)
        from volcano_tpu.analysis import metric_hygiene

        findings = metric_hygiene.run(str(root))
        assert [f.code for f in findings] == ["MTR002"]
        assert findings[0].symbol == "update_never_called"
        # wiring the helper clears the finding
        (root / "volcano_tpu/product.py").write_text(
            "def other():\n    update_never_called(1.0)\n"
        )
        assert metric_hygiene.run(str(root)) == []

    def test_real_tree_is_clean(self):
        from volcano_tpu.analysis import metric_hygiene
        from volcano_tpu.analysis.__main__ import find_root

        assert metric_hygiene.run(find_root()) == []


# ---- merged multi-process Chrome export (small fix) ----

class TestMergedChromeExport:
    def test_distinct_pids_shared_clock(self, tmp_path):
        from volcano_tpu import trace as _trace
        from volcano_tpu.trace.export import merge_chrome_traces

        # two per-process journals whose local epochs differ wildly
        records = []
        for i, (epoch_shift, wall) in enumerate(((0.0, 100.0),
                                                 (9000.0, 100.005))):
            records.append({
                "cycle": i,
                "start_us": epoch_shift,
                "duration_ms": 2.0,
                "wall_time": wall + 0.002,  # end-of-cycle wall stamp
                "events": [{
                    "name": f"action:p{i}", "cat": "action", "ph": "X",
                    "ts": epoch_shift + 500.0, "dur": 100.0, "tid": 1,
                }],
                "decisions": [],
            })
        merged = merge_chrome_traces(records, labels=["a", "b"])
        xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {1, 2}
        # process b started 5ms after a on the wall clock; after the
        # per-process offset correction their events are ~5ms apart
        t_by_pid = {e["pid"]: e["ts"] for e in xs}
        assert abs((t_by_pid[2] - t_by_pid[1]) - 5000.0) < 1.0
        metas = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
        assert len(metas) == 2

        # the vtctl path: two journals on disk, -d twice
        from volcano_tpu.cli.vtctl import main as vtctl_main

        dirs = []
        for i, rec in enumerate(records):
            j = _trace.Journal(str(tmp_path / f"j{i}"))
            j.write_cycle(rec)
            dirs.append(str(tmp_path / f"j{i}"))
        out_path = str(tmp_path / "merged.json")
        out = io.StringIO()
        rc = vtctl_main(
            ["trace", "export", "-d", dirs[0], "-d", dirs[1],
             "-o", out_path],
            api=APIServer(), out=out,
        )
        assert rc == 0
        data = json.load(open(out_path))
        assert {e["pid"] for e in data["traceEvents"]
                if e.get("ph") == "X"} == {1, 2}

    def test_single_dir_unchanged(self, tmp_path):
        from volcano_tpu import trace as _trace
        from volcano_tpu.cli.vtctl import main as vtctl_main

        j = _trace.Journal(str(tmp_path / "j"))
        j.write_cycle({"cycle": 0, "start_us": 0.0, "duration_ms": 1.0,
                       "wall_time": 1.0, "events": [], "decisions": []})
        out = io.StringIO()
        rc = vtctl_main(["trace", "export", "-d", str(tmp_path / "j")],
                        api=APIServer(), out=out)
        assert rc == 0
        data = json.loads(out.getvalue())
        assert data["metadata"]["cycle"] == 0


# ---- loadgen stage breakdown plumbing ----

class TestStageBreakdown:
    def test_attribution_from_spans(self):
        t1 = obs.trace_id_for_pod("ns", "p1")
        spans = [
            _mk("cycle:task", "c1", ts=0.0, dur=8000.0),
            _mk("kernel:execute", "k1", parent="c1", ts=1000.0, dur=2000.0),
            _mk("bind:landed", "b1", parent="c1", trace=t1, ts=7000.0,
                dur=0.0),
        ]
        out = obs.stage_breakdown(spans, [("ns", "p1"), ("ns", "absent")])
        assert out["pods_with_spans"] == 1
        assert out["stages"]["kernel:execute"]["mean_ms"] == 2.0
        assert out["stages"]["cycle:task"]["count"] == 1
