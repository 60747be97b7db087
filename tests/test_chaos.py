"""Chaos: seeded randomized fault schedules over multi-cycle e2e runs.

The proof for the fault plane (ISSUE 5): the full control loop — store
→ BusServer → RemoteAPIServer informers → SchedulerCache → jax-allocate
→ the dispatch's degradation ladder → bind effects — runs for many
cycles while the seeded plane fires faults at every seam at once (bus
drops/partitions/relist storms, device lowering failures and corrupt
kernel outputs, bind-failure bursts feeding the resync queue), and the
run must end with

  * zero duplicate binds (no pod ever re-bound at the store),
  * zero lost jobs (every pod bound + running once faults stop),
  * store/cache coherence (node-held task sets equal API truth),
  * for the selector-pinned workload, a binding map BIT-IDENTICAL to
    the fault-free twin run on the same workload.

The tier-1 smoke runs a short mixed schedule; the ≥200-cycle soak and
the rolling-workload convergence runs are marked ``slow``.
"""

from __future__ import annotations

import time
from collections import defaultdict

import pytest

from volcano_tpu import faults, trace
from volcano_tpu.bus.remote import RemoteAPIServer
from volcano_tpu.bus.server import BusServer
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.client import (
    ADDED,
    APIServer,
    KubeClient,
    MODIFIED,
    SchedulerClient,
    VolcanoClient,
)
from volcano_tpu.scheduler.scheduler import Scheduler

from tests.builders import build_node, build_pod, build_pod_group, build_queue

CONF = """
actions: "enqueue, jax-allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure(None)
    faults.reset_breakers()
    faults.configure_deadline(None)
    yield
    faults.configure(None)
    faults.reset_breakers()
    faults.configure_deadline(None)
    trace.disable()


def _wait(pred, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class ChaosCluster:
    """The full control loop in one harness, every seam real: informers
    over a TCP bus, kernels behind the dispatch's degradation ladder,
    binds through the bus client.  The store-side audit watch records every
    bind transition from API truth (it runs on the in-process server,
    outside fault injection)."""

    def __init__(self, tmp_path, name, n_nodes=8, node_cpu="4"):
        self.api = APIServer()
        self.bus = BusServer(self.api).start()
        self.remote = RemoteAPIServer(
            f"tcp://127.0.0.1:{self.bus.port}", timeout=5.0
        )
        assert self.remote.wait_ready(10.0)
        self.kube = KubeClient(self.api)
        self.vc = VolcanoClient(self.api)
        self.vc.create_queue(build_queue("default"))
        self.n_nodes = n_nodes
        for i in range(n_nodes):
            self.kube.create_node(build_node(
                f"n{i}", {"cpu": node_cpu, "memory": "64Gi"},
                labels={"slot": f"s{i}"},
            ))

        #: ns/name → node, from store truth; rebind = duplicate bind
        self.bound = {}
        self.rebinds = []
        self._kubelet_pending = []
        self.api.watch("Pod", self._audit, send_initial=False)

        self.client = SchedulerClient(self.remote)
        #: ns/name → successful bind calls — a second successful bind
        #: for one pod is a duplicate even if it picked the same node
        #: (the k8s binding subresource would 409).  Both bind paths are
        #: counted: per-object bind_pod AND binds riding the coalesced
        #: commit_batch frame (the pipelined plane's fast path).
        self.bind_calls = defaultdict(int)
        original_bind = self.client.bind_pod

        def counted_bind(namespace, name, hostname):
            original_bind(namespace, name, hostname)
            self.bind_calls[f"{namespace}/{name}"] += 1

        self.client.bind_pod = counted_bind
        original_commit = self.client.commit_batch

        def counted_commit(binds=(), evicts=(), events=(), conditions=(),
                           pod_groups=()):
            binds = list(binds)
            results = original_commit(
                binds=binds, evicts=evicts, events=events,
                conditions=conditions, pod_groups=pod_groups,
            )
            for b, err in zip(binds, results.get("binds", ())):
                if err is None:
                    self.bind_calls[f"{b['namespace']}/{b['name']}"] += 1
            return results

        self.client.commit_batch = counted_commit

        # the chaos loop runs with the PIPELINED commit plane on —
        # faults fire while commits are in flight, and the acceptance
        # bar (no dup binds, no lost jobs, coherence, bit-identical
        # pinned map vs the fault-free twin) must hold regardless
        self.cache = SchedulerCache(
            client=self.client, scheduler_name="volcano-tpu",
            pipelined_commit=True,
        )
        # chaos-rate timing: resync retries and quarantine re-entry
        # collapse from seconds to cycle-scale
        self.cache._RESYNC_BACKOFF_BASE = 0.01
        self.cache._QUARANTINE_COOLDOWN = 0.1
        conf = tmp_path / f"{name}-conf.yaml"
        conf.write_text(CONF)
        self.scheduler = Scheduler(self.cache, scheduler_conf_path=str(conf))
        self.cache.run()
        self.cycle_errors = 0

    # ---- store-truth watchers ----

    def _audit(self, event, old, new):
        if event not in (ADDED, MODIFIED) or new is None:
            return
        key = f"{new.metadata.namespace}/{new.metadata.name}"
        node = new.spec.node_name
        if not node:
            return
        prev = self.bound.get(key)
        if prev is None:
            self.bound[key] = node
        elif prev != node:
            self.rebinds.append((key, prev, node))
        if new.status.phase == "Pending":
            self._kubelet_pending.append((new.metadata.namespace,
                                          new.metadata.name))

    def _kubelet_drain(self):
        while self._kubelet_pending:
            namespace, name = self._kubelet_pending.pop()
            pod = self.kube.get_pod(namespace, name)
            if pod is not None and pod.spec.node_name and \
                    pod.status.phase == "Pending":
                pod.status.phase = "Running"
                self.kube.update_pod_status(pod)

    # ---- workload ----

    def submit(self, name, replicas=3, cpu="1", pin_slots=None):
        """One gang job: a PodGroup with min_member=replicas plus its
        pods.  ``pin_slots`` gives each pod a node selector to a unique
        slot label — the workload whose final binding map is forced,
        hence comparable bit-for-bit across runs."""
        self.vc.create_pod_group(build_pod_group("ns", name, replicas))
        for i in range(replicas):
            selector = None
            if pin_slots is not None:
                selector = {"slot": f"s{pin_slots[i] % self.n_nodes}"}
            self.kube.create_pod(build_pod(
                "ns", f"{name}-t{i}", "", {"cpu": cpu, "memory": "1Gi"},
                group=name, selector=selector,
            ))

    def finish(self, name, replicas):
        for i in range(replicas):
            pod = self.kube.get_pod("ns", f"{name}-t{i}")
            if pod is not None and pod.status.phase == "Running":
                pod.status.phase = "Succeeded"
                self.kube.update_pod_status(pod)

    # ---- the loop ----

    def cycle(self):
        try:
            self.scheduler.run_once()
        except Exception:  # noqa: BLE001 — a partitioned cycle fails fast,
            # exactly like BaseDaemon._loop logs and retries in prod
            self.cycle_errors += 1
        self._kubelet_drain()

    def run_cycles(self, n, pause=0.01):
        for _ in range(n):
            self.cycle()
            time.sleep(pause)  # let watch frames propagate off-thread

    # ---- assertions ----

    def pods(self):
        return self.kube.list_pods("ns")

    def all_placed(self):
        pods = self.pods()
        return bool(pods) and all(p.spec.node_name for p in pods)

    def assert_no_duplicate_binds(self):
        assert self.rebinds == [], f"store saw rebinds: {self.rebinds}"
        dupes = {k: c for k, c in self.bind_calls.items() if c > 1}
        assert not dupes, f"duplicate successful bind calls: {dupes}"

    def assert_coherent(self):
        """Cache node accounting == API truth (non-terminated pods with
        a node), after the informers settle."""
        def check():
            truth = defaultdict(set)
            for pod in self.pods():
                if pod.spec.node_name and pod.status.phase in (
                    "Pending", "Running",
                ):
                    truth[pod.spec.node_name].add(pod.metadata.uid)
            with self.cache._mutex:
                for name in truth:
                    node = self.cache.nodes.get(name)
                    if node is None or set(node.tasks) != truth[name]:
                        return False
                for name, node in self.cache.nodes.items():
                    if name not in truth and node.tasks:
                        return False
            return True

        assert _wait(check, timeout=15.0), "cache diverged from store truth"

    def binding_map(self):
        return dict(self.bound)

    def close(self):
        self.cache.stop_commit_plane()
        self.remote.close()
        self.bus.stop()


#: the mixed schedule of the acceptance criterion: bus drops + device
#: lowering failures + corrupt kernel outputs + bind bursts, all bounded
#: by count so the settle phase converges
MIXED_FAULTS = (
    "seed={seed};"
    "bus.disconnect=0.03:count=4;"
    "bus.drop_event=0.02:count=4;"
    "bus.force_relist=0.3:count=4;"
    "bus.delay=0.05:count=6:ms=5;"
    "bus.client_drop=0.03:count=3;"
    "device.lowering=0.12:count=3;"
    "device.nan=0.1:count=2;"
    "cache.bind_fail=0.12:count=5;"
    "cache.resync_fail=0.3:count=3;"
    "commit.fail=0.15:count=4;"
    "commit.delay=0.2:count=6:ms=30"
)


def _submit_mixed_workload(cluster):
    cluster.submit("free-a", replicas=3)
    cluster.submit("free-b", replicas=3)
    cluster.submit("free-c", replicas=2)
    cluster.submit("pinned", replicas=4, pin_slots=[4, 5, 6, 7])


class TestChaosSmoke:
    def test_mixed_fault_schedule_converges(self, tmp_path):
        """Tier-1 chaos smoke: every seam faulted at once over a
        multi-cycle run; convergence, no-dup, no-loss, coherence, and
        the pinned workload bit-identical to a fault-free twin."""
        faulty = ChaosCluster(tmp_path, "faulty")
        try:
            _submit_mixed_workload(faulty)
            faults.configure(MIXED_FAULTS.format(seed=1234))
            plane = faults.get_plane()
            faulty.run_cycles(25)
            fired = plane.fired()
            faults.configure(None)
            # settle: faults off, the loop must converge
            assert _wait(
                lambda: (faulty.cycle() or True) and faulty.all_placed(),
                timeout=30.0, interval=0.05,
            ), f"pods still unplaced; faults fired: {fired}"
            assert len(faulty.pods()) == 12
            faulty.assert_no_duplicate_binds()
            faulty.assert_coherent()
            # the schedule actually exercised multiple seams
            assert len(fired) >= 4, f"schedule barely fired: {fired}"
            faulty_map = faulty.binding_map()
        finally:
            faulty.close()
            faults.configure(None)
            faults.reset_breakers()

        clean = ChaosCluster(tmp_path, "clean")
        try:
            _submit_mixed_workload(clean)
            assert _wait(
                lambda: (clean.cycle() or True) and clean.all_placed(),
                timeout=30.0, interval=0.05,
            )
            clean.assert_no_duplicate_binds()
            clean_map = clean.binding_map()
        finally:
            clean.close()

        # pinned workload: bit-identical bindings vs the fault-free run
        pinned = {k: v for k, v in faulty_map.items() if "pinned" in k}
        pinned_clean = {k: v for k, v in clean_map.items() if "pinned" in k}
        assert pinned == pinned_clean and len(pinned) == 4
        # free jobs: same placement count either way (no lost pods)
        assert set(faulty_map) == set(clean_map)

    def test_chaos_run_is_journaled(self, tmp_path):
        """Fault firings land in the PR-1 trace journal — the chaos run
        is replayable forensics.  CI points VTPU_CHAOS_JOURNAL_DIR at a
        stable path and uploads it as a build artifact."""
        import os

        jdir = os.environ.get("VTPU_CHAOS_JOURNAL_DIR") or str(
            tmp_path / "journal"
        )
        rec = trace.enable(jdir)
        cluster = ChaosCluster(tmp_path, "journaled")
        try:
            cluster.submit("j0", replicas=2)
            faults.configure(
                "seed=7;cache.bind_fail=1:count=2;device.lowering=1:count=1"
            )
            cluster.run_cycles(6)
            faults.configure(None)
            _wait(lambda: (cluster.cycle() or True) and cluster.all_placed(),
                  timeout=20.0)
        finally:
            cluster.close()
            trace.disable()
        journal = trace.Journal(jdir)
        fault_events = []
        for cid in journal.cycles():
            record = journal.read_cycle(cid)
            fault_events += [
                e["name"] for e in record.get("events", [])
                if e["name"].startswith("fault:")
            ]
        assert any(e == "fault:cache.bind_fail" for e in fault_events)
        assert any(e == "fault:device.lowering" for e in fault_events)


class TestKillRecovery:
    def test_kill_apiserver_mid_watch_recovers(self, tmp_path):
        """Acceptance: kill-the-apiserver mid-watch → the bus client
        redials the restarted incarnation, relists (new epoch), and the
        control loop converges with no duplicate binds."""
        from volcano_tpu.bus.server import BusServer as _BusServer

        cluster = ChaosCluster(tmp_path, "bus-kill")
        try:
            cluster.submit("b0", replicas=3)
            assert _wait(
                lambda: (cluster.cycle() or True) and cluster.all_placed(),
                timeout=30.0, interval=0.05,
            )
            port = cluster.bus.port
            cluster.bus.stop()
            # work submitted during the outage (store is still alive —
            # the bus is the watch/CRUD front door, not the store)
            cluster.submit("b1", replicas=3)
            cluster.run_cycles(3)  # these fail fast on BusError
            # restart on the same port, same store, NEW epoch → resume
            # tokens are rejected and every informer relists
            cluster.bus = _BusServer(
                cluster.api, port=port
            ).start()
            assert _wait(
                lambda: (cluster.cycle() or True) and cluster.all_placed(),
                timeout=45.0, interval=0.05,
            ), "control loop did not converge after apiserver restart"
            cluster.assert_no_duplicate_binds()
            cluster.assert_coherent()
        finally:
            cluster.close()

    def test_cycle_deadline_completes_on_host_path(self, tmp_path):
        """Acceptance: an overrunning device phase is abandoned by the
        cycle watchdog and the cycle completes on the host path — jobs
        still schedule, the demotion is counted."""
        from volcano_tpu.metrics import metrics

        cluster = ChaosCluster(tmp_path, "watchdog")
        try:
            faults.configure_deadline(250.0)
            # the device phase sleeps past the whole budget every time
            # it runs for the next few sessions
            faults.configure("seed=1;device.slow=1:count=3:ms=400")
            cluster.submit("w0", replicas=3)
            assert _wait(
                lambda: (cluster.cycle() or True) and cluster.all_placed(),
                timeout=40.0, interval=0.05,
            )
            cluster.assert_no_duplicate_binds()
            key = ("volcano_executor_fallbacks_total",
                   (("cause", "deadline"), ("from", "device"),
                    ("to", "host")))
            assert metrics.registry._counters.get(key, 0) >= 1
        finally:
            faults.configure_deadline(None)
            cluster.close()


@pytest.mark.slow
class TestChaosSoak:
    def test_soak_200_cycles_rolling_workload_bit_identical(self, tmp_path):
        """≥200 cycles under the mixed schedule with a rolling pinned
        workload (jobs arrive and complete throughout).  Ends with zero
        duplicate binds, zero lost jobs, coherence, and a binding map
        bit-identical to the fault-free twin on the same workload."""
        def drive(name, spec):
            cluster = ChaosCluster(tmp_path, name, n_nodes=8)
            submitted = []
            try:
                if spec:
                    faults.configure(spec)
                plane = faults.get_plane()
                for i in range(210):
                    if i % 7 == 0 and i // 7 < 24:
                        j = i // 7
                        jname = f"roll-{j}"
                        # 3 tasks pinned to a sliding slot window: jobs
                        # overlapping on slots serialize, completions
                        # free them — arrival/completion dynamics with a
                        # forced final map
                        cluster.submit(
                            jname, replicas=3,
                            pin_slots=[j, j + 1, j + 2],
                        )
                        submitted.append(jname)
                    if i % 7 == 5 and submitted:
                        # completions free the slots for the next wave
                        cluster.finish(submitted[0], 3)
                        submitted.pop(0)
                    cluster.cycle()
                    time.sleep(0.005)
                fired = dict(plane.fired()) if plane.enabled else {}
                faults.configure(None)
                assert _wait(
                    lambda: (cluster.cycle() or True) and cluster.all_placed(),
                    timeout=60.0, interval=0.05,
                ), f"lost pods after soak; fired: {fired}"
                assert len(cluster.pods()) == 24 * 3
                cluster.assert_no_duplicate_binds()
                cluster.assert_coherent()
                return cluster.binding_map(), fired
            finally:
                cluster.close()
                faults.configure(None)
                faults.reset_breakers()

        faulty_map, fired = drive("soak-faulty", MIXED_FAULTS.format(seed=77))
        assert len(fired) >= 5, f"soak schedule barely fired: {fired}"
        clean_map, _ = drive("soak-clean", "")
        assert faulty_map == clean_map
        assert len(faulty_map) == 24 * 3
