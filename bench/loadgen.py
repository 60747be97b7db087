"""loadgen — the sustained-load SLO harness (ROADMAP item 2).

Batch cycle latency stopped being the user-visible number once the
relay floor collapsed (BENCH_r05): under sustained churn what a user
feels is **submit→bind latency** — how long a freshly-created pod waits
before its binding lands back on the bus.  This harness measures
exactly that, over the REAL bus topology (TCP BusServer, RemoteAPIServer
informers, pipelined commit plane, event-driven micro-cycle scheduler):

  * an **open-loop** arrival stream — job arrival times are fixed by
    the offered rate up front, never gated on the system keeping up, so
    saturation shows up as growing latency instead of a politely
    slowed-down generator;
  * per-pod submit→bind latency observed from store truth (an audit
    watch on the in-process server, outside the measured path);
  * p50/p95/p99/max, achieved throughput, the micro-vs-full cycle mix,
    and the full-cycle fallback causes;
  * optionally (``--find-saturation``) a rate ramp that reports the
    highest offered rate whose p99 still meets the SLO.

This is the regression gate for subsequent perf PRs: CI runs
``--quick`` and uploads the JSON next to the relay-breakdown artifact.

Usage::

    JAX_PLATFORMS=cpu python bench/loadgen.py --quick
    python bench/loadgen.py --rate 2000 --duration 30 --nodes 1000
    python bench/loadgen.py --find-saturation --slo-ms 100

The O(pending) resident drill (``--resident-sweep``; CI runs it at
``--quick`` shape and uploads the JSON as the ``resident-slo``
artifact) grows the already-Running job population 10× while the
pending stream stays constant, and gates p99 submit→bind within 1.2×
and the restricted session-open mean within 2×::

    JAX_PLATFORMS=cpu python bench/loadgen.py --quick --resident-sweep

The full 100k-node / 1M-resident-job campaign is a slow/bench recipe,
not a CI job — run it on a real machine with ~1h and tens of GB of
RAM.  Preloading 1M pods through the store dominates setup time;
budget ~20 min before the measured stream starts::

    JAX_PLATFORMS=cpu python bench/loadgen.py \\
        --nodes 100000 --node-cpu 64 \\
        --resident 100000 --resident-sweep \\
        --rate 200 --duration 60 --drain-timeout 600 \\
        --warmup-timeout 1200 --period 30

(``--resident 100000`` sweeps 100k → 1M resident jobs; ``--period
30`` keeps the periodic full-session re-equilibration — which stays
O(resident) by design — from swamping the run.  Track ``rss_bytes``
per member across the sweep for the memory half of the headline; a
federated variant adds ``--shards 4`` and reads per-process RSS.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List

import numpy as np

# run from the repo root OR as bench/loadgen.py — same bootstrap the
# other bench/prof_* scripts use
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

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


class LoadgenTopology:
    """The full control loop, every seam real: in-process store behind a
    TCP BusServer, the scheduler cache fed by RemoteAPIServer informers,
    binds riding the pipelined commit plane, and the event-driven
    micro-cycle loop doing the scheduling.  The audit watch runs on the
    in-process store — store truth, off the measured path."""

    def __init__(self, n_nodes: int, node_cpu: int, conf_path: str,
                 period: float, debounce_ms: float,
                 micro_cycles: bool = True, restricted: bool = False,
                 resident: int = 0):
        self._init_store(n_nodes, node_cpu, resident=resident)
        self._start_scheduler(conf_path, period, debounce_ms, micro_cycles,
                              restricted=restricted)

    def _init_store(self, n_nodes: int, node_cpu: int,
                    resident: int = 0) -> None:
        from volcano_tpu.bus.server import BusServer
        from volcano_tpu.client import (
            ADDED,
            APIServer,
            KubeClient,
            MODIFIED,
            VolcanoClient,
        )

        self.api = APIServer()
        self.bus = BusServer(self.api).start()
        self.bus_address = f"tcp://127.0.0.1:{self.bus.port}"
        # arrivals land on the in-process store (the generator is
        # colocated with the apiserver, off the measured path) and reach
        # the SCHEDULER over the real TCP watch stream — the measured
        # leg.  Submitting over a third TCP connection would serialize
        # the open-loop generator on round-trips it is not supposed to
        # be measuring.
        self.kube = KubeClient(self.api)
        self.vc = VolcanoClient(self.api)

        self.vc.create_queue(_build_queue("default"))
        for i in range(n_nodes):
            self.kube.create_node(
                _build_node(_node_name(i), {"cpu": str(node_cpu),
                                            "memory": "256Gi"})
            )

        #: ``--resident``: preload N already-Running single-task jobs
        #: (pods pre-bound round-robin, 1m/1Mi requests so they occupy
        #: jobs, not capacity) BEFORE the scheduler attaches — the
        #: resident ballast the incremental-session plane claims not to
        #: pay per cycle.  The reaper skips them (fixed population).
        self.n_resident = resident
        if resident:
            from volcano_tpu.apis import scheduling

        for i in range(resident):
            name = f"resident-r{i:06d}"
            pg = _build_pod_group("ns", name, 1)
            # already Running at store truth — a fresh Inqueue phase
            # would make the first full cycle write back O(resident)
            # phase migrations, which no real resident population pays
            pg.status.phase = scheduling.POD_GROUP_RUNNING
            self.vc.create_pod_group(pg)
            pod = _build_pod("ns", f"{name}-t0",
                             {"cpu": "1m", "memory": "1Mi"}, group=name)
            pod.spec.node_name = _node_name(i % n_nodes)
            pod.status.phase = "Running"
            self.kube.create_pod(pod)

        #: ns/name → wall-clock the bind landed at store truth
        self.bind_ts: Dict[str, float] = {}
        self._bind_lock = threading.Lock()

        def audit(event, old, new):
            if event not in (ADDED, MODIFIED) or new is None:
                return
            if not new.spec.node_name:
                return
            key = f"{new.metadata.namespace}/{new.metadata.name}"
            with self._bind_lock:
                self.bind_ts.setdefault(key, time.time())

        self.api.watch("Pod", audit, send_initial=False)

        #: completion churn: bound pods finish ``complete_after_s`` after
        #: their bind and their job objects are deleted — sustained load
        #: means arrivals AND departures, and without departures the
        #: resident job count (and with it the O(jobs) session cost of
        #: every cycle) grows without bound, which is a different
        #: experiment.  0 disables (short drains / saturation probes).
        self.complete_after_s = 0.0
        self._group_size: Dict[str, int] = {}
        self._reaper_stop = threading.Event()
        self._reaper = threading.Thread(
            target=self._reap_loop, name="loadgen-reaper", daemon=True
        )
        self._reaper.start()

    def _start_scheduler(self, conf_path: str, period: float,
                         debounce_ms: float, micro_cycles: bool,
                         restricted: bool = False) -> None:
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.cache import SchedulerCache
        from volcano_tpu.client import SchedulerClient
        from volcano_tpu.scheduler.scheduler import Scheduler

        self.sched_remote = RemoteAPIServer(self.bus_address, timeout=10.0)
        assert self.sched_remote.wait_ready(10.0)
        self.cache = SchedulerCache(
            client=SchedulerClient(self.sched_remote),
            scheduler_name="volcano-tpu",
            pipelined_commit=True,
        )
        self.scheduler = Scheduler(
            self.cache, scheduler_conf_path=conf_path, period=period,
            micro_cycles=micro_cycles, micro_debounce_ms=debounce_ms,
            restricted_sessions=restricted,
        )
        self._thread = threading.Thread(
            target=self.scheduler.run, name="loadgen-scheduler", daemon=True
        )
        self._thread.start()

    def _reap_loop(self) -> None:
        from volcano_tpu.client.apiserver import ApiError

        reaped = set()
        done_per_group: Dict[str, int] = {}
        while not self._reaper_stop.wait(0.1):
            if self.complete_after_s <= 0:
                continue
            cutoff = time.time() - self.complete_after_s
            with self._bind_lock:
                due = [
                    k for k, ts in self.bind_ts.items()
                    if ts <= cutoff and k not in reaped
                    # resident ballast never completes — its population
                    # is the controlled variable of --resident runs
                    and not k.partition("/")[2].startswith("resident-")
                ]
            for key in due:
                ns, name = key.split("/", 1)
                group = name.rsplit("-t", 1)[0]
                try:
                    self.api.delete("Pod", ns, name)
                except ApiError:
                    pass
                reaped.add(key)
                done_per_group[group] = done_per_group.get(group, 0) + 1
                if done_per_group[group] >= self._group_size.get(group, 1):
                    try:
                        self.api.delete("PodGroup", ns, group)
                    except ApiError:
                        pass

    def submit_job(self, name: str, tasks: int, cpu: str) -> List[str]:
        """One job: PodGroup + its pods, onto the store.  Returns the
        pod keys whose binds the audit watch will stamp."""
        self.vc.create_pod_group(_build_pod_group("ns", name, tasks))
        self._group_size[name] = tasks
        keys = []
        for i in range(tasks):
            pod_name = f"{name}-t{i}"
            self.kube.create_pod(
                _build_pod("ns", pod_name, {"cpu": cpu, "memory": "1Gi"},
                           group=name)
            )
            keys.append(f"ns/{pod_name}")
        return keys

    def bound_count(self, keys) -> int:
        with self._bind_lock:
            return sum(1 for k in keys if k in self.bind_ts)

    def rss_report(self) -> Dict[str, int]:
        """Resident-set size per scheduling member, bytes.  The
        in-process topology's scheduler shares the harness process."""
        return {"scheduler": _rss_bytes()}

    def close(self):
        self._reaper_stop.set()
        self._reaper.join(timeout=5)
        self.scheduler.stop()
        self._thread.join(timeout=15)
        self.cache.stop_commit_plane()
        self.sched_remote.close()
        self.bus.stop()


def _free_port() -> int:
    import socket as _socket

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class FederatedTopology(LoadgenTopology):
    """The sharded federation under load, topology fully real: the same
    in-process store + TCP bus + audit watch, but scheduling is done by
    ``--shards N`` **separate OS processes** running the actual
    ``vtpu-scheduler`` binary — shard-assignment leases, filtered
    informers, spillover CAS binds, pipelined commits, micro-cycles,
    the lot.  This is the harness behind the 1M-pods/100k-nodes
    aggregate headline and the near-linear 1→4 shard throughput claim.
    """

    def __init__(self, n_nodes: int, node_cpu: int, conf_path: str,
                 period: float, debounce_ms: float, n_shards: int,
                 lease_duration: float = 2.0,
                 micro_cycles: bool = True,
                 startup_timeout: float = 180.0,
                 log_dir: str = "",
                 n_members: int = 0,
                 extra_flags=(), resident: int = 0):
        import subprocess

        self._init_store(n_nodes, node_cpu, resident=resident)
        self.n_shards = n_shards
        #: with ``n_members > n_shards`` the extra schedulers run as
        #: warm STANDBYS: registered members that hold no slice until
        #: the map grows (fair share hands them nothing) — the ramp
        #: drill's pre-provisioned pool, so the rebalance gate measures
        #: the lease plane, not Python process startup
        self.n_members = n_members or n_shards
        self.procs = []
        self._logs = []
        url = f"tcp://127.0.0.1:{self.bus.port}"
        for i in range(self.n_members):
            cmd = [
                sys.executable, "-m", "volcano_tpu.cmd.scheduler",
                "--bus", url,
                "--shards", str(n_shards),
                "--shard-identity", f"shard{i}",
                "--shard-lease-duration", str(lease_duration),
                "--schedule-period", str(period),
                "--micro-debounce-ms", str(debounce_ms),
                "--pipelined-commit",
                "--scheduler-conf", conf_path,
                "--listen-port", "0",
                *extra_flags,
            ]
            if micro_cycles:
                cmd.append("--micro-cycles")
            log_path = os.path.join(
                log_dir or tempfile.gettempdir(), f"loadgen-shard{i}.log"
            )
            logf = open(log_path, "w")  # noqa: SIM115 — held for the proc
            self._logs.append(logf)
            self.procs.append(subprocess.Popen(
                cmd, stdout=logf, stderr=subprocess.STDOUT,
                # N schedulers cannot share one chip: a host-only drill
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            ))
        self._wait_federation(startup_timeout)

    def _wait_federation(self, timeout: float) -> None:
        from volcano_tpu.federation import read_shard_map

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for p in self.procs:
                rc = p.poll()
                if rc is not None:
                    raise RuntimeError(
                        f"shard scheduler exited rc={rc} during startup"
                    )
            rec = read_shard_map(self.api)
            if rec is not None:
                holders = {
                    e.get("holder")
                    for e in rec.get("shards", {}).values()
                }
                if "" not in holders and None not in holders and len(
                    rec.get("members", {})
                ) >= self.n_members:
                    return
            time.sleep(0.1)
        raise RuntimeError(
            f"federation did not form within {timeout}s "
            f"(map: {read_shard_map(self.api)})"
        )

    def kill_member(self, index: int) -> str:
        """SIGKILL one shard scheduler process mid-run — the loadgen
        face of the shard-kill chaos scenario.  Survivors must absorb
        its slices within one lease TTL and the drain still requires
        every pod to bind."""
        proc = self.procs[index]
        proc.kill()
        proc.wait(timeout=10)
        return f"shard{index}"

    def rss_report(self) -> Dict[str, int]:
        """RSS per member PROCESS — the resident-memory-per-member
        number the 1M-job campaign tracks."""
        return {
            f"shard{i}": _rss_bytes(p.pid)
            for i, p in enumerate(self.procs)
            if p.poll() is None
        }

    def shard_report(self) -> dict:
        from volcano_tpu.federation import read_shard_map

        rec = read_shard_map(self.api) or {}
        return {
            "shards": self.n_shards,
            "holders": {
                i: e.get("holder")
                for i, e in rec.get("shards", {}).items()
            },
            "members": sorted(rec.get("members", {})),
            "stats": rec.get("stats", {}),
        }

    def close(self):
        self._reaper_stop.set()
        self._reaper.join(timeout=5)
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — escalate to SIGKILL
                p.kill()
                p.wait(timeout=5)
        for f in self._logs:
            f.close()
        self.bus.stop()


class _ScaleWatcher(threading.Thread):
    """Ramp-drill observer: polls the shard map, records every shard-
    count change the autoscaler commits, and stamps how long the fleet
    took to REBALANCE after it (every slice of the new partition held
    by an unexpired lease) — the `rebalance within K lease TTLs` gate's
    measurement, taken from store truth off the measured path."""

    def __init__(self, api, lease_duration: float):
        super().__init__(name="loadgen-scale-watcher", daemon=True)
        self.api = api
        self.lease_duration = lease_duration
        # NOT `_stop`: threading.Thread uses a private `_stop()` METHOD
        # internally (tstate-lock cleanup) — shadowing it with an Event
        # crashes join()
        self._stop_evt = threading.Event()
        self._lock = threading.Lock()
        #: committed scale events: {"from", "target", "direction",
        #: "reason", "rebalance_s" (None until every slice is held)}
        self.events = []  # guarded-by: self._lock

    def run(self) -> None:
        from volcano_tpu.client.apiserver import ApiError
        from volcano_tpu.federation import read_shard_map

        last_n = None
        pending = []  # [t0, event] awaiting full coverage
        while not self._stop_evt.wait(0.05):
            try:
                rec = read_shard_map(self.api)
            except ApiError:
                continue
            if rec is None:
                continue
            n = int(rec.get("nShards", 0) or 0)
            if last_n is None:
                last_n = n
            elif n != last_n:
                blob = rec.get("autoscale", {}) or {}
                event = {
                    "from": last_n, "target": n,
                    "direction": blob.get("direction", "?"),
                    "reason": blob.get("reason", ""),
                    "rebalance_s": None,
                }
                with self._lock:
                    self.events.append(event)
                pending.append([time.monotonic(), event])
                last_n = n
            if pending:
                now_wall = time.time()
                covered = all(
                    e.get("holder")
                    and now_wall - float(e.get("renewTime", 0.0))
                    <= float(e.get("leaseDurationSeconds", 0.0) or 0.0)
                    for e in rec.get("shards", {}).values()
                ) and len(rec.get("shards", {})) == n
                if covered:
                    now = time.monotonic()
                    with self._lock:
                        for t0, event in pending:
                            event["rebalance_s"] = round(now - t0, 3)
                    pending = []

    def report(self) -> list:
        with self._lock:
            return [dict(e) for e in self.events]

    def stop(self) -> None:
        self._stop_evt.set()
        self.join(timeout=5)


class ReplicatedBusTopology(LoadgenTopology):
    """The replicated persistent bus under load: N real
    ``vtpu-apiserver`` OS processes (WAL dirs, leader election, quorum
    commit) instead of the in-process store, with the harness's own
    clients — submission, audit watch, the scheduler — dialing the full
    endpoint list.  ``--kill-apiserver-after`` SIGKILLs the LEADER mid
    open-loop stream; the drill passes only if a follower promotes,
    every submitted pod still binds (zero lost acknowledged binds), and
    no pod is ever re-bound."""

    def __init__(self, n_nodes: int, node_cpu: int, conf_path: str,
                 period: float, debounce_ms: float, n_replicas: int = 3,
                 lease_ttl: float = 1.0, micro_cycles: bool = True,
                 startup_timeout: float = 120.0):
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.client import ADDED, KubeClient, MODIFIED, VolcanoClient
        from volcano_tpu.client.apiserver import ApiError

        self.n_replicas = n_replicas
        self.lease_ttl = lease_ttl
        ports = [_free_port() for _ in range(n_replicas)]
        self.endpoints = [f"tcp://127.0.0.1:{p}" for p in ports]
        self.bus_address = ",".join(self.endpoints)
        self._data_root = tempfile.mkdtemp(prefix="loadgen-bus-")
        self.procs = []
        self._logs = []
        #: membership-drill forensics ({"op", "url", "ok", "error"})
        self.membership_events = []
        for i in range(n_replicas):
            self._spawn_apiserver(i, self.bus_address)

        # the audit/submission client dials the endpoint list REVERSED:
        # the staggered election makes replica 0 the bootstrap leader
        # (the kill target), and an audit watch riding the killed
        # replica would stamp every pre-kill bind at watch-RESUME time
        # — a measurement artifact, not system latency.  Watching from
        # a follower measures honestly: followers stream commit-gated
        # events continuously through the failover.
        self.api = RemoteAPIServer(
            ",".join(reversed(self.endpoints)), timeout=15.0
        )
        if not self.api.wait_ready(startup_timeout):
            raise RuntimeError("replicated apiserver group never came up")
        self.kube = KubeClient(self.api)
        self.vc = VolcanoClient(self.api)

        # seeding waits out the election + quorum window
        deadline = time.monotonic() + startup_timeout
        while True:
            try:
                self.vc.create_queue(_build_queue("default"))
                break
            except ApiError as e:
                if "already exists" in str(e):
                    break
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.25)
        for i in range(n_nodes):
            self.kube.create_node(
                _build_node(_node_name(i), {"cpu": str(node_cpu),
                                            "memory": "256Gi"})
            )

        self.bind_ts: Dict[str, float] = {}
        self.rebinds = 0
        self._bind_lock = threading.Lock()

        def audit(event, old, new):
            if event not in (ADDED, MODIFIED) or new is None:
                return
            if not new.spec.node_name:
                return
            key = f"{new.metadata.namespace}/{new.metadata.name}"
            with self._bind_lock:
                self.bind_ts.setdefault(key, time.time())
                if (
                    old is not None and old.spec.node_name
                    and old.spec.node_name != new.spec.node_name
                ):
                    self.rebinds += 1

        self.api.watch("Pod", audit, send_initial=False)

        self.complete_after_s = 0.0
        self._group_size: Dict[str, int] = {}
        self._reaper_stop = threading.Event()
        self._reaper = threading.Thread(
            target=self._reap_loop, name="loadgen-reaper", daemon=True
        )
        self._reaper.start()
        self._start_scheduler(conf_path, period, debounce_ms, micro_cycles)

    def _spawn_apiserver(self, index: int, replicas: str):
        """Start one real ``vtpu-apiserver`` process.  ``replicas`` is
        the endpoint list IT is told (a joiner gets the new full list;
        the original members keep theirs — the replicated membership
        config reconciles them after the add commits)."""
        import subprocess

        log_path = os.path.join(tempfile.gettempdir(),
                                f"loadgen-apiserver{index}.log")
        logf = open(log_path, "w")  # noqa: SIM115 — held for the proc
        self._logs.append(logf)
        port = int(self.endpoints[index].rsplit(":", 1)[1])
        proc = subprocess.Popen(
            [sys.executable, "-m", "volcano_tpu.cmd.apiserver",
             "--listen-host", "127.0.0.1", "--port", str(port),
             "--listen-port", "0",
             "--data-dir", os.path.join(self._data_root, f"r{index}"),
             "--replicas", replicas,
             "--replica-index", str(index),
             "--repl-lease-ttl", str(self.lease_ttl)],
            stdout=logf, stderr=subprocess.STDOUT,
            env=dict(os.environ),
        )
        if index < len(self.procs):
            self.procs[index] = proc
        else:
            self.procs.append(proc)
        return proc

    # ---- the membership add-then-remove drill ----

    def add_replica_member(self) -> dict:
        """Grow the group by ONE mid-stream: spawn a fresh apiserver
        told the whole NEW endpoint list (itself last), let it attach
        as a learner, then ask the group (through whichever replica we
        are connected to — a follower proxies) to admit it.  Retried
        across the catch-up window; the event record lands in
        ``membership_events`` for the report."""
        from volcano_tpu.client.apiserver import ApiError

        index = len(self.endpoints)
        url = f"tcp://127.0.0.1:{_free_port()}"
        self.endpoints.append(url)
        self._spawn_apiserver(index, ",".join(self.endpoints))
        event = {"op": "add", "url": url, "ok": False, "error": ""}
        deadline = time.monotonic() + max(self.lease_ttl * 30, 60.0)
        while time.monotonic() < deadline:
            try:
                res = self.api.bus_add_replica(url)
                event.update(ok=True, epoch=res.get("epoch"),
                             endpoints=res.get("endpoints"))
                break
            except ApiError as e:
                event["error"] = str(e)
                if "already a member" in str(e):
                    event["ok"] = True  # an earlier ambiguous try won
                    break
                time.sleep(0.5)
        self.membership_events.append(event)
        return event

    def remove_replica_member(self) -> dict:
        """Shrink the group by ONE mid-stream: retire the first
        ORIGINAL follower (never the leader — the op refuses that) and
        terminate its process once the config commits."""
        from volcano_tpu.client.apiserver import ApiError

        event = {"op": "remove", "url": "", "ok": False, "error": ""}
        deadline = time.monotonic() + max(self.lease_ttl * 30, 60.0)
        while time.monotonic() < deadline:
            lidx = self.leader_index()
            victims = [
                i for i in range(self.n_replicas)
                if i != lidx and self.procs[i].poll() is None
            ]
            if lidx is None or not victims:
                time.sleep(0.5)
                continue
            url = self.endpoints[victims[0]]
            event["url"] = url
            try:
                res = self.api.bus_remove_replica(url)
                event.update(ok=True, epoch=res.get("epoch"),
                             endpoints=res.get("endpoints"))
                # the retired replica stood down; take its process out
                # so the end-state probe proves the group is healthy
                # WITHOUT it
                self.procs[victims[0]].terminate()
                break
            except ApiError as e:
                event["error"] = str(e)
                if "is not a member" in str(e):
                    # an earlier ambiguous attempt committed (the
                    # answer was lost to a failover/proxy teardown) —
                    # the config no longer lists the victim, which is
                    # the outcome the drill wanted
                    event["ok"] = True
                    self.procs[victims[0]].terminate()
                    break
                time.sleep(0.5)
        self.membership_events.append(event)
        return event

    def membership_report(self) -> dict:
        """End-state membership truth: every live replica's epoch and
        endpoint list (the `exactly one surviving config` gate reads
        this), plus the drill's event log."""
        from volcano_tpu.bus.replication import probe_status

        epochs = {}
        configs = set()
        for i, url in enumerate(self.endpoints):
            if i < len(self.procs) and self.procs[i].poll() is not None:
                continue
            st = probe_status(url)
            if st is None or st.get("role") == "removed":
                continue
            epochs[url] = st.get("membership_epoch")
            members = st.get("membership")
            if members is not None:
                configs.add(tuple(members))
        return {
            "events": list(self.membership_events),
            "epochs": epochs,
            "distinct_configs": len(configs),
            "config": sorted(configs.pop()) if len(configs) == 1 else None,
        }

    def submit_job(self, name: str, tasks: int, cpu: str):
        """Bounded, IDEMPOTENT retry across the failover window: an
        arrival landing mid-election is retried rather than crashing
        the open-loop generator (its lag still counts as system latency
        — the clock started at the scheduled arrival instant), and a
        retry after an ambiguous failure treats AlreadyExists as
        success (the earlier attempt committed)."""
        from volcano_tpu.client.apiserver import AlreadyExistsError, ApiError

        # the budget must cover one full client timeout (a call parked
        # on a mid-reconnect connection) PLUS the election window
        deadline = time.monotonic() + max(self.lease_ttl * 10, 30.0)

        def create(fn, *args):
            while True:
                try:
                    fn(*args)
                    return
                except AlreadyExistsError:
                    return  # an ambiguous earlier attempt committed
                except ApiError:
                    if time.monotonic() >= deadline:
                        raise
                    time.sleep(0.1)

        create(self.vc.create_pod_group, _build_pod_group("ns", name, tasks))
        self._group_size[name] = tasks
        keys = []
        for i in range(tasks):
            pod_name = f"{name}-t{i}"
            create(
                self.kube.create_pod,
                _build_pod("ns", pod_name,
                           {"cpu": cpu, "memory": "1Gi"}, group=name),
            )
            keys.append(f"ns/{pod_name}")
        return keys

    def leader_index(self):
        from volcano_tpu.bus.replication import probe_status

        for i, url in enumerate(self.endpoints):
            st = probe_status(url)
            if st is not None and st.get("role") == "leader":
                return i
        return None

    def kill_leader(self) -> str:
        idx = self.leader_index()
        if idx is None:
            return "<no leader found>"
        self.procs[idx].kill()
        self.procs[idx].wait(timeout=10)
        return f"replica-{idx}"

    def bus_report(self) -> dict:
        from volcano_tpu.bus.replication import probe_status

        roles = {}
        for i, url in enumerate(self.endpoints):
            st = probe_status(url)
            roles[f"replica-{i}"] = (
                st.get("role") if st is not None else "dead"
            )
        with self._bind_lock:
            rebinds = self.rebinds
        return {"replicas": self.n_replicas, "roles": roles,
                "rebinds": rebinds}

    def close(self):
        self._reaper_stop.set()
        self._reaper.join(timeout=5)
        self.scheduler.stop()
        self._thread.join(timeout=15)
        self.cache.stop_commit_plane()
        self.sched_remote.close()
        self.api.close()
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — escalate to SIGKILL
                p.kill()
                p.wait(timeout=5)
        for f in self._logs:
            f.close()


# ---- builders (bench is standalone: no tests/ import) ----

def _node_name(i: int) -> str:
    """The topology's node naming — ONE copy, because `_gang_plan`
    recomputes per-shard node counts from these names via the same
    crc32 hash the schedulers use: a rename here that missed the
    sizing would silently stop gang auto-sizing being oversized."""
    return f"n{i:04d}"


def _build_node(name, alloc):
    from volcano_tpu.apis import core

    alloc = dict(alloc)
    alloc.setdefault("pods", 1024)
    return core.Node(
        metadata=core.ObjectMeta(name=name, namespace=""),
        spec=core.NodeSpec(),
        status=core.NodeStatus(allocatable=alloc, capacity=dict(alloc)),
    )


def _build_pod(namespace, name, req, group):
    from volcano_tpu.apis import core, scheduling

    return core.Pod(
        metadata=core.ObjectMeta(
            name=name, namespace=namespace,
            annotations={scheduling.GROUP_NAME_ANNOTATION_KEY: group},
        ),
        spec=core.PodSpec(
            containers=[core.Container(
                name="main", resources={"requests": dict(req)}
            )],
        ),
        status=core.PodStatus(phase="Pending"),
    )


def _build_pod_group(namespace, name, min_member):
    from volcano_tpu.apis import core, scheduling

    return scheduling.PodGroup(
        metadata=core.ObjectMeta(name=name, namespace=namespace),
        spec=scheduling.PodGroupSpec(min_member=min_member, queue="default"),
        status=scheduling.PodGroupStatus(phase=scheduling.POD_GROUP_INQUEUE),
    )


def _build_queue(name):
    from volcano_tpu.apis import core, scheduling

    return scheduling.Queue(
        metadata=core.ObjectMeta(name=name, namespace=""),
        spec=scheduling.QueueSpec(weight=1),
    )


def _rss_bytes(pid="self") -> int:
    """Resident-set size of a process in bytes (0 when unreadable —
    e.g. a member that exited, or a non-/proc platform)."""
    try:
        with open(f"/proc/{pid}/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        return 0


# ---- the measured phase ----

def run_phase(topo: LoadgenTopology, rate: float, duration: float,
              tasks_per_job: int, cpu: str, drain_timeout: float,
              label: str = "run", gang_every: int = 0,
              gang_size: int = 0, gang_cpu: str = "") -> dict:
    """Open-loop arrivals at ``rate`` jobs/sec for ``duration`` seconds;
    returns the phase's latency/throughput report.  With ``gang_every``
    set, every Nth arrival is an OVERSIZED gang (``gang_size`` tasks of
    ``gang_cpu`` each, minMember == size — sized larger than any one
    shard can hold, so binding it requires a cross-shard txn_commit
    assembly); the report then carries per-gang full-assembly latency
    (submit → LAST member bound) and the partial-gang count, which the
    exit gate requires to be zero."""
    n_jobs = max(int(rate * duration), 1)
    interval = 1.0 / rate
    submit_ts: Dict[str, float] = {}
    all_keys: List[str] = []
    gangs: Dict[str, tuple] = {}
    late = 0

    start = time.monotonic()
    wall0 = time.time()
    for i in range(n_jobs):
        due = start + i * interval
        now = time.monotonic()
        if now < due:
            time.sleep(due - now)
        elif now - due > interval:
            late += 1  # generator fell behind the open-loop schedule
        # the latency clock starts at the SCHEDULED arrival instant, not
        # the actual create call — open-loop discipline: if the
        # generator falls behind, the lag counts as system latency
        # instead of being silently absorbed (coordinated omission)
        t_submit = wall0 + (due - start)
        if gang_every and gang_size > 1 and i % gang_every == 0:
            name = f"{label}-g{i:06d}"
            keys = topo.submit_job(name, gang_size, gang_cpu)
            gangs[name] = (keys, t_submit)
        else:
            keys = topo.submit_job(f"{label}-j{i:06d}", tasks_per_job, cpu)
        for k in keys:
            submit_ts[k] = t_submit
        all_keys.extend(keys)

    # drain: every submitted pod must bind (or the run reports the loss)
    deadline = time.monotonic() + drain_timeout
    while time.monotonic() < deadline:
        if topo.bound_count(all_keys) == len(all_keys):
            break
        time.sleep(0.05)

    with topo._bind_lock:
        pairs = [
            (k, (topo.bind_ts[k] - submit_ts[k]) * 1e3)
            for k in all_keys if k in topo.bind_ts
        ]
        last_bind = max(
            (topo.bind_ts[k] for k in all_keys if k in topo.bind_ts),
            default=wall0,
        )
    lat = [v for _k, v in pairs]
    bound = len(lat)
    lat_arr = np.asarray(lat) if lat else np.asarray([float("nan")])
    span = max(last_bind - wall0, 1e-9)
    report = {
        "offered_rate_jobs_per_s": rate,
        "jobs": n_jobs,
        "tasks_per_job": tasks_per_job,
        "submitted_pods": len(all_keys),
        "bound_pods": bound,
        "late_arrivals": late,
        "p50_ms": round(float(np.percentile(lat_arr, 50)), 3),
        "p95_ms": round(float(np.percentile(lat_arr, 95)), 3),
        "p99_ms": round(float(np.percentile(lat_arr, 99)), 3),
        "max_ms": round(float(lat_arr.max()), 3),
        "achieved_pods_per_s": round(bound / span, 1),
    }
    if gangs:
        assembly: List[float] = []
        partial = 0
        with topo._bind_lock:
            for _name, (keys, t0) in gangs.items():
                binds = [topo.bind_ts.get(k) for k in keys]
                done = [t for t in binds if t is not None]
                if len(done) == len(keys):
                    # full-assembly latency: the gang is usable only
                    # when its LAST member is bound
                    assembly.append((max(done) - t0) * 1e3)
                elif done:
                    partial += 1  # the state txn_commit exists to forbid
        asm_arr = (
            np.asarray(assembly) if assembly else np.asarray([float("nan")])
        )
        report["gang_mix"] = {
            "gangs": len(gangs),
            "gang_size": gang_size,
            "gang_cpu": gang_cpu,
            "assembled": len(assembly),
            "partial_gangs": partial,
            "assembly_p50_ms": round(float(np.percentile(asm_arr, 50)), 3),
            "assembly_p99_ms": round(float(np.percentile(asm_arr, 99)), 3),
            "assembly_max_ms": round(float(asm_arr.max()), 3),
        }
    n_shards = getattr(topo, "n_shards", 0)
    if n_shards > 1:
        # per-shard percentiles, grouped by each pod's HOME shard (the
        # scheduler accountable for it — spillover binds still count
        # toward the home shard's latency, which is the user-visible
        # attribution)
        from volcano_tpu.federation.sharding import home_shard

        by_shard: Dict[int, List[float]] = {}
        for key, v in pairs:
            ns, name = key.split("/", 1)
            group = name.rsplit("-t", 1)[0]
            by_shard.setdefault(
                home_shard(ns, group, n_shards), []
            ).append(v)
        report["per_shard"] = {
            str(s): {
                "bound_pods": len(vals),
                "p50_ms": round(float(np.percentile(vals, 50)), 3),
                "p95_ms": round(float(np.percentile(vals, 95)), 3),
                "p99_ms": round(float(np.percentile(vals, 99)), 3),
            }
            for s, vals in sorted(by_shard.items())
        }
    return report


def _stage_breakdown(topo: LoadgenTopology, cap: int = 500) -> dict:
    """Attribute submit→bind latency to pipeline stages from the
    flight-recorder spans collected during the run (volcano_tpu/obs):
    per-stage count / mean / p99 over up to ``cap`` bound pods, plus
    the telemetry channel's own health (exported vs dropped).  The
    ``--stage-breakdown`` report CI uploads next to the SLO JSON."""
    from volcano_tpu import obs

    exp = obs.get_exporter()
    if exp is not None:
        exp.flush_all()
    spans = obs.collect_spans(topo.api)
    with topo._bind_lock:
        pods = [
            tuple(k.split("/", 1)) for k in list(topo.bind_ts)[:cap]
            if "-warm-" not in k
        ]
    out = obs.stage_breakdown(spans, pods)
    out["spans_collected"] = len(spans)
    if exp is not None:
        out["spans_exported"] = exp.exported
        out["spans_dropped"] = exp.dropped
    obs.disable()
    return out


def _cycle_mix(topo: LoadgenTopology) -> dict:
    from volcano_tpu.metrics import metrics

    micro = topo.scheduler.micro_cycles_run
    full = topo.scheduler.full_cycles_run
    fallbacks = {}
    with metrics.registry._lock:
        for (name, labels), v in metrics.registry._counters.items():
            if name.endswith("full_cycle_fallbacks_total"):
                fallbacks[dict(labels).get("cause", "?")] = v
    return {
        "micro_cycles": micro,
        "full_cycles": full,
        "micro_mix": round(micro / max(micro + full, 1), 3),
        "full_cycle_fallbacks": fallbacks,
    }


def _session_stats(topo: LoadgenTopology) -> dict:
    """Session-open cost + incremental-plane counters from the
    in-process scheduler — the numbers the --resident-sweep gates."""
    s = topo.scheduler
    return {
        "sessions_opened": s.sessions_opened,
        "session_open_mean_ms": round(
            s.session_open_seconds / max(s.sessions_opened, 1) * 1e3, 3),
        "restricted_cycles": s.restricted_cycles_run,
        "restricted_open_mean_ms": round(
            s.restricted_open_seconds
            / max(s.restricted_open_cycles, 1) * 1e3, 3),
        # median: the steady-cycle cost — one GC/contention stall in a
        # short CI run must not read as an O(resident) regression
        "restricted_open_p50_ms": round(float(np.median(
            s.restricted_open_samples)) * 1e3, 3)
        if s.restricted_open_samples else 0.0,
        "shadow_checks": s.shadow_checks_run,
        "shadow_divergences": s.shadow_divergences,
    }


def _warm_names(label: str, n_shards: int):
    """Warm job names covering every home shard (so each federation
    member compiles its kernels off the clock, not on the first
    measured arrival)."""
    from volcano_tpu.federation.sharding import home_shard

    out = []
    for shard in range(max(n_shards, 1)):
        k = 0
        while True:
            name = f"{label}-warm-s{shard}-{k}"
            if n_shards <= 1 or home_shard("ns", name, n_shards) == shard:
                out.append(name)
                break
            k += 1
    return out


def _gang_plan(args) -> tuple:
    """(gang_every, gang_size, gang_cpu) for ``--gang-mix``.  The auto
    size is deliberately OVERSIZED: larger than the task capacity of
    the biggest single shard (per-shard node counts come from the same
    crc32 hash every member uses), so no home shard can ever fit it and
    every gang exercises the cross-shard txn_commit assembly path."""
    if args.gang_mix <= 0:
        return 0, 0, ""
    gang_every = max(int(round(1.0 / args.gang_mix)), 1)
    gang_cpu = args.gang_cpu or str(max(args.node_cpu // 2, 1))
    # gang_cpu is a k8s cpu quantity like --cpu ("500m" or "2") — parse
    # with the store's own quantity parser so sizing cannot drift from
    # how the schedulers account the same string
    from volcano_tpu.apis.quantity import milli_value

    cores = milli_value(gang_cpu) / 1e3
    slots_per_node = max(int(args.node_cpu / max(cores, 1e-9)), 1)
    gang_size = args.gang_size
    if gang_size <= 0:
        if args.shards > 1:
            from volcano_tpu.federation.sharding import shard_of_node

            per_shard: Dict[int, int] = {}
            for i in range(args.nodes):
                s = shard_of_node(_node_name(i), args.shards)
                per_shard[s] = per_shard.get(s, 0) + 1
            gang_size = max(per_shard.values()) * slots_per_node + 1
        else:
            gang_size = min(8, args.nodes * slots_per_node)
    # an infeasible gang (bigger than the whole cluster) would wedge
    # the drain by design — clamp to what the fleet can ever hold
    gang_size = min(gang_size, args.nodes * slots_per_node)
    return gang_every, gang_size, gang_cpu


def run_loadgen(args) -> dict:
    with tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False) as f:
        f.write(CONF)
        conf_path = f.name

    gang_every, gang_size, gang_cpu = _gang_plan(args)

    def fresh_topo():
        if args.shards > 0:
            ramp_flags = []
            n_members = 0
            if args.ramp:
                # the scale-up-under-load drill: every member runs the
                # autoscale controller with a CI-tight policy (short
                # sustain/cooldown, queue-depth trigger) and the member
                # pool is pre-provisioned to the ceiling so the
                # rebalance gate measures the LEASE PLANE, not Python
                # process startup.  Scale-down is disabled for the
                # drill (down-pending 0 can never be breached): the
                # drill gates the up transition; the drain must not
                # race a shrink re-key.
                n_members = args.ramp_max_shards
                ramp_flags = [
                    "--shard-autoscale", "on",
                    "--autoscale-min", str(args.shards),
                    "--autoscale-max", str(args.ramp_max_shards),
                    "--autoscale-up-pending", str(args.ramp_up_pending),
                    "--autoscale-up-p99-ms", "1500",
                    "--autoscale-down-pending", "0",
                    "--autoscale-sustain", "2",
                    "--autoscale-cooldown-s", "3.0",
                    "--autoscale-period-s", "0.5",
                ]
            if args.restricted_sessions:
                ramp_flags = [*ramp_flags, "--restricted-sessions"]
            topo = FederatedTopology(
                n_nodes=args.nodes, node_cpu=args.node_cpu,
                conf_path=conf_path, period=args.period,
                debounce_ms=args.debounce_ms,
                n_shards=args.shards,
                lease_duration=args.shard_lease_duration,
                micro_cycles=not args.no_micro_cycles,
                n_members=n_members,
                extra_flags=ramp_flags,
                resident=args.resident,
            )
        elif args.apiserver_replicas > 0:
            topo = ReplicatedBusTopology(
                n_nodes=args.nodes, node_cpu=args.node_cpu,
                conf_path=conf_path, period=args.period,
                debounce_ms=args.debounce_ms,
                n_replicas=args.apiserver_replicas,
                lease_ttl=args.repl_lease_ttl,
                micro_cycles=not args.no_micro_cycles,
            )
        else:
            topo = LoadgenTopology(
                n_nodes=args.nodes, node_cpu=args.node_cpu,
                conf_path=conf_path, period=args.period,
                debounce_ms=args.debounce_ms,
                micro_cycles=not args.no_micro_cycles,
                restricted=args.restricted_sessions,
                resident=args.resident,
            )
        topo.complete_after_s = args.complete_after_s
        return topo

    def one_run(rate: float, label: str) -> dict:
        topo = fresh_topo()
        killers = []
        drill_done = threading.Event()
        drill_done.set()  # only the membership drill clears it
        scale_watcher = None
        if args.ramp:
            scale_watcher = _ScaleWatcher(
                topo.api, args.shard_lease_duration
            )
            scale_watcher.start()
        try:
            # warmup: prime the jit cache + watch streams off the clock,
            # so the first measured pod doesn't pay a kernel compile.
            # Two bursts of different sizes walk the scatter/kernel
            # shape buckets a churning run will actually hit; federated
            # runs warm EVERY member (one name per home shard).
            deadline = time.monotonic() + args.warmup_timeout
            for wi, burst in enumerate((4, 24)):
                warm = []
                for name in _warm_names(f"{label}w{wi}", args.shards):
                    warm.extend(topo.submit_job(name, burst, args.cpu))
                while time.monotonic() < deadline:
                    if topo.bound_count(warm) == len(warm):
                        break
                    time.sleep(0.05)
                if topo.bound_count(warm) != len(warm):
                    raise RuntimeError("warmup pods never bound")
            if args.shards > 0 and args.kill_shard_after > 0:
                # the shard-kill scenario under load: SIGKILL member 0
                # mid-stream; survivors must absorb its slices and the
                # drain still requires every pod to bind
                killer = threading.Timer(
                    args.kill_shard_after,
                    lambda: topo.kill_member(0),
                )
                killer.daemon = True
                killer.start()
                killers.append(killer)
            if args.apiserver_replicas > 0 and args.membership_drill:
                # the elastic-membership drill: grow the replication
                # group by one mid-stream (spawn + learner catch-up +
                # add-replica), then retire an original follower — all
                # while the open-loop arrivals keep landing.  Gates:
                # both changes commit, exactly ONE surviving config,
                # zero lost acked binds, zero re-binds.
                drill_done.clear()

                def _membership_drill():
                    try:
                        topo.add_replica_member()
                        time.sleep(1.0)
                        topo.remove_replica_member()
                    finally:
                        drill_done.set()

                killer = threading.Timer(args.membership_after,
                                         _membership_drill)
                killer.daemon = True
                killer.start()
                killers.append(killer)
            if args.apiserver_replicas > 0 and args.kill_apiserver_after > 0:
                # the bus-HA drill: SIGKILL the apiserver LEADER
                # mid-stream; a follower must promote within one lease
                # TTL and the drain still requires every pod to bind
                # (zero lost acknowledged binds, zero re-binds)
                killed = {}
                killer = threading.Timer(
                    args.kill_apiserver_after,
                    lambda: killed.setdefault("id", topo.kill_leader()),
                )
                killer.daemon = True
                killer.start()
                killers.append(killer)
            if args.stage_breakdown and hasattr(topo, "scheduler"):
                # flight recorder on the in-process scheduler: spans
                # batch to the topology's store; attribution runs AFTER
                # the drain, off the measured path.  (Federated runs
                # spawn real daemons — pass --flight-recorder there via
                # VTPU_FLIGHT_RECORDER instead.)
                from volcano_tpu import obs as _obs

                _obs.enable(topo.api, identity=f"loadgen-{label}")
            report = run_phase(
                topo, rate, args.duration, args.tasks_per_job, args.cpu,
                args.drain_timeout, label=label,
                gang_every=gang_every, gang_size=gang_size,
                gang_cpu=gang_cpu,
            )
            if hasattr(topo, "scheduler"):
                report.update(_cycle_mix(topo))
                report.update(_session_stats(topo))
            report["resident_jobs"] = getattr(topo, "n_resident", 0)
            report["rss_bytes"] = topo.rss_report()
            if args.stage_breakdown and hasattr(topo, "scheduler"):
                report["stage_breakdown"] = _stage_breakdown(topo)
            if args.apiserver_replicas > 0:
                report["bus_ha"] = topo.bus_report()
                if args.kill_apiserver_after > 0:
                    report["bus_ha"]["killed_leader"] = killed.get(
                        "id", "<kill timer never fired>"
                    )
                if args.membership_drill:
                    # the drill thread may still be mid-change when the
                    # drain finishes — the report must show END state
                    drill_done.wait(120.0)
                    report["bus_ha"]["membership"] = (
                        topo.membership_report()
                    )
            if args.shards > 0:
                report["federation"] = topo.shard_report()
                if scale_watcher is not None:
                    # give a mid-flight rebalance a bounded window to
                    # complete before stamping the report — the gate
                    # itself is judged in main()
                    gate_s = (args.ramp_rebalance_ttls
                              * args.shard_lease_duration)
                    deadline = time.monotonic() + gate_s
                    while time.monotonic() < deadline:
                        events = scale_watcher.report()
                        if events and all(
                            e["rebalance_s"] is not None for e in events
                        ):
                            break
                        time.sleep(0.2)
                    report["elastic"] = {
                        "events": scale_watcher.report(),
                        "lease_ttl_s": args.shard_lease_duration,
                        "gate_ttls": args.ramp_rebalance_ttls,
                    }
                if args.kill_shard_after > 0:
                    report["killed_member"] = "shard0"
                from volcano_tpu.federation import verify_federation

                policy = verify_federation(topo.api, args.shards)
                report["policy_equivalent"] = policy["ok"]
                if not policy["ok"]:
                    report["policy_violations"] = policy["violations"][:20]
            return report
        finally:
            for killer in killers:
                killer.cancel()
            if scale_watcher is not None:
                scale_watcher.stop()
            if args.stage_breakdown:
                from volcano_tpu import obs as _obs

                _obs.disable()  # idempotent; guards the error paths
            topo.close()

    out = {
        "harness": "loadgen",
        "config": {
            "nodes": args.nodes,
            "node_cpu": args.node_cpu,
            "duration_s": args.duration,
            "debounce_ms": args.debounce_ms,
            "schedule_period_s": args.period,
            "micro_cycles": not args.no_micro_cycles,
            "shards": args.shards,
            "gang_mix": args.gang_mix,
            "quick": args.quick,
        },
    }
    out["run"] = one_run(args.rate, "run")

    if args.find_saturation:
        # ramp the offered rate until p99 breaks the SLO (or pods stop
        # binding); each step runs on a FRESH topology so earlier
        # backlogs can't poison later steps
        rate = args.rate
        best = None
        steps = []
        for _ in range(args.saturation_steps):
            rate = rate * 1.5
            r = one_run(rate, f"sat{int(rate)}")
            steps.append(r)
            ok = (
                r["bound_pods"] == r["submitted_pods"]
                and r["p99_ms"] <= args.slo_ms
            )
            if not ok:
                break
            best = r
        out["saturation_steps"] = steps
        out["saturation_throughput_pods_per_s"] = (
            best["achieved_pods_per_s"] if best is not None
            else out["run"]["achieved_pods_per_s"]
        )
    return out


def run_resident_sweep(args) -> dict:
    """The O(pending) flagship drill: hold the pending stream constant
    (same rate, duration, fleet) while the RESIDENT (already-Running)
    job population grows 10×, and require the restricted-session
    scheduler's user-visible numbers to stay put.

    Three runs on fresh topologies:

      1. ``full_baseline``  — full sessions,       ``--resident`` jobs
      2. ``restricted_1x``  — restricted sessions, ``--resident`` jobs
      3. ``restricted_10x`` — restricted sessions, 10 × ``--resident``

    Gates (judged in main, printed as ``LOADGEN FAIL:``):

      * every submitted pod bound, in all three runs;
      * zero shadow-cross-check divergences in the restricted runs
        (and the restricted runs must actually run restricted cycles);
      * p99 submit→bind: restricted_10x ≤ 1.2 × restricted_1x
        (+ a small absolute grace for timer noise at CI shape);
      * steady-cycle open cost: restricted-cycle session-open MEDIAN
        of restricted_10x ≤ 2 × restricted_1x (+0.25ms timer-noise
        floor).  Periodic FULL cycles stay O(resident) by design, so
        the gate reads the restricted-only samples, not the blended
        mean — and the median, so one GC stall in a short CI run
        doesn't read as an O(resident) regression.

    Two O(resident)-BY-DESIGN costs are deliberately kept off the
    measured clock, in both the 1x and 10x runs, so the gates read the
    steady-state plane and not the amortized maintenance:

      * periodic full-session re-equilibration — run the sweep with
        ``--period`` longer than the stream (the quick preset and the
        campaign recipe both do);
      * shadow cross-check audits (each one opens a FULL session over
        the same snapshot) — sampling is disabled during the stream,
        then forced to EVERY cycle for a burst of post-drain audit
        jobs, so each restricted run still proves zero divergence on
        live traffic (``shadow_checks`` ≥ 1 is itself gated).
    """
    with tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False) as f:
        f.write(CONF)
        conf_path = f.name

    # periodic full re-equilibration is O(resident) by design — one
    # tick landing mid-stream adds a full-session stall to whatever
    # pods collide with it, which is maintenance cost, not the
    # steady-state plane the gates read.  Push it past the run window
    # (identically in all three runs; full_baseline still measures
    # full-session cost, every micro wake opens one there).
    period = max(args.period,
                 4.0 * (args.duration + args.drain_timeout
                        + args.warmup_timeout))

    def one(resident: int, restricted: bool, label: str) -> dict:
        topo = LoadgenTopology(
            n_nodes=args.nodes, node_cpu=args.node_cpu,
            conf_path=conf_path, period=period,
            debounce_ms=args.debounce_ms,
            micro_cycles=not args.no_micro_cycles,
            restricted=restricted, resident=resident,
        )
        topo.complete_after_s = args.complete_after_s
        try:
            if restricted:
                # shadow sampling off during the measured stream (see
                # the docstring); the audit burst below re-enables it
                topo.scheduler.shadow_every = 0
            # same warmup contract as run_loadgen: kernels compile off
            # the clock, so the first measured pod pays scheduling, not
            # jit
            deadline = time.monotonic() + args.warmup_timeout
            for wi, burst in enumerate((4, 24)):
                warm = []
                for name in _warm_names(f"{label}w{wi}", 0):
                    warm.extend(topo.submit_job(name, burst, args.cpu))
                while time.monotonic() < deadline:
                    if topo.bound_count(warm) == len(warm):
                        break
                    time.sleep(0.05)
                if topo.bound_count(warm) != len(warm):
                    raise RuntimeError("warmup pods never bound")
            # quiesce: the gang warmup breaks a cycle window, and the
            # NEXT window opens with an unconditional full cycle whose
            # commit barrier drains the warm binds — all O(resident)
            # effluent that must finish off the measured clock
            settle = time.monotonic()
            last = -1
            while time.monotonic() < deadline:
                n = topo.scheduler.sessions_opened
                if n != last:
                    last, settle = n, time.monotonic()
                elif time.monotonic() - settle >= 0.6:
                    break
                time.sleep(0.1)
            # GC off for the measured window (all three runs alike): a
            # gen-2 collection over a 10x-resident heap is a ~100ms
            # stop-the-world stall that lands on whatever pod is in
            # flight — allocator noise, not scheduler behavior.  The
            # window is short; refcounting still frees the bulk.
            import gc

            gc.collect()
            gc.disable()
            try:
                report = run_phase(
                    topo, args.rate, args.duration, args.tasks_per_job,
                    args.cpu, args.drain_timeout, label=label,
                )
            finally:
                gc.enable()
            if restricted:
                # forced-audit burst: every cycle now runs the shadow
                # full-session cross-check, so the zero-divergence gate
                # is proven on live traffic, off the measured clock
                topo.scheduler.shadow_every = 1
                audit = []
                for i in range(3):
                    audit.extend(
                        topo.submit_job(f"{label}-audit-{i}", 1, args.cpu)
                    )
                deadline = time.monotonic() + args.drain_timeout
                while time.monotonic() < deadline:
                    if topo.bound_count(audit) == len(audit):
                        break
                    time.sleep(0.05)
                if topo.bound_count(audit) != len(audit):
                    raise RuntimeError("audit pods never bound")
            report.update(_cycle_mix(topo))
            report.update(_session_stats(topo))
            report["resident_jobs"] = resident
            report["restricted_sessions"] = restricted
            report["rss_bytes"] = topo.rss_report()
            return report
        finally:
            topo.close()

    base = args.resident
    return {
        "harness": "loadgen-resident",
        "config": {
            "nodes": args.nodes,
            "node_cpu": args.node_cpu,
            "rate": args.rate,
            "duration_s": args.duration,
            "resident_base": base,
            "p99_ratio_gate": 1.2,
            "p99_grace_ms": args.resident_p99_grace_ms,
            "open_cost_ratio_gate": 2.0,
            "quick": args.quick,
        },
        "full_baseline": one(base, False, "f1x"),
        "restricted_1x": one(base, True, "r1x"),
        "restricted_10x": one(base * 10, True, "r10x"),
    }


def _resident_gates(report, grace_ms: float) -> list:
    """Gate messages for a --resident-sweep report ([] = pass)."""
    fails = []
    for key in ("full_baseline", "restricted_1x", "restricted_10x"):
        r = report[key]
        if r["bound_pods"] != r["submitted_pods"]:
            fails.append(
                f"{key}: {r['submitted_pods'] - r['bound_pods']} pods "
                "never bound"
            )
    r1 = report["restricted_1x"]
    r10 = report["restricted_10x"]
    for key, r in (("restricted_1x", r1), ("restricted_10x", r10)):
        if r["restricted_cycles"] == 0:
            fails.append(f"{key}: no restricted cycles ran — the sweep "
                         "never exercised the incremental plane")
        if r["shadow_checks"] == 0:
            fails.append(f"{key}: no shadow cross-checks ran — the "
                         "zero-divergence gate is vacuous")
        if r["shadow_divergences"]:
            fails.append(f"{key}: {r['shadow_divergences']} shadow "
                         "cross-check divergences (ledger unsound)")
    p99_gate = 1.2 * r1["p99_ms"] + grace_ms
    if r10["p99_ms"] > p99_gate:
        fails.append(
            f"p99 regressed with 10x resident jobs: {r10['p99_ms']}ms > "
            f"1.2 x {r1['p99_ms']}ms + {grace_ms}ms grace"
        )
    # median, not mean: the steady-cycle cost.  +0.25ms absolute
    # grace — the timer-noise floor at CI shape.
    open_gate = 2.0 * r1["restricted_open_p50_ms"] + 0.25
    if r10["restricted_open_p50_ms"] > open_gate:
        fails.append(
            "restricted session-open cost is not O(pending): "
            f"{r10['restricted_open_p50_ms']}ms median at 10x resident "
            f"> 2 x {r1['restricted_open_p50_ms']}ms + 0.25ms grace"
        )
    return fails


def run_slo_burn_drill(args) -> dict:
    """The black-box drill (ISSUE 19): run the in-process topology with
    the diagnostics plane armed — tail-sampled flight recorder, a
    burn-rate watchdog over a TimeSeriesRing of the live registry, and
    an IncidentManager — then inject a seeded ``commit.delay`` burst so
    submit→bind p99 burns through its objective.  The breach must
    edge-trigger EXACTLY ONE incident bundle, the bundle must land
    while the cluster capture boost it CAS'd is still live and carry
    the breach-window bind traces, and the watchdog must CLEAR once the
    burst rolls out of its windows (main() gates all of it)."""
    from volcano_tpu import faults, obs
    from volcano_tpu.metrics.timeseries import TimeSeriesRing
    from volcano_tpu.obs.incident import IncidentManager
    from volcano_tpu.obs.slo import BurnRateWatchdog, resolve_slos

    with tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False) as f:
        f.write(CONF)
        conf_path = f.name
    incident_dir = args.incident_dir or tempfile.mkdtemp(
        prefix="vtpu-incidents-")

    topo = LoadgenTopology(
        n_nodes=args.nodes, node_cpu=args.node_cpu, conf_path=conf_path,
        period=args.period, debounce_ms=args.debounce_ms,
        micro_cycles=not args.no_micro_cycles,
    )
    topo.complete_after_s = args.complete_after_s
    # the diagnostics plane, exactly as a daemon wires it: tail-mode
    # exporter (steady traces drop, evidence keeps), ring + watchdog,
    # breach → incident manager (bundle + capture boost CAS)
    obs.enable(topo.api, identity="loadgen-sched", flush_interval=0.1,
               sample=0.05, tail=True)
    ring = TimeSeriesRing()
    mgr = IncidentManager(
        topo.api, "loadgen-sched", incident_dir,
        cooldown_s=300.0,  # one bundle per episode, guaranteed
        boost_ttl_s=args.burn_boost_ttl, settle_s=1.5, metrics_ring=ring,
    )
    fast_s, slow_s = 3.0, 9.0
    breach_ts: List[float] = []

    def on_breach(alert):
        breach_ts.append(time.time())
        mgr.on_alert(alert)

    # only the SLO the burst targets: the default set also watches
    # micro-cycle latency etc., which CI-shape load can breach on its
    # own and would double the episode count
    slos = [s for s in resolve_slos(
        f"submit-bind-p99={args.burn_objective_ms:g}")
        if s.name == "submit-bind-p99"]
    wd = BurnRateWatchdog(
        ring, slos=slos,
        fast_window_s=fast_s, slow_window_s=slow_s, on_breach=on_breach,
    )
    wd_stop = threading.Event()

    def _wd_loop():
        while not wd_stop.wait(0.5):
            try:
                wd.run_once()
            except Exception:  # noqa: BLE001 — the drill gates on
                pass           # outcomes, not watchdog uptime

    degraded_during = False
    cleared = False
    try:
        # warmup off the clock (jit compile latencies must not reach
        # the ring — the watchdog only ever sees steady-state samples)
        warm = topo.submit_job("burnwarm", 8, args.cpu)
        deadline = time.monotonic() + args.warmup_timeout
        while time.monotonic() < deadline:
            if topo.bound_count(warm) == len(warm):
                break
            time.sleep(0.05)
        if topo.bound_count(warm) != len(warm):
            raise RuntimeError("warmup pods never bound")

        threading.Thread(target=_wd_loop, name="burn-watchdog",
                         daemon=True).start()
        # both burn windows need history before they can confirm a
        # breach — idle until the ring spans the slow window
        deadline = time.monotonic() + 4.0 * slow_s
        while time.monotonic() < deadline and ring.span_seconds() < slow_s:
            time.sleep(0.25)

        faults.configure(
            f"seed=19;commit.delay=1.0:ms={int(args.burn_delay_ms)}")
        phase = run_phase(topo, args.rate, args.duration,
                          args.tasks_per_job, args.cpu,
                          args.drain_timeout, label="burn")
        degraded_during = bool(wd.degraded_reasons()) or bool(breach_ts)
        faults.configure(None)
        # the burst is over: the alert must CLEAR as the windows roll
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if not wd.degraded_reasons():
                cleared = True
                break
            time.sleep(0.5)
    finally:
        wd_stop.set()
        faults.configure(None)
        obs.disable()
        topo.close()
        os.unlink(conf_path)

    bundles = sorted(
        d for d in (os.listdir(incident_dir)
                    if os.path.isdir(incident_dir) else [])
        if d.startswith("incident-")
    )
    bundle = {}
    bundle_within_boost = False
    bundle_has_bind_trace = False
    if bundles:
        bdir = os.path.join(incident_dir, bundles[0])
        with open(os.path.join(bdir, "meta.json")) as f:
            meta = json.load(f)
        # captured while the boost it armed was still live
        boost_until = float((meta.get("boost") or {}).get("until", 0.0))
        bundle_within_boost = meta["ts"] <= boost_until
        try:
            with open(os.path.join(bdir, "spans.json")) as f:
                spans = json.load(f)
        except (OSError, ValueError):
            spans = []
        bundle_has_bind_trace = any(
            s.get("name") == "bind:landed" for s in spans)
        bundle = {
            "path": bdir,
            "reason": meta.get("reason"),
            "alerts": meta.get("alerts"),
            "span_count": meta.get("spanCount"),
            "files": meta.get("files"),
            "errors": meta.get("errors"),
        }
    return {
        "config": {
            "topology": "in-process",
            "nodes": args.nodes,
            "burn_delay_ms": args.burn_delay_ms,
            "burn_objective_ms": args.burn_objective_ms,
            "burn_boost_ttl_s": args.burn_boost_ttl,
            "fast_window_s": fast_s,
            "slow_window_s": slow_s,
            "incident_dir": incident_dir,
            "quick": args.quick,
        },
        "run": phase,
        "drill": {
            "breaches": len(breach_ts),
            "degraded_during": degraded_during,
            "degraded_cleared": cleared,
            "bundles": len(bundles),
            "bundle": bundle,
            "bundle_within_boost": bundle_within_boost,
            "bundle_has_bind_trace": bundle_has_bind_trace,
            "suppressed_triggers": mgr.suppressed_triggers,
        },
    }


def _burn_gates(report) -> list:
    """Gate messages for a --slo-burn-drill report ([] = pass)."""
    fails = []
    r = report["run"]
    d = report["drill"]
    if r["bound_pods"] != r["submitted_pods"]:
        fails.append(f"{r['submitted_pods'] - r['bound_pods']} pods "
                     "never bound under the commit.delay burst")
    if not d["breaches"]:
        fails.append("the watchdog never fired — the seeded burst did "
                     "not breach the burn threshold")
    if not d["degraded_during"]:
        fails.append("the breach never surfaced as a degraded reason")
    if d["bundles"] != 1:
        fails.append(f"{d['bundles']} incident bundles captured — the "
                     "episode must produce exactly one")
    elif not d["bundle_within_boost"]:
        fails.append("the bundle landed after its capture boost "
                     "expired (settle/TTL misconfigured)")
    elif not d["bundle_has_bind_trace"]:
        fails.append("the bundle carries no bind:landed span — the "
                     "boost did not retain the breach-window traces")
    if not d["degraded_cleared"]:
        fails.append("the alert never cleared after the burst (stuck "
                     "degraded state)")
    return fails


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="loadgen", description="sustained-load submit→bind SLO harness"
    )
    p.add_argument("--rate", type=float, default=100.0,
                   help="offered arrival rate, jobs/sec (open-loop)")
    p.add_argument("--duration", type=float, default=10.0,
                   help="measured arrival-stream length, seconds")
    p.add_argument("--tasks-per-job", type=int, default=1)
    p.add_argument("--cpu", default="100m", help="per-pod cpu request")
    p.add_argument("--nodes", type=int, default=64)
    p.add_argument("--node-cpu", type=int, default=64)
    p.add_argument("--period", type=float, default=1.0,
                   help="full-cycle re-equilibration period, seconds")
    p.add_argument("--debounce-ms", type=float, default=5.0)
    p.add_argument("--no-micro-cycles", action="store_true",
                   help="baseline: the fixed-period loop (what the SLO "
                   "numbers look like without event-driven scheduling)")
    p.add_argument("--complete-after-s", type=float, default=0.75,
                   help="bound pods complete (pod + podgroup deleted) "
                   "this long after their bind — sustained churn means "
                   "departures too, keeping the resident job count (and "
                   "the O(jobs) session cost) steady.  0 = never")
    p.add_argument("--drain-timeout", type=float, default=30.0)
    p.add_argument("--warmup-timeout", type=float, default=120.0)
    p.add_argument("--find-saturation", action="store_true")
    p.add_argument("--saturation-steps", type=int, default=4)
    p.add_argument("--slo-ms", type=float, default=100.0,
                   help="p99 submit→bind SLO the saturation ramp gates on")
    p.add_argument("--shards", type=int, default=0,
                   help="sharded scheduler federation: spawn N real "
                   "vtpu-scheduler OS processes over the TCP bus, each "
                   "owning a node shard via CAS leases, and report "
                   "per-shard + aggregate percentiles (0 = the "
                   "single-scheduler topology)")
    p.add_argument("--shard-lease-duration", type=float, default=2.0)
    p.add_argument("--apiserver-replicas", type=int, default=0,
                   help="replicated persistent bus: spawn N real "
                   "vtpu-apiserver OS processes (WAL dirs, leader "
                   "election, quorum-acked writes) instead of the "
                   "in-process store (0 = in-process)")
    p.add_argument("--repl-lease-ttl", type=float, default=1.0,
                   help="apiserver leader-liveness lease TTL")
    p.add_argument("--kill-apiserver-after", type=float, default=0.0,
                   help="SIGKILL the apiserver LEADER this many seconds "
                   "into the measured stream (bus HA drill: a follower "
                   "must promote within one lease TTL, every pod must "
                   "still bind, and no pod may be re-bound)")
    p.add_argument("--gang-mix", type=float, default=0.0,
                   help="fraction of arrivals submitted as OVERSIZED "
                   "gangs (minMember == size, auto-sized LARGER than "
                   "any single shard's task capacity) — each one must "
                   "bind via a cross-shard txn_commit assembly; the "
                   "exit gate requires zero partial gangs and the "
                   "report carries full-assembly latency percentiles "
                   "(0 = none; meant for --shards >= 2)")
    p.add_argument("--gang-size", type=int, default=0,
                   help="gang task count (0 = auto: biggest shard's "
                   "task capacity + 1)")
    p.add_argument("--gang-cpu", default="",
                   help="per-gang-task cpu request (default: half a "
                   "node, so each node holds two gang tasks)")
    p.add_argument("--gang-slo-ms", type=float, default=0.0,
                   help="gate: fail when gang full-assembly p99 "
                   "exceeds this (0 = report only)")
    p.add_argument("--ramp", action="store_true",
                   help="elastic scale-up-under-load drill (needs "
                   "--shards >= 1): members run the SLO-driven shard "
                   "autoscaler with a CI-tight policy and the member "
                   "pool is pre-provisioned to --ramp-max-shards; the "
                   "offered stream oversubscribes the fleet so a "
                   "sustained pending backlog forms, the controller "
                   "grows the shard count, and the exit gates require "
                   "zero lost acked binds plus every committed scale "
                   "event rebalanced within --ramp-rebalance-ttls "
                   "lease TTLs")
    p.add_argument("--ramp-max-shards", type=int, default=2,
                   help="autoscaler ceiling (and pre-provisioned "
                   "member-pool size) for the ramp drill")
    p.add_argument("--ramp-up-pending", type=int, default=8,
                   help="per-shard pending-task bar the drill's "
                   "scale-up trigger uses")
    p.add_argument("--ramp-rebalance-ttls", type=float, default=8.0,
                   help="gate: every committed scale event must have "
                   "every slice of the new partition re-held within "
                   "this many lease TTLs")
    p.add_argument("--membership-drill", action="store_true",
                   help="dynamic-membership drill (needs "
                   "--apiserver-replicas >= 2): grow the replication "
                   "group by one mid-stream (spawn + learner catch-up "
                   "+ add-replica), then retire an original follower "
                   "— exit gates: both changes commit, exactly ONE "
                   "surviving config, zero lost acked binds, zero "
                   "re-binds")
    p.add_argument("--membership-after", type=float, default=1.0,
                   help="seconds into the measured stream the "
                   "membership drill starts")
    p.add_argument("--kill-shard-after", type=float, default=0.0,
                   help="SIGKILL shard member 0 this many seconds into "
                   "the measured stream (federation chaos: survivors "
                   "must absorb its slices within one lease TTL and "
                   "every pod must still bind)")
    p.add_argument("--stage-breakdown", action="store_true",
                   help="enable the flight recorder during the run and "
                   "attribute submit→bind latency to stages (cycle, "
                   "kernel, commit flush, bus op, WAL fsync, quorum "
                   "wait, bind landing) from collected spans — the "
                   "per-stage report CI uploads next to the SLO JSON")
    p.add_argument("--restricted-sessions", action="store_true",
                   help="open RESTRICTED sessions (O(pending) "
                   "micro-cycles over the share ledger, with sampled "
                   "shadow full-session cross-checks) — in-process "
                   "topologies flip the Scheduler flag, --shards "
                   "members get the daemon flag (ignored by "
                   "--apiserver-replicas runs)")
    p.add_argument("--resident", type=int, default=0,
                   help="preload this many already-Running single-task "
                   "jobs before the scheduler attaches — the resident "
                   "ballast the incremental-session plane must not pay "
                   "per cycle (the reaper never completes them)")
    p.add_argument("--resident-sweep", action="store_true",
                   help="the O(pending) flagship drill: three runs at "
                   "identical offered load — full sessions at "
                   "--resident jobs, restricted at --resident, "
                   "restricted at 10x --resident — gating p99 within "
                   "1.2x and restricted session-open mean within 2x "
                   "across the 10x resident growth, with zero shadow "
                   "divergences")
    p.add_argument("--resident-p99-grace-ms", type=float, default=10.0,
                   help="absolute grace added to the sweep's 1.2x p99 "
                   "gate (timer noise at CI shape)")
    p.add_argument("--slo-burn-drill", action="store_true",
                   help="black-box diagnostics drill: arm the burn-rate "
                   "watchdog + incident manager over the in-process "
                   "topology, inject a seeded commit.delay burst, and "
                   "gate that the breach produces exactly one incident "
                   "bundle within the capture-boost TTL carrying the "
                   "breach-window traces, then clears")
    p.add_argument("--incident-dir", default="",
                   help="where the drill's incident bundles land "
                   "(default: a fresh temp dir; CI points this at the "
                   "artifact upload path)")
    p.add_argument("--burn-delay-ms", type=float, default=150.0,
                   help="per-commit injected delay during the burst")
    p.add_argument("--burn-objective-ms", type=float, default=50.0,
                   help="submit-bind-p99 objective the drill burns "
                   "through")
    p.add_argument("--burn-boost-ttl", type=float, default=15.0,
                   help="capture-boost TTL the breach arms")
    p.add_argument("--quick", action="store_true",
                   help="CI smoke preset: small fleet, short stream")
    args = p.parse_args(argv)

    if args.ramp and args.shards < 1:
        args.shards = 1  # the drill starts from a 1-shard federation
    if args.membership_drill and args.apiserver_replicas < 2:
        p.error("--membership-drill needs --apiserver-replicas >= 2")

    if args.quick:
        args.rate = 25.0
        args.duration = 4.0
        args.nodes = 16
        args.node_cpu = 64
        args.drain_timeout = 60.0
        if args.ramp:
            # the scale-up drill needs a SUSTAINED backlog: offered
            # residency (rate × complete_after_s × slots-per-pod) must
            # exceed the fleet's slot capacity, so pending depth holds
            # above the trigger bar until the stream ends.  8 nodes ×
            # 8 cpu at 1-cpu pods = 64 slots; 90 pods/s × 1s residency
            # ≈ 90 resident demand → a steady ~25-task queue.
            args.nodes = 8
            args.node_cpu = 8
            args.cpu = "1"
            args.rate = 75.0
            args.duration = 5.0
            args.complete_after_s = 1.0
            args.drain_timeout = 180.0
        if args.gang_mix > 0:
            # gang arrivals are node-sized: 25 jobs/s of half-node
            # tasks would oversubscribe the 16-node quick fleet many
            # times over before churn can free it
            args.rate = 5.0
            args.drain_timeout = 120.0
        if args.slo_burn_drill:
            # the burn windows need the burst to SPAN them: a longer,
            # gentler stream so the breach, the settled capture, and
            # post-breach binds all land inside the measured phase
            args.rate = 15.0
            args.duration = 8.0
        if args.resident_sweep and args.resident == 0:
            # 100 → 1000 resident jobs across the sweep: enough that an
            # O(resident) open cost would blow the 2x gate, small
            # enough for CI
            args.resident = 100

    if args.slo_burn_drill:
        report = run_slo_burn_drill(args)
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
        fails = _burn_gates(report)
        for msg in fails:
            print(f"LOADGEN FAIL: {msg}", file=sys.stderr)
        return 1 if fails else 0

    if args.resident_sweep:
        if args.resident <= 0:
            p.error("--resident-sweep needs --resident > 0 (or --quick)")
        report = run_resident_sweep(args)
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
        fails = _resident_gates(report, args.resident_p99_grace_ms)
        for msg in fails:
            print(f"LOADGEN FAIL: {msg}", file=sys.stderr)
        return 1 if fails else 0

    report = run_loadgen(args)
    json.dump(report, sys.stdout, indent=2)
    sys.stdout.write("\n")
    # the acceptance gate: every pod bound, and (micro mode) the quick
    # config meets the p99 SLO
    r = report["run"]
    if r["bound_pods"] != r["submitted_pods"]:
        print(f"LOADGEN FAIL: {r['submitted_pods'] - r['bound_pods']} pods "
              f"never bound", file=sys.stderr)
        return 1
    if args.shards > 0 and not r.get("policy_equivalent", True):
        print("LOADGEN FAIL: federation run is not policy-equivalent: "
              f"{r.get('policy_violations')}", file=sys.stderr)
        return 1
    gm = r.get("gang_mix")
    if gm is not None:
        if gm["partial_gangs"]:
            print(f"LOADGEN FAIL: {gm['partial_gangs']} gangs are "
                  "PARTIALLY placed — the txn_commit atomicity "
                  "invariant is broken", file=sys.stderr)
            return 1
        if args.gang_slo_ms > 0 and gm["assembly_p99_ms"] > args.gang_slo_ms:
            print(f"LOADGEN FAIL: gang assembly p99 "
                  f"{gm['assembly_p99_ms']}ms > SLO {args.gang_slo_ms}ms",
                  file=sys.stderr)
            return 1
    if args.ramp:
        el = r.get("elastic", {})
        ups = [e for e in el.get("events", ())
               if e.get("direction") == "up"]
        if not ups:
            print("LOADGEN FAIL: the ramp drill committed no scale-up "
                  f"(events: {el.get('events')})", file=sys.stderr)
            return 1
        gate_s = args.ramp_rebalance_ttls * args.shard_lease_duration
        for e in el.get("events", ()):
            if e.get("rebalance_s") is None or e["rebalance_s"] > gate_s:
                print("LOADGEN FAIL: scale event "
                      f"{e['from']}->{e['target']} rebalanced in "
                      f"{e.get('rebalance_s')}s > gate {gate_s}s "
                      f"({args.ramp_rebalance_ttls} lease TTLs)",
                      file=sys.stderr)
                return 1
    if args.membership_drill:
        mem = r.get("bus_ha", {}).get("membership", {})
        bad = [e for e in mem.get("events", ()) if not e.get("ok")]
        if bad or len(mem.get("events", ())) != 2:
            print(f"LOADGEN FAIL: membership drill events: "
                  f"{mem.get('events')}", file=sys.stderr)
            return 1
        if mem.get("distinct_configs") != 1:
            print("LOADGEN FAIL: live replicas disagree on the "
                  f"membership config ({mem.get('distinct_configs')} "
                  f"distinct; epochs {mem.get('epochs')})",
                  file=sys.stderr)
            return 1
    if args.apiserver_replicas > 0:
        ha = r.get("bus_ha", {})
        if ha.get("rebinds", 0) != 0:
            print(f"LOADGEN FAIL: {ha['rebinds']} pods were re-bound "
                  "across the failover (duplicate acknowledged binds)",
                  file=sys.stderr)
            return 1
        if args.kill_apiserver_after > 0:
            roles = list(ha.get("roles", {}).values())
            if roles.count("leader") != 1:
                print(f"LOADGEN FAIL: no single promoted leader after "
                      f"the kill (roles: {ha.get('roles')})",
                      file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
