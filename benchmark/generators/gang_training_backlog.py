"""A batch/ML training cluster with a backlog of gang Jobs larger than
its free room (config ``gang-training-backlog``) as Volcano API objects.

scheduler_perf's node template, 5,000 nodes; each node holds 0, 1 or 2
Running resident pods (drawn by the seed), each a job of its own; then
the backlog, all pending in one namespace and queue: gangs of 16, 8 and
4 workers, each Job a PodGroup with minMember = replicas and its worker
pods (Volcano's ``example/job.yaml``: a Job with ``minAvailable``,
scheduled by the gang plugin).  The seed draws the resident pods and the
order in which the gangs were created, which is the order allocate takes
them in, and the order in which the objects reach the cache.

A program whose allocate kernel does not commit or discard each gang in
its scan would discard gangs on the host, sweeping every node for each:
the run is refused in set-up instead.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import numpy as np


def node_name(i: int) -> str:
    return f"n{i:05d}"


@dataclass
class Gang:
    name: str
    workers: int
    cpu_milli: int
    mem_mib: int

    def worker_uid(self, k: int) -> str:
        return f"{self.name}-w{k:02d}"


@dataclass
class World:
    cfg: dict
    resident: np.ndarray  # [N] Running resident pods on each node
    gangs: List[Gang] = field(default_factory=list)  # in creation order
    n_nodes: int = 0
    objects: dict = None


def require_device_gangs() -> None:
    """Refuse a program whose allocate scan lacks Volcano's per-job
    commit/discard (``ops/kernels.py`` ``schedule_episodes``)."""
    from harness.core import Refused
    from volcano_tpu.ops import kernels

    if not hasattr(kernels, "schedule_episodes"):
        raise Refused(
            "this volcano_tpu does not commit or discard gangs in the allocate "
            "scan: every discarded gang would take the host predicate sweep"
        )


def full_cycle(cfg: dict, seed: int) -> World:
    require_device_gangs()
    rng = np.random.default_rng(seed)
    n_nodes = cfg["nodes"]
    gangs = [
        (g["workers"], g["cpu_milli"], g["mem_mib"])
        for g in cfg["gangs"] for _ in range(g["count"])
    ]
    order = rng.permutation(len(gangs))
    world = World(
        cfg=cfg,
        resident=rng.choice(np.asarray(cfg["resident_per_node"]), size=n_nodes),
        gangs=[Gang(f"gang-{j:05d}", *gangs[k]) for j, k in enumerate(order)],
        n_nodes=n_nodes,
    )
    world.objects = _objects(world, rng)
    return world


def _objects(w: World, rng) -> dict:
    from volcano_tpu.apis import core, scheduling

    cfg = w.cfg
    ns, queue = cfg["namespace"], cfg["queue"]
    alloc = {
        "cpu": f"{cfg['node_cpu_milli']}m",
        "memory": f"{cfg['node_mem_mib']}Mi",
        "pods": cfg["node_max_pods"],
    }
    nodes = [
        core.Node(
            metadata=core.ObjectMeta(
                name=node_name(i), namespace="", uid=f"node-{i}",
                creation_timestamp=float(i),
            ),
            spec=core.NodeSpec(taints=[], unschedulable=False),
            status=core.NodeStatus(allocatable=dict(alloc), capacity=dict(alloc)),
        )
        for i in range(w.n_nodes)
    ]
    queues = [scheduling.Queue(
        metadata=core.ObjectMeta(name=queue, namespace="", uid=f"q-{queue}"),
        spec=scheduling.QueueSpec(weight=1, capability={}),
    )]

    def pod_group(name, created, min_member, running):
        return scheduling.PodGroup(
            metadata=core.ObjectMeta(
                name=name, namespace=ns, uid=f"pg-{name}", creation_timestamp=created,
            ),
            spec=scheduling.PodGroupSpec(min_member=min_member, queue=queue),
            status=scheduling.PodGroupStatus(
                phase=scheduling.POD_GROUP_RUNNING if running
                else scheduling.POD_GROUP_INQUEUE
            ),
        )

    def pod(name, uid, group, created, cpu, mem, node):
        return core.Pod(
            metadata=core.ObjectMeta(
                name=name, namespace=ns, uid=uid,
                annotations={scheduling.GROUP_NAME_ANNOTATION_KEY: group},
                creation_timestamp=created,
            ),
            spec=core.PodSpec(
                containers=[core.Container(name="worker", resources={
                    "requests": {"cpu": f"{cpu}m", "memory": f"{mem}Mi"},
                })],
                node_name=None if node is None else node_name(node),
            ),
            status=core.PodStatus(phase="Pending" if node is None else "Running"),
        )

    groups, pods = [], []
    created = 0.0
    for i in range(w.n_nodes):
        for k in range(int(w.resident[i])):
            name = f"resident-{i:05d}-{k}"
            groups.append(pod_group(name, created, 1, True))
            pods.append(pod(name, name, name, created, cfg["resident_cpu_milli"],
                            cfg["resident_mem_mib"], i))
            created += 1.0
    for g in w.gangs:
        groups.append(pod_group(g.name, created, g.workers, False))
        for k in range(g.workers):
            uid = g.worker_uid(k)
            pods.append(pod(uid, uid, g.name, created + k / 100.0, g.cpu_milli,
                            g.mem_mib, None))
        created += 1.0
    go, po = rng.permutation(len(groups)), rng.permutation(len(pods))
    return {
        "nodes": nodes, "queues": queues,
        "pod_groups": [groups[k] for k in go],
        "pods": [pods[k] for k in po],
    }
