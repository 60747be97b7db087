"""kube-scheduler scheduler_perf ``PreemptionBasic`` (config
``preemption-basic``) as Volcano API objects.

Identical nodes, each holding as many low-priority init pods as fit (4 x
900m of 4 cpu); then the high-priority pods, pending, each of which fits
only once three of a node's init pods are evicted.  Every pod is a job
of its own (a PodGroup of minMember 1, as Volcano's podgroup controller
makes for a plain pod) in the one queue.  The seed draws which node
each init pod sits on, and so which of a node's pods are the youngest,
and the order in which the objects reach the cache; every seed gives
every node four init pods, so every seed gives the same work.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

NAMESPACE = "bench"
LOW, HIGH = "low-priority", "high-priority"


def node_name(i: int) -> str:
    return f"n{i:05d}"


@dataclass
class World:
    cfg: dict
    n_nodes: int
    low_node: np.ndarray  # [L] node index of each init pod
    n_high: int
    objects: dict = None

    @staticmethod
    def low_uid(i: int) -> str:
        return f"lpod-{i}"

    @staticmethod
    def high_uid(i: int) -> str:
        return f"hpod-{i}"


def full_cycle(cfg: dict, seed: int) -> World:
    n_nodes, L = cfg["nodes"], cfg["low_pods"]
    per_node = min(cfg["node_cpu_milli"] // cfg["low_cpu_milli"], cfg["node_max_pods"])
    if L > n_nodes * per_node:
        raise ValueError(f"{L} init pods do not fit {n_nodes} nodes")
    rng = np.random.default_rng(seed)
    world = World(cfg=cfg, n_nodes=n_nodes, low_node=rng.permutation(L) % n_nodes,
                  n_high=cfg["high_pods"])
    world.objects = _objects(world, rng)
    return world


def _objects(w: World, rng) -> dict:
    from volcano_tpu.apis import core, scheduling

    cfg = w.cfg
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
        metadata=core.ObjectMeta(name=cfg["queue"], namespace="", uid=f"q-{cfg['queue']}"),
        spec=scheduling.QueueSpec(weight=1, capability={}),
    )]
    pcs = [
        core.PriorityClass(metadata=core.ObjectMeta(name=LOW, namespace=""),
                           value=cfg["low_priority"]),
        core.PriorityClass(metadata=core.ObjectMeta(name=HIGH, namespace=""),
                           value=cfg["high_priority"]),
    ]

    def job(name, uid, created, klass, cpu, mem, node, phase):
        pg = scheduling.PodGroup(
            metadata=core.ObjectMeta(
                name=f"pg-{name}", namespace=NAMESPACE, uid=f"pg-{uid}",
                creation_timestamp=created,
            ),
            spec=scheduling.PodGroupSpec(
                min_member=1, queue=cfg["queue"], priority_class_name=klass,
            ),
            status=scheduling.PodGroupStatus(
                phase=scheduling.POD_GROUP_RUNNING if node is not None
                else scheduling.POD_GROUP_INQUEUE
            ),
        )
        pod = core.Pod(
            metadata=core.ObjectMeta(
                name=name, namespace=NAMESPACE, uid=uid,
                annotations={scheduling.GROUP_NAME_ANNOTATION_KEY: f"pg-{name}"},
                creation_timestamp=created,
            ),
            spec=core.PodSpec(
                containers=[core.Container(name="pause", resources={
                    "requests": {"cpu": f"{cpu}m", "memory": f"{mem}Mi"},
                })],
                priority=pcs[klass == HIGH].value, priority_class_name=klass,
                node_name=None if node is None else node_name(node),
            ),
            status=core.PodStatus(phase=phase),
        )
        return pg, pod

    L = len(w.low_node)
    made = [
        job(f"low-{i:06d}", w.low_uid(i), float(i), LOW, cfg["low_cpu_milli"],
            cfg["low_mem_mib"], int(w.low_node[i]), "Running")
        for i in range(L)
    ] + [
        job(f"high-{i:06d}", w.high_uid(i), float(L + i), HIGH, cfg["high_cpu_milli"],
            cfg["high_mem_mib"], None, "Pending")
        for i in range(w.n_high)
    ]
    order = rng.permutation(len(made))
    return {
        "nodes": nodes, "queues": queues, "priority_classes": pcs,
        "pod_groups": [made[k][0] for k in order],
        "pods": [made[k][1] for k in order],
    }
