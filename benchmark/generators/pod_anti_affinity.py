"""kube-scheduler scheduler_perf ``SchedulingPodAntiAffinity`` (config
``pod-anti-affinity``) as Volcano API objects.

Identical nodes, each labelled ``kubernetes.io/hostname`` with its own
name; green init pods in ``sched-0``, Running, one on each of as many
distinct nodes; then the measured green pods in ``sched-1``, pending.
Every pod carries the template's one required anti-affinity term
(selector ``color: green``, hostname key, namespaces ``sched-1`` and
``sched-0``), so no two green pods may share a node.  Every pod is a job
of its own (a PodGroup of minMember 1, as Volcano's podgroup controller
makes for a plain pod) in the one queue.  The seed draws which nodes
hold the init pods and the order in which the objects reach the cache.

A program that does not decide required anti-affinity on the device
would check every pending pod against every node on the host, each
check a scan of every placed pod: the run is refused in set-up instead.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def node_name(i: int) -> str:
    return f"n{i:05d}"


@dataclass
class World:
    cfg: dict
    init_node: np.ndarray  # [I] node index of each init pod, all distinct
    n_nodes: int
    n_measure: int
    objects: dict = None

    @staticmethod
    def init_uid(i: int) -> str:
        return f"ipod-{i}"

    @staticmethod
    def measure_uid(i: int) -> str:
        return f"apod-{i}"


def require_device_anti_affinity() -> None:
    """Refuse a program whose packer does not encode required pod
    anti-affinity for the allocate kernel (``ops/packing.py``
    ``affinity_row_encodable``)."""
    from harness.core import Refused
    from volcano_tpu.ops import packing

    if not hasattr(packing, "affinity_row_encodable"):
        raise Refused(
            "this volcano_tpu does not encode required pod anti-affinity on the "
            "device: every pending pod would take the host predicate sweep"
        )


def full_cycle(cfg: dict, seed: int) -> World:
    require_device_anti_affinity()
    n_nodes, n_init = cfg["nodes"], cfg["init_pods"]
    if n_init > n_nodes:
        raise ValueError(f"{n_init} init pods need as many distinct nodes, of {n_nodes}")
    rng = np.random.default_rng(seed)
    world = World(
        cfg=cfg,
        init_node=rng.choice(n_nodes, size=n_init, replace=False),
        n_nodes=n_nodes,
        n_measure=cfg["measure_pods"],
    )
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
                labels={cfg["hostname_key"]: node_name(i)},
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
    term = cfg["anti_affinity"]

    def job(name, uid, namespace, created, node):
        pg = scheduling.PodGroup(
            metadata=core.ObjectMeta(
                name=f"pg-{name}", namespace=namespace, uid=f"pg-{uid}",
                creation_timestamp=created,
            ),
            spec=scheduling.PodGroupSpec(min_member=1, queue=cfg["queue"]),
            status=scheduling.PodGroupStatus(
                phase=scheduling.POD_GROUP_RUNNING if node is not None
                else scheduling.POD_GROUP_INQUEUE
            ),
        )
        pod = core.Pod(
            metadata=core.ObjectMeta(
                name=name, namespace=namespace, uid=uid, labels=dict(cfg["pod_labels"]),
                annotations={scheduling.GROUP_NAME_ANNOTATION_KEY: f"pg-{name}"},
                creation_timestamp=created,
            ),
            spec=core.PodSpec(
                containers=[core.Container(name="pause", resources={
                    "requests": {"cpu": f"{cfg['pod_cpu_milli']}m",
                                 "memory": f"{cfg['pod_mem_mib']}Mi"},
                })],
                node_name=None if node is None else node_name(node),
                affinity={"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": dict(term["labelSelector"]["matchLabels"])},
                        "topologyKey": term["topologyKey"],
                        "namespaces": list(term["namespaces"]),
                    }],
                }},
            ),
            status=core.PodStatus(phase="Pending" if node is None else "Running"),
        )
        return pg, pod

    n_init = len(w.init_node)
    made = [
        job(f"init-{i:06d}", w.init_uid(i), cfg["init_namespace"], float(i),
            int(w.init_node[i]))
        for i in range(n_init)
    ] + [
        job(f"anti-{i:06d}", w.measure_uid(i), cfg["measure_namespace"],
            float(n_init + i), None)
        for i in range(w.n_measure)
    ]
    order = rng.permutation(len(made))
    return {
        "nodes": nodes, "queues": queues,
        "pod_groups": [made[k][0] for k in order],
        "pods": [made[k][1] for k in order],
    }
