"""kube-scheduler scheduler_perf ``TopologySpreading`` (config
``topology-spreading``) as Volcano API objects.

Identical nodes, each labelled with one of three zones, each holding one
Running init pod; then the measured pods, pending, each labelled
``color: blue`` and constrained to spread over the zones with maxSkew 5
(``DoNotSchedule``, selector ``color In [blue]``).  Every pod is a job
of its own (a PodGroup of minMember 1, as Volcano's podgroup controller
makes for a plain pod) in the one queue.  The seed draws each node's
zone, which init pod sits on which node, and the order in which the
objects reach the cache.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

NAMESPACE = "bench"


def node_name(i: int) -> str:
    return f"n{i:05d}"


@dataclass
class World:
    cfg: dict
    zone: np.ndarray  # [N] zone index of each node
    init_node: np.ndarray  # [I] node index of each init pod
    n_measure: int
    objects: dict = None

    @staticmethod
    def init_uid(i: int) -> str:
        return f"ipod-{i}"

    @staticmethod
    def measure_uid(i: int) -> str:
        return f"spod-{i}"


def full_cycle(cfg: dict, seed: int) -> World:
    n_nodes, n_init = cfg["nodes"], cfg["init_pods"]
    if n_init > n_nodes * cfg["node_max_pods"]:
        raise ValueError(f"{n_init} init pods do not fit {n_nodes} nodes")
    rng = np.random.default_rng(seed)
    world = World(
        cfg=cfg,
        zone=rng.integers(len(cfg["zones"]), size=n_nodes),
        init_node=rng.permutation(n_init) % n_nodes,
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
                labels={cfg["zone_key"]: cfg["zones"][int(w.zone[i])]},
                creation_timestamp=float(i),
            ),
            spec=core.NodeSpec(taints=[], unschedulable=False),
            status=core.NodeStatus(allocatable=dict(alloc), capacity=dict(alloc)),
        )
        for i in range(len(w.zone))
    ]
    queues = [scheduling.Queue(
        metadata=core.ObjectMeta(name=cfg["queue"], namespace="", uid=f"q-{cfg['queue']}"),
        spec=scheduling.QueueSpec(weight=1, capability={}),
    )]
    spread = cfg["spread"]
    constraint = dict(
        max_skew=spread["max_skew"], topology_key=spread["topology_key"],
        when_unsatisfiable=spread["when_unsatisfiable"],
        label_selector=spread["label_selector"],
    )

    def job(name, uid, created, cpu, mem, node, labels, spreads):
        pg = scheduling.PodGroup(
            metadata=core.ObjectMeta(
                name=f"pg-{name}", namespace=NAMESPACE, uid=f"pg-{uid}",
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
                name=name, namespace=NAMESPACE, uid=uid, labels=dict(labels),
                annotations={scheduling.GROUP_NAME_ANNOTATION_KEY: f"pg-{name}"},
                creation_timestamp=created,
            ),
            spec=core.PodSpec(
                containers=[core.Container(name="pause", resources={
                    "requests": {"cpu": f"{cpu}m", "memory": f"{mem}Mi"},
                })],
                node_name=None if node is None else node_name(node),
                topology_spread_constraints=(
                    [core.TopologySpreadConstraint(**constraint)] if spreads else []
                ),
            ),
            status=core.PodStatus(phase="Pending" if node is None else "Running"),
        )
        return pg, pod

    n_init = len(w.init_node)
    made = [
        job(f"init-{i:06d}", w.init_uid(i), float(i), cfg["init_cpu_milli"],
            cfg["init_mem_mib"], int(w.init_node[i]), {}, False)
        for i in range(n_init)
    ] + [
        job(f"spread-{i:06d}", w.measure_uid(i), float(n_init + i), cfg["measure_cpu_milli"],
            cfg["measure_mem_mib"], None, cfg["measure_labels"], True)
        for i in range(w.n_measure)
    ]
    order = rng.permutation(len(made))
    return {
        "nodes": nodes, "queues": queues,
        "pod_groups": [made[k][0] for k in order],
        "pods": [made[k][1] for k in order],
    }
