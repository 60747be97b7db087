"""Plain reference of one scheduling cycle of ``enqueue, jax-allocate,
backfill`` on the ``SchedulingPodAntiAffinity`` configuration.

Imports nothing of the program.  Enqueue has nothing to admit and
backfill has no best-effort pod, so the cycle's outcome is Volcano's
allocate action (pkg/scheduler/actions/allocate/allocate.go) with the
predicates plugin's inter-pod affinity filter (k8s v1.30
plugins/interpodaffinity, required terms) and node order ``nodeorder +
binpack``, on jobs of one pod (minMember 1) in one queue:

* pods in job order: priority (equal), then creation, then uid, as
  ``reference/preempt.py`` documents it; each binds once placed;
* one pod: nodes where its request fits and, unless ``control``, both
  anti-affinity rules hold in the node's hostname domain (the node
  itself: every node carries its own name as ``kubernetes.io/hostname``)
  — no pod there matches the pod's own term (a green pod of ``sched-0``
  or ``sched-1``), and no pod there holds a term that matches the pod
  (the symmetry rule); among them the highest score (binpack +
  leastrequested + balancedresource, ``reference/preempt.py``
  ``scores``), ties in name order.

Every pod of the configuration is green, in one of the term's
namespaces, and holds the term, so both rules exclude exactly the nodes
that hold a pod.  The control (``control=True``,
``benchmark/controls.py``) breaks the anti-affinity guarantee the
configuration states: it ignores both rules, and binpack then stacks the
pods onto nodes that hold one.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference.preempt import CPU_TOLERANCE_MILLI, MEM_TOLERANCE_MIB, scores


def _matches(cfg: dict, namespace: str) -> bool:
    """Does a pod of the configuration in ``namespace`` match the term?"""
    term = cfg["anti_affinity"]
    labels = cfg["pod_labels"]
    return namespace in term["namespaces"] and all(
        labels.get(k) == v for k, v in term["labelSelector"]["matchLabels"].items()
    )


def expected_full_cycle(world, control: bool = False) -> Dict[str, Dict[str, int]]:
    """``{"binds": {uid: node}, "evicts": {}, "pipelined": {}}`` with node
    indices."""
    cfg = world.cfg
    n_nodes = world.n_nodes
    acpu, amem, max_pods = cfg["node_cpu_milli"], cfg["node_mem_mib"], cfg["node_max_pods"]
    pcpu, pmem = cfg["pod_cpu_milli"], cfg["pod_mem_mib"]

    held = np.bincount(world.init_node, minlength=n_nodes)
    used_cpu = held * pcpu
    used_mem = held * pmem
    count = held.astype(np.int64)
    # per node: pods matching the term (own rule), pods holding it (symmetry)
    matching = held * int(_matches(cfg, cfg["init_namespace"]))
    holding = held.copy()
    measured_matches = _matches(cfg, cfg["measure_namespace"])

    binds: Dict[str, int] = {}
    for i in range(world.n_measure):
        ok = (
            (count < max_pods)
            & (pcpu < acpu - used_cpu + CPU_TOLERANCE_MILLI)
            & (pmem < amem - used_mem + MEM_TOLERANCE_MIB)
        )
        if not control:
            ok &= matching == 0
            if measured_matches:
                ok &= holding == 0
        if not ok.any():
            continue
        total = np.where(ok, scores(pcpu, pmem, used_cpu, used_mem, acpu, amem), -np.inf)
        n = int(np.argmax(total))
        used_cpu[n] += pcpu
        used_mem[n] += pmem
        count[n] += 1
        matching[n] += int(measured_matches)
        holding[n] += 1
        binds[world.measure_uid(i)] = n
    return {"binds": binds, "evicts": {}, "pipelined": {}}
