"""Plain reference of one scheduling cycle of ``enqueue, jax-allocate,
backfill`` on the ``TopologySpreading`` configuration.

Imports nothing of the program.  Enqueue has nothing to admit and
backfill has no best-effort pod, so the cycle's outcome is Volcano's
allocate action (pkg/scheduler/actions/allocate/allocate.go) with the
predicates plugin's PodTopologySpread filter (k8s v1.30
plugins/podtopologyspread, ``DoNotSchedule``) and node order
``nodeorder + binpack``, on jobs of one pod (minMember 1) in one queue:

* pods in job order: priority (equal), then creation, then uid, as
  ``reference/preempt.py`` documents it; each binds once placed;
* one pod: nodes where its request fits and, unless ``control``, the
  skew holds — the blue pods of the node's zone, plus one for the pod
  itself (it matches its own selector), less the fewest of any zone
  that has a node, at most maxSkew (every node carries the zone key and
  is eligible: the pods have no node selector, and taints are ignored);
  among them the highest score (binpack + leastrequested +
  balancedresource, ``reference/preempt.py`` ``scores``), ties in name
  order.  Init pods carry no ``color`` label, so only blue pods count.

The control (``control=True``, ``benchmark/controls.py``) breaks the
spread guarantee the configuration states: it ignores the constraint.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference.preempt import CPU_TOLERANCE_MILLI, MEM_TOLERANCE_MIB, scores


def expected_full_cycle(world, control: bool = False) -> Dict[str, Dict[str, int]]:
    """``{"binds": {uid: node}, "evicts": {}, "pipelined": {}}`` with node
    indices."""
    cfg = world.cfg
    n_nodes = len(world.zone)
    n_zones = len(cfg["zones"])
    acpu, amem, max_pods = cfg["node_cpu_milli"], cfg["node_mem_mib"], cfg["node_max_pods"]
    pcpu, pmem = cfg["measure_cpu_milli"], cfg["measure_mem_mib"]
    max_skew = cfg["spread"]["max_skew"]

    held = np.bincount(world.init_node, minlength=n_nodes)
    used_cpu = held * cfg["init_cpu_milli"]
    used_mem = held * cfg["init_mem_mib"]
    count = held.astype(np.int64)
    present = np.bincount(world.zone, minlength=n_zones) > 0
    blue = np.zeros(n_zones, dtype=np.int64)

    binds: Dict[str, int] = {}
    for i in range(world.n_measure):
        ok = (
            (count < max_pods)
            & (pcpu < acpu - used_cpu + CPU_TOLERANCE_MILLI)
            & (pmem < amem - used_mem + MEM_TOLERANCE_MIB)
        )
        if not control:
            ok &= blue[world.zone] + 1 - blue[present].min() <= max_skew
        if not ok.any():
            continue
        total = np.where(ok, scores(pcpu, pmem, used_cpu, used_mem, acpu, amem), -np.inf)
        n = int(np.argmax(total))
        used_cpu[n] += pcpu
        used_mem[n] += pmem
        count[n] += 1
        blue[world.zone[n]] += 1
        binds[world.measure_uid(i)] = n
    return {"binds": binds, "evicts": {}, "pipelined": {}}
