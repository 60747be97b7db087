"""Plain reference of one scheduling cycle of ``enqueue, jax-allocate,
jax-preempt, backfill`` on the ``PreemptionBasic`` configuration.

Imports nothing of the program.  Allocate places nothing (no node has
room for a high-priority pod without an eviction) and backfill has no
best-effort pod, so the cycle's outcome is Volcano's preempt action
(pkg/scheduler/actions/preempt/preempt.go:45-276) with the preemptable
tier ``priority, gang, conformance`` and node order ``nodeorder +
binpack``, on jobs of one pod (minMember 1) in one queue:

* preemptors in job order: priority, then creation, then uid; each job
  under its own statement, pipelined once its one pod is;
* one preemptor: nodes with room for one more pod, by score (binpack +
  leastrequested + balancedresource over the node's used resources,
  which evictions and pipelines do not change) descending, ties in name
  order; the first node whose candidate victims — Running, of a lower
  priority, their job above its gang floor (minAvailable <= ready - 1,
  or minAvailable 1), not critical — would free enough is taken: its
  victims are evicted youngest first until the request fits the node's
  future idle, and the preemptor is pipelined there.

The control (``control=True``, ``benchmark/controls.py``) breaks the
least-eviction guarantee the configuration states: it evicts every
candidate on the node it takes, not only until the preemptor fits.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

MAX_PRIORITY = 10
CPU_TOLERANCE_MILLI = 10  # resource_info.go minMilliCPU
MEM_TOLERANCE_MIB = 10  # resource_info.go minMemory (10 MiB)


def scores(cpu_req, mem_req, used_cpu, used_mem, alloc_cpu, alloc_mem) -> np.ndarray:
    """Total node score for one request over node arrays, float64."""
    rc = used_cpu + cpu_req
    rm = used_mem + mem_req
    # binpack.go:200-259 — (used+request)*weight/capacity per resource,
    # 0 on overflow, summed, over the weight sum (2), x10 x weight (1)
    lane_c = np.where(rc <= alloc_cpu, rc * 1.0 / alloc_cpu, 0.0)
    lane_m = np.where(rm <= alloc_mem, rm * 1.0 / alloc_mem, 0.0)
    binpack = (0.0 + lane_c + lane_m) / 2.0 * MAX_PRIORITY * 1.0
    # least_requested.go:36-53, integer arithmetic
    lr_c = np.where(rc > alloc_cpu, 0, (alloc_cpu - rc) * MAX_PRIORITY // alloc_cpu)
    lr_m = np.where(rm > alloc_mem, 0, (alloc_mem - rm) * MAX_PRIORITY // alloc_mem)
    least = (lr_c + lr_m) // 2
    # balanced_resource_allocation.go:41-70
    cf = rc / alloc_cpu
    mf = rm / alloc_mem
    bal = np.where(
        (cf >= 1) | (mf >= 1), 0, np.trunc((1 - np.abs(cf - mf)) * MAX_PRIORITY)
    )
    nodeorder = 0.0 + least.astype(np.float64) + bal + 0.0
    return 0.0 + nodeorder + binpack


def expected_full_cycle(world, control: bool = False) -> Dict[str, Dict[str, int]]:
    """``{"binds": {}, "evicts": {uid: node}, "pipelined": {uid: node}}``
    with node indices."""
    cfg = world.cfg
    N = world.n_nodes
    L = len(world.low_node)
    vcpu, vmem = cfg["low_cpu_milli"], cfg["low_mem_mib"]
    pcpu, pmem = cfg["high_cpu_milli"], cfg["high_mem_mib"]
    acpu, amem, max_pods = cfg["node_cpu_milli"], cfg["node_mem_mib"], cfg["node_max_pods"]
    if cfg["high_priority"] <= cfg["low_priority"]:
        raise ValueError("reference covers preemptors above the init pods' priority")

    # node state: used is static; future idle, candidates and pod count move
    held = np.bincount(world.low_node, minlength=N)
    used_cpu, used_mem = held * vcpu, held * vmem
    fi_cpu, fi_mem = acpu - used_cpu, amem - used_mem
    count = held.astype(np.int64)
    # every init pod is a candidate victim: lower priority, a job of
    # minMember 1 (the gang floor lets it go), not critical
    cand_cpu, cand_mem, cand_cnt = used_cpu.copy(), used_mem.copy(), count.copy()
    # per node, victims youngest first (equal priorities, so creation —
    # the init pod's index — descending)
    on_node = [[] for _ in range(N)]
    for v in range(L - 1, -1, -1):
        on_node[world.low_node[v]].append(v)

    total = scores(pcpu, pmem, used_cpu, used_mem, acpu, amem)
    order = np.lexsort((np.arange(N), -total))

    evicts: Dict[str, int] = {}
    pipelined: Dict[str, int] = {}
    for i in range(world.n_high):  # job order: equal priority, creation = index
        ok = (
            (count[order] < max_pods) & (cand_cnt[order] > 0)
            & (pcpu < fi_cpu[order] + cand_cpu[order] + CPU_TOLERANCE_MILLI)
            & (pmem < fi_mem[order] + cand_mem[order] + MEM_TOLERANCE_MIB)
        )
        if not ok.any():
            continue
        n = int(order[np.argmax(ok)])
        while on_node[n]:
            if not control and (pcpu < fi_cpu[n] + CPU_TOLERANCE_MILLI
                                and pmem < fi_mem[n] + MEM_TOLERANCE_MIB):
                break
            v = on_node[n].pop(0)
            fi_cpu[n] += vcpu
            fi_mem[n] += vmem
            cand_cpu[n] -= vcpu
            cand_mem[n] -= vmem
            cand_cnt[n] -= 1
            evicts[world.low_uid(v)] = n
        fi_cpu[n] -= pcpu
        fi_mem[n] -= pmem
        count[n] += 1
        pipelined[world.high_uid(i)] = n
    return {"binds": {}, "evicts": evicts, "pipelined": pipelined}
