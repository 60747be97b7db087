"""Plain reference of one scheduling cycle of ``enqueue, jax-allocate,
backfill`` on the ``gang-training-backlog`` configuration.

Imports nothing of the program.  Enqueue has nothing to admit and
backfill has no best-effort pod, so the cycle's outcome is Volcano's
allocate action (pkg/scheduler/actions/allocate/allocate.go) with the
gang plugin's commit/discard (statement.go) and node order ``nodeorder +
binpack``, on one queue:

* jobs in Volcano's order — priority (equal), gang not-ready first (every
  pending Job; a committed one turns ready and goes behind them), drf
  share (0 for every pending Job), then creation and uid — which here is
  the gangs' creation order;
* a gang's workers in creation order; one worker: nodes where its
  request fits (with the resource tolerances) and a pod slot is free,
  among them the highest score (binpack + leastrequested +
  balancedresource, ``reference/preempt.py`` ``scores``), ties in name
  order;
* at the first worker that fits nowhere the gang stops; a gang short of
  minAvailable (= its replicas) is discarded, its placements undone, and
  the room it held is free for the gangs after it.

The control (``control=True``, ``benchmark/controls.py``) breaks the
capacity the configuration states: a worker whose request fits no node
is bound anyway, to the highest-scoring node with a free pod slot, so
no gang is ever short of minAvailable.  Every cycle of the cell
discards gangs, so the control binds pods the program does not on
every seed.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference.preempt import CPU_TOLERANCE_MILLI, MEM_TOLERANCE_MIB, scores


def expected_full_cycle(world, control: bool = False) -> Dict[str, Dict[str, int]]:
    """``{"binds": {uid: node}, "evicts": {}, "pipelined": {}}`` with node
    indices."""
    cfg = world.cfg
    acpu, amem, max_pods = cfg["node_cpu_milli"], cfg["node_mem_mib"], cfg["node_max_pods"]
    count = world.resident.astype(np.int64)
    used_cpu = count * cfg["resident_cpu_milli"]
    used_mem = count * cfg["resident_mem_mib"]

    binds: Dict[str, int] = {}
    for gang in world.gangs:
        placed = []
        for k in range(gang.workers):
            ok = (
                (count < max_pods)
                & (gang.cpu_milli < acpu - used_cpu + CPU_TOLERANCE_MILLI)
                & (gang.mem_mib < amem - used_mem + MEM_TOLERANCE_MIB)
            )
            if not ok.any():
                if not control:
                    break
                ok = count < max_pods
            total = np.where(
                ok, scores(gang.cpu_milli, gang.mem_mib, used_cpu, used_mem, acpu, amem),
                -np.inf,
            )
            n = int(np.argmax(total))
            used_cpu[n] += gang.cpu_milli
            used_mem[n] += gang.mem_mib
            count[n] += 1
            placed.append((k, n))
        if len(placed) < gang.workers:
            for _k, n in placed:
                used_cpu[n] -= gang.cpu_milli
                used_mem[n] -= gang.mem_mib
                count[n] -= 1
            continue
        for k, n in placed:
            binds[gang.worker_uid(k)] = n
    return {"binds": binds, "evicts": {}, "pipelined": {}}
