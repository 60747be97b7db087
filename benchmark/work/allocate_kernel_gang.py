"""Operations and HBM bytes of one pass of the allocate kernel over a
session of gangs (``ops/pallas_session.py``, Volcano's per-job
commit/discard in the scan), added to and taken from the count of
``work/allocate_kernel.py``.

Rows the scan skips (a task of a job that already ended) do no predicate
or score work.  A gang that needs two or more placements copies the
node state it begins from (R used lanes and the pod count, plus one
plane per class: a load and a store at each node); one discarded after
placing restores it (the same).  A row that fits no node counts, at that
step, each node's first failing predicate — the class code, the fit and
room selects and the padding select (4) — and one compare and sum per
reason (2 x 8).
"""

from __future__ import annotations

from work import allocate_kernel

#: reasons the failing step counts (ops/kernels.py N_EXPLAIN_REASONS)
REASONS = 8


def reason_ops_per_node() -> int:
    return 4 + 2 * REASONS


def count(tasks: int, nodes: int, resources: int, skipped: int = 0,
          gang_runs: int = 0, rollback_runs: int = 0, failed: int = 0,
          classes: int = 0) -> dict:
    out = allocate_kernel.count(tasks - skipped, nodes, resources, classes)
    # every row is read, with its job column, and written with its
    # reason counts; the skipped ones too
    out["bytes"] += (skipped * (resources + 2 + 2 * classes) + tasks) * 4
    out["bytes"] += (skipped + tasks * REASONS) * 4
    planes = resources + 1 + classes
    copies = gang_runs + rollback_runs
    out["ops"] += copies * nodes * planes * 2 + failed * nodes * reason_ops_per_node()
    return out
