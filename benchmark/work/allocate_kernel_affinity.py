"""Operations and HBM bytes of one pass of the allocate kernel with
required pod anti-affinity classes (``ops/packing.py``: a pod's own
term, or a placed pod's term the row matches), added to the count of
``work/allocate_kernel.py``.

Such a class is checked against an absolute bound, not against the
minimum over domains: at each node of each step, the count against the
bound, or with "not constrained", and into the mask (3); the update
after the placement — domain compare, select, add (3), as for spread.
Its planes and task columns are those of a spread class.
"""

from __future__ import annotations

from work import allocate_kernel


def affinity_ops_per_node() -> int:
    return 3 + 3


def count(tasks: int, nodes: int, resources: int, spread_classes: int = 0,
          affinity_classes: int = 0) -> dict:
    K = spread_classes + affinity_classes
    out = allocate_kernel.count(tasks, nodes, resources, K)
    # the spread count charged every class a minimum and a skew check
    out["ops"] -= tasks * nodes * affinity_classes * (
        allocate_kernel.spread_ops_per_node() - affinity_ops_per_node()
    )
    return out
