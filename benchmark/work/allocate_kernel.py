"""Operations and HBM bytes of one pass of the allocate kernel
(``ops/pallas_session.py``, Volcano allocate.go's per-task predicate,
score and select over every node), as the plain formulation does it:
for each task, the predicates and node score of every node
(``preempt_kernel.ops_per_pair``), and for each hard topology spread
class the check against the domain counts, their minimum over eligible
nodes, and the count update after the placement.

A gang round that discards a job runs the pass again; one pass is
counted, a lower bound of the call's work.  Bytes: task rows, the class
feasibility plane and the node planes read once, the chosen node of
each task written once; the node state lives in VMEM for the pass.
"""

from __future__ import annotations

from work.preempt_kernel import ops_per_pair


def spread_ops_per_node() -> int:
    """One spread class at one node of one step: the eligible select and
    the min for the minimum (2); the check — key present, count less
    minimum, against the threshold, and, or with "not constrained", and
    into the mask (6); the update — domain compare, select, add (3)."""
    return 2 + 6 + 3


def count(tasks: int, nodes: int, resources: int, classes: int = 0) -> dict:
    R, K = resources, classes
    ops = tasks * nodes * (ops_per_pair(R) + K * spread_ops_per_node())
    read = tasks * (R + 2 + 2 * K) * 4 + nodes * (1 + (3 * R + 2 + 3 * K) * 4)
    written = tasks * 4
    return {"ops": ops, "bytes": read + written}
