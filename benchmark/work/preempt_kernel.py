"""Operations and HBM bytes of one preempt pass (Volcano preempt.go,
in-queue): for each preemptor, the node score and static predicates over
every node, and the eligibility and freed-resource sum over every victim
candidate, as the plain formulation does it.

Preemptors are counted once each, though the replay visits a starving
job's leftover tasks again in the under-request sweep; the count is a
lower bound of the pass's work.  Bytes: preemptor rows, node planes and
victim rows read once, evictions and placements written once.
"""

from __future__ import annotations


def ops_per_pair(R: int) -> int:
    """Static predicates and node score of one (preemptor, node) pair,
    per resource lane ``R``, from the formula of ``ops/kernels.py``
    (``step_feasible_score`` and ``node_scores``) as the plain
    formulation does it."""
    mask = R + 2 * R + (R - 1) + 1 + 3  # idle, fit (+tol, <), and-reduce, room, ands
    binpack = R + 2 * R + R + 2 * R + R + (R - 1) + 1 + 2  # uf, valid, and, *w /alloc, select, sum, /wsum, x10 xw
    least = 2 * R + 2 * R + 3 * R + 2 * R + 2 * R + 3 * R + (R - 1) + 2  # (cap-req)*10, /,floor, +1 check, q*c check, +-, where, sum, *0.5 floor
    balanced = 2 * R + 2 + 2 + 4  # frac + select, |diff|, (1-d)*10 floor, >=1 guards
    total = 2 + 2 + 1 + 3  # weights, sum, mask select, running argmax
    return mask + binpack + least + balanced + total


def ops_per_victim(R: int) -> int:
    # alive, same queue, other job, lower priority, gang floor: 5 tests
    # and 4 ands; the resource sum (R adds) and the fit test (R adds, R
    # compares, R-1 ands)
    return 5 + 4 + R + 2 * R + (R - 1)


def count(preemptors: int, nodes: int, victims: int, resources: int) -> dict:
    R = resources
    ops = preemptors * (nodes * ops_per_pair(R) + victims * ops_per_victim(R))
    read = preemptors * (R + 2) * 4 + nodes * (3 * R + 2) * 4 + victims * (R + 3) * 4
    written = victims * 1 + preemptors * 4
    return {"ops": ops, "bytes": read + written}
