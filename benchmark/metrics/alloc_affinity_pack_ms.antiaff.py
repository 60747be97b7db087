"""Host time of the inter-pod affinity pack per cycle, in ms: the
duration of the program's ``vtpu/allocate.affinity_pack`` span inside
the allocate pack (the anti-affinity classes, their domain counts and
the task columns), which jax-allocate reports as ``affinity_pack_ms`` in
its phase stats.  None where the program reports no such stat."""


def read(run):
    values = [c.phases["affinity_pack_ms"] for c in run.cycles if "affinity_pack_ms" in c.phases]
    return sum(values) / len(values) if values else None
