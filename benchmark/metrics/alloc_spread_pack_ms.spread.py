"""Host time of the topology spread pack per cycle, in ms: the duration
of the program's ``vtpu/allocate.spread_pack`` span inside the
allocate pack (the domain, eligibility and count planes and the task
columns), which jax-allocate reports as ``spread_pack_ms`` in its phase
stats.  None where the program reports no such stat."""


def read(run):
    values = [c.phases["spread_pack_ms"] for c in run.cycles if "spread_pack_ms" in c.phases]
    return sum(values) / len(values) if values else None
