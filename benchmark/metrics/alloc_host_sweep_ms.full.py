"""Host time of jax-allocate's host predicate sweeps per cycle, in ms:
the summed durations of the program's ``vtpu/allocate.host_sweep``
spans, one per task the sweep tried over every node, which jax-allocate
reports as ``host_sweep_ms`` in its phase stats.  None where the program
reports no such stat."""


def read(run):
    values = [c.phases["host_sweep_ms"] for c in run.cycles if "host_sweep_ms" in c.phases]
    return sum(values) / len(values) if values else None
