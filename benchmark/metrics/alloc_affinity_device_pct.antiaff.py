"""Share of the pending tasks under inter-pod (anti-)affinity whose
constraints jax-allocate's kernel decided, over the window, in percent:
the rows ``volcano_allocate_affinity_rows_total{path="device"}`` counts
over those of both paths (reported per cycle as ``affinity_rows_device``
and ``affinity_rows_host`` in the program's phase stats).  None where
the program reports no such stat."""

KEYS = ("affinity_rows_device", "affinity_rows_host")


def read(run):
    cycles = [c.phases for c in run.cycles if all(k in c.phases for k in KEYS)]
    total = sum(p[k] for p in cycles for k in KEYS)
    if not total:
        return None
    return 100.0 * sum(p["affinity_rows_device"] for p in cycles) / total
