"""Share of the window's gang outcomes — a Job committed or discarded
by allocate — that jax-allocate applied from the kernel's decision, in
percent: ``volcano_allocate_gang_outcomes_total{path="device"}`` over
both paths (reported per cycle as ``gang_outcomes_device`` and
``gang_outcomes_host`` in the program's phase stats).  None where the
program reports no such stat."""

KEYS = ("gang_outcomes_device", "gang_outcomes_host")


def read(run):
    cycles = [c.phases for c in run.cycles if all(k in c.phases for k in KEYS)]
    total = sum(p[k] for p in cycles for k in KEYS)
    if not total:
        return None
    return 100.0 * sum(p["gang_outcomes_device"] for p in cycles) / total
