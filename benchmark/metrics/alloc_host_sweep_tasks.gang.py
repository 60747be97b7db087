"""Tasks per cycle that jax-allocate's APPLY sent to the host predicate
sweep: what the program counts into
``volcano_allocate_apply_tasks_total{path="host_sweep"}``, reported per
cycle as ``apply_host_sweep`` in its phase stats.  A discarded gang's
failing task explained from the kernel's step counts sweeps nothing, so
here it reads 0.  None where the program reports no such stat."""


def read(run):
    values = [c.phases["apply_host_sweep"] for c in run.cycles if "apply_host_sweep" in c.phases]
    return sum(values) / len(values) if values else None
