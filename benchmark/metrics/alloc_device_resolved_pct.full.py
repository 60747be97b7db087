"""Share of the tasks jax-allocate's APPLY resolved without the host
predicate sweep, over the window, in percent: the bulk commit, validated
kernel proposals and device-proven explanations, over those three and
the sweep (``volcano_allocate_apply_tasks_total{path}``, reported per
cycle as ``apply_<path>`` in the program's phase stats).  None where the
program reports no such stat."""

DEVICE = ("apply_fast_apply", "apply_proposal", "apply_explained")
ALL = DEVICE + ("apply_host_sweep",)


def read(run):
    cycles = [c.phases for c in run.cycles if all(k in c.phases for k in ALL)]
    total = sum(p[k] for p in cycles for k in ALL)
    if not total:
        return None
    return 100.0 * sum(p[k] for p in cycles for k in DEVICE) / total
