"""The allocate kernel's share of its roofline in the anti-affinity
cell, in percent: for each of the window's cycles the least time of one
pass at the shape jax-allocate dispatched (``kernel_tasks``,
``kernel_nodes``, ``kernel_resources``, ``spread_classes`` and
``affinity_classes`` of its phase stats;
``work/allocate_kernel_affinity.py``) by the peaks of ``peaks.json``,
summed, over the kernel program's device time in the window.  None
where the program reports no such shape (a program without
anti-affinity classes reports no ``affinity_classes``) or the trace
holds no such program."""
from harness.view import roofline_pct

from work import allocate_kernel_affinity

KERNEL = r"^jit_schedule_session_pallas"
SHAPE = ("kernel_tasks", "kernel_nodes", "kernel_resources", "affinity_classes")


def read(run):
    works = [
        allocate_kernel_affinity.count(
            c.phases["kernel_tasks"], c.phases["kernel_nodes"],
            c.phases["kernel_resources"], c.phases.get("spread_classes", 0),
            c.phases["affinity_classes"],
        )
        for c in run.cycles if all(k in c.phases for k in SHAPE)
    ]
    return roofline_pct(run, KERNEL, works)
