"""The allocate kernel's share of its roofline, in percent: for each of
the window's cycles the least time of one pass at the shape jax-allocate
dispatched (``kernel_tasks``, ``kernel_nodes``, ``kernel_resources`` and
``spread_classes`` of its phase stats; ``work/allocate_kernel.py``) by
the peaks of ``peaks.json``, summed, over the kernel program's device
time in the window.  None where the program reports no such shape or the
trace holds no such program."""
from harness.view import roofline_pct

from work import allocate_kernel

KERNEL = r"^jit_schedule_session_pallas"
SHAPE = ("kernel_tasks", "kernel_nodes", "kernel_resources")


def read(run):
    works = [
        allocate_kernel.count(
            c.phases["kernel_tasks"], c.phases["kernel_nodes"],
            c.phases["kernel_resources"], c.phases.get("spread_classes", 0),
        )
        for c in run.cycles if all(k in c.phases for k in SHAPE)
    ]
    return roofline_pct(run, KERNEL, works)
