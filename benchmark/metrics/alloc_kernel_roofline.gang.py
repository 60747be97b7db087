"""The allocate kernel's share of its roofline in the gang cell, in
percent: for each of the window's cycles the least time of one pass at
the shape jax-allocate dispatched (``kernel_tasks``, ``kernel_nodes``,
``kernel_resources`` of its phase stats) and the work of its gangs
(``kernel_skipped_rows``, ``kernel_gang_runs``, ``kernel_rollback_runs``,
``kernel_failed_rows``; ``work/allocate_kernel_gang.py``) by the peaks
of ``peaks.json``, summed, over the kernel program's device time in the
window.  None where the program reports no such counts (a program
without gang commit/discard in its scan) or the trace holds no such
program."""
from harness.view import roofline_pct

from work import allocate_kernel_gang

KERNEL = r"^jit_schedule_session_pallas"
SHAPE = ("kernel_tasks", "kernel_nodes", "kernel_resources", "kernel_skipped_rows",
         "kernel_gang_runs", "kernel_rollback_runs", "kernel_failed_rows")


def read(run):
    works = [
        allocate_kernel_gang.count(
            c.phases["kernel_tasks"], c.phases["kernel_nodes"],
            c.phases["kernel_resources"], c.phases["kernel_skipped_rows"],
            c.phases["kernel_gang_runs"], c.phases["kernel_rollback_runs"],
            c.phases["kernel_failed_rows"], c.phases.get("spread_classes", 0),
        )
        for c in run.cycles if all(k in c.phases for k in SHAPE)
    ]
    return roofline_pct(run, KERNEL, works)
