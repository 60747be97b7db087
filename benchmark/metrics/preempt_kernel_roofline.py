"""The preempt kernel's share of its roofline, in percent: for each of
the window's calls the least time of one pass at that session's shape
(``work/preempt_kernel.py``) by the peaks of ``peaks.json``, summed,
over the kernel program's device time in the window."""
from harness.view import roofline_pct

from work import preempt_kernel

KERNEL = r"^jit__preempt_call"


def read(run):
    works = [preempt_kernel.count(**s) for c in run.cycles for s in c.preempt_shapes]
    return roofline_pct(run, KERNEL, works)
