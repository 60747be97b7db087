"""Device time of the preempt kernel's ops per cycle, from the trace,
found by name, in ms."""
from harness.view import kernel_ms_per_cycle

KERNEL = r"^jit__preempt_call"


def read(run):
    return kernel_ms_per_cycle(run, KERNEL)
