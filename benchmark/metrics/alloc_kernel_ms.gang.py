"""Device time of the allocate kernel's program per cycle, from the
trace, found by its jitted name (``schedule_session_pallas_buf`` and its
siblings of ``ops/pallas_session.py``), in ms, in the gang cell.  None
where the trace holds none of it."""
from harness.view import kernel_ms_per_cycle

KERNEL = r"^jit_schedule_session_pallas"


def read(run):
    return kernel_ms_per_cycle(run, KERNEL)
