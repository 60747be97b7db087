"""Session open per cycle: the delta of ``Scheduler.session_open_seconds``
over the window's cycles (a program counter), in ms."""
from harness.view import mean_ms


def read(run):
    return mean_ms(run, lambda c: c.open_ms)
