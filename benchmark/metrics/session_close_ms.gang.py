"""Session close per cycle (``close_session``: the plugins' close and
``JobUpdater.update_all``, which writes the discarded gangs'
Unschedulable conditions), from the harness's annotation on the
profiler's clock, in ms."""
from harness.view import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "session_close")
