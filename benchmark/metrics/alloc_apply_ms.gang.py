"""Host time of jax-allocate's APPLY (``JaxAllocateAction._apply``: here
the bulk commit of the committed gangs and the recording of the
discarded ones, with their fit errors taken from the kernel's step
counts) per cycle, from the harness's annotation on the profiler's
clock, in ms."""
from harness.view import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "allocate:apply")
