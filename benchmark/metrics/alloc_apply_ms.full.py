"""Host time of jax-allocate's APPLY (``JaxAllocateAction._apply``: the
proposals validated and committed, and the host predicate sweep for each
task neither placed nor explained by the device) per cycle, from the
harness's annotation on the profiler's clock, in ms."""
from harness.view import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "allocate:apply")
