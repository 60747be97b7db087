"""The jax-allocate action per cycle: the duration ``run_once`` records
for it (``volcano_action_scheduling_latency_microseconds``), in ms."""
from harness.view import action_ms


def read(run):
    return action_ms(run, "jax-allocate")
