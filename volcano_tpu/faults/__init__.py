"""volcano_tpu.faults — deterministic fault injection + unified
graceful degradation.

Three pieces:

* :mod:`volcano_tpu.faults.plane` — the seedable fault-injection plane
  (``VTPU_FAULTS`` / ``--faults``): named injection points threaded
  through every recovery seam, deterministic per-point decision
  streams, journaled firings, compiled out to a no-op by default.
* :mod:`volcano_tpu.faults.breaker` — per-executor circuit breakers
  with cooldown and half-open re-probe, behind the degradation ladder
  (pallas → blocked/sharded, native → xla-scan, preempt pallas → dense).
* :mod:`volcano_tpu.faults.watchdog` — the ``--cycle-deadline-ms``
  cycle watchdog bounding the device phase, with host-path completion.

The canonical hot-path guard::

    from volcano_tpu import faults
    fp = faults.get_plane()
    if fp.enabled and fp.should("bus.disconnect"):
        ...inject...
"""

from volcano_tpu.faults.breaker import (
    CircuitBreaker,
    all_breakers,
    degraded_reasons,
    get_breaker,
    reset_breakers,
)
from volcano_tpu.faults.plane import (
    FaultPlane,
    FaultRule,
    FaultSpec,
    NullFaultPlane,
    configure,
    get_plane,
    parse_faults,
)
from volcano_tpu.faults.watchdog import (
    CycleDeadlineExceeded,
    begin_cycle,
    configure_deadline,
    remaining_s,
    run_with_deadline,
)

__all__ = [
    "CircuitBreaker",
    "CycleDeadlineExceeded",
    "FaultPlane",
    "FaultRule",
    "FaultSpec",
    "NullFaultPlane",
    "all_breakers",
    "begin_cycle",
    "configure",
    "configure_deadline",
    "degraded_reasons",
    "get_breaker",
    "get_plane",
    "parse_faults",
    "remaining_s",
    "reset_breakers",
    "run_with_deadline",
]
