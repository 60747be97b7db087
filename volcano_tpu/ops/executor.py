"""Kernel executor indirection: in-process device kernels, or the
compute-plane sidecar when one is configured.

``VTPU_COMPUTE_PLANE=<socket path>`` (or ``configure(path)``) routes the
packed kernels over the serialized boundary
(serving/compute_plane.py).  Every remote failure — sidecar down,
timeout, protocol error — falls back to the in-process executor and
marks the sidecar unhealthy; a background-free probe-on-next-session
retries it, so a bounced sidecar is picked back up without operator
action.  Semantics are identical either way (the sidecar runs the same
run_packed_auto / preempt dispatch on the same packed arrays).  The
sidecar holds the chip, so a scheduler behind it runs on the host
(vtpu-scheduler pins JAX to the CPU at startup) and stages nothing on a
device (:func:`owns_device`).
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import numpy as np

from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: seconds to wait before re-probing an unhealthy sidecar
_RETRY_PERIOD = 5.0


class _Remote:
    def __init__(self, path: str):
        from volcano_tpu.faults.breaker import get_breaker
        from volcano_tpu.serving.compute_plane import ComputePlaneClient

        self.client = ComputePlaneClient(path)
        self.path = path
        self.healthy = True
        self.last_probe = 0.0
        #: threshold 1: one failed session is enough — the in-process
        #: fallback is exact, so there is no reason to pay a second
        #: failure latency before demoting.  The breaker mirrors the
        #: probe state into /healthz (degraded) and the breaker gauge.
        self.breaker = get_breaker(
            "compute-plane", failure_threshold=1, cooldown_s=_RETRY_PERIOD
        )

    def usable(self) -> bool:
        if self.healthy:
            return True
        now = time.monotonic()
        if now - self.last_probe < _RETRY_PERIOD:
            return False
        self.last_probe = now
        self.healthy = self.client.health()
        if self.healthy:
            self.breaker.record_success()
            log.info("compute plane %s back up", self.path)
        return self.healthy

    def mark_unhealthy(self, error: str) -> None:
        """Session-loss handling: demote the route AND drop the
        connection — a restarted (or abandoned mid-read) sidecar shares
        no session state with us, so the delta handshake must restart
        from a full frame (ComputePlaneClient.close clears the acked
        revisions)."""
        self.healthy = False
        self.last_probe = time.monotonic()
        self.breaker.record_failure(error)
        self.client.close()


_UNSET = object()  # env-derived default; distinct from "explicitly off"
_remote: object = _UNSET


def configure(socket_path: Optional[str]) -> None:
    """Point the executors at a sidecar.  ``None`` explicitly DISABLES
    the remote path — including a VTPU_COMPUTE_PLANE env setting."""
    global _remote
    old = _remote
    _remote = _Remote(socket_path) if socket_path else None
    if isinstance(old, _Remote):
        # the replaced route's connection must close NOW, not at gc: a
        # live healthy client holds both ends of the sidecar socket
        # open (fd-leak-guard catch), and captured log records can pin
        # the abandoned object past interpreter cleanup
        old.client.close()


def _get_remote() -> Optional[_Remote]:
    global _remote
    if _remote is _UNSET:
        path = os.environ.get("VTPU_COMPUTE_PLANE", "")
        _remote = _Remote(path) if path else None
    return _remote


def owns_device() -> bool:
    """Whether this process may touch the accelerator.  On a chip host
    exactly one process holds the chip: with a compute-plane sidecar
    configured that process is the sidecar, and this one stages nothing
    on a device."""
    return _get_remote() is None


#: did the last execute_allocate run in-process or on the sidecar?
_last_route = "local"

#: reason counts of the last execute_allocate(explain=True) — [T, P]
#: int32 aligned with the snapshot's ordered tasks, or None when the
#: session needed no explanation (everything placed) or explain was
#: off.  Same single-threaded read-right-after-the-call discipline as
#: the dispatch state above.
_last_explain_counts = None

#: wall-clock ms of the reduction behind _last_explain_counts, or None
#: when the counts were reduced REMOTELY (the sidecar's own metrics
#: carry that cost — reporting a stale local number here would
#: fabricate phase stats in remote-executor configurations)
_last_explain_ms = None


def last_explain_counts():
    return _last_explain_counts


def last_explain_ms():
    return _last_explain_ms


def _maybe_explain(snap, assignment) -> None:
    """Lazy explain: the reason-count reduction runs only when a valid
    task went unplaced — fully-placed warm cycles pay nothing — and
    only over the unplaced rows."""
    global _last_explain_counts, _last_explain_ms
    _last_explain_counts = None
    _last_explain_ms = None
    unplaced = np.nonzero(np.asarray(assignment)[: snap.n_tasks] < 0)[0]
    if unplaced.size:
        from volcano_tpu.ops import explain as _explain

        _last_explain_counts = _explain.run_explain(
            snap, task_rows=unplaced
        ).counts
        _last_explain_ms = _explain.last_run_ms


def last_allocate_executor() -> str:
    """Name of what the most recent execute_allocate actually ran —
    deliberately NOT called last_executor, so it can't be confused with
    ops/dispatch.last_executor (local dispatch vocabulary, blind to the
    sidecar route).  'auto' when the assignment came from the sidecar —
    its dispatch picks there against ITS hardware, so the local pick
    would be a guess; 'auto' tells replay to re-dispatch.  Otherwise the
    local dispatcher's record, which includes mid-session degradations.
    Same-thread read right after the call, like the dispatch state it
    wraps."""
    if _last_route == "remote":
        return "auto"
    from volcano_tpu.ops.dispatch import last_executor as _dispatch_last

    return _dispatch_last()


def execute_allocate(
    snap, weights=None, gang_rounds: int = 3, explain: bool = False
) -> np.ndarray:
    """PackedSnapshot → assignment, via sidecar when configured.

    ``explain=True`` additionally computes the per-task reason-count
    matrix when any valid task went unplaced (read it back with
    :func:`last_explain_counts`).  The sidecar computes the counts
    against the snapshot it already holds — same request, no second
    round trip; a pre-explain sidecar returns no counts and the local
    reduction fills in."""
    from volcano_tpu.faults import watchdog
    from volcano_tpu.faults.watchdog import CycleDeadlineExceeded
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops.dispatch import run_packed_auto
    from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS

    from volcano_tpu import trace

    rec = trace.get_recorder()
    weights = weights or DEFAULT_WEIGHTS
    remote = _get_remote()
    # the wire protocol carries neither weights nor gang_rounds — only
    # default-configured sessions may route remotely, or the sidecar
    # would silently run different parameters than the fallback
    global _last_route, _last_explain_counts, _last_explain_ms
    # cleared up front: an aborted call (deadline, error) must not leave
    # a previous session's counts readable as this session's
    _last_explain_counts = None
    _last_explain_ms = None
    if (
        remote is not None
        and weights == DEFAULT_WEIGHTS
        and gang_rounds == 3
        # nor does it carry the topology spread planes
        and not snap.has_spread
        and remote.usable()
    ):
        try:
            with rec.span("executor:remote-allocate", "kernel"):
                out = watchdog.run_with_deadline(
                    lambda: remote.client.allocate(snap, explain=explain),
                    watchdog.remaining_s(),
                    "remote-allocate",
                )
            _last_route = "remote"
            if explain:
                counts = remote.client.last_reason_counts
                if counts is not None:
                    _last_explain_counts = counts
                else:
                    # pre-explain sidecar — same lazy unplaced-rows
                    # reduction as the local path
                    _maybe_explain(snap, out)
            return out
        except CycleDeadlineExceeded as e:
            # budget gone mid-RPC: the abandoned read desynced the
            # connection — drop it (full-frame re-handshake later) and
            # fall through; the local wrapper below raises immediately
            # on the exhausted budget, handing the cycle to the host
            # path in jax-allocate, which records the ONE
            # device→host/deadline fallback count for this cycle
            remote.mark_unhealthy(str(e))
            rec.event("executor:remote-fallback", "kernel", error=str(e))
            log.error("compute plane allocate overran the cycle deadline")
        except Exception as e:  # noqa: BLE001 — degrade to in-process
            remote.mark_unhealthy(str(e))
            metrics.register_executor_fallback("remote", "local", "error")
            rec.event("executor:remote-fallback", "kernel", error=str(e))
            log.error(
                "compute plane allocate failed (%s); in-process fallback", e
            )
    _last_route = "local"
    out = watchdog.run_with_deadline(
        lambda: run_packed_auto(snap, weights=weights, gang_rounds=gang_rounds),
        watchdog.remaining_s(),
        "local-allocate",
    )
    if explain:
        _maybe_explain(snap, out)
    else:
        _last_explain_counts = None
        _last_explain_ms = None
    return out


def execute_preempt(pk) -> Tuple[np.ndarray, np.ndarray]:
    """PreemptPacked → (evicted, pipelined), via sidecar when configured.
    The cycle watchdog does not bound this phase — preempt has no
    host-completion seam to hand an abandoned device pass to."""
    from volcano_tpu import trace
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops.dispatch import run_preempt_auto

    rec = trace.get_recorder()
    remote = _get_remote()
    if remote is not None and remote.usable():
        try:
            with rec.span("executor:remote-preempt", "kernel"):
                return remote.client.preempt(pk)
        except Exception as e:  # noqa: BLE001
            remote.mark_unhealthy(str(e))
            metrics.register_executor_fallback("remote", "local", "error")
            rec.event("executor:remote-fallback", "kernel", error=str(e))
            log.error(
                "compute plane preempt failed (%s); in-process fallback", e
            )
    return run_preempt_auto(pk)
