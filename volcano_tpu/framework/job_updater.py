"""Job status writeback at session close.

Reference: pkg/scheduler/framework/job_updater.go.  The reference fans out
over 16 goroutines.  Here every job's status is derived on the calling
thread, which is pure Python under the GIL, and only the writes that are
due leave it: through a thread pool when the cache's writeback may block
on I/O, through the commit plane when it is on, and inline otherwise.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple, TYPE_CHECKING

from volcano_tpu import trace
from volcano_tpu.api import JobInfo
from volcano_tpu.apis import scheduling
from volcano_tpu.metrics import metrics
from volcano_tpu.utils.logging import get_logger

if TYPE_CHECKING:
    from volcano_tpu.framework.session import Session

log = get_logger(__name__)

_WORKERS = 16


def is_pod_group_status_updated(old, new) -> bool:
    """job_updater.go:56-76 — compare phase, counts and conditions."""
    if old is None or new is None:
        return True
    if old.phase != new.phase:
        return True
    if (old.running, old.succeeded, old.failed) != (new.running, new.succeeded, new.failed):
        return True
    old_conds = {(c.type, c.status, c.reason, c.message) for c in old.conditions}
    new_conds = {(c.type, c.status, c.reason, c.message) for c in new.conditions}
    return old_conds != new_conds


class JobUpdater:
    def __init__(self, ssn: "Session"):
        self.ssn = ssn
        self.job_queue: List[JobInfo] = list(ssn.jobs.values())

    def _derive(self, job: JobInfo) -> Tuple[bool, Optional[str]]:
        """Set the job's PodGroup status from the session's outcome;
        ``(write due, attempt bucket)``."""
        ssn = self.ssn
        # was the job already Running when this session OPENED?  The
        # conditions-based pod_group_status record is empty for healthy
        # Running groups, so the phase is snapshotted separately at open
        # (Session.pod_group_phase0) — steady-state Running jobs must
        # not re-count as a fresh "scheduled" attempt every cycle.
        was_running = (
            ssn.pod_group_phase0.get(job.uid) == scheduling.POD_GROUP_RUNNING
        )
        job.pod_group.status = ssn.job_status(job)
        # schedule_attempts_total (metrics.go:74-121): exactly ONE
        # attempt per job the session actually worked on, bucketed by
        # outcome (a writeback failure overrides it to "error")
        phase = job.pod_group.status.phase
        attempt = None
        if phase == scheduling.POD_GROUP_RUNNING:
            if not was_running:
                attempt = "scheduled"
                if job.creation_timestamp > 0:
                    metrics.update_job_schedule_duration(
                        max(time.time() - job.creation_timestamp, 0.0)
                    )
        elif job.job_fit_errors or phase == scheduling.POD_GROUP_UNKNOWN:
            attempt = "unschedulable"
        due = is_pod_group_status_updated(
            ssn.pod_group_status.get(job.uid), job.pod_group.status
        )
        return due, attempt

    def _write(self, job: JobInfo, attempt: Optional[str]) -> None:
        # pipelined caches capture the whole per-job writeback (events +
        # conditions + PodGroup status) as one commit-plane item — a
        # 50k-pod close issues O(jobs) coalesced frames, not O(pods)
        # round trips.  Other caches keep the synchronous write.
        cache = self.ssn.cache
        try:
            updater = getattr(cache, "update_job_status_async", None)
            if updater is not None:
                updater(job)
            else:
                cache.update_job_status(job)
        except Exception as e:  # noqa: BLE001 — next session retries
            attempt = "error"
            log.error("Failed to update job status <%s/%s>: %s", job.namespace, job.name, e)
        if attempt is not None:
            metrics.register_schedule_attempt(attempt)

    def _write_path(self, n_due: int) -> str:
        """Where the due writes go, from what the cache shows: the
        commit plane takes them serially (its workers do the bus
        writes); a status writer that may block on I/O gets the
        reference's 16-way fan-out; an inert writeback stays inline."""
        cache = self.ssn.cache
        if getattr(cache, "_commit_plane", None) is not None:
            return "plane"
        if n_due > 1 and getattr(cache, "status_writeback_io", True):
            return "pool"
        return "inline"

    def update_all(self) -> None:
        due: List[Tuple[JobInfo, Optional[str]]] = []
        for job in self.job_queue:
            if job.pod_group is None:
                continue
            write, attempt = self._derive(job)
            if write:
                due.append((job, attempt))
            elif attempt is not None:
                metrics.register_schedule_attempt(attempt)
        if not due:
            return
        path = self._write_path(len(due))
        metrics.register_job_status_writes(path, len(due))
        with trace.span("vtpu/session.job_writeback", "framework", path=path, jobs=len(due)):
            if path == "pool":
                with ThreadPoolExecutor(max_workers=_WORKERS) as pool:
                    list(pool.map(lambda item: self._write(*item), due))
            else:
                for job, attempt in due:
                    self._write(job, attempt)
