"""jax-preempt — the preempt action with the victim-selection replay on
device.

Reference behavior: pkg/scheduler/actions/preempt/preempt.go:45-276.
Design mirrors actions/jax_allocate.py: the host packs the session
(ops/preempt_pack.pack_preempt_session — order replay + victim sort
happen host-side), one device program replays the whole preemption pass
(ops/preempt_pallas.run_preempt_pallas; numpy ``preempt_dense`` off-TPU),
and the result applies through a real Statement so plugin event handlers
and cache eviction stay intact.

Because phase-1 discards are resolved ON DEVICE (shadow-buffer
rollback), the returned (evicted, pipelined) sets are the committed
outcome only — the host application is a single bulk statement:

  1. validate every pipelined placement (plugin predicates on the
     proposed node — the host preempt path's predicate set);
  2. evict the device-chosen victims (global eviction order);
  3. pipeline each preemptor after an O(R) fit check against the node's
     updated future_idle.

Any validation failure discards the bulk statement and falls back to
the pure host PreemptAction — semantics never degrade below the host
path (the same guarantee jax-allocate gives per-task, here per-pass
since preemption outcomes are interdependent).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from volcano_tpu import trace
from volcano_tpu.actions.preempt import PreemptAction
from volcano_tpu.api import FitError, TaskStatus
from volcano_tpu.framework.interface import Action
from volcano_tpu.framework.session import Session
from volcano_tpu.metrics import metrics
from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)


class JaxPreemptAction(Action):
    def __init__(self, weights=None):
        from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS

        self.weights = weights or DEFAULT_WEIGHTS

    def name(self) -> str:
        return "jax-preempt"

    def _device_outcome(self, pk) -> Tuple[np.ndarray, np.ndarray]:
        """(evicted[V] bool, pipelined_node[P]) via the selected executor,
        degrading pallas → dense on runtime failure (the same native-path
        degradation discipline run_packed_auto uses)."""
        from volcano_tpu.ops.dispatch import run_preempt_auto

        return run_preempt_auto(pk, weights=self.weights)

    def execute(self, ssn: Session) -> None:
        from volcano_tpu.ops.preempt_pack import pack_preempt_session

        try:
            with trace.span("vtpu/preempt.pack", "action"):
                pk = pack_preempt_session(ssn)
        except ValueError as e:
            # unsupported preemptable tier configuration → host path
            log.info("preempt pack refused (%s); host fallback", e)
            PreemptAction().execute(ssn)
            return
        if pk.base.n_tasks == 0:
            return
        if pk.base.needs_host_validation:
            # relational predicates the packer could not encode: the bulk
            # apply below re-validates every placement, but victim
            # *selection* could still diverge — run the host action.
            PreemptAction().execute(ssn)
            return

        with trace.span("vtpu/preempt.device", "kernel"):
            evicted, pipelined = self._device_outcome(pk)

        if not evicted.any() and not (pipelined >= 0).any():
            # nothing to evict — the preemptors stay Pending; explain
            # the ones the device proves fit no node at all, so the
            # Unschedulable event/condition writeback fires like on a
            # host-scheduled cycle (ops/explain)
            from volcano_tpu.ops.explain import (
                synthesize_no_victim_explanations,
            )

            synthesize_no_victim_explanations(ssn, pk)
            metrics.register_preemption_attempts()
            return

        with trace.span("vtpu/preempt.apply", "action"):
            self._apply(ssn, pk, evicted, pipelined)

    def _apply(self, ssn: Session, pk, evicted, pipelined) -> None:
        """Evict the device's victims, pipeline its preemptors, commit."""
        stmt = ssn.statement()
        try:
            # victims in global (node-major) eviction order
            for i in np.nonzero(evicted)[0]:
                job = ssn.jobs.get(pk.job_uids[pk.vic_job[i]])
                task = job.tasks.get(pk.vic_uids[i]) if job else None
                if task is None or task.status != TaskStatus.Running:
                    raise FitError(task, None, "victim vanished")
                stmt.evict(task, "preempt")
            # pipelines in task order, validated against the live session
            # (ptasks are laid out job-contiguously: base.task_job[p] is
            # the owning job row — O(1) lookup, not a session scan)
            for p in np.nonzero(pipelined >= 0)[0]:
                node = ssn.nodes.get(pk.node_names[pipelined[p]])
                job = ssn.jobs.get(pk.job_uids[pk.base.task_job[p]])
                task = job.tasks.get(pk.ptask_uids[p]) if job else None
                if task is None or node is None:
                    raise FitError(task, node, "preemptor vanished")
                ssn.predicate_fn(task, node)  # raises FitError on veto
                if not task.init_resreq.less_equal(node.future_idle()):
                    raise FitError(task, node, "device fit diverged")
                stmt.pipeline(task, node.name)
        except FitError as e:
            # Fall back WITHOUT recording metrics here — the host action
            # records its own attempts/victims (no double count).
            log.error("device preempt apply diverged (%s); host fallback", e)
            stmt.discard()
            PreemptAction().execute(ssn)
            return
        # committed — record what actually happened
        metrics.update_preemption_victims_count(int(evicted.sum()))
        metrics.register_preemption_attempts()
        stmt.commit()


def new() -> JaxPreemptAction:
    return JaxPreemptAction()
