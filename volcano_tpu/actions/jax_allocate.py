"""jax-allocate — the allocate action with the O(tasks×nodes) hot loop on
TPU.

Reference behavior: pkg/scheduler/actions/allocate/allocate.go.  Design
(SURVEY.md §7): the reference's per-task PredicateNodes/PrioritizeNodes/
SelectBestNode (scheduler_helper.go:64-211) is replaced by one fused device
kernel over the whole session; results apply through the same Statement so
gang commit/discard and plugin event handlers stay intact.

Three phases, all built on the single control-flow skeleton in
actions/allocate.py (drive_allocate_loop):

1. ORDER — replay the control flow *without placements* to obtain the task
   processing order.  Exact because every order-determining quantity (DRF
   share, proportion queue share/overused, gang readiness, priorities)
   updates from task resreqs only, never from which node a task landed on.
   The replay mutates session accounting through the real event handlers
   and then unwinds itself, Statement-style.
2. KERNEL — pack the snapshot (ops/packing.py) and run the fused
   predicate+score+assign scan (ops/kernels.py) over the ordered tasks.
3. APPLY — run the real control flow, placing each task on its kernel-
   proposed node after an O(1) host validation (plugin predicates + fit on
   that node only); tasks whose proposal fails validation — and tasks the
   kernel cannot score faithfully (preferred-affinity terms) — fall back
   to the host scoring path for that task alone.  Unplaced tasks the
   device proves fit no node skip that path (_ExplainContext), ORDER's
   rows and the rows it left out alike.

Bindings equivalence: with deterministic tie-break, phase 2's argmax equals
the host path's SelectBestNode per task, so bindings are identical whenever
every ordered task is placeable (tests/test_jax_allocate.py).  When a
placement fails (capacity race against the static proposal), the fallback
keeps the result valid — semantics never degrade below the host action.
"""

from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from volcano_tpu import trace
from volcano_tpu.actions.allocate import (
    drive_allocate_loop,
    eligible_jobs,
    gang_end_job,
    host_node_chooser,
    make_place_task,
    make_predicate_fn,
)
from volcano_tpu.api import FitError, TaskInfo, TaskStatus
from volcano_tpu.framework.interface import Action
from volcano_tpu.framework.session import Session
from volcano_tpu.metrics import metrics
from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)


class _ExplainContext:
    """Device-derived unschedulability explanations for one session.

    Built from the reason-count matrix the dispatch returned alongside
    the assignment (ops/explain).  ``try_explain`` replaces the host
    fallback's O(N) predicate sweep for a task the device PROVED fits no
    node — synthesizing the reference-identical FitErrors from the
    counts — under gates that keep the messages byte-faithful to what
    the host path would have recorded on the same snapshot:

      * the predicates plugin is in the session, with no opt-in
        pressure predicates (ops.explain.session_explain_compatible —
        the host chain's pressure checks have no device plane);
      * the task row is bitset-exact and memory-exact
        (ops.explain.task_exactly_encoded);
      * the counts hold for the node state the host sees: either the
        kernel counted them at the task's failing step of its scan
        (``at_step``) and APPLY has followed the kernel's placements so
        far (``diverged`` unset), or they are the pack's and no
        placement has mutated node state since — after a placement the
        host's pop-time first-failure can shift to a resource-fit
        failure the snapshot-time counts predate, so those tasks take
        the host sweep instead.

    APPLY reaches pending tasks ORDER never listed when ORDER's
    every-task-places simulation used up a queue's share that the real
    loop never does.  The first such task reached with the gate still
    open builds the *tail*: every other pending task of the eligible
    jobs, packed on its own against the same nodes and reduced on the
    device in one pass, so the rest of a saturated cycle needs no host
    sweep either.  The tail has no proposals and never meets the
    assignment kernel, the pack cache or the device stager.
    """

    def __init__(self, ssn: Session, snap, counts, ordered, nodes,
                 planes=None, at_step=False):
        from volcano_tpu.ops.explain import (
            ExplainResult,
            session_explain_compatible,
        )

        self.ssn = ssn
        self.snap = snap
        self.result = ExplainResult(counts, snap.n_nodes, planes)
        self.index = {t.uid: i for i, t in enumerate(ordered)}
        self.nodes = nodes
        self.node_names = [n.name for n in nodes]
        self.enabled = session_explain_compatible(ssn)
        #: counts taken at each row's failing step of the scan
        self.at_step = at_step
        #: set once APPLY placed a task elsewhere than the kernel did,
        #: or committed out of ORDER: the step counts no longer hold
        self.diverged = False
        #: planes given → the tail retains its own too
        self.retain_planes = planes is not None
        #: node-state epoch at pack time — any later mutation (even of a
        #: node some earlier action already touched) advances it, so the
        #: gate below cannot be fooled by repeat mutations the
        #: touched-SET would deduplicate away
        self._epoch0 = ssn.node_state_epoch
        #: (snapshot, result, uid → row) of the tail, once built
        self._tail = None
        #: tasks the tail packed, and the ms its pack and reduction took
        self.tail_tasks = 0
        self.tail_ms = 0.0
        #: task uid → reason histogram, for the cycle summary
        self.explained: Dict[str, Dict[str, int]] = {}

    def _row(self, uid: str):
        """(snapshot, result, row) that holds ``uid``, building the tail
        on the first task ORDER did not list; None if neither does."""
        i = self.index.get(uid)
        if i is not None:
            return self.snap, self.result, i
        if self._tail is None:
            self._tail = self._explain_tail()
        snap, result, index = self._tail
        i = index.get(uid)
        return None if i is None else (snap, result, i)

    def _explain_tail(self):
        """Pack and reduce every pending task of the eligible jobs that
        is not an ordered row (the tasks ``drive_allocate_loop`` can
        still reach), with the primary pack's nodes and pod-count rule
        and registries of its own."""
        from volcano_tpu.ops.explain import run_explain
        from volcano_tpu.ops.packing import pack_session

        with trace.span("vtpu/allocate.explain_tail", "explain") as sp:
            tasks, jobs = [], []
            for job in eligible_jobs(self.ssn):
                pending = sorted(
                    (t for t in job.task_status_index.get(TaskStatus.Pending, {}).values()
                     if t.uid not in self.index and not t.resreq.is_empty()),
                    key=lambda t: t.uid,
                )
                if pending:
                    tasks.extend(pending)
                    jobs.append(job)
            snap = result = None
            if tasks:
                snap = pack_session(
                    tasks, jobs, self.nodes,
                    enforce_pod_count="predicates" in self.ssn.predicate_fns,
                )
                result = run_explain(snap, retain_planes=self.retain_planes)
            sp.set(tasks=len(tasks))
        self.tail_tasks = len(tasks)
        self.tail_ms = sp.seconds * 1e3
        metrics.register_explain_tail(len(tasks))
        return snap, result, {t.uid: i for i, t in enumerate(tasks)}

    def _proven(self, task: TaskInfo):
        """(result, row) when the device counts prove ``task`` fits no
        node at the state the host is at, else None."""
        from volcano_tpu.ops.explain import task_exactly_encoded

        if not self.enabled:
            return None
        if self.at_step and task.uid in self.index:
            if self.diverged:
                return None
        elif self.ssn.node_state_epoch != self._epoch0:
            return None
        found = self._row(task.uid)
        if found is None:
            return None
        snap, result, i = found
        if i >= len(result.counts):
            return None
        if not task_exactly_encoded(snap, i):
            return None
        if not result.all_infeasible(i):
            return None
        return result, i

    def can_explain(self, task: TaskInfo) -> bool:
        """Would :meth:`record` find ``task``'s fit errors now?"""
        return self._proven(task) is not None

    def record(self, task: TaskInfo, job) -> None:
        """Write ``task``'s fit errors onto ``job``, as the host sweep
        would; :meth:`can_explain` said it can."""
        job.nodes_fit_errors[task.uid] = self.try_explain(task)

    def try_explain(self, task: TaskInfo):
        """FitErrors for ``task`` when the device counts prove it
        unschedulable everywhere — None sends the caller to the host
        sweep."""
        found = self._proven(task)
        if found is None:
            return None
        result, i = found
        hist = result.histogram(i)
        self.explained[task.uid] = hist
        for reason in hist:
            metrics.register_unschedulable_reason(reason)
        return result.fit_errors(i)

    def node_reasons(self, uid: str) -> Dict[str, str]:
        """node name → failing reason for an explained task, from the
        planes of whichever pack holds it (empty without planes)."""
        _snap, result, i = self._row(uid)
        return result.node_reasons(i, self.node_names)

    def summary(self) -> Dict[str, int]:
        """Aggregate reason → node-count histogram over the explained
        tasks (the per-cycle trace journal record)."""
        agg: Dict[str, int] = {}
        for hist in self.explained.values():
            for reason, count in hist.items():
                agg[reason] = agg.get(reason, 0) + count
        return agg


def compute_task_order(ssn: Session) -> List[TaskInfo]:
    """Phase 1: the task processing order.

    Sessions whose ordering semantics match the standard plugin shape
    take the episode-level simulation (actions/fast_order.py, ~10x
    cheaper than the replay at 50k tasks); anything else falls back to
    the exact replay below.  tests/test_fast_order.py pins the two
    orders equal."""
    from volcano_tpu.actions.fast_order import try_compute_task_order

    fast = try_compute_task_order(ssn)
    if fast is not None:
        return fast
    return compute_task_order_replay(ssn)


def compute_task_order_replay(ssn: Session) -> List[TaskInfo]:
    """Replay the loop assuming every task places, recording pop
    order; then unwind all accounting (reverse order, like
    Statement.Discard)."""
    order: List[TaskInfo] = []
    touched: List[Tuple[TaskInfo, TaskStatus]] = []

    def place_task(_ctx, task: TaskInfo, job) -> bool:
        order.append(task)
        touched.append((task, task.status))
        # the unwind restores statuses exactly but job.allocated float
        # lanes round-trip through add/sub — the clone is dirty for the
        # snapshot reuse pool
        ssn.touched_jobs.add(task.job)
        job.update_task_status(task, TaskStatus.Allocated)
        ssn._fire_allocate(task)
        return True

    drive_allocate_loop(
        ssn,
        begin_job=lambda job: None,
        place_task=place_task,
        end_job=lambda ctx, job: None,
    )

    for task, prior_status in reversed(touched):
        job = ssn.jobs[task.job]
        job.update_task_status(task, prior_status)
        ssn._fire_deallocate(task)

    return order


def _spread_cut(snap) -> int:
    """Ordered rows whose proposals stand: all of them, or those before
    the first row with a hard topology spread constraint or a required
    inter-pod term the pack could not encode.  The kernel placed that
    row with no mask, and the counts it moved there steer every later
    proposal, so from it on each task takes the host chooser — the host
    action's own choices."""
    host = snap.task_spread_host
    if host is None or not host[: snap.n_tasks].any():
        return snap.n_tasks
    return int(np.argmax(host[: snap.n_tasks]))


def _order_cut(ssn: Session, ordered, outcome) -> Tuple[int, bool]:
    """(rows of ORDER the real loop runs as the kernel did, whether
    nothing past them needs the host).  ORDER simulated every task
    placing; a discarded or ended gang leaves its queue's share below
    that.  In a single-queue session the share's one effect is
    ``overused``, which ends the loop.  Where ORDER ``lists_all``, the
    rows from the first episode the real queue is overused at are never
    reached.  Otherwise ORDER stopped where its share ran out; the real
    share, never above it, cannot stop the loop sooner, and the loop
    goes on past ORDER's last row, to the host, unless everything
    committed as simulated.  Elsewhere shares decide between queues, so
    every row after the first gang that did not fully place takes the
    host chooser."""
    from volcano_tpu.actions.fast_order import TaskOrder, order_cut

    n = len(ordered)
    committed = outcome.assignment[:n] >= 0
    if isinstance(ordered, TaskOrder) and ordered.single_queue:
        if ordered.lists_all:
            return order_cut(ssn, ordered, committed), True
        return n, bool(committed.all())
    short = np.nonzero(~committed)[0]
    if not short.size:
        return n, True
    i = int(short[0])
    job = ordered[i].job
    while i < n and ordered[i].job == job:
        i += 1
    metrics.register_order_cut(n - i)
    return i, False


#: phase timings of the most recent execute() — read by bench.py right
#: after the call, same single-threaded discipline as dispatch state
last_phase_stats: Dict[str, float] = {}

class _Decided(NamedTuple):
    """The kernel's decisions on ORDER's rows: the proposals it made,
    its ScanOutcome, the rows the real loop runs as the kernel did
    (``cut``), whether nothing past them needs the host, and the reason
    counts of the rows that fit no node (taken at each row's step of the
    scan when ``at_step``)."""

    proposals: Dict[str, str]
    outcome: object
    cut: int
    complete: bool
    counts: Optional[np.ndarray]
    at_step: bool


#: the gang outcome counts APPLY reports every cycle, both paths summed
_GANG_KEYS = ("gang_committed", "gang_discarded", "gang_outcomes_device",
              "gang_outcomes_host")


class JaxAllocateAction(Action):
    def __init__(
        self,
        weights=None,
        explain: Optional[bool] = None,
        explain_planes: Optional[bool] = None,
    ):
        from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS

        self.weights = weights or DEFAULT_WEIGHTS
        #: what the last _kernel_proposals decided, beside its proposals
        self._decided: Optional[_Decided] = None
        from volcano_tpu.ops.explain import explain_enabled

        #: device-derived unschedulability explanations (ops/explain).
        #: On by default: the reason-count reduction only runs when a
        #: task went unplaced, so fully-placed warm cycles pay nothing.
        #: VTPU_NO_EXPLAIN=1 (or explain=False) turns it off.
        self.explain = explain_enabled() if explain is None else explain
        #: additionally retain the per-pair [T, N] reason plane for the
        #: /explain endpoint's node-level attribution.  Off by default —
        #: the retention transfer scales with T×N, the counts with T×5.
        self.explain_planes = (
            bool(os.environ.get("VTPU_EXPLAIN_PLANES"))
            if explain_planes is None
            else explain_planes
        )

    def name(self) -> str:
        return "jax-allocate"

    # ---- phase 2 ----

    def _kernel_proposals(
        self,
        ssn: Session,
        ordered_tasks: List[TaskInfo],
        nodes: Optional[List] = None,
        pack_cache=None,
    ) -> Tuple[Dict[str, str], Optional[object]]:
        """Pack + run the device kernel; ({task uid → node name}, snap).

        Tasks flagged ``task_has_preferences`` are excluded — the kernel
        has no lanes for preferred (anti-)affinity scores, so those route
        to the host chooser.  Relational predicates the packer could not
        encode (needs_host_validation) are safe regardless: phase 3
        validates every proposal against the full host predicate set."""
        from volcano_tpu.ops import dispatch
        from volcano_tpu.ops.kernels import NOT_REACHED
        from volcano_tpu.ops.packing import pack_session

        jobs = {}
        for t in ordered_tasks:
            job = ssn.jobs.get(t.job)
            if job is not None and job.uid not in jobs:
                jobs[job.uid] = job
        if nodes is None:
            nodes = [ssn.nodes[name] for name in sorted(ssn.nodes)]
        if not nodes or not ordered_tasks:
            return {}, None

        enforce = "predicates" in ssn.predicate_fns
        with trace.span("vtpu/allocate.pack", "kernel", label="kernel:pack") as sp:
            if pack_cache is not None and ssn.pack_epoch is not None:
                # warm path: delta-assemble from the cycle-persistent cache
                snap = pack_cache.pack(
                    ordered_tasks,
                    list(jobs.values()),
                    nodes,
                    ssn.pack_epoch,
                    enforce_pod_count=enforce,
                )
                last_phase_stats.update(pack_cache.last_stats)
                if getattr(ssn.cache, "in_micro_cycle", False):
                    # a micro-triggered cycle that still had to cold-rebuild
                    # (registry overflow, axis change, …) paid full-cycle
                    # cost — attribute the cause so the SLO harness can see
                    # why the incremental path was unsound
                    cause = pack_cache.last_stats.get("cold_cause")
                    if cause is not None:
                        metrics.register_full_cycle_fallback(cause)
            else:
                snap = pack_session(
                    ordered_tasks,
                    list(jobs.values()),
                    nodes,
                    enforce_pod_count=enforce,
                )
        last_phase_stats["pack_ms"] = sp.seconds * 1e3
        metrics.update_kernel_duration("pack", sp.seconds)
        if snap.spread_stats is not None:
            # spread_rows_device / _host, spread_classes, spread_pack_ms
            last_phase_stats.update(snap.spread_stats)
            metrics.register_spread_rows(
                snap.spread_stats["spread_rows_device"],
                snap.spread_stats["spread_rows_host"],
            )
        if snap.affinity_stats is not None:
            # affinity_rows_device / _host, affinity_classes, affinity_pack_ms
            last_phase_stats.update(snap.affinity_stats)
            metrics.register_affinity_rows(
                snap.affinity_stats["affinity_rows_device"],
                snap.affinity_stats["affinity_rows_host"],
            )

        if snap.cache_key is not None:
            # attach the device-resident mirror: only dirty rows travel
            try:
                from volcano_tpu.ops.device_stage import get_stager

                snap.device_planes = get_stager(snap.cache_key).stage(snap)
            except Exception as e:  # noqa: BLE001 — numpy path still valid
                # counted with the other demotions, so a run that must
                # stay on the device (chip_smoke.py) can see it
                metrics.register_executor_fallback("device-stage", "host", "error")
                log.error("device staging failed (%s); numpy planes", e)

        # explain=True makes the dispatch reduce the reason-count matrix
        # alongside the assignment when tasks went unplaced (lazy — a
        # fully-placed session computes nothing extra).
        from volcano_tpu.faults.watchdog import CycleDeadlineExceeded

        try:
            with trace.span(
                "vtpu/allocate.dispatch", "kernel", label="kernel:execute"
            ) as sp:
                outcome = dispatch.run_allocate(
                    snap, weights=self.weights, explain=self.explain)
        except CycleDeadlineExceeded as e:
            # cycle watchdog: the device phase overran its budget and
            # was abandoned.  Nothing session-side has mutated (the
            # device phase is pure), so the cycle completes on the host
            # scoring path: no proposals → every task takes host_choose
            # in _apply.  The demotion is journaled and counted.
            log.error("device phase abandoned: %s", e)
            metrics.register_executor_fallback("device", "host", "deadline")
            rec = ssn._trace
            if rec.enabled:
                rec.event("watchdog:device-phase-abandoned", "fault",
                          error=str(e))
            return {}, snap
        metrics.update_kernel_duration("execute", sp.seconds)
        # reason counts of the rows that fit no node, and whether they
        # were taken at each row's step of the scan
        counts = dispatch.last_explain_counts()
        at_step = dispatch.last_explain_at_step()
        # the shape the kernel ran at, for work counts per dispatch
        last_phase_stats.update(
            kernel_tasks=snap.n_tasks,
            kernel_nodes=snap.n_nodes,
            kernel_resources=int(snap.task_resreq.shape[1]),
            kernel_max_gang=int(max(snap.gang_need().max(initial=0), 0)),
        )

        rec = ssn._trace
        if rec.enabled and rec.should_capture():
            # sampled journal capture: the packed session + the kernel's
            # assignment + the kernel parameters, the replayable tuple
            # trace.replay.verify diffs.  The label is the executor that
            # actually produced the assignment (including mid-session
            # degradations), translated to replay vocabulary.
            from volcano_tpu.trace.replay import replay_executor_name

            rec.capture(
                snap,
                outcome.assignment,
                executor=replay_executor_name(dispatch.last_executor()),
                weights=self.weights,
            )

        # where the real loop leaves ORDER's rows, now that the kernel
        # decided which gangs commit
        cut, complete = _order_cut(ssn, ordered_tasks, outcome)
        spread_cut = _spread_cut(snap)
        complete = complete and spread_cut >= cut
        placed = outcome.placed
        rolled = (placed >= 0) & (outcome.assignment < 0)
        rollback = int(rolled[:cut].sum())
        last_phase_stats["gang_rollback_tasks"] = rollback
        metrics.register_gang_rollback(rollback)
        # the scan's gang work, for work counts per dispatch
        rows_job = snap.task_job[: snap.n_tasks]
        last_phase_stats.update(
            kernel_skipped_rows=(last_phase_stats["kernel_tasks"]
                                 - int((placed != NOT_REACHED).sum())),
            kernel_failed_rows=int((placed == -1).sum()),
            kernel_gang_runs=int(np.unique(rows_job[snap.gang_need() >= 2]).size),
            kernel_rollback_runs=int(np.unique(rows_job[rolled]).size),
        )
        # every row the scan placed is proposed, a discarded gang's too:
        # the Statement loop then tries them where the kernel did
        proposals = {}
        for i, task in enumerate(ordered_tasks[: min(cut, spread_cut)]):
            if placed[i] >= 0 and not snap.task_has_preferences[i]:
                proposals[task.uid] = nodes[placed[i]].name
        self._decided = _Decided(proposals, outcome, cut, complete, counts, at_step)
        return proposals, snap

    # ---- phase 3 ----

    def execute(self, ssn: Session) -> None:
        last_phase_stats.clear()
        epoch = ssn.pack_epoch
        pc = getattr(ssn.cache, "pack_cache", None) if epoch is not None else None
        nodes = [ssn.nodes[name] for name in sorted(ssn.nodes)]

        # Warm cycles stage the dynamic node planes BEFORE the ORDER
        # phase: node rows don't depend on task order, so the host→device
        # transfer runs concurrently with the pure-host ORDER replay and
        # the remaining relay is only the (delta-sized) task planes.
        prestaged = False
        if pc is not None and nodes:
            with trace.span("vtpu/allocate.prestage", "kernel") as sp:
                pending = pc.begin_nodes(
                    nodes, epoch, "predicates" in ssn.predicate_fns
                )
                if pending is not None:
                    try:
                        from volcano_tpu.ops.device_stage import get_stager

                        get_stager(pc.key).prestage(
                            pending["planes"], pending["dirty_pos"], pc.rev + 1
                        )
                        prestaged = True
                    except Exception as e:  # noqa: BLE001 — stage() recovers
                        log.error("node-plane prestage failed: %s", e)
            last_phase_stats["node_prepack_ms"] = sp.seconds * 1e3

        with trace.span(
            "vtpu/allocate.order", "action", label="jax-allocate:order"
        ) as sp:
            ordered = compute_task_order(ssn)
        last_phase_stats["order_ms"] = sp.seconds * 1e3
        if prestaged:
            # the window the staged transfer had to overlap host work
            last_phase_stats["relay_overlap_ms"] = sp.seconds * 1e3
        if not ordered:
            if self.explain:
                # nothing pending → nothing to explain; clear the
                # surface so /explain never serves a previous cycle
                self._publish_explain(ssn, None)
            return
        self._decided = None
        proposals, snap = self._kernel_proposals(ssn, ordered, nodes, pc)
        # the kernel's whole outcome stands beside proposals it made itself
        decided = self._decided
        self._decided = None
        if decided is not None and decided.proposals is not proposals:
            decided = None

        # Reason counts the dispatch produced for unplaced tasks — the
        # device-derived "why pending" source (ops/explain).
        explain_ctx = None
        if self.explain and snap is not None:
            from volcano_tpu.ops import dispatch
            from volcano_tpu.ops import explain as _explain

            counts = dispatch.last_explain_counts()
            at_step = dispatch.last_explain_at_step()
            if decided is not None:
                counts, at_step = decided.counts, decided.at_step
            if counts is not None:
                planes = None
                if self.explain_planes:
                    # node-level attribution for the /explain surface,
                    # over the rows that recorded any infeasibility
                    import numpy as _np

                    planes = _explain.run_explain(
                        snap, retain_planes=True,
                        task_rows=_np.nonzero(counts.sum(axis=1) > 0)[0],
                    ).reasons
                explain_ctx = _ExplainContext(
                    ssn, snap, counts, ordered, nodes, planes=planes,
                    at_step=at_step,
                )
                last_phase_stats["explain_ms"] = dispatch.last_explain_ms()

        try:
            with trace.span("vtpu/allocate.apply", "action"):
                self._apply(ssn, ordered, proposals, snap, explain_ctx, decided)
        finally:
            if self.explain:
                # also clears: a cycle that explained nothing (all
                # placed, gate closed, or no packed session) must not
                # leave the /explain surface serving a previous cycle's
                # explanation as current
                self._publish_explain(ssn, explain_ctx)

    def _apply(self, ssn, ordered, proposals, snap, explain_ctx,
               decided=None) -> None:
        """APPLY, counting the path that resolves each task
        (``metrics.APPLY_PATHS``) into the Prometheus counters and
        ``last_phase_stats`` (``apply_<path>``, ``apply_rejected``,
        ``host_sweep_nodes``, ``host_sweep_ms``, the gang outcomes
        ``gang_committed``, ``gang_discarded``, ``gang_outcomes_device``
        and ``_host``, and the tail explanation's ``explain_tail_tasks``
        and ``explain_tail_ms``)."""
        paths = dict.fromkeys(metrics.APPLY_PATHS, 0)
        tally = {"apply_rejected": 0, "host_sweep_nodes": 0, "host_sweep_ms": 0.0}
        for key in _GANG_KEYS:
            last_phase_stats.setdefault(key, 0)
        try:
            self._apply_paths(ssn, ordered, proposals, snap, explain_ctx, paths,
                              tally, decided)
        finally:
            metrics.register_allocate_apply(paths, tally["apply_rejected"])
            last_phase_stats.update({f"apply_{p}": n for p, n in paths.items()})
            for key in _GANG_KEYS:
                last_phase_stats[key] += tally.pop(key, 0)
            last_phase_stats.update(tally)
            last_phase_stats["explain_tail_tasks"] = explain_ctx.tail_tasks if explain_ctx else 0
            last_phase_stats["explain_tail_ms"] = explain_ctx.tail_ms if explain_ctx else 0.0

    def _apply_paths(self, ssn, ordered, proposals, snap, explain_ctx,
                     paths, tally, decided=None) -> None:
        # Device-decided exact sessions apply in bulk (actions/fast_apply);
        # anything outside that envelope runs the loop below.
        bulk_jobs = frozenset()
        if snap is not None:
            from volcano_tpu.actions import fast_apply
            from volcano_tpu.ops.kernels import ScanOutcome

            rows, outcome, complete = ordered, None, False
            if decided is not None:
                cut, complete = decided.cut, decided.complete
                rows = ordered[:cut]
                outcome = ScanOutcome(decided.outcome.assignment[:cut],
                                      decided.outcome.placed[:cut])
            with trace.span("vtpu/allocate.fast_apply", "action") as sp:
                done = fast_apply.try_fast_apply(
                    ssn, rows, proposals, snap, outcome=outcome,
                    explain=explain_ctx, complete=complete,
                )
                sp.set(tasks=last_phase_stats.get("fast_apply_tasks", 0))
            paths["fast_apply"] = last_phase_stats.pop("fast_apply_tasks", 0)
            paths["explained"] += last_phase_stats.pop("fast_apply_explained", 0)
            if done:
                return
            bulk_jobs = fast_apply.last_decided_jobs
            if explain_ctx is not None and paths["fast_apply"]:
                # the bulk commit ran ahead of ORDER
                explain_ctx.diverged = True

        predicate_fn = make_predicate_fn(ssn)
        host_choose = host_node_chooser(ssn, tally)

        def choose_node(task: TaskInfo, job):
            """Kernel proposal with O(1) validation; host path fallback."""
            name = proposals.get(task.uid)
            if name is not None:
                node = ssn.nodes.get(name)
                if node is not None:
                    try:
                        predicate_fn(task, node)
                        paths["proposal"] += 1
                        return node
                    except FitError:
                        pass  # capacity/relational race → host fallback
                tally["apply_rejected"] += 1
                if explain_ctx is not None:
                    explain_ctx.diverged = True
            if explain_ctx is not None:
                fe = explain_ctx.try_explain(task)
                if fe is not None:
                    # device-proven unschedulable: record the synthesized
                    # FitErrors (the same writeback the host sweep feeds)
                    # and skip the O(N) host predicate scan entirely
                    job.nodes_fit_errors[task.uid] = fe
                    paths["explained"] += 1
                    return None
            paths["host_sweep"] += 1
            node = host_choose(task, job)
            if node is not None and explain_ctx is not None:
                explain_ctx.diverged = True
            return node

        drive_allocate_loop(
            ssn,
            begin_job=lambda job: ssn.statement(),
            place_task=make_place_task(ssn, choose_node),
            end_job=gang_end_job(ssn, bulk_jobs, tally),
        )

    def _publish_explain(
        self, ssn: Session, ctx: Optional[_ExplainContext]
    ) -> None:
        """Per-cycle reason summary → trace journal + /explain surface.
        A ``None`` context or an empty explained set CLEARS the surface
        — it reflects the most recent cycle, never a stale one."""
        from volcano_tpu.ops.explain import set_last_explain

        if ctx is None or not ctx.explained:
            set_last_explain(None)
            return
        summary = ctx.summary()
        rec = ssn._trace
        if rec.enabled:
            rec.event(
                "explain-summary", "action",
                tasks=len(ctx.explained), reasons=summary,
            )
        from volcano_tpu import trace as _trace

        detail = {}
        if ctx.retain_planes:
            detail = {uid: ctx.node_reasons(uid) for uid in ctx.explained}
        set_last_explain(
            {
                "cycle": _trace.current_cycle(),
                "n_nodes": ctx.result.n_nodes,
                "tasks": {
                    uid: {
                        "reasons": hist,
                        **({"nodes": detail[uid]} if uid in detail else {}),
                    }
                    for uid, hist in ctx.explained.items()
                },
                "summary": summary,
            }
        )


def new() -> JaxAllocateAction:
    return JaxAllocateAction()
