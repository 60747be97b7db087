"""Bulk APPLY phase for jax-allocate: the kernel's decisions applied in
one pass, bypassing the per-task statement/heap/event machinery.

The slow path (drive_allocate_loop + Statement) costs ~90µs/task of pure
Python at the 50k headline shape — 20x the whole device-kernel budget —
yet when the packer encoded every predicate exactly, the loop is
mechanical: the kernel already ran Volcano's per-job commit/discard
(ops/kernels.py), so each committed gang's tasks land on their proposed
nodes and commit, and each discarded gang leaves what Statement.Discard
leaves — its placements allocated and undone, and the fit errors of the
task it failed at, which the kernel counted at that step.  This module
reproduces that exact final state with one pass over the ordered tasks
plus per-object bulk writebacks:

  * float accounting (job.allocated/total_request, node.idle/used, drf /
    proportion / namespace shares) applies the same per-lane operation
    sequences the slow path would (grouped by owning object, which
    preserves IEEE bit-identity — lanes of different objects never mix;
    a discarded gang's placements are added and then taken off again,
    in reverse, where its episode ends)
  * dict state (job.tasks order, task_status_index buckets, node.tasks
    clones, the two PodLister views) is rebuilt with the same insertion
    orders
  * cache side effects flow through SchedulerCache.bind_batch — the same
    internal mutations as per-task bind() under one mutex hold, with the
    binder/event effects run in task order

A session is applied whole when every job of ORDER is device-decided
and nothing outside ORDER needs the host.  Otherwise the commit is
PARTIAL at job granularity: jobs whose every ordered task carries a
clean validated-exact proposal the kernel committed bulk-commit; jobs
with a preference task, a PVC-backed pod, a missing proposal or a task
that did not commit stay on the slow Statement loop, which runs only
over that residual (committed jobs drain to empty pending queues).
Session-level envelope violations (unknown plugins, inexact packing,
host-validation needs) refuse wholesale with nothing committed.

Equivalence scope: tests/test_fast_apply.py pins the resulting session
+ cache state equal to the slow path's, field by field, for sessions
applied whole, discarded gangs included.  For PARTIAL sessions the bulk
subset commits before the residual loop runs, so when a residual job
sorts BEFORE a clean job in the drive order AND the two contend for
capacity, placements can differ from the pure slow path's interleaving
— the same capacity-race envelope the kernel-proposal fallback already
documents (jax_allocate.py): every placement is still individually
valid, kernel resource accounting is conservative (it reserved for the
residual tasks too), and the partial-path tests pin exact state
equality for the residual-sorts-last case.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from volcano_tpu import trace
from volcano_tpu.api import TaskInfo, TaskStatus
from volcano_tpu.api.job_info import _READY_STATUSES
from volcano_tpu.framework.session import Session
from volcano_tpu.metrics import metrics

#: plugins whose event handlers / state this bulk path models exactly
_KNOWN_PLUGINS = frozenset(
    (
        "priority",
        "gang",
        "conformance",
        "drf",
        "proportion",
        "predicates",
        "nodeorder",
        "binpack",
    )
)

#: plugins that register an allocate/deallocate EventHandler
_HANDLER_PLUGINS = ("drf", "proportion", "predicates", "nodeorder")


class _LaneAcc:
    """Float lanes (cpu, memory, scalars) mutated with the exact op
    sequence the slow path would apply to the owning Resource object."""

    __slots__ = ("cpu", "mem", "scalars")

    def __init__(self, res):
        self.cpu = res.milli_cpu
        self.mem = res.memory
        self.scalars = dict(res.scalars) if res.scalars else {}

    def store(self, res) -> None:
        res.milli_cpu = self.cpu
        res.memory = self.mem
        if self.scalars or res.scalars:
            res.scalars = self.scalars


def _acc_alloc(a0: _LaneAcc, a1: _LaneAcc, rr) -> None:
    """Statement.allocate's job-lane ops: allocated +r; total -r,+r
    (update_task_status Pending→Allocated = delete_task_info +
    add_task_info)."""
    a0.cpu += rr.milli_cpu
    a0.mem += rr.memory
    a1.cpu = (a1.cpu - rr.milli_cpu) + rr.milli_cpu
    a1.mem = (a1.mem - rr.memory) + rr.memory
    if rr.scalars:
        _seq_add_scalars(a0, rr.scalars, (1,))
        _seq_add_scalars(a1, rr.scalars, (-1, 1))


def _acc_commit(a0: _LaneAcc, a1: _LaneAcc, rr) -> None:
    """Commit's job-lane ops: allocated -r,+r; total -r,+r
    (update_task_status Allocated→Binding — both allocated statuses)."""
    a0.cpu = (a0.cpu - rr.milli_cpu) + rr.milli_cpu
    a0.mem = (a0.mem - rr.memory) + rr.memory
    a1.cpu = (a1.cpu - rr.milli_cpu) + rr.milli_cpu
    a1.mem = (a1.mem - rr.memory) + rr.memory
    if rr.scalars:
        _seq_add_scalars(a0, rr.scalars, (-1, 1))
        _seq_add_scalars(a1, rr.scalars, (-1, 1))


def _seq_add_scalars(acc: _LaneAcc, scalars, pattern) -> None:
    """Apply +v/-v in ``pattern`` order per scalar lane (float
    non-associativity means x+v-v+v != x+v in general — the sequence must
    match the slow path's)."""
    sc = acc.scalars
    for name, v in scalars.items():
        x = sc.get(name, 0.0)
        for sign in pattern:
            x = x + v if sign > 0 else x - v
        sc[name] = x


#: the jobs the last try_fast_apply applied (their gang decisions made
#: on the device) — read by jax-allocate right after the call
last_decided_jobs: frozenset = frozenset()


def _lane_op(acc: _LaneAcc, rr, sign: int) -> None:
    """``acc += rr`` or ``acc -= rr``, lane by lane."""
    if sign > 0:
        acc.cpu += rr.milli_cpu
        acc.mem += rr.memory
    else:
        acc.cpu -= rr.milli_cpu
        acc.mem -= rr.memory
    if rr.scalars:
        _seq_add_scalars(acc, rr.scalars, (sign,))


def _row_ops(ordered, rows, placed_ok, rolled_back):
    """(task, host, +1 | -1) in the slow path's order over the decided
    rows: a placement where its row stands; a rolled-back gang's
    placements undone in reverse at its episode's end (Statement.Discard
    right after the episode's last row)."""
    ops = []
    undo = []
    prev_job = None
    for i in rows:
        t = ordered[i]
        if undo and t.job != prev_job:
            ops.extend(reversed(undo))
            undo = []
        prev_job = t.job
        host = placed_ok.get(i)
        if host is None:
            if undo:  # the failing row closes the episode
                ops.extend(reversed(undo))
                undo = []
            continue
        ops.append((t, host, 1))
        if i in rolled_back:
            undo.append((t, host, -1))
    ops.extend(reversed(undo))
    return ops


def try_fast_apply(
    ssn: Session,
    ordered: List[TaskInfo],
    proposals: Dict[str, str],
    snap,
    outcome=None,
    explain=None,
    complete: bool = False,
) -> bool:
    """Apply the kernel's decisions in bulk.

    With ``outcome`` (ops/kernels.ScanOutcome over ``ordered``), an
    ``explain`` context (``can_explain(task)``, ``record(task, job)``)
    and ``complete`` (no task outside ``ordered`` needs the host), a
    session whose every job is device-decided is applied whole: each
    committed gang's placements commit in bulk, each discarded gang is
    recorded as the Statement path leaves it — its failing task's fit
    errors, its placements allocated and undone (float lanes in the slow
    path's op order, task status and job dicts through the job's own
    methods, ``task.node_name`` left on the node it was tried on) — and
    the caller skips the Statement loop.  Returns True then.

    Otherwise the bulk commit is PARTIAL at job granularity, as before:
    jobs whose every ordered task carries a clean validated-exact
    proposal that the kernel committed bulk-commit; jobs with a
    preference task, a PVC-backed pod, a missing proposal or a task that
    did not commit stay on the slow Statement loop, which runs only over
    that residual (committed jobs drain to empty pending queues).
    Returns True only when every ordered task committed; session-level
    envelope violations (unknown plugins, inexact packing,
    host-validation needs) refuse wholesale with nothing committed."""
    if snap.needs_host_validation or not snap.memory_exact:
        return False
    if not set(ssn.plugins) <= _KNOWN_PLUGINS:
        return False
    expected_handlers = sum(1 for p in _HANDLER_PLUGINS if p in ssn.plugins)
    if len(ssn.event_handlers) != expected_handlers:
        return False
    ready_chain = [
        p.name
        for tier in ssn.tiers
        for p in tier.plugins
        if p.enabled_job_ready and p.name in ssn.job_ready_fns
    ]
    if not set(ready_chain) <= {"gang"}:
        return False
    cache = ssn.cache
    if not hasattr(cache, "bind_batch"):
        return False

    drf = ssn.plugins.get("drf")
    proportion = ssn.plugins.get("proportion")
    # weighted-namespace DRF mirrors the plugin's own enablement check
    ns_enabled = drf is not None and any(
        p.enabled_namespace_order
        for tier in ssn.tiers
        for p in tier.plugins
        if p.name == "drf"
    )
    # the PodLister views live only in handler closures; locate them so
    # the bulk path can update them without firing per-task events
    listers = _find_pod_listers(ssn)
    if listers is None:
        return False
    # A PRE-ASSIGNED pod's required anti-affinity binds every pending
    # task it matches (the symmetry rule): the pack encodes each such
    # term as a class, or marks the matching rows for the host, which
    # sets needs_host_validation and refused above.

    nodes_by_name = ssn.nodes
    gang_ready = bool(ready_chain)

    # ---- what the kernel did with each row: committed it, placed it
    # (kept or rolled back), or found it no node ----
    n = len(ordered)
    if outcome is not None:
        committed = outcome.assignment[:n] >= 0
        placed = outcome.placed[:n] >= 0
        no_node = outcome.placed[:n] == -1
    else:
        committed = placed = np.array([t.uid in proposals for t in ordered], dtype=bool)
        no_node = np.zeros(n, dtype=bool)
    # plain lists: the loops below read them a row at a time
    all_committed = bool(committed.all())
    committed, placed, no_node = committed.tolist(), placed.tolist(), no_node.tolist()
    has_pref = snap.task_has_preferences

    groups: Dict[str, List[int]] = {}
    for i, t in enumerate(ordered):
        groups.setdefault(t.job, []).append(i)

    def clean(i: int) -> bool:
        """Row ``i``'s proposal may stand in for the host's choice."""
        t = ordered[i]
        host = proposals.get(t.uid)
        if host is None or (i < len(has_pref) and has_pref[i]):
            return False
        node = nodes_by_name.get(host)
        if node is None or node.node is None:
            return False
        # PVC flows keep the slow path's volume logic
        return t.pod is None or not cache.task_claim_names(t)

    committed_jobs: set = set()
    decided_jobs: set = set()
    #: jobs whose gang the kernel discarded (short of minAvailable)
    discarded_jobs: set = set()
    for uid, rows in groups.items():
        job = ssn.jobs.get(uid)
        if job is None:
            continue
        if drf is not None and uid not in drf.job_attrs:
            continue
        if ns_enabled and any(
            ordered[i].namespace not in drf.namespace_opts for i in rows
        ):
            continue
        n_commit = sum(committed[i] for i in rows)
        placed_ok = all(clean(i) for i in rows if placed[i])
        need = job.min_available - job.ready_task_num() if gang_ready else 0
        if n_commit == len(rows) and placed_ok and n_commit >= need:
            # the slow path would gang-discard a job that cannot reach
            # min_available — such jobs stay off the bulk commit
            committed_jobs.add(uid)
            decided_jobs.add(uid)
            continue
        if (
            outcome is not None
            and placed_ok
            and (n_commit == 0 or n_commit >= need)
            and all(explain is not None and explain.can_explain(ordered[i])
                    for i in rows if no_node[i])
        ):
            decided_jobs.add(uid)
            if n_commit < need:
                discarded_jobs.add(uid)

    full = (
        complete and outcome is not None
        and len(decided_jobs) == len(groups)
    )
    global last_decided_jobs
    last_decided_jobs = frozenset()
    if not full:
        if outcome is not None and not all_committed:
            # a gang the kernel did not commit moves what the Statement
            # loop sees at every later job: it replays the whole ORDER
            return False
        decided_jobs = committed_jobs
        discarded_jobs = set()
    if not decided_jobs:
        return False
    last_decided_jobs = frozenset(decided_jobs)
    rows_decided = [i for i, t in enumerate(ordered) if t.job in decided_jobs]
    bulk = [ordered[i] for i in rows_decided if committed[i]]
    placed_rows = {i: proposals[ordered[i].uid] for i in rows_decided if placed[i]}
    rolled_back = {i for i in placed_rows if not committed[i]}
    bulk_uids = {t.uid for t in bulk}
    failed = [i for i in rows_decided if no_node[i]]
    ops = _row_ops(ordered, rows_decided, placed_rows, rolled_back)

    # ---- single pass over the placements, in the slow path's order ----
    job_accs: Dict[str, tuple] = {}
    job_ready0: Dict[str, int] = {}
    node_ops: Dict[str, list] = {}
    drf_accs: Dict[str, _LaneAcc] = {}
    ns_accs: Dict[str, _LaneAcc] = {}
    q_accs: Dict[str, _LaneAcc] = {}

    for t, host, sign in ops:
        rr = t.resreq
        job = ssn.jobs[t.job]
        rows = node_ops.get(host)
        if rows is None:
            rows = []
            node_ops[host] = rows
        rows.append((t, sign))

        if drf is not None:
            jacc = drf_accs.get(t.job)
            if jacc is None:
                jacc = _LaneAcc(drf.job_attrs[t.job].allocated)
                drf_accs[t.job] = jacc
            _lane_op(jacc, rr, sign)
            if ns_enabled:
                nacc = ns_accs.get(t.namespace)
                if nacc is None:
                    nacc = _LaneAcc(drf.namespace_opts[t.namespace].allocated)
                    ns_accs[t.namespace] = nacc
                _lane_op(nacc, rr, sign)
        if proportion is not None:
            qacc = q_accs.get(job.queue)
            if qacc is None:
                attr = proportion.queue_opts.get(job.queue)
                if attr is None:
                    continue
                qacc = _LaneAcc(attr.allocated)
                q_accs[job.queue] = qacc
            _lane_op(qacc, rr, sign)

    for t in bulk:
        job = ssn.jobs[t.job]
        acc = job_accs.get(job.uid)
        if acc is None:
            acc = (_LaneAcc(job.allocated), _LaneAcc(job.total_request), job, [])
            job_accs[job.uid] = acc
            job_ready0[job.uid] = job.ready_task_num()
        acc[3].append(t)

    # ---- mutate: everything above validated, nothing mutated yet ----
    # the session-side touched sets feed the cache's snapshot clone
    # pool: marked before the first write, so a raise below cannot hand
    # a written clone back
    if hasattr(ssn, "touched_jobs"):
        ssn.touched_jobs.update(decided_jobs)
        ssn.touched_nodes.update(node_ops)
        ssn.node_state_epoch += 1
    binding = TaskStatus.Binding
    for host, rows in node_ops.items():
        node = nodes_by_name[host]
        idle, used = _LaneAcc(node.idle), _LaneAcc(node.used)
        ntasks = node.tasks
        for t, sign in rows:
            _lane_op(idle, t.resreq, -sign)
            _lane_op(used, t.resreq, sign)
            if sign > 0 and t.uid in bulk_uids:
                t.volume_ready = True
                t.node_name = host
                ti = t.clone()
                ti.status = TaskStatus.Allocated
                ntasks[t.uid] = ti
        idle.store(node.idle)
        used.store(node.used)

    with trace.span("vtpu/allocate.gang_discard", "action",
                    discarded=len(discarded_jobs), rolled_back=len(rolled_back)):
        # a discarded gang's placements: allocated, then undone in
        # reverse (Statement.allocate / Statement.discard on the job's
        # own records)
        for i in sorted(rolled_back):
            t = ordered[i]
            t.volume_ready = True
            ssn.jobs[t.job].update_task_status(t, TaskStatus.Allocated)
            t.node_name = placed_rows[i]
        for i in sorted(rolled_back, reverse=True):
            t = ordered[i]
            ssn.jobs[t.job].update_task_status(t, TaskStatus.Pending)
        # the task each ended or discarded gang failed at: its fit
        # errors, from the scan's reason counts at that step
        for i in failed:
            t = ordered[i]
            explain.record(t, ssn.jobs[t.job])

    for alloc_acc, total_acc, job, tasks in job_accs.values():
        # job.allocated/total_request follow the slow path's EPISODE
        # structure: the first episode feeds until gang-ready (all its
        # Statement.allocate ops, then all its commit ops), later episodes
        # are one task each.  Per-lane op order must match for IEEE
        # bit-identity — per-task interleave rounds differently on lanes
        # with non-exact values.
        ready0 = job_ready0[job.uid]
        k1 = 1
        if gang_ready and ready0 < job.min_available:
            k1 = min(max(job.min_available - ready0, 1), len(tasks))
        first, rest = tasks[:k1], tasks[k1:]
        for t in first:  # episode-1 allocates
            _acc_alloc(alloc_acc, total_acc, t.resreq)
        for t in first:  # episode-1 commits
            _acc_commit(alloc_acc, total_acc, t.resreq)
        for t in rest:  # single-task episodes
            _acc_alloc(alloc_acc, total_acc, t.resreq)
            _acc_commit(alloc_acc, total_acc, t.resreq)
        alloc_acc.store(job.allocated)
        total_acc.store(job.total_request)
        jtasks = job.tasks
        pending = job.task_status_index.get(TaskStatus.Pending)
        bbucket = job.task_status_index.setdefault(binding, {})
        ready_gain = 0
        for t in tasks:
            jtasks.pop(t.uid, None)
            jtasks[t.uid] = t
            if pending is not None:
                pending.pop(t.uid, None)
            if t.status not in _READY_STATUSES:
                ready_gain += 1  # Pending → Binding enters the ready set
            t.status = binding
            bbucket[t.uid] = t
        job.ready_num += ready_gain
        if pending is not None and not pending:
            del job.task_status_index[TaskStatus.Pending]

    if drf is not None:
        for uid, jacc in drf_accs.items():
            attr = drf.job_attrs[uid]
            jacc.store(attr.allocated)
            drf._update_share(attr)
        for ns, nacc in ns_accs.items():
            opt = drf.namespace_opts[ns]
            nacc.store(opt.allocated)
            drf._update_share(opt)
    if proportion is not None:
        for q, qacc in q_accs.items():
            attr = proportion.queue_opts[q]
            qacc.store(attr.allocated)
            proportion._update_share(attr)

    for pl in listers:
        tn = pl._task_nodes
        for t in bulk:
            tn[t.uid] = t.node_name
        for i in rolled_back:  # the deallocate event's write
            tn[ordered[i].uid] = ""
        # Topology spread and inter-pod affinity counts, once a check
        # built them, move as the per-task allocate events would have
        # moved them (a rolled-back placement moves them back).
        if pl.spread.active:
            for t in bulk:
                pl.spread.update(t, t.node_name)
        if pl.affinity.tracking:
            for t in bulk:
                pl.affinity.update(t)

    import time

    t0 = time.perf_counter()
    counts = cache.bind_batch([(t, t.node_name) for t in bulk]) if bulk else None
    # what the scheduling thread actually paid for the commit: with the
    # pipelined plane this is the mutex-held state mutation plus the
    # queue handoff — the binder/bus round trips land on the bind
    # workers, overlapped with the next cycle
    from volcano_tpu.actions import jax_allocate as _ja

    _ja.last_phase_stats["commit_handoff_ms"] = (
        time.perf_counter() - t0
    ) * 1e3
    # the batch's binds and the distinct jobs and nodes it marked (a
    # cache stand-in that records binds without marking returns None)
    binds, jobs_marked, nodes_marked = counts or (0, 0, 0)
    _ja.last_phase_stats.update(commit_binds=binds, commit_jobs_marked=jobs_marked,
                                commit_nodes_marked=nodes_marked)
    _ja.last_phase_stats["fast_apply_tasks"] = len(bulk)
    _ja.last_phase_stats["fast_apply_explained"] = len(failed)
    n_disc = len(discarded_jobs)
    n_commit_jobs = len(decided_jobs) - n_disc
    metrics.register_gang_outcome("committed", "device", n_commit_jobs)
    metrics.register_gang_outcome("discarded", "device", n_disc)
    for key, n_jobs in (("gang_committed", n_commit_jobs), ("gang_discarded", n_disc),
                        ("gang_outcomes_device", len(decided_jobs))):
        _ja.last_phase_stats[key] = _ja.last_phase_stats.get(key, 0) + n_jobs
    # journal only after the batch landed — "bind" means an actual
    # cache bind, and bind_batch dispatches nothing when it raises
    if ssn._trace.enabled:
        for t in bulk:
            ssn._trace.decision("bind", t.uid, t.node_name)
    return full or len(bulk) == len(ordered)


def _find_pod_listers(ssn: Session):
    """The predicates/nodeorder PodListers live in handler closures; pull
    them out so the bulk path can update them without firing per-task
    events.  None when a closure doesn't look like a PodLister-backed
    handler (unknown handler shape — refuse)."""
    from volcano_tpu.plugins.util import PodLister

    listers = []
    for eh in ssn.event_handlers:
        fn = eh.allocate_func
        if fn is None:
            continue
        found = None
        closure = getattr(fn, "__closure__", None) or ()
        for cell in closure:
            try:
                if isinstance(cell.cell_contents, PodLister):
                    found = cell.cell_contents
                    break
            except ValueError:  # pragma: no cover - empty cell
                continue
        if found is not None:
            listers.append(found)
    expected = sum(1 for p in ("predicates", "nodeorder") if p in ssn.plugins)
    if len(listers) != expected:
        return None
    return listers
