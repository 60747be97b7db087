"""Packing + dense reference for the device preempt pass.

Tensorizes the in-queue preemption session (actions/preempt.py, mirroring
pkg/scheduler/actions/preempt/preempt.go:45-276) into flat arrays:

  * preemptor tasks, grouped per job in task-order (the statement scope);
  * victim candidates (Running tasks), statically sorted per node in
    the host's eviction order — inverse task order, i.e. lowest
    priority first, youngest (latest-created) first among equals;
  * job/queue tables carrying the gang/priority plugin state the
    preemptable intersection reads (ready count, waiting count,
    min_available, job priority, queue id);
  * a static processing schedule replaying the host action's control
    flow: per queue, starving jobs in job-order (phase 1, statement
    commit/discard per job), then the under-request sweep (phase 2,
    intra-job preemption, unconditional commit).

``preempt_dense`` is the numpy reference implementation of the exact
same semantics — the spec the Pallas kernel must match and the bridge
asserted against the host action in tests/test_preempt_kernel.py.

Key host facts the dense formulation relies on (verified against
api/node_info.py and the plugins):

  * evict (Running→Releasing) and pipeline (Pending→Pipelined) leave
    ``node.used`` untouched — only future_idle moves — so node scores
    for every preemptor can be computed at static session state;
  * gang's preemptable is a per-job boolean (min_avail <= ready-1 or
    min_avail == 1), not an order-dependent countdown (gang.go:75-94);
  * priority's preemptable admits strictly-lower-priority jobs;
  * the host tries candidate nodes in descending score order with ties
    in name order, and the first node passing victim validation wins —
    identical to a masked argmax with lowest-index tie-break.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

from volcano_tpu.api import TaskStatus
from volcano_tpu.apis import scheduling
from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS, node_scores, ScoreWeights
from volcano_tpu.ops.packing import pack_session, PackedSnapshot


@functools.lru_cache(maxsize=1)
def _scores_jit():
    """Jitted node_scores (weights static), constructed once on the
    first dense replay — the jit wrapper itself is cheap, but building
    it at module import would run before any caller had a chance to
    configure jax platforms."""
    import jax

    return jax.jit(node_scores, static_argnames=("weights",))


@dataclass
class PreemptPacked:
    """Dense preempt-session state.  ``base`` holds the preemptor tasks
    (as the packed task axis) and all node arrays."""

    base: PackedSnapshot = None

    # future_idle at session open, aligned with base.node_* rows
    node_fi0: np.ndarray = None  # [N_pad, R]

    # victims sorted per node in eviction order (see module doc)
    n_victims: int = 0
    vic_resreq: np.ndarray = None  # [V, R]
    vic_node: np.ndarray = None  # [V] i32
    vic_job: np.ndarray = None  # [V] i32 → job table row
    vic_uids: List[str] = field(default_factory=list)
    vic_names: List[str] = field(default_factory=list)  # "ns/name"

    # job table (ALL session jobs, row 0..J-1)
    n_jobs: int = 0
    job_prio: np.ndarray = None  # [J] i64
    job_min_avail: np.ndarray = None  # [J] i32
    job_ready0: np.ndarray = None  # [J] i32 — ready_task_num at open
    job_waiting0: np.ndarray = None  # [J] i32 — waiting_task_num at open
    job_queue: np.ndarray = None  # [J] i32 → queue index
    job_uids: List[str] = field(default_factory=list)

    # preemptor grouping: base tasks are laid out job-contiguously in
    # task-order; job_ptask_start/end give each job's slice
    job_ptask_start: np.ndarray = None  # [J] i32
    job_ptask_end: np.ndarray = None  # [J] i32

    # processing schedule: rows of (phase, job_row); phase 1 = statement
    # scope with commit/discard, phase 2 = under-request sweep
    schedule: np.ndarray = None  # [S, 2] i32

    ptask_uids: List[str] = field(default_factory=list)
    node_names: List[str] = field(default_factory=list)

    # enabled-preemptable tier flags (which filters the dense replay
    # applies); the classic {priority, gang, conformance} triple is the
    # only shape the Pallas kernel models — drf routes to dense
    use_prio: bool = True
    use_gang: bool = True
    use_conf: bool = True
    use_drf: bool = False

    # DRF-preemptable state (drf.go:120-221, non-namespace policy):
    # per-job allocated lanes + cluster total at session open, and each
    # victim's rank within its node's uid-sorted candidate list (the
    # order the per-node preemptable call subtracts in).  total_lanes
    # marks lanes present in total.resource_names() — the share max
    # iterates only those (a task-only scalar never contributes).
    job_alloc0: np.ndarray = None  # [J, R] f64
    total_res: np.ndarray = None  # [R] f64
    total_lanes: np.ndarray = None  # [R] bool
    vic_uid_pos: np.ndarray = None  # [V] i32
    #: False for conformance-critical victims packed ONLY so DRF's
    #: running subtraction sees them (the host's plugins each scan the
    #: FULL preemptees list; conformance removes critical tasks from the
    #: eviction intersection but not from DRF's share arithmetic)
    vic_evictable: np.ndarray = None  # [V] bool


def _cmp_from_less(less):
    def cmp(a, b):
        if less(a, b):
            return -1
        if less(b, a):
            return 1
        return 0

    return cmp


def _order_stable(items, less):
    """PriorityQueue pop order: less-fn sort, stable by insertion."""
    return sorted(items, key=functools.cmp_to_key(_cmp_from_less(less)))


def collect_preempt_work(ssn):
    """Replicates PreemptAction.execute's setup (preempt.go:45-84):
    queue discovery order, starving jobs per queue in job-order,
    per-job pending preemptors in task-order, the under-request list."""
    queues: Dict[str, object] = {}
    starving: Dict[str, List] = {}
    tasks: Dict[str, List] = {}
    under_request: List = []

    for job in sorted(ssn.jobs.values(), key=lambda j: j.uid):
        if (
            job.pod_group is not None
            and job.pod_group.status.phase == scheduling.POD_GROUP_PENDING
        ):
            continue
        vr = ssn.job_valid(job)
        if vr is not None and not vr.pass_:
            continue
        queue = ssn.queues.get(job.queue)
        if queue is None:
            continue
        queues.setdefault(queue.uid, queue)
        if job.task_status_index.get(TaskStatus.Pending) and not ssn.job_pipelined(job):
            starving.setdefault(queue.uid, []).append(job)
            under_request.append(job)
            tasks[job.uid] = _order_stable(
                sorted(
                    job.task_status_index[TaskStatus.Pending].values(),
                    key=lambda t: t.uid,
                ),
                lambda l, r: ssn.task_order_fn(l, r),
            )

    for quid in starving:
        starving[quid] = _order_stable(
            starving[quid], lambda l, r: ssn.job_order_fn(l, r)
        )
    return queues, starving, tasks, under_request


#: Preemptable plugins the dense formulation can express as filters.
#: DRF (non-namespace policy) is dense-only; the Pallas kernel models
#: the classic {priority, gang, conformance} triple.  Anything else in
#: the first enabled-preemptable tier would silently diverge — pack
#: refuses it instead.
_SUPPORTED_PREEMPTABLE = {"priority", "gang", "conformance", "drf"}


def _check_preemptable_tiers(ssn) -> dict:
    """Return the enabled-filter flags for the first tier with
    preemptable plugins; raise when that tier contains anything the
    dense formulation cannot express (ADVICE r2: fail loudly, not
    wrongly)."""
    for tier in ssn.tiers:
        enabled = {
            p.name
            for p in tier.plugins
            if getattr(p, "enabled_preemptable")
            and p.name in ssn.preemptable_fns
        }
        if enabled:
            if not enabled <= _SUPPORTED_PREEMPTABLE:
                raise ValueError(
                    "dense preempt formulation supports preemptable plugins "
                    f"{sorted(_SUPPORTED_PREEMPTABLE)}, session has "
                    f"{sorted(enabled)}"
                )
            if "drf" in enabled:
                drf = ssn.plugins.get("drf")
                if drf is None or not hasattr(drf, "job_attrs"):
                    raise ValueError("drf preemptable without plugin state")
                # the weighted-namespace policy (drf.go:127-175) only
                # bites when preemptor and preemptee namespaces differ —
                # single-namespace sessions reduce to the job-share
                # policy the dense replay models
                namespaces = {j.namespace for j in ssn.jobs.values()}
                if drf.namespace_opts and len(namespaces) > 1:
                    raise ValueError(
                        "weighted-namespace DRF preemption across "
                        "namespaces is host-only"
                    )
            return {
                "use_prio": "priority" in enabled,
                "use_gang": "gang" in enabled,
                "use_conf": "conformance" in enabled,
                "use_drf": "drf" in enabled,
            }
    raise ValueError("session has no enabled preemptable plugins")


def pack_preempt_session(ssn) -> PreemptPacked:
    """Session → PreemptPacked (order replay happens here, host-side)."""
    flags = _check_preemptable_tiers(ssn)
    queues, starving, ptasks_by_job, under_request = collect_preempt_work(ssn)

    # job table over ALL session jobs (victims may belong to any)
    jobs = sorted(ssn.jobs.values(), key=lambda j: j.uid)
    job_row = {j.uid: i for i, j in enumerate(jobs)}
    queue_row = {quid: i for i, quid in enumerate(queues)}

    # preemptor stream: starving jobs' pending tasks, job-contiguous;
    # order inside a job = task-order (the host pops them in this order
    # in both phases)
    ordered_ptasks: List = []
    job_start = np.zeros(len(jobs), dtype=np.int32)
    job_end = np.zeros(len(jobs), dtype=np.int32)
    for quid in queues:
        for job in starving.get(quid, []):
            job_start[job_row[job.uid]] = len(ordered_ptasks)
            ordered_ptasks.extend(ptasks_by_job[job.uid])
            job_end[job_row[job.uid]] = len(ordered_ptasks)

    nodes = [ssn.nodes[name] for name in sorted(ssn.nodes)]
    base = pack_session(
        ordered_ptasks,
        jobs,
        nodes,
        enforce_pod_count="predicates" in ssn.predicate_fns,
        # the kernel models no topology spread: such tasks set
        # needs_host_validation and the action runs on the host
        spread=False,
    )

    pk = PreemptPacked(base=base)
    pk.ptask_uids = list(base.task_uids)
    pk.node_names = list(base.node_names)
    R = base.task_resreq.shape[1]
    names = base.resource_names

    N_pad = base.node_idle.shape[0]
    pk.node_fi0 = np.zeros((N_pad, R), dtype=np.float32)
    from volcano_tpu.ops.packing import _res_vec

    node_row = {n.name: i for i, n in enumerate(nodes)}
    for i, n in enumerate(nodes):
        pk.node_fi0[i] = _res_vec(n.future_idle(), names, base)

    # victims: Running tasks per node, in the host's eviction order —
    # inverse task order (priority asc, creation/uid desc), stable over
    # the uid-sorted preemptee list (preempt.py victims_queue)
    from volcano_tpu.plugins.conformance import _is_critical

    # Frozen-order soundness guard (mirrors reclaim_pack): phase 1 pops
    # starving jobs from a LIVE PriorityQueue, so evicting a victim whose
    # job is ITSELF starving flips that job's DRF share / gang readiness
    # and can reorder it against other still-unprocessed starving jobs in
    # the same queue.  The pack-time frozen order cannot observe that —
    # refuse such sessions (host fallback).  With a single starving job
    # in the victim job's queue there is no order to disturb.
    starving_uids = {
        job.uid: quid for quid, jobs_ in starving.items() for job in jobs_
    }

    vics = []
    for n in nodes:
        all_vics = [
            t
            for t in sorted(n.tasks.values(), key=lambda t: t.uid)
            if t.status == TaskStatus.Running and t.job in ssn.jobs
        ]
        # rank within the node's uid-sorted candidate list — the order
        # the per-node preemptable call processes (DRF's running
        # subtraction depends on it, and counts CRITICAL tasks too)
        uid_pos = {t.uid: i for i, t in enumerate(all_vics)}
        # conformance veto applied at pack time: critical victims never
        # enter the evictable set (conformance.go:45-60).  DRF sessions
        # keep them as subtraction-only participants — the host's DRF
        # plugin scans the full preemptees list.
        node_vics = []
        for t in all_vics:
            critical = flags["use_conf"] and _is_critical(t)
            if critical and not flags["use_drf"]:
                continue
            node_vics.append((t, not critical))
        for t, _ in node_vics:
            vquid = starving_uids.get(t.job)
            if vquid is not None and len(starving.get(vquid, [])) >= 2:
                raise ValueError(
                    f"job {t.job} is both starving preemptor and victim "
                    "source in a multi-job queue; frozen order replay "
                    "would diverge"
                )
        node_vics = _order_stable(
            node_vics, lambda l, r: ssn.task_order_fn(r[0], l[0])
        )
        for t, evictable in node_vics:
            vics.append((node_row[n.name], t, uid_pos[t.uid], evictable))
    V = len(vics)
    pk.n_victims = V
    pk.vic_resreq = np.zeros((max(V, 1), R), dtype=np.float32)
    pk.vic_node = np.zeros(max(V, 1), dtype=np.int32)
    pk.vic_job = np.zeros(max(V, 1), dtype=np.int32)
    pk.vic_uid_pos = np.zeros(max(V, 1), dtype=np.int32)
    pk.vic_evictable = np.ones(max(V, 1), dtype=bool)
    for i, (nrow, t, upos, evictable) in enumerate(vics):
        pk.vic_resreq[i] = _res_vec(t.resreq, names, base)
        pk.vic_node[i] = nrow
        pk.vic_job[i] = job_row[t.job]
        pk.vic_uid_pos[i] = upos
        pk.vic_evictable[i] = evictable
        pk.vic_uids.append(t.uid)
        pk.vic_names.append(f"{t.namespace}/{t.name}")

    pk.use_prio = flags["use_prio"]
    pk.use_gang = flags["use_gang"]
    pk.use_conf = flags["use_conf"]
    pk.use_drf = flags["use_drf"]
    if flags["use_drf"]:
        drf = ssn.plugins["drf"]
        pk.job_alloc0 = np.zeros((len(jobs), R), dtype=np.float64)
        for i, j in enumerate(jobs):
            attr = drf.job_attrs.get(j.uid)
            if attr is not None:
                pk.job_alloc0[i] = _res_vec(attr.allocated, names, base)
        pk.total_res = _res_vec(drf.total_resource, names, base).astype(
            np.float64
        )
        pk.total_lanes = np.array(
            [True, True]
            + [name in drf.total_resource.scalars for name in names[2:]],
            dtype=bool,
        )

    J = len(jobs)
    pk.n_jobs = J
    pk.job_prio = np.array([j.priority for j in jobs], dtype=np.int64)
    pk.job_min_avail = np.array([j.min_available for j in jobs], dtype=np.int32)
    pk.job_ready0 = np.array([j.ready_task_num() for j in jobs], dtype=np.int32)
    pk.job_waiting0 = np.array([j.waiting_task_num() for j in jobs], dtype=np.int32)
    pk.job_queue = np.array(
        [queue_row.get(ssn.queues[j.queue].uid, -1) if j.queue in ssn.queues else -1
         for j in jobs],
        dtype=np.int32,
    )
    pk.job_uids = [j.uid for j in jobs]
    pk.job_ptask_start = job_start
    pk.job_ptask_end = job_end

    # schedule: phase 1 per queue over starving jobs; phase 2 per queue
    # over the full under-request list (preempt.go:96-112 iterates it
    # inside the queue loop)
    sched: List[Tuple[int, int]] = []
    for quid in queues:
        for job in starving.get(quid, []):
            sched.append((1, job_row[job.uid]))
        for job in under_request:
            sched.append((2, job_row[job.uid]))
    pk.schedule = (
        np.array(sched, dtype=np.int32) if sched else np.zeros((0, 2), np.int32)
    )
    return pk


# ---- dense reference implementation (numpy, exact) ----


def _fit(resreq: np.ndarray, avail: np.ndarray, tol: np.ndarray) -> bool:
    """Resource.less_equal on packed lanes (scalar lanes skip when the
    request is within tolerance)."""
    ok = resreq < avail + tol
    skip = np.zeros_like(ok)
    skip[2:] = resreq[2:] <= tol[2:]
    return bool(np.all(ok | skip))


def preempt_dense(
    pk: PreemptPacked, weights: ScoreWeights = DEFAULT_WEIGHTS
) -> Tuple[np.ndarray, np.ndarray]:
    """Dense replay → (evicted[V] bool, pipelined_node[P] i32, -1 = none).

    Mutable state: future_idle[N,R], victim alive[V], job ready/waiting.
    Node scores are computed per preemptor at static ``used`` (evict and
    pipeline never change it — see module docstring).
    """
    base = pk.base
    R = base.task_resreq.shape[1]
    N = base.n_nodes
    V = pk.n_victims
    P = base.n_tasks
    tol = base.tolerance

    # static per-(preemptor, node) feasibility: labels/taints/readiness
    # (the host preempt predicate set is ssn.PredicateFn alone — no
    # resource fit; the predicates plugin's pod-count limit is dynamic
    # and checked per attempt below)
    sel_ok = (
        (base.task_sel_bits[:P, None, :] & ~base.node_label_bits[None, :N, :]) == 0
    ).all(-1)
    tol_ok = (
        (base.node_taint_bits[None, :N, :] & ~base.task_tol_bits[:P, None, :]) == 0
    ).all(-1)
    static_feas = sel_ok & tol_ok & base.node_ok[None, :N]  # [P, N]

    # static scores at session-open used (f32, same math as the device).
    # ONE jitted call over the FULL (bucket-padded) snapshot arrays:
    # calling node_scores op-by-op compiled each jnp op per novel [P, N]
    # shape — ~30-50s of compile per unseen session shape through the
    # device link, vs ~0.5s for the whole warm dense replay.
    # pack_session already bucket-pads these arrays, so shapes recur
    # across sessions and the jit cache holds; padded rows are sliced
    # off (the score is elementwise per (task, node), so padding cannot
    # change the live region).
    scores = np.asarray(
        _scores_jit()(
            base.task_resreq, base.node_used, base.node_alloc,
            weights=weights,
        )
    )[:P, :N]

    fi = pk.node_fi0[:N].copy()
    alive = np.ones(V, dtype=bool)
    ready = pk.job_ready0.copy()
    waiting = pk.job_waiting0.copy()
    cursor = pk.job_ptask_start.copy()
    # DRF-preemptable live state: job allocated lanes move with every
    # evict (on_deallocate) / pipeline (on_allocate), drf.go:255-291
    job_alloc = pk.job_alloc0.copy() if pk.use_drf else None
    if pk.use_drf:
        drf_order = np.lexsort(
            (pk.vic_uid_pos[:V], pk.vic_job[:V], pk.vic_node[:V])
        )

    def _share_max(alloc_lanes: np.ndarray) -> np.ndarray:
        """share = max over total.resource_names() lanes of alloc/total
        with the reference's zero conventions (drf.go:299-311 via
        share_fn).  ``alloc_lanes`` is [..., R]."""
        total = pk.total_res
        with np.errstate(divide="ignore", invalid="ignore"):
            frac = np.where(
                total > 0,
                alloc_lanes / np.where(total > 0, total, 1.0),
                np.where(alloc_lanes > 0, 1.0, 0.0),
            )
        frac = np.where(pk.total_lanes, frac, -np.inf)
        # the reference's accumulator starts at 0.0 (`if s > res`), so
        # all-negative lane shares clamp to zero
        return np.maximum(frac.max(axis=-1), 0.0)
    # pod-count predicate state: pipeline adds the task to the node's
    # task map (count +1); evict only flips status, count unchanged
    ncount = base.node_task_count[:N].astype(np.int64)
    nmax = base.node_max_tasks[:N].astype(np.int64)

    evicted = np.zeros(V, dtype=bool)
    pipelined_node = np.full(P, -1, dtype=np.int32)

    def job_pipelined(j):
        return waiting[j] + ready[j] >= pk.job_min_avail[j]

    def try_preempt(p, pjob, same_job: bool) -> bool:
        """_preempt (preempt.go:181-259) for one preemptor task."""
        resreq = base.task_resreq[p]
        # victim eligibility at current state.  The preemptable
        # intersection (tier 1: priority ∩ gang ∩ conformance) applies in
        # both phases — priority admits strictly-lower-priority JOBS, so
        # the intra-job phase (same job ⇒ equal priority) can never evict
        # while the priority plugin is enabled, matching the host.
        if same_job:
            cand = alive & (pk.vic_job == pjob)
        else:
            cand = (
                alive
                & (pk.job_queue[pk.vic_job] == pk.job_queue[pjob])
                & (pk.vic_job != pjob)
            )
        elig = cand
        if pk.vic_evictable is not None:
            elig = elig & pk.vic_evictable
        if pk.use_prio:
            elig = elig & (pk.job_prio[pk.vic_job] < pk.job_prio[pjob])
        if pk.use_gang:
            # gang: victim's job must stay >= minAvailable
            elig = elig & (
                (pk.job_min_avail[pk.vic_job] <= ready[pk.vic_job] - 1)
                | (pk.job_min_avail[pk.vic_job] == 1)
            )
        if pk.use_drf and cand.any():
            # drf.go:180-199: ls = preemptor-job share with the task
            # added; per candidate IN THE PER-NODE UID ORDER, subtract
            # its resreq from a running same-(node, job) clone and admit
            # while ls < rs (or within SHARE_DELTA).  Candidates the
            # other plugins veto still participate in the subtraction —
            # the plugins each scan the full preemptees list.
            ls = float(
                _share_max(job_alloc[pjob] + resreq.astype(np.float64))
            )
            order = drf_order[cand[drf_order]]
            vals = pk.vic_resreq[order].astype(np.float64)
            cs = np.cumsum(vals, axis=0)
            vn2, vj2 = pk.vic_node[order], pk.vic_job[order]
            new_grp = np.concatenate(
                [[True], (vn2[1:] != vn2[:-1]) | (vj2[1:] != vj2[:-1])]
            )
            starts = np.flatnonzero(new_grp)
            run_start = np.repeat(
                starts, np.diff(np.append(starts, order.shape[0]))
            )
            offs = np.where(
                (run_start > 0)[:, None], cs[np.maximum(run_start - 1, 0)], 0.0
            )
            alloc_at = job_alloc[vj2] - (cs - offs)
            rs = _share_max(alloc_at)
            from volcano_tpu.plugins.drf import SHARE_DELTA

            drf_ok = np.zeros(V, dtype=bool)
            drf_ok[order] = (ls < rs) | (np.abs(ls - rs) <= SHARE_DELTA)
            elig = elig & drf_ok
        if V == 0 or not elig.any():
            return False

        # per-node victim sums + counts
        vsum = np.zeros((N, R), dtype=np.float64)
        np.add.at(vsum, pk.vic_node[elig], pk.vic_resreq[elig].astype(np.float64))
        vcnt = np.zeros(N, dtype=np.int64)
        np.add.at(vcnt, pk.vic_node[elig], 1)

        # validation per node (victims non-empty + resreq <= fi + victims)
        ok_lane = resreq[None, :] < fi + vsum.astype(np.float32) + tol[None, :]
        skip = np.zeros_like(ok_lane)
        skip[:, 2:] = (resreq[2:] <= tol[2:])[None, :]
        valid = (
            static_feas[p]
            & (ncount < nmax)
            & (vcnt > 0)
            & np.all(ok_lane | skip, axis=-1)
        )
        if not valid.any():
            return False

        # best validating node: max score, lowest index tie-break
        s = np.where(valid, scores[p], -np.inf)
        n_star = int(np.argmax(s))

        # evict in array order (node, prio, uid) until the task fits
        for v in np.nonzero(elig & (pk.vic_node == n_star))[0]:
            if _fit(resreq, fi[n_star], tol):
                break
            alive[v] = False
            evicted[v] = True
            fi[n_star] += pk.vic_resreq[v]
            ready[pk.vic_job[v]] -= 1
            if job_alloc is not None:  # drf on_deallocate
                job_alloc[pk.vic_job[v]] -= pk.vic_resreq[v].astype(np.float64)
        if not _fit(resreq, fi[n_star], tol):
            return False
        # pipeline
        fi[n_star] -= resreq
        ncount[n_star] += 1
        waiting[pjob] += 1
        if job_alloc is not None:  # drf on_allocate for the pipelined task
            job_alloc[pjob] += resreq.astype(np.float64)
        pipelined_node[p] = n_star
        return True

    for phase, j in pk.schedule:
        if phase == 1:
            # statement scope: commit iff the job ends pipelined.  Task
            # pops are NOT part of the statement — a discarded job's
            # popped tasks stay popped (the host PQ has no rollback), so
            # the cursor is excluded from the restore.
            saved = (
                fi.copy(), alive.copy(), ready.copy(), waiting.copy(),
                evicted.copy(), pipelined_node.copy(), ncount.copy(),
                job_alloc.copy() if job_alloc is not None else None,
            )
            while cursor[j] < pk.job_ptask_end[j]:
                if job_pipelined(j):
                    break
                p = cursor[j]
                cursor[j] += 1
                try_preempt(p, j, same_job=False)
            if not job_pipelined(j):
                fi, alive, ready, waiting, evicted, pipelined_node, ncount = (
                    saved[0], saved[1], saved[2], saved[3], saved[4], saved[5],
                    saved[6],
                )
                job_alloc = saved[7]
        else:
            # under-request sweep: unconditional commit, stop at first
            # unassigned task (preempt.go:96-112)
            while cursor[j] < pk.job_ptask_end[j]:
                p = cursor[j]
                cursor[j] += 1
                if not try_preempt(p, j, same_job=True):
                    break

    return evicted, pipelined_node
