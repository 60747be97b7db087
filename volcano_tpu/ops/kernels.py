"""Device session kernels: fused predicate-mask + score + greedy gang assign.

This replaces the reference's per-task 16-goroutine loop
(pkg/scheduler/util/scheduler_helper.go:64-211 driven from
pkg/scheduler/actions/allocate/allocate.go:191-224) with one jitted program:

  1. predicate mask — broadcast comparisons + bitset ops over [T, N]
     (replaces predicates.go:156-301 and the resource-fit closure
     allocate.go:100-107)
  2. score — closed-form binpack (binpack.go:248-259) +
     least-requested/balanced (vendored k8s priorities) arithmetic
  3. assignment — lax.scan over tasks in ORDER with node state
     (idle/used/count) carried, mirroring the sequential feedback of
     Statement.Allocate; deterministic first-index tie-break
  4. gang commit/discard in the same pass — a task that fits no node
     ends its job, and a job's run of rows that closes short of
     minAvailable is undone in the node state (Statement.Commit/Discard,
     statement.go:309-337, as drive_allocate_loop + gang_end_job apply
     them one job at a time).

Everything is static-shaped and branch-free inside jit; ties break to the
lowest node index so host and device paths agree bindings-exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from volcano_tpu.ops.packing import PackedSnapshot

MAX_PRIORITY = 10.0


class ScoreWeights(NamedTuple):
    """Plugin weights, matching binpack.go:94-151 + nodeorder.go:68-112.

    ``binpack_scalar`` defaults to 0 because the host plugin skips scalar
    resources absent from its ``binpack.resources`` weight map
    (binpack.go:224-228 falls through to continue on unknown resources).

    ``lr_int_exact`` switches least-requested to exact int32 division for
    sessions with nodes beyond the f32 floor-division exactness envelope
    (~2 TiB memory / 1600 cores); run_packed sets it from the packed data.
    """

    binpack_weight: float = 1.0
    binpack_cpu: float = 1.0
    binpack_memory: float = 1.0
    binpack_scalar: float = 0.0  # host default: unknown scalars skipped
    least_requested_weight: float = 1.0
    balanced_resource_weight: float = 1.0
    lr_int_exact: bool = False


DEFAULT_WEIGHTS = ScoreWeights()


def f32_lr_exact(snap: "PackedSnapshot") -> bool:
    """True when every node's cpu/memory capacity keeps the f32
    floor-division least-requested path exact (products stay below 2^24 —
    see least_requested_score).  The single copy of the envelope check,
    consulted by every kernel wrapper and the dispatcher."""
    return float(snap.node_alloc[:, :2].max(initial=0.0)) * MAX_PRIORITY < 2**24


# ---- predicate mask (vectorized over all T×N pairs) ----

def _component_planes(
    task_resreq: jnp.ndarray,  # [T, R]
    task_sel_bits: jnp.ndarray,  # [T, W] uint32
    task_tol_bits: jnp.ndarray,  # [T, W] uint32
    node_future_idle: jnp.ndarray,  # [N, R]
    node_label_bits: jnp.ndarray,  # [N, W]
    node_taint_bits: jnp.ndarray,  # [N, W]
    node_task_count: jnp.ndarray,  # [N] i32
    node_max_tasks: jnp.ndarray,  # [N] i32
    tolerance: jnp.ndarray,  # [R]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The four task-dependent predicate planes (fit, sel_ok, tol_ok,
    room), each [T, N] bool — the single copy shared by the AND-ing hot
    mask (predicate_mask) and the explain reduction (explain_counts),
    so the explanation can never disagree with the decision."""
    # resreq <= future_idle with per-lane tolerance margin.  The
    # sub-tolerance skip applies to scalar lanes only — host LessEqual
    # (resource_info.go:292-326) short-circuits small *scalars* but still
    # compares cpu/memory.
    scalar_lane = jnp.arange(task_resreq.shape[-1]) >= 2
    fit = jnp.all(
        (task_resreq[:, None, :] < node_future_idle[None, :, :] + tolerance[None, None, :])
        | (scalar_lane[None, None, :] & (task_resreq[:, None, :] <= tolerance[None, None, :])),
        axis=-1,
    )
    # selector: every required label bit present on the node
    sel_ok = jnp.all(
        (task_sel_bits[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1
    )
    # taints: every node taint bit tolerated
    tol_ok = jnp.all(
        (node_taint_bits[None, :, :] & ~task_tol_bits[:, None, :]) == 0, axis=-1
    )
    room = (node_task_count < node_max_tasks)[None, :]
    return fit, sel_ok, tol_ok, room


def predicate_mask(
    task_resreq: jnp.ndarray,  # [T, R]
    task_sel_bits: jnp.ndarray,  # [T, W] uint32
    task_tol_bits: jnp.ndarray,  # [T, W] uint32
    node_future_idle: jnp.ndarray,  # [N, R]
    node_label_bits: jnp.ndarray,  # [N, W]
    node_taint_bits: jnp.ndarray,  # [N, W]
    node_ok: jnp.ndarray,  # [N] bool
    node_task_count: jnp.ndarray,  # [N] i32
    node_max_tasks: jnp.ndarray,  # [N] i32
    tolerance: jnp.ndarray,  # [R]
) -> jnp.ndarray:
    """[T, N] feasibility — resource fit (LessEqual w/ tolerance,
    resource_info.go:292-326), selector/affinity bits, taint bits, pod
    count, node readiness."""
    fit, sel_ok, tol_ok, room = _component_planes(
        task_resreq, task_sel_bits, task_tol_bits, node_future_idle,
        node_label_bits, node_taint_bits, node_task_count, node_max_tasks,
        tolerance,
    )
    return fit & sel_ok & tol_ok & room & node_ok[None, :]


# ---- explain: first-failure reason planes + on-device histogram ----

#: reason-plane order = the HOST first-failure precedence: the resource
#: fit check prepended by actions/allocate.make_predicate_fn, then the
#: predicates plugin's own order (pod count, unschedulable, selector,
#: taints, topology spread — plugins/predicates.py).  Within a session
#: every node passed ready() at snapshot time (cache.snapshot skips
#: unready nodes), so the packed ¬node_ok is exactly "unschedulable".
#: Spread fails either on a node without the constraint's key or on
#: the skew, in the order of the pod's constraints; a class of the
#: absolute kind (required pod anti-affinity) fails after every spread
#: class, as the host's inter-pod filter runs after its spread filter.
N_EXPLAIN_REASONS = 8
(R_FIT, R_ROOM, R_UNSCHED, R_SEL, R_TOL, R_SPREAD_LABEL, R_SPREAD,
 R_AFFINITY) = range(N_EXPLAIN_REASONS)


@jax.jit
def explain_counts(
    task_resreq: jnp.ndarray,  # [T, R]
    task_sel_bits: jnp.ndarray,  # [T, W] uint32
    task_tol_bits: jnp.ndarray,  # [T, W] uint32
    node_future_idle: jnp.ndarray,  # [N, R]
    node_label_bits: jnp.ndarray,  # [N, W]
    node_taint_bits: jnp.ndarray,  # [N, W]
    node_ok: jnp.ndarray,  # [N] bool
    node_task_count: jnp.ndarray,  # [N] i32
    node_max_tasks: jnp.ndarray,  # [N] i32
    tolerance: jnp.ndarray,  # [R]
    n_nodes: jnp.ndarray,  # i32 scalar — valid node rows (rest padding)
    spread=None,  # (thr [T, K], dom, elig, count [K, N], absolute [K]) or None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(reason[T, N] i8, counts[T, P] i32), P = 5 without ``spread``
    and 8 with it (the two spread reasons and the anti-affinity one).

    ``reason[t, n]`` is the index of the FIRST predicate the pair fails
    in host order, or ``N_EXPLAIN_REASONS`` when the node is feasible
    (or padding).  ``counts[t, p]`` is the number of valid nodes whose
    first failure for task ``t`` is reason ``p`` — the on-device
    reduction of the reference's FitErrors histogram
    (unschedule_info.go), so a 50k×10k explanation costs a handful of
    [T, N] reductions instead of a host predicate sweep."""
    fit, sel_ok, tol_ok, room = _component_planes(
        task_resreq, task_sel_bits, task_tol_bits, node_future_idle,
        node_label_bits, node_taint_bits, node_task_count, node_max_tasks,
        tolerance,
    )
    ok = node_ok[None, :]
    feasible = jnp.int8(N_EXPLAIN_REASONS)
    reason = jnp.where(
        ~fit, jnp.int8(R_FIT),
        jnp.where(
            ~room, jnp.int8(R_ROOM),
            jnp.where(
                ~ok, jnp.int8(R_UNSCHED),
                jnp.where(
                    ~sel_ok, jnp.int8(R_SEL),
                    jnp.where(~tol_ok, jnp.int8(R_TOL), feasible),
                ),
            ),
        ),
    )
    planes = R_SPREAD_LABEL
    if spread is not None:
        thr, dom, elig, count, absolute = spread
        label_fail, skew_fail = spread_ok(dom, elig, count, thr, absolute)  # [T, K, N]
        first = jnp.full(reason.shape, feasible)
        for k in reversed(range(thr.shape[1])):
            first = jnp.where(
                label_fail[:, k], jnp.int8(R_SPREAD_LABEL),
                jnp.where(skew_fail[:, k] & ~absolute[k], jnp.int8(R_SPREAD), first),
            )
        anti_fail = jnp.any(skew_fail & absolute[None, :, None], axis=1)
        first = jnp.where((first == feasible) & anti_fail, jnp.int8(R_AFFINITY), first)
        reason = jnp.where(reason == feasible, first, reason)
        planes = N_EXPLAIN_REASONS
    valid = jnp.arange(reason.shape[1]) < n_nodes
    reason = jnp.where(valid[None, :], reason, feasible)
    counts = jnp.stack(
        [jnp.sum(reason == jnp.int8(p), axis=1, dtype=jnp.int32) for p in range(planes)],
        axis=1,
    )
    return reason, counts


# ---- scores (closed-form plugin math) ----

def _split(a):
    """Dekker's split of a float32 into a high half of 12 significant
    bits and the rest, so that products of halves are exact."""
    t = a * 4097.0
    hi = t - (t - a)
    return hi, a - hi


def rn_correct(x, y, q):
    """``q`` ≈ x / y, within a few ulps, brought to x / y rounded to
    nearest, as IEEE division gives it: the residual x - q·y is taken
    exactly (Dekker's product; Sterbenz's lemma for the subtraction) and
    its quotient by y added back.  Needed where the divide is a
    reciprocal and a multiply, as on the TPU; elsewhere the identity."""
    p = q * y
    qh, ql = _split(q)
    yh, yl = _split(y)
    e = ((qh * yh - p) + qh * yl + ql * yh) + ql * yl
    return q + ((x - p) - e) / y


def div_rn(x, y):
    """float32 x / y rounded to nearest on every backend (rn_correct)."""
    return rn_correct(x, y, x / y)


def binpack_score(
    task_resreq: jnp.ndarray,  # [T, R]
    node_used: jnp.ndarray,  # [N, R]
    node_alloc: jnp.ndarray,  # [N, R]
    weights: ScoreWeights,
) -> jnp.ndarray:
    """[T, N] — binpack.go:200-259: per-resource (used+req)*w/alloc summed
    over requested resources, normalized by summed weights, ×10×weight."""
    R = task_resreq.shape[-1]
    lane_w = jnp.concatenate(
        [
            jnp.array([weights.binpack_cpu, weights.binpack_memory], dtype=jnp.float32),
            jnp.full((R - 2,), weights.binpack_scalar, dtype=jnp.float32),
        ]
    )
    req = task_resreq[:, None, :]  # [T,1,R]
    used_finally = req + node_used[None, :, :]
    alloc = node_alloc[None, :, :]
    requested_mask = req > 0
    valid = requested_mask & (alloc > 0) & (used_finally <= alloc)
    lane_score = jnp.where(valid, div_rn(used_finally * lane_w, jnp.maximum(alloc, 1.0)), 0.0)
    score = jnp.sum(lane_score, axis=-1)
    weight_sum = jnp.sum(jnp.where(requested_mask, lane_w, 0.0), axis=-1)
    score = jnp.where(weight_sum > 0, div_rn(score, jnp.maximum(weight_sum, 1e-30)), 0.0)
    return score * MAX_PRIORITY * weights.binpack_weight


def least_requested_score(
    task_resreq: jnp.ndarray,
    node_used: jnp.ndarray,
    node_alloc: jnp.ndarray,
    int_exact: bool = False,
) -> jnp.ndarray:
    """[T, N] — least_requested.go:36-53 with the reference's integer floors:
    ((cap-req)*10)//cap averaged over cpu+memory.

    Default path: float32 floor division on integer-valued lanes
    (cpu-milli / memory-MiB) with a multiply-back correction, so the
    result is exact even when XLA lowers f32 divide to reciprocal-multiply
    (TPU): after q = floor(p/c), q is nudged so that q*c <= p < (q+1)*c
    holds in exact f32 integer arithmetic.  Exact while the products stay
    below 2^24 — node capacity below ~1.5 TiB / 1500 cores; ``int_exact``
    selects exact int32 division beyond that (slower lowering on TPU).
    """
    req = task_resreq[:, None, :2] + node_used[None, :, :2]
    cap = node_alloc[None, :, :2]
    if int_exact:
        reqi = req.astype(jnp.int32)
        capi = cap.astype(jnp.int32)
        lane = jnp.where(
            (capi > 0) & (reqi <= capi),
            (capi - reqi) * jnp.int32(MAX_PRIORITY) // jnp.maximum(capi, 1),
            0,
        )
        return (jnp.sum(lane, axis=-1) // 2).astype(jnp.float32)
    c = jnp.maximum(cap, 1.0)
    p = (cap - req) * MAX_PRIORITY
    q = jnp.floor(p / c)
    # Correction for up-to-1-ulp divide error in either direction.
    q = q + ((q + 1.0) * c <= p) - (q * c > p)
    lane = jnp.where((cap > 0) & (req <= cap), q, 0.0)
    return jnp.floor(jnp.sum(lane, axis=-1) * 0.5)


def balanced_resource_score(
    task_resreq: jnp.ndarray, node_used: jnp.ndarray, node_alloc: jnp.ndarray
) -> jnp.ndarray:
    """[T, N] — balanced_resource_allocation.go:41-70.

    Fractions are computed in float32, rounded to nearest on every
    backend (div_rn), where the host uses float64; the floor
    can differ by 1 when (1-|Δfrac|)*10 sits within float32 eps of an
    integer.  Bounded, rare, and only able to flip exact-tie argmaxes —
    jax-allocate's validation keeps any such placement feasible."""
    req = task_resreq[:, None, :2] + node_used[None, :, :2]
    cap = node_alloc[None, :, :2]
    frac = jnp.where(cap > 0, div_rn(req, jnp.maximum(cap, 1.0)), 1.0)
    cpu_f, mem_f = frac[..., 0], frac[..., 1]
    diff = jnp.abs(cpu_f - mem_f)
    score = jnp.floor((1.0 - diff) * MAX_PRIORITY)
    return jnp.where((cpu_f >= 1.0) | (mem_f >= 1.0), 0.0, score)


def node_scores(
    task_resreq: jnp.ndarray,
    node_used: jnp.ndarray,
    node_alloc: jnp.ndarray,
    weights: ScoreWeights,
) -> jnp.ndarray:
    """[T, N] total score — the additive NodeOrderFn dispatch
    (session_plugins.go:423-441)."""
    s = binpack_score(task_resreq, node_used, node_alloc, weights)
    s += weights.least_requested_weight * least_requested_score(
        task_resreq, node_used, node_alloc, int_exact=weights.lr_int_exact
    )
    s += weights.balanced_resource_weight * balanced_resource_score(
        task_resreq, node_used, node_alloc
    )
    return s



# ---- greedy assignment scan with Volcano's per-job commit/discard ----

#: ``jstate`` values the episode scans keep per job row
J_UNSEEN, J_READY, J_ENDED, J_DISCARDED = 0, 1, -1, -2

#: ``placed`` value of a row the scan never tried: its job had already
#: ended (a task that fit nowhere) or been discarded
NOT_REACHED = -2


class ScanOutcome(NamedTuple):
    """What one allocate pass decided, row by row (the first ``n_tasks``
    ordered rows).

    ``assignment`` — the committed node, or -1.  ``placed`` — the node
    the scan put the row on before its job's commit or discard (kept or
    rolled back), -1 where the row fit no node, ``NOT_REACHED`` where
    the scan skipped it.  ``reasons`` — for each row that fit no node,
    the node counts per first failing predicate at that step of the
    scan ([n_tasks, N_EXPLAIN_REASONS], zero elsewhere), or None where
    the formulation does not report them."""

    assignment: np.ndarray
    placed: np.ndarray
    reasons: Optional[np.ndarray] = None

    @staticmethod
    def of_assignment(assignment: np.ndarray) -> "ScanOutcome":
        """A pass that placed or failed every row once and never rolled
        one back (a session without multi-task gangs)."""
        a = np.asarray(assignment, dtype=np.int32)
        return ScanOutcome(a, a.copy(), None)


def class_reason_codes(class_sel, class_tol, node_label_bits, node_taint_bits,
                       node_ok, xp=jnp):
    """[C, N] the first static predicate each (feasibility class, node)
    pair fails, in host order — ``R_UNSCHED``, ``R_SEL``, ``R_TOL`` — or
    ``N_EXPLAIN_REASONS`` where it passes all three.  The scans read
    feasibility and the failing step's reasons off the same plane."""
    sel_ok = xp.all((class_sel[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1)
    tol_ok = xp.all((node_taint_bits[None, :, :] & ~class_tol[:, None, :]) == 0, axis=-1)
    ok = xp.asarray(node_ok)[None, :]
    return xp.where(
        ~ok, R_UNSCHED,
        xp.where(~sel_ok, R_SEL, xp.where(~tol_ok, R_TOL, N_EXPLAIN_REASONS)),
    ).astype(xp.int32)


def step_reason_counts(fit, room, code_row, valid, spread=None):
    """[N_EXPLAIN_REASONS] node counts per first failing predicate of
    one row at one step — explain_counts' precedence over the scan's
    live planes: ``fit``, ``room`` [N] bool, ``code_row`` [N] the row's
    class codes, ``valid`` [N] real nodes; ``spread`` =
    (label_fail, skew_fail [K, N], absolute [K]) or None."""
    feasible = N_EXPLAIN_REASONS
    reason = jnp.where(~fit, R_FIT, jnp.where(~room, R_ROOM, code_row))
    if spread is not None:
        label_fail, skew_fail, absolute = spread
        first = jnp.full(reason.shape, feasible, jnp.int32)
        for k in reversed(range(label_fail.shape[0])):
            first = jnp.where(
                label_fail[k], R_SPREAD_LABEL,
                jnp.where(skew_fail[k] & ~absolute[k], R_SPREAD, first),
            )
        anti = jnp.any(skew_fail & absolute[:, None], axis=0)
        first = jnp.where((first == feasible) & anti, R_AFFINITY, first)
        reason = jnp.where(reason == feasible, first, reason)
    reason = jnp.where(valid, reason, feasible)
    return jnp.stack([jnp.sum(reason == p, dtype=jnp.int32) for p in range(feasible)])


class _EpisodeState(NamedTuple):
    # used_ext packs [used lanes..., task count] so one scatter per step
    # updates both (scatters are the dominant per-step cost at large N).
    used_ext: jnp.ndarray  # [N, R+1]
    #: [K, N] each node's domain count per spread class, or None
    spread_count: object
    #: node state when the open run of a gang began (restored on discard)
    saved_used: jnp.ndarray
    saved_spread: object
    jstate: jnp.ndarray  # [J] i32, J_* values
    cur_job: jnp.ndarray  # job row of the open run, -1 = none
    run_placed: jnp.ndarray  # placements of the open run
    run_need: jnp.ndarray  # placements the open run needs to commit


#: stands in for "no eligible domain" in the spread minimum: every
#: count less it passes any maxSkew, as k8s's MaxInt32 minimum does
SPREAD_BIG = float(2**30)


def spread_ok(dom, elig, count, thr, absolute):
    """Hard topology spread and required anti-affinity on every node —
    the kernels' form of PodTopologySpread.Filter and of
    InterPodAffinity's anti-affinity rules.  ``dom``, ``elig``,
    ``count`` are [K, N] planes (ops.packing.spread_node_planes);
    ``thr`` is [..., K], negative where the class does not constrain the
    row; ``absolute`` [K] bool marks the classes of the absolute kind.
    A node passes a spread class when it carries the key and its
    domain's count less the minimum over eligible nodes is at most
    ``thr`` (the row's maxSkew less its self-match); it passes an
    absolute class when it lacks the key or its domain's count is at
    most ``thr`` (the bound): the minimum is taken as 0.  Returns
    ([..., K, N] per-class failures of the label, of the count), both
    False where the class does not constrain."""
    mn = jnp.min(jnp.where(elig > 0.0, count, SPREAD_BIG), axis=-1, keepdims=True)
    mn = jnp.where(absolute[:, None], 0.0, mn)
    on = (thr >= 0.0)[..., None]
    label_fail = on & (dom < 0.0) & ~absolute[:, None]
    skew_fail = on & (dom >= 0.0) & (count - mn > thr[..., None])
    return label_fail, skew_fail


def spread_update(dom, elig, count, inc, best, ok):
    """Counts after a placement at node ``best``: every class the
    placed row counts toward (``inc`` [K]) gains one in the chosen
    node's domain, when that node is eligible for the class."""
    add = inc * elig[:, best] * jnp.where(ok, 1.0, 0.0)
    return count + jnp.where(dom == dom[:, best][:, None], add[:, None], 0.0)


def step_fit(tolerance, idle, resreq):
    """[N] resource fit of one row against every node's idle lanes,
    with the sub-tolerance skip on scalar lanes only (see
    predicate_mask)."""
    scalar_lane = jnp.arange(resreq.shape[-1]) >= 2
    return jnp.all(
        (resreq[None, :] < idle + tolerance[None, :])
        | (scalar_lane[None, :] & (resreq[None, :] <= tolerance[None, :])),
        axis=-1,
    )


def step_feasible_score(
    weights: ScoreWeights,
    tolerance,
    base,  # [N, R] = idle0 + used0 (idle = base - used, no idle carry)
    node_alloc,
    node_max_tasks,
    used_ext,
    resreq,
    feas_row,
    active,
):
    """Per-step feasibility + masked score for the single-chip scan step
    below.  (The blocked/sharded kernels use the same semantics through
    blocked._block_scores / blocked.make_inner_step.)"""
    used = used_ext[:, :-1]
    count = used_ext[:, -1]
    fit = step_fit(tolerance, base - used, resreq)
    feasible = fit & (count < node_max_tasks) & feas_row & active
    score = node_scores(resreq[None, :], used, node_alloc, weights)[0]
    return feasible, jnp.where(feasible, score, -jnp.inf)


def step_delta_ext(resreq, ok):
    """Packed (resource, +1 count) update row, zeroed when not placing."""
    okf = jnp.where(ok, 1.0, 0.0)
    return jnp.concatenate([resreq, jnp.ones((1,), resreq.dtype)]) * okf


def _close_run(st: _EpisodeState, close, now_placed) -> _EpisodeState:
    """End the open run where ``close``: a run short of its need is
    discarded — the node state goes back to where the run began and the
    job is marked discarded; otherwise the job is ready (J_READY) —
    the caller marks it ended when a task failed."""
    disc = close & (now_placed < st.run_need)
    back = disc & (now_placed > 0)
    used_ext = jnp.where(back, st.saved_used, st.used_ext)
    spread = st.spread_count
    if spread is not None:
        spread = jnp.where(back, st.saved_spread, spread)
    cj = jnp.maximum(st.cur_job, 0)
    jstate = st.jstate.at[cj].set(
        jnp.where(close, jnp.where(disc, J_DISCARDED, J_READY), st.jstate[cj])
    )
    return st._replace(used_ext=used_ext, spread_count=spread, jstate=jstate,
                       cur_job=jnp.where(close, -1, st.cur_job))


def _episode_step(weights, tolerance, base, node_alloc, node_max_tasks,
                  class_code, job_need, n_valid, spread_nodes, reasons,
                  st: _EpisodeState, task):
    """One ordered row under Volcano's per-job commit/discard
    (drive_allocate_loop + gang_end_job): consecutive rows of one job
    form a run — its episodes as ORDER listed them — opened by the
    job's first row and closed when another job's row arrives, the
    pass ends, or a row fits no node.  A run that closes short of the
    job's need (minAvailable less its ready tasks; 0 once the job was
    ready) is discarded: the node state goes back to the run's start.
    A row that fits no node ends its job: its later rows are skipped."""
    resreq, cls, j, valid = task[:4]
    st = _close_run(st, valid & (j != st.cur_job) & (st.cur_job >= 0), st.run_placed)
    js = st.jstate[j]
    alive = valid & (js >= 0)
    start = alive & (j != st.cur_job)
    run_need = jnp.where(start, jnp.where(js == J_UNSEEN, job_need[j], 0), st.run_need)
    keep = start & (run_need >= 2)
    st = st._replace(
        run_need=run_need,
        run_placed=jnp.where(start, 0, st.run_placed),
        cur_job=jnp.where(start, j, st.cur_job),
        saved_used=jnp.where(keep, st.used_ext, st.saved_used),
        saved_spread=(
            None if st.spread_count is None
            else jnp.where(keep, st.spread_count, st.saved_spread)
        ),
    )

    code_row = class_code[cls]
    feasible, score = step_feasible_score(
        weights, tolerance, base, node_alloc, node_max_tasks,
        st.used_ext, resreq, code_row == N_EXPLAIN_REASONS, alive,
    )
    spread_fail = None
    if spread_nodes is not None:
        thr, inc = task[4:]
        dom, elig, absolute = spread_nodes
        label_fail, skew_fail = spread_ok(dom, elig, st.spread_count, thr, absolute)
        spread_fail = (label_fail, skew_fail, absolute)
        passes = ~jnp.any(label_fail | skew_fail, axis=0)
        feasible = feasible & passes
        score = jnp.where(passes, score, -jnp.inf)
    best = jnp.argmax(score)  # first max index — deterministic tie-break
    ok = feasible[best]
    fail = alive & ~ok

    counts = None
    if reasons:
        def why():
            used = st.used_ext[:, :-1]
            fit = step_fit(tolerance, base - used, resreq)
            room = st.used_ext[:, -1] < node_max_tasks
            valid_n = jnp.arange(base.shape[0]) < n_valid
            return step_reason_counts(fit, room, code_row, valid_n, spread_fail)

        counts = jax.lax.cond(
            fail, why, lambda: jnp.zeros((N_EXPLAIN_REASONS,), jnp.int32)
        )

    used_ext = st.used_ext.at[best].add(step_delta_ext(resreq, ok))
    spread = st.spread_count
    if spread_nodes is not None:
        spread = spread_update(dom, elig, spread, inc, best, ok)
    placed_now = st.run_placed + jnp.where(ok, 1, 0)
    st = st._replace(used_ext=used_ext, spread_count=spread, run_placed=placed_now)
    st = _close_run(st, fail, placed_now)
    st = st._replace(jstate=st.jstate.at[j].set(
        jnp.where(fail & (st.jstate[j] == J_READY), J_ENDED, st.jstate[j])
    ))
    placed = jnp.where(alive, jnp.where(ok, best, -1), NOT_REACHED)
    return st, (placed, counts)


def _episode_scan(weights, tolerance, base, node_alloc, node_max_tasks,
                  class_code, used_ext0, job_need, task_job, xs, n_valid,
                  spread_nodes=None, count0=None, reasons=True):
    """The pass over the rows ``xs`` = (resreq, feasibility class, job,
    valid[, thr, inc]) → (assignment, placed, reasons)."""
    J = job_need.shape[0]
    init = _EpisodeState(
        used_ext0, count0, used_ext0, count0,
        jnp.zeros((J,), jnp.int32), jnp.int32(-1), jnp.int32(0), jnp.int32(0),
    )

    def step(st, task):
        return _episode_step(weights, tolerance, base, node_alloc, node_max_tasks,
                             class_code, job_need, n_valid, spread_nodes, reasons,
                             st, task)

    final, (placed, counts) = jax.lax.scan(step, init, xs)
    final = _close_run(final, final.cur_job >= 0, final.run_placed)
    committed = (placed >= 0) & (final.jstate[task_job] != J_DISCARDED)
    return jnp.where(committed, placed, -1), placed, counts


@functools.partial(jax.jit, static_argnames=("weights",))
def schedule_session(
    task_resreq: jnp.ndarray,
    task_job: jnp.ndarray,
    task_sel_bits: jnp.ndarray,
    task_tol_bits: jnp.ndarray,
    node_idle: jnp.ndarray,
    node_used: jnp.ndarray,
    node_alloc: jnp.ndarray,
    node_label_bits: jnp.ndarray,
    node_taint_bits: jnp.ndarray,
    node_ok: jnp.ndarray,
    node_task_count: jnp.ndarray,
    node_max_tasks: jnp.ndarray,
    job_min_available: jnp.ndarray,
    job_ready_count: jnp.ndarray,
    tolerance: jnp.ndarray,
    task_valid: jnp.ndarray,  # [T] bool — padding mask
    weights: ScoreWeights = DEFAULT_WEIGHTS,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-session kernel over raw per-task bitsets → (assignment[T]
    node index or -1, committed[T]), with the per-job commit/discard of
    :func:`_episode_step`."""
    T = task_resreq.shape[0]
    code = class_reason_codes(task_sel_bits, task_tol_bits, node_label_bits,
                              node_taint_bits, node_ok)
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )
    xs = (task_resreq, jnp.arange(T, dtype=jnp.int32), task_job, task_valid)
    assignment, _, _ = _episode_scan(
        weights, tolerance, node_idle + node_used, node_alloc, node_max_tasks,
        code, used_ext0, job_min_available - job_ready_count, task_job, xs,
        node_idle.shape[0], reasons=False,
    )
    return assignment, assignment >= 0


@functools.partial(jax.jit, static_argnames=("weights",))
def schedule_episodes(
    task_resreq: jnp.ndarray,
    task_job: jnp.ndarray,
    task_feas_class: jnp.ndarray,  # [T] index into class_sel/tol_bits
    class_sel_bits: jnp.ndarray,  # [C, W] distinct task bitset signatures
    class_tol_bits: jnp.ndarray,  # [C, W]
    node_idle: jnp.ndarray,
    node_used: jnp.ndarray,
    node_alloc: jnp.ndarray,
    node_label_bits: jnp.ndarray,
    node_taint_bits: jnp.ndarray,
    node_ok: jnp.ndarray,
    node_task_count: jnp.ndarray,
    node_max_tasks: jnp.ndarray,
    job_min_available: jnp.ndarray,
    job_ready_count: jnp.ndarray,
    tolerance: jnp.ndarray,
    valid: jnp.ndarray,  # [T] bool
    n_nodes: jnp.ndarray,  # i32 — valid node rows
    spread=None,  # (thr, inc [T, K], dom, elig, count0 [K, N], absolute [K]) or None
    weights: ScoreWeights = DEFAULT_WEIGHTS,
):
    """One pass → (assignment[T], placed[T], reasons[T, P]): the plain
    scan, the reference the other formulations are held to.  ``spread``
    adds the hard topology spread and anti-affinity mask, the per-class
    domain counts carried through the scan (ops.packing's
    spread_task_columns and spread_node_planes).

    Static feasibility (labels/taints/readiness) is evaluated per distinct
    bitset signature class, not per task: the scan gathers a [N] row from
    the small [C, N] matrix instead of slicing a [T, N] one (at 50k×10k
    that matrix is 1 GB and its per-step slice dominated the step cost)."""
    code = class_reason_codes(class_sel_bits, class_tol_bits, node_label_bits,
                              node_taint_bits, node_ok)
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )
    xs = (task_resreq, task_feas_class, task_job, valid)
    spread_nodes = count0 = None
    if spread is not None:
        thr, inc, dom, elig, count0, absolute = spread
        xs = xs + (thr, inc)
        spread_nodes = (dom, elig, absolute)
    return _episode_scan(
        weights, tolerance, node_idle + node_used, node_alloc, node_max_tasks,
        code, used_ext0, job_min_available - job_ready_count, task_job, xs,
        n_nodes, spread_nodes, count0,
    )


def _feasibility_classes(snap: PackedSnapshot):
    """Unique (sel_bits, tol_bits) rows → (class idx per task, class bit
    matrices).

    Row-uniqueness is computed by cascading cheap 1D uniques column by
    column (code = code * |u| + inv, re-densified each step) instead of
    ``np.unique(axis=0)`` — the structured row compare is ~7x slower at
    50k tasks and this runs on every session.  Class order differs from
    the lexicographic row order but class identity (what the kernel
    consumes) is the same.

    Memoized on the snapshot object: the VMEM-budget gate in the
    dispatcher and the kernel array preparation both need the classes,
    and each runs once per session.
    """
    cached = getattr(snap, "_feas_classes_cache", None)
    if cached is not None:
        return cached
    combined = np.concatenate([snap.task_sel_bits, snap.task_tol_bits], axis=1)
    T, Wc = combined.shape
    code = np.zeros(T, dtype=np.int64)
    for c in range(Wc):
        u, inv = np.unique(combined[:, c], return_inverse=True)
        code = code * np.int64(len(u)) + inv
        if c < Wc - 1:
            _, code = np.unique(code, return_inverse=True)
            code = code.astype(np.int64)
    uc, inverse = np.unique(code, return_inverse=True)
    first = np.full(len(uc), T, dtype=np.int64)
    np.minimum.at(first, inverse, np.arange(T, dtype=np.int64))
    uniq = combined[first]
    W = snap.task_sel_bits.shape[1]
    result = (
        inverse.astype(np.int32),
        np.ascontiguousarray(uniq[:, :W]),
        np.ascontiguousarray(uniq[:, W:]),
    )
    snap._feas_classes_cache = result
    return result


def spread_arrays(snap: PackedSnapshot, rows: int, width: int):
    """schedule_episodes' ``spread`` operand for ``rows`` task rows and
    ``width`` node columns, or None when the session has no spread or
    anti-affinity class."""
    if not snap.has_spread:
        return None
    from volcano_tpu.ops.packing import (
        class_absolute,
        spread_node_planes,
        spread_task_columns,
    )

    thr, inc = spread_task_columns(snap, rows)
    dom, elig, count = spread_node_planes(snap, width)
    return tuple(jnp.asarray(a) for a in (thr, inc, dom, elig, count, class_absolute(snap)))


def run_outcome(snap: PackedSnapshot, weights: ScoreWeights = DEFAULT_WEIGHTS) -> ScanOutcome:
    """Host wrapper of the plain scan: PackedSnapshot → ScanOutcome over
    the first ``n_tasks`` rows."""
    T = snap.task_resreq.shape[0]
    valid = np.zeros(T, dtype=bool)
    valid[: snap.n_tasks] = True

    # Large nodes fall outside the f32 floor-division exactness envelope
    # (see least_requested_score) — switch to exact int division.
    if not f32_lr_exact(snap):
        weights = weights._replace(lr_int_exact=True)

    task_feas_class, class_sel, class_tol = _feasibility_classes(snap)
    # staged sessions (ops/device_stage.py) resolve most planes to
    # device-resident buffers here — jnp.asarray is then a no-op and the
    # session ships only the dirty-row scatters plus the derived
    # feasibility-class arrays
    from volcano_tpu.ops.device_stage import device_plane as _dp

    dev = [
        jnp.asarray(x)
        for x in (
            _dp(snap, "task_resreq"),
            _dp(snap, "task_job"),
            task_feas_class,
            class_sel,
            class_tol,
            _dp(snap, "node_idle"),
            _dp(snap, "node_used"),
            _dp(snap, "node_alloc"),
            _dp(snap, "node_label_bits"),
            _dp(snap, "node_taint_bits"),
            _dp(snap, "node_ok"),
            _dp(snap, "node_task_count"),
            _dp(snap, "node_max_tasks"),
            _dp(snap, "job_min_available"),
            _dp(snap, "job_ready_count"),
            _dp(snap, "tolerance"),
        )
    ]
    spread = spread_arrays(snap, T, snap.node_idle.shape[0])
    assignment, placed, counts = schedule_episodes(
        *dev, jnp.asarray(valid), jnp.int32(snap.n_nodes), spread=spread,
        weights=weights,
    )
    n = snap.n_tasks
    return ScanOutcome(
        np.asarray(assignment)[:n].astype(np.int32),
        np.asarray(placed)[:n].astype(np.int32),
        np.asarray(counts)[:n],
    )


def run_packed(snap: PackedSnapshot, weights: ScoreWeights = DEFAULT_WEIGHTS) -> np.ndarray:
    """PackedSnapshot → committed assignment[n_tasks] (np.int32) of the
    plain scan (:func:`run_outcome`)."""
    return run_outcome(snap, weights).assignment
