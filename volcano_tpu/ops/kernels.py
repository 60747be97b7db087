"""Device session kernels: fused predicate-mask + score + greedy gang assign.

This replaces the reference's per-task 16-goroutine loop
(pkg/scheduler/util/scheduler_helper.go:64-211 driven from
pkg/scheduler/actions/allocate/allocate.go:191-224) with one jitted program:

  1. predicate mask — broadcast comparisons + bitset ops over [T, N]
     (replaces predicates.go:156-301 and the resource-fit closure
     allocate.go:100-107)
  2. score — closed-form binpack (binpack.go:248-259) +
     least-requested/balanced (vendored k8s priorities) arithmetic
  3. assignment — lax.scan over tasks in priority order with node state
     (idle/used/count) carried, mirroring the sequential feedback of
     Statement.Allocate; deterministic first-index tie-break
  4. gang commit — jobs reaching min_available keep their placements,
     others are discarded and the kernel re-runs without them (the
     Statement.Commit/Discard semantics, statement.go:309-337) until a
     fixpoint — at most gang_rounds device passes.

Everything is static-shaped and branch-free inside jit; ties break to the
lowest node index so host and device paths agree bindings-exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

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
    lane_score = jnp.where(valid, used_finally * lane_w / jnp.maximum(alloc, 1.0), 0.0)
    score = jnp.sum(lane_score, axis=-1)
    weight_sum = jnp.sum(jnp.where(requested_mask, lane_w, 0.0), axis=-1)
    score = jnp.where(weight_sum > 0, score / weight_sum, 0.0)
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

    Fractions are computed in float32 (the host uses float64); the floor
    can differ by 1 when (1-|Δfrac|)*10 sits within float32 eps of an
    integer.  Bounded, rare, and only able to flip exact-tie argmaxes —
    jax-allocate's validation keeps any such placement feasible."""
    req = task_resreq[:, None, :2] + node_used[None, :, :2]
    cap = node_alloc[None, :, :2]
    frac = jnp.where(cap > 0, req / jnp.maximum(cap, 1.0), 1.0)
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


# ---- greedy assignment scan ----

class _ScanState(NamedTuple):
    # used_ext packs [used lanes..., task count] so one scatter per step
    # updates both (scatters are the dominant per-step cost at large N).
    used_ext: jnp.ndarray  # [N, R+1]
    job_assigned: jnp.ndarray  # [J]
    #: [K, N] each node's domain count per spread class, or None
    spread_count: object = None


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
    blocked._block_scores / blocked.make_inner_step; the sharded mesh
    kernel no longer consumes this helper.)  Sub-tolerance skip on scalar
    lanes only (see predicate_mask)."""
    used = used_ext[:, :-1]
    count = used_ext[:, -1]
    idle = base - used
    scalar_lane = jnp.arange(resreq.shape[-1]) >= 2
    fit = jnp.all(
        (resreq[None, :] < idle + tolerance[None, :])
        | (scalar_lane[None, :] & (resreq[None, :] <= tolerance[None, :])),
        axis=-1,
    )
    feasible = fit & (count < node_max_tasks) & feas_row & active
    score = node_scores(resreq[None, :], used, node_alloc, weights)[0]
    return feasible, jnp.where(feasible, score, -jnp.inf)


def step_delta_ext(resreq, ok):
    """Packed (resource, +1 count) update row, zeroed when not placing."""
    okf = jnp.where(ok, 1.0, 0.0)
    return jnp.concatenate([resreq, jnp.ones((1,), resreq.dtype)]) * okf


def _assign_step(
    weights: ScoreWeights,
    tolerance,
    base,
    node_alloc,
    node_max_tasks,
    state: _ScanState,
    task: Tuple,
    spread_nodes=None,
):
    """One task: mask → score → argmax → tentative allocate.

    Mirrors the per-task body of allocate.go:177-230 with the
    resource-fit + plugin predicates folded into the mask and
    SelectBestNode's tie-break made deterministic (first index).
    ``spread_nodes`` = (dom, elig [K, N], absolute [K]) when the
    session has spread or anti-affinity classes; the task then carries
    its (thr, inc) columns and the state its running domain counts."""
    resreq, sel_tol_row, job_idx, active = task[:4]
    used_ext, job_assigned, spread_count = state

    feasible, score = step_feasible_score(
        weights, tolerance, base, node_alloc, node_max_tasks,
        used_ext, resreq, sel_tol_row, active,
    )
    if spread_nodes is not None:
        thr, inc = task[4:]
        dom, elig, absolute = spread_nodes
        label_fail, skew_fail = spread_ok(dom, elig, spread_count, thr, absolute)
        passes = ~jnp.any(label_fail | skew_fail, axis=0)
        feasible = feasible & passes
        score = jnp.where(passes, score, -jnp.inf)
    best = jnp.argmax(score)  # first max index — deterministic tie-break
    ok = feasible[best]

    used_ext = used_ext.at[best].add(step_delta_ext(resreq, ok))
    job_assigned = job_assigned.at[job_idx].add(jnp.where(ok, 1, 0))
    if spread_nodes is not None:
        spread_count = spread_update(dom, elig, spread_count, inc, best, ok)

    chosen = jnp.where(ok, best, -1)
    return _ScanState(used_ext, job_assigned, spread_count), chosen


@functools.partial(jax.jit, static_argnames=("weights", "gang_rounds"))
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
    gang_rounds: int = 3,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-session kernel → (assignment[T] node index or -1, committed[T]).

    Gang fixpoint: after each greedy pass, jobs with
    assigned+ready < minAvailable are discarded (their tasks deactivated)
    and the pass re-runs from the original state — device analogue of
    per-job Statement.Commit/Discard.  ``gang_rounds`` bounds the cascade
    (an unsettled fixpoint ships the last round's commits, which are
    always individually valid placements).
    """
    # Static (state-independent) feasibility per [T, N]: labels, taints,
    # node readiness.  Resource fit and pod-count recheck dynamically in
    # the scan.
    sel_ok = jnp.all(
        (task_sel_bits[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1
    )
    tol_ok = jnp.all(
        (node_taint_bits[None, :, :] & ~task_tol_bits[:, None, :]) == 0, axis=-1
    )
    static_feasible = sel_ok & tol_ok & node_ok[None, :]  # [T, N]

    base = node_idle + node_used
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )
    init = _ScanState(used_ext0, jnp.zeros_like(job_min_available), None)

    def one_pass(active):
        def step(state, task):
            return _assign_step(
                weights, tolerance, base, node_alloc, node_max_tasks, state, task
            )

        final, chosen = jax.lax.scan(
            step, init, (task_resreq, static_feasible, task_job, active)
        )
        return final, chosen

    def round_body(carry, _):
        active, _, _ = carry
        final, chosen = one_pass(active)
        ready = final.job_assigned + job_ready_count >= job_min_available
        committed = ready[task_job] & (chosen >= 0)
        # Discard tasks of non-ready jobs for the next round.
        next_active = active & ready[task_job]
        return (next_active, chosen, committed), None

    carry0 = (
        task_valid,
        jnp.full_like(task_job, -1),
        jnp.zeros_like(task_valid),
    )
    (active, chosen, committed), _ = jax.lax.scan(
        round_body, carry0, None, length=gang_rounds
    )

    assignment = jnp.where(committed, chosen, -1)
    return assignment, committed


@functools.partial(jax.jit, static_argnames=("weights",))
def schedule_pass(
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
    tolerance: jnp.ndarray,
    active: jnp.ndarray,
    spread=None,  # (thr, inc [T, K], dom, elig, count0 [K, N], absolute [K]) or None
    weights: ScoreWeights = DEFAULT_WEIGHTS,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One greedy pass → (chosen[T], job_assigned[J]).  The host loop in
    run_packed applies the gang commit/discard between passes — typical
    sessions converge after one pass instead of paying gang_rounds fixed
    device rounds.  ``spread`` adds the hard topology spread and
    anti-affinity mask, the per-class domain counts carried through the
    scan (ops.packing's spread_task_columns and spread_node_planes).

    Static feasibility (labels/taints/readiness) is evaluated per distinct
    bitset signature class, not per task: the scan gathers a [N] row from
    the small [C, N] matrix instead of slicing a [T, N] one (at 50k×10k
    that matrix is 1 GB and its per-step slice dominated the step cost)."""
    sel_ok = jnp.all(
        (class_sel_bits[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1
    )
    tol_ok = jnp.all(
        (node_taint_bits[None, :, :] & ~class_tol_bits[:, None, :]) == 0, axis=-1
    )
    class_feasible = sel_ok & tol_ok & node_ok[None, :]  # [C, N]

    base = node_idle + node_used
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )

    xs = (task_resreq, task_feas_class, task_job, active)
    spread_nodes = count0 = None
    if spread is not None:
        thr, inc, dom, elig, count0, absolute = spread
        xs = xs + (thr, inc)
        spread_nodes = (dom, elig, absolute)

    def step(state, task):
        resreq, feas_cls, job_idx, act = task[:4]
        return _assign_step(
            weights,
            tolerance,
            base,
            node_alloc,
            node_max_tasks,
            state,
            (resreq, class_feasible[feas_cls], job_idx, act) + task[4:],
            spread_nodes,
        )

    init = _ScanState(used_ext0, jnp.zeros_like(job_min_available), count0)
    final, chosen = jax.lax.scan(step, init, xs)
    return chosen, final.job_assigned


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
    """schedule_pass's ``spread`` operand for ``rows`` task rows and
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


def run_packed(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    gang_rounds: int = 3,
    discard_unstable: bool = False,
) -> np.ndarray:
    """Host wrapper: PackedSnapshot → assignment[T] (np.int32), with the
    gang fixpoint driven host-side (adaptive: stops as soon as the active
    set is stable, which for well-provisioned sessions is after round 1 —
    identical outcome to the fixed-round schedule_session).

    ``discard_unstable`` opts into the reference's Statement semantics
    for an unsettled cascade (statement.go:309-337: discard until
    stable): the loop ignores the ``gang_rounds`` bound and runs to the
    true fixpoint.  Terminates structurally — every non-stable round
    strictly shrinks the active set."""
    T = snap.task_resreq.shape[0]
    active = np.zeros(T, dtype=bool)
    active[: snap.n_tasks] = True

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
            _dp(snap, "tolerance"),
        )
    ]
    spread = spread_arrays(snap, T, snap.node_idle.shape[0])
    task_job = snap.task_job
    min_avail = snap.job_min_available.astype(np.int64)
    ready_count = snap.job_ready_count.astype(np.int64)

    chosen_np = np.full(T, -1, dtype=np.int32)
    committed = np.zeros(T, dtype=bool)
    rounds = 0
    while True:
        chosen, job_assigned = schedule_pass(
            *dev, jnp.asarray(active), spread=spread, weights=weights
        )
        chosen_np = np.asarray(chosen)
        ready = np.asarray(job_assigned, dtype=np.int64) + ready_count >= min_avail
        committed = ready[task_job] & (chosen_np >= 0)
        next_active = active & ready[task_job]
        rounds += 1
        if (next_active == active).all():
            break
        if not discard_unstable and rounds >= gang_rounds:
            break
        active = next_active

    assignment = np.where(committed & active, chosen_np, -1)
    return assignment[: snap.n_tasks]
