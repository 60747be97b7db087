"""Pallas TPU kernel for the full greedy session scan.

The sequential-greedy semantics (one task at a time, each placement
feeding the next task's scores — allocate.go:177-230 +
statement.go:199-246 in the reference) caps how much the XLA scan
formulations can help: per step, `lax.scan` dispatches a handful of
full-width HBM-resident ops, and the fixed per-op overhead (~µs each)
dominates at 50k steps.  This kernel runs the ENTIRE scan inside one
``pallas_call``:

  * node state (used lanes + task count) lives in VMEM scratch across the
    whole grid — zero HBM traffic per step;
  * tasks stream in blocks of ``TB`` via the grid pipeline (SMEM blocks,
    double-buffered DMA);
  * each step is ~90 VPU ops over [NS, 128] node planes (~10 cycles per
    op at 10k nodes) → sub-µs per task instead of tens of µs.

Semantics are op-for-op identical to ops/kernels.py `schedule_episodes`
(same predicate mask, same score arithmetic and operation order, same
first-lowest-node-index tie-break), so host/device/native bindings
equivalence carries over.  Volcano's per-job gang commit/discard
(drive_allocate_loop + gang_end_job over Statement.Commit/Discard,
statement.go:309-337) runs in the same pass: a job's run of rows that
closes short of minAvailable puts back the node state it began from, a
task that fits no node ends its job, and that step's per-reason node
counts ride out beside the placements — so the whole session pays one
host→device→host round trip and the host never sweeps the nodes for a
discarded gang.

Hard topology spread and required pod anti-affinity (K classes,
ops/packing.pack_spread) ride the same layout: each task row gains K
thresholds and K count flags, the node array gains per-class domain,
eligibility and initial-count planes, and a VMEM scratch carries the
running domain counts.  Each step masks the scores by every
constraining class — against one cross-node minimum per spread class,
against its bound for a class of the absolute kind (anti-affinity; the
kind is static, ``absolute``) — and a placement adds one to the chosen
node's whole domain (kernels.spread_ok / spread_update, op for op).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from volcano_tpu.ops.chip_memory import pallas_compiler_params
from volcano_tpu.ops.kernels import (
    _feasibility_classes,
    class_reason_codes,
    DEFAULT_WEIGHTS,
    div_rn,
    f32_lr_exact,
    MAX_PRIORITY,
    N_EXPLAIN_REASONS,
    R_AFFINITY,
    R_FIT,
    R_ROOM,
    R_SPREAD,
    R_SPREAD_LABEL,
    ScanOutcome,
    ScoreWeights,
    SPREAD_BIG,
)
from volcano_tpu.ops.packing import (
    class_absolute,
    PackedSnapshot,
    spread_node_planes,
    spread_task_columns,
)

LANES = 128
INT_BIG = np.int32(2**31 - 1)


def score_planes(
    rr,  # list of R scalar resource requests
    req,  # list of R planes: rr[r] + used[r]
    alloc,  # callable r -> plane
    maxal,  # callable r -> plane (max(alloc, 1))
    allocpos,  # callable r -> plane f32 (alloc > 0)
    weights: ScoreWeights,
    shape,  # plane shape tuple
):
    """Total node-score plane for one task — the in-kernel copy of
    kernels.py node_scores (binpack + least-requested + balanced), with
    the same op order and f32 rounding: each division rounded to nearest
    (kernels.div_rn), as the TPU's reciprocal-and-multiply divide is not
    (benchmark/tests/score_division_check.py).  Shared by the allocate
    scan kernel below and the preempt kernel (ops/preempt_pallas.py)."""
    R = len(rr)
    w_bp = float(weights.binpack_weight)
    lane_w = [float(weights.binpack_cpu), float(weights.binpack_memory)] + [
        float(weights.binpack_scalar)
    ] * (R - 2)
    w_lr = float(weights.least_requested_weight)
    w_bal = float(weights.balanced_resource_weight)

    # --- binpack (binpack_score op order) ---
    bp = None
    ws = jnp.float32(0.0)
    for r in range(R):
        if lane_w[r] == 0.0:
            continue
        reqmask = rr[r] > 0.0
        valid = reqmask & (allocpos(r) > 0.0) & (req[r] <= alloc(r))
        lane = jnp.where(valid, div_rn(req[r] * lane_w[r], maxal(r)), 0.0)
        bp = lane if bp is None else bp + lane
        ws = ws + jnp.where(reqmask, jnp.float32(lane_w[r]), 0.0)
    if bp is None:
        s_bp = jnp.zeros(shape, jnp.float32)
    else:
        # Sequential multiplies, matching binpack_score's
        # `score * MAX_PRIORITY * weights.binpack_weight` f32 rounding
        # exactly (folding the constants can differ by 1 ulp for
        # non-default weights).
        s_bp = jnp.where(ws > 0.0, div_rn(bp, jnp.maximum(ws, 1e-30)), 0.0) * jnp.float32(
            MAX_PRIORITY)
        if w_bp != 1.0:
            s_bp = s_bp * jnp.float32(w_bp)

    # --- least-requested (f32 exact floor-div path) ---
    lr = None
    fracs = []
    for r in range(2):
        cap = alloc(r)
        c = maxal(r)
        p = (cap - req[r]) * jnp.float32(MAX_PRIORITY)
        q = jnp.floor(p / c)
        q = q + ((q + 1.0) * c <= p) - (q * c > p)
        lane = jnp.where((allocpos(r) > 0.0) & (req[r] <= cap), q, 0.0)
        lr = lane if lr is None else lr + lane
        # balanced fractions reuse req/cap
        fracs.append(jnp.where(allocpos(r) > 0.0, div_rn(req[r], c), 1.0))
    s_lr = jnp.floor(lr * 0.5)

    # --- balanced resource ---
    cpu_f, mem_f = fracs
    diff = jnp.abs(cpu_f - mem_f)
    s_bal = jnp.floor((1.0 - diff) * jnp.float32(MAX_PRIORITY))
    s_bal = jnp.where((cpu_f >= 1.0) | (mem_f >= 1.0), 0.0, s_bal)

    return s_bp + jnp.float32(w_lr) * s_lr + jnp.float32(w_bal) * s_bal


def _make_kernel(R: int, TB: int, NS: int, JS: int, weights: ScoreWeights,
                 K: int = 0, absolute: Tuple[bool, ...] = (), G: int = 1):
    """Kernel factory — R resource lanes, TB tasks per grid step, NS node
    sublanes (nodes = NS*128), JS job sublanes, static plugin weights, K
    classes, of which those flagged in ``absolute`` (missing entries read
    False) are of the absolute kind, G grid steps.

    Gang commit/discard (drive_allocate_loop + gang_end_job, in one
    pass): consecutive rows of one job form a run, opened by its first
    row and closed when another job's row arrives, the pass ends, or a
    row fits no node.  A run needing two or more placements keeps a copy
    of the node state it began from; closing short of its need restores
    it, and the job is marked discarded in the per-job state plane.  A
    row that fits no node ends its job — its later rows are skipped —
    and records, at that step, how many nodes fail each predicate first
    (ops/kernels.py step_reason_counts).

    Incremental repeated-row fast path: a placement at step k-1 changes
    node state at ONE node, so when task k's row (resreq lanes + class +
    active + class columns; the job column aside) equals task k-1's,
    every node's masked score is unchanged except the selected node's —
    the kernel keeps the masked-score plane in VMEM scratch and
    recomputes only the [1, 128] sublane row holding the previous pick.
    Gangs submit replicas with identical rows (job.go:43-60: one
    PodTemplate per task group), so at gang_size g, (g-1)/g of all steps
    take the fast path; a rollback invalidates the plane.  Every
    recomputation uses the same elementwise formulas, so results stay
    bit-identical to the full per-step recompute (and to kernels.py
    schedule_episodes).  The spread mask, which a placement moves across
    a whole domain, is kept out of that plane: it is applied to it
    afresh every step."""

    TBS = TB // LANES
    kinds = tuple(absolute) + (False,) * (K - len(absolute))
    RC = R + 3 + 2 * K  # task row width
    JC = RC - 1  # job column
    SP = 3 * R + 2  # first spread plane of nd
    OK = float(N_EXPLAIN_REASONS)  # class code of a passing (class, node) pair
    PAD = float(N_EXPLAIN_REASONS + 1)  # class code of a padding node

    def kernel(
        tol_ref,  # SMEM [1, R]
        task_ref,  # VMEM [TB, RC] — resreq lanes, class, active, thr[K], inc[K], job
        cf_ref,  # VMEM [C, NS, 128] f32 class codes (kernels.class_reason_codes)
        nd_ref,  # VMEM [3R+2+3K, NS, 128] — base | alloc | used0 | count0, maxt | dom[K] | elig[K] | scount0[K]
        maxal_ref,  # VMEM [R, NS, 128] max(alloc, 1)
        allocpos_ref,  # VMEM [R, NS, 128] f32 (alloc > 0)
        need_ref,  # VMEM [JS, 128] f32 — each job's need (minAvailable - ready)
        chosen_ref,  # out VMEM [1, TBS, 128] i32 — placed node, -1 failed, -2 skipped
        why_ref,  # out VMEM [1, P, TBS, 128] i32 — failing steps' reason counts
        jst_ref,  # out VMEM [JS, 128] f32 — per-job state (kernels.J_*), resident
        used_s,  # scratch VMEM [R, NS, 128]
        cnt_s,  # scratch VMEM [1, NS, 128]
        chosen_s,  # scratch VMEM [TBS, 128] i32
        masked_s,  # scratch VMEM [NS, 128] f32 — masked scores, kept current
        prev_s,  # scratch VMEM [1, RC] f32 — previous task row
        ctrl_s,  # scratch SMEM [5] i32 — have_prev, prev_best, run job, run placed, run need
        sv_used,  # scratch VMEM [R, NS, 128] — node state the open run began from
        sv_cnt,  # scratch VMEM [1, NS, 128]
        *spread_s,  # K > 0: scratch VMEM [K, NS, 128] f32 — domain counts, and its copy
    ):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            used_s[:] = nd_ref[2 * R : 3 * R]
            cnt_s[:] = nd_ref[3 * R : 3 * R + 1]
            if K:
                spread_s[0][:] = nd_ref[SP + 2 * K : SP + 3 * K]
            jst_ref[:] = jnp.zeros((JS, LANES), jnp.float32)
            ctrl_s[0] = 0
            ctrl_s[1] = -1
            ctrl_s[2] = -1
            ctrl_s[3] = 0
            ctrl_s[4] = 0

        why_ref[...] = jnp.zeros(why_ref.shape, jnp.int32)

        idxp = (
            jax.lax.broadcasted_iota(jnp.int32, (NS, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (NS, LANES), 1)
        )
        # scalar extraction one-hots over the task row (no SMEM scalar
        # loads — Mosaic would relocate the whole buffer into SMEM)
        row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, RC), 1)
        # chosen-plane write mask coordinates
        csub = jax.lax.broadcasted_iota(jnp.int32, (TBS, LANES), 0)
        clane = jax.lax.broadcasted_iota(jnp.int32, (TBS, LANES), 1)
        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def job_get(ref, j):
            return jnp.sum(jnp.where(lane1 == j % LANES, ref[pl.ds(j // LANES, 1), :], 0.0))

        def job_set(j, value):
            rowj = jst_ref[pl.ds(j // LANES, 1), :]
            jst_ref[pl.ds(j // LANES, 1), :] = jnp.where(lane1 == j % LANES, value, rowj)

        def close_run(placed):
            """End the open run: restore the node state and mark the job
            discarded when it closes short of its need, else mark it
            ready."""
            cur = ctrl_s[2]
            disc = placed < ctrl_s[4]

            @pl.when(jnp.logical_and(disc, placed > 0))
            def _():
                used_s[:] = sv_used[:]
                cnt_s[:] = sv_cnt[:]
                if K:
                    spread_s[0][:] = spread_s[1][:]
                ctrl_s[0] = 0  # the masked-score plane predates the restore

            job_set(cur, jnp.where(disc, -2.0, 1.0))
            ctrl_s[2] = -1

        def step(k, _):
            row = task_ref[pl.ds(k, 1), :]  # [1, RC]

            def col(r):
                return jnp.sum(jnp.where(row_lane == r, row, 0.0))

            act = col(R + 1) > 0.0
            job = col(JC).astype(jnp.int32)

            @pl.when(jnp.logical_and(act, jnp.logical_and(job != ctrl_s[2], ctrl_s[2] >= 0)))
            def _():
                close_run(ctrl_s[3])

            js = job_get(jst_ref, job)
            alive = jnp.logical_and(act, js >= 0.0)
            kmask = (csub == k // LANES) & (clane == k % LANES)

            @pl.when(jnp.logical_and(alive, job != ctrl_s[2]))
            def _start():
                need = jnp.where(js == 0.0, job_get(need_ref, job), 0.0).astype(jnp.int32)
                ctrl_s[2] = job
                ctrl_s[3] = 0
                ctrl_s[4] = need

                @pl.when(need >= 2)
                def _():
                    sv_used[:] = used_s[:]
                    sv_cnt[:] = cnt_s[:]
                    if K:
                        spread_s[1][:] = spread_s[0][:]

            @pl.when(jnp.logical_not(alive))
            def _skip():
                chosen_s[:] = jnp.where(kmask, jnp.int32(-2), chosen_s[:])

            @pl.when(alive)
            def _place():
                cls = col(R).astype(jnp.int32)
                rr = [col(r) for r in range(R)]

                have_prev = ctrl_s[0] > 0
                prev_best = ctrl_s[1]
                same = jnp.logical_and(
                    have_prev, jnp.all((row == prev_s[:]) | (row_lane == JC))
                )

                def fit_room(rowslice):
                    """(fit, room, req) over one node row-slice view."""
                    fit = None
                    req = []
                    for r in range(R):
                        used_r = rowslice(used_s, r)
                        idle_r = rowslice(nd_ref, r) - used_r
                        lane_ok = rr[r] < idle_r + tol_ref[0, r]
                        if r >= 2:
                            lane_ok = jnp.logical_or(lane_ok, rr[r] <= tol_ref[0, r])
                        fit = lane_ok if fit is None else jnp.logical_and(fit, lane_ok)
                        req.append(rr[r] + used_r)  # shared by all three scores
                    room = rowslice(cnt_s, 0) < rowslice(nd_ref, 3 * R + 1)
                    return fit, room, req

                def masked_row(rowslice):
                    """Masked score over one node row-slice view ([NS|1, 128])
                    — the single copy of the predicate + score arithmetic;
                    ``rowslice(ref_3d, plane)`` selects a plane row."""
                    fit, room, req = fit_room(rowslice)
                    feas = fit & room & (rowslice(cf_ref, cls) == OK)
                    total = score_planes(
                        rr,
                        req,
                        lambda r: rowslice(nd_ref, R + r),
                        lambda r: rowslice(maxal_ref, r),
                        lambda r: rowslice(allocpos_ref, r),
                        weights,
                        feas.shape,
                    )
                    return jnp.where(feas, total, -jnp.inf)

                @pl.when(jnp.logical_not(same))
                def _full():
                    masked_s[:] = masked_row(lambda ref, p: ref[p])

                @pl.when(jnp.logical_and(same, prev_best >= 0))
                def _inc():
                    bq = prev_best // LANES
                    masked_s[pl.ds(bq, 1), :] = masked_row(
                        lambda ref, p: ref[p, pl.ds(bq, 1), :]
                    )

                # --- spread and anti-affinity classes (kernels.spread_ok) ---
                masked = masked_s[:]
                fails = []
                for c in range(K):
                    thr = col(R + 2 + c)
                    count = spread_s[0][c]
                    dom = nd_ref[SP + c]
                    on = thr >= 0.0
                    if kinds[c]:
                        # the minimum is taken as 0; a node without the key passes
                        label_fail = None
                        skew_fail = on & (dom >= 0.0) & (count > thr)
                    else:
                        mn = jnp.min(jnp.where(nd_ref[SP + K + c] > 0.0, count, SPREAD_BIG))
                        label_fail = on & (dom < 0.0)
                        skew_fail = on & (dom >= 0.0) & (count - mn > thr)
                    fails.append((label_fail, skew_fail))
                    bad = skew_fail if label_fail is None else (label_fail | skew_fail)
                    masked = jnp.where(bad, -jnp.inf, masked)

                # --- lowest-index argmax + row-sliced state update ---
                m = jnp.max(masked)
                ok = jnp.isfinite(m)
                best = jnp.min(jnp.where(masked == m, idxp, INT_BIG))

                @pl.when(ok)
                def _update():
                    bq = best // LANES
                    selr = lane1 == best % LANES
                    for r in range(R):
                        used_s[r, pl.ds(bq, 1), :] = used_s[
                            r, pl.ds(bq, 1), :
                        ] + jnp.where(selr, rr[r], 0.0)
                    cnt_s[0, pl.ds(bq, 1), :] = cnt_s[0, pl.ds(bq, 1), :] + jnp.where(
                        selr, 1.0, 0.0
                    )
                    # kernels.spread_update: the chosen node's whole domain
                    for c in range(K):
                        dom_b = jnp.sum(jnp.where(selr, nd_ref[SP + c, pl.ds(bq, 1), :], 0.0))
                        elig_b = jnp.sum(jnp.where(selr, nd_ref[SP + K + c, pl.ds(bq, 1), :], 0.0))
                        add = col(R + 2 + K + c) * elig_b
                        spread_s[0][c] = spread_s[0][c] + jnp.where(
                            nd_ref[SP + c] == dom_b, add, 0.0
                        )
                    ctrl_s[3] = ctrl_s[3] + 1

                chosen_s[:] = jnp.where(
                    kmask, jnp.where(ok, best, jnp.int32(-1)), chosen_s[:]
                )
                prev_s[:] = row
                ctrl_s[0] = 1
                ctrl_s[1] = jnp.where(ok, best, jnp.int32(-1))

                @pl.when(jnp.logical_not(ok))
                def _fail():
                    # why no node fits, at this step (kernels.step_reason_counts)
                    fit, room, _ = fit_room(lambda ref, p: ref[p])
                    code = cf_ref[cls]
                    reason = jnp.where(~fit, float(R_FIT), jnp.where(~room, float(R_ROOM), code))
                    if K:
                        first = jnp.full((NS, LANES), OK, jnp.float32)
                        for c in reversed(range(K)):
                            label_fail, skew_fail = fails[c]
                            if kinds[c]:
                                continue
                            first = jnp.where(label_fail, float(R_SPREAD_LABEL),
                                              jnp.where(skew_fail, float(R_SPREAD), first))
                        anti = None
                        for c in range(K):
                            if kinds[c]:
                                anti = fails[c][1] if anti is None else (anti | fails[c][1])
                        if anti is not None:
                            first = jnp.where((first == OK) & anti, float(R_AFFINITY), first)
                        reason = jnp.where(reason == OK, first, reason)
                    reason = jnp.where(code == PAD, PAD, reason)
                    for p in range(N_EXPLAIN_REASONS):
                        n_p = jnp.sum(jnp.where(reason == float(p), 1, 0))
                        why_ref[0, p] = jnp.where(kmask, n_p, why_ref[0, p])
                    close_run(ctrl_s[3])
                    # a job that was ready when its task failed stays
                    # committed and ends: mark it so its later rows skip
                    job_set(job, jnp.where(job_get(jst_ref, job) > 0.0, -1.0,
                                           job_get(jst_ref, job)))

            return 0

        jax.lax.fori_loop(0, TB, step, 0)
        chosen_ref[0] = chosen_s[:]

        @pl.when(jnp.logical_and(i == G - 1, ctrl_s[2] >= 0))
        def _():
            close_run(ctrl_s[3])

    return kernel


def _pass_call(
    taskrow, cf, nd, maxal, allocpos, tol, need, weights, block_size, interpret,
    absolute=(),
):
    """Build + invoke the pallas_call for one pass →
    (placed[T_act], why[T_act, P], jstate[JS*128])."""
    T_act, RC = taskrow.shape
    R = tol.shape[1]
    K = (RC - R - 3) // 2
    C, NS, _ = cf.shape
    JS = need.shape[0]
    ND = nd.shape[0]
    assert ND == 3 * R + 2 + 3 * K
    TB = block_size
    assert TB % LANES == 0 and T_act % TB == 0
    TBS = TB // LANES
    G = T_act // TB
    P = N_EXPLAIN_REASONS
    kernel = _make_kernel(R, TB, NS, JS, weights, K, absolute, G)

    full = lambda *shape: pl.BlockSpec(
        shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.VMEM
    )
    chosen, why, jst = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, R), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((TB, RC), lambda i: (i, 0), memory_space=pltpu.VMEM),
            full(C, NS, LANES),
            full(ND, NS, LANES),
            full(R, NS, LANES),
            full(R, NS, LANES),
            full(JS, LANES),
        ],
        out_specs=[
            pl.BlockSpec((1, TBS, LANES), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, P, TBS, LANES), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM),
            full(JS, LANES),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, TBS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((G, P, TBS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((JS, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, NS, LANES), jnp.float32),
            pltpu.VMEM((1, NS, LANES), jnp.float32),
            pltpu.VMEM((TBS, LANES), jnp.int32),
            pltpu.VMEM((NS, LANES), jnp.float32),
            pltpu.VMEM((1, RC), jnp.float32),
            pltpu.SMEM((5,), jnp.int32),
            pltpu.VMEM((R, NS, LANES), jnp.float32),
            pltpu.VMEM((1, NS, LANES), jnp.float32),
        ] + ([pltpu.VMEM((K, NS, LANES), jnp.float32)] * 2 if K else []),
        compiler_params=pallas_compiler_params(),
        interpret=interpret,
    )(tol, taskrow, cf, nd, maxal, allocpos, need)
    why = jnp.transpose(why, (0, 2, 3, 1)).reshape(T_act, P)
    return chosen.reshape(T_act), why, jst.reshape(JS * LANES)


@functools.partial(
    jax.jit,
    static_argnames=("weights", "block_size", "interpret", "absolute"),
)
def schedule_session_pallas(
    taskrow: jnp.ndarray,  # [T_act, R+2+2K] f32 (active column ignored)
    cf_u8: jnp.ndarray,  # [C, NS, 128] u8 class codes
    nd: jnp.ndarray,  # [3R+2+3K, NS, 128]
    tol: jnp.ndarray,  # [1, R]
    task_job: jnp.ndarray,  # [T_act] i32 → job row
    job_min_avail: jnp.ndarray,  # [J_pad] i32
    job_ready: jnp.ndarray,  # [J_pad] i32
    active0: jnp.ndarray,  # [T_act] bool
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
    absolute: Tuple[bool, ...] = (),
):
    """Whole session on device → (assignment[T_act] committed node or
    -1, placed[T_act], why[T_act, P]) — kernels.ScanOutcome's fields:
    one pass with the per-job commit/discard inside the kernel, so the
    session pays one host→device→host round trip."""
    R = tol.shape[1]
    J = job_min_avail.shape[0]
    JS = -(-J // LANES)
    cf = cf_u8.astype(jnp.float32)
    alloc = nd[R : 2 * R]
    maxal = jnp.maximum(alloc, 1.0)
    allocpos = (alloc > 0.0).astype(jnp.float32)
    need = job_min_avail.astype(jnp.int32) - job_ready.astype(jnp.int32)
    need = jnp.minimum(need, 2**24).astype(jnp.float32)
    need = jnp.zeros((JS * LANES,), jnp.float32).at[:J].set(need).reshape(JS, LANES)
    tr = jnp.concatenate(
        [taskrow.at[:, R + 1].set(active0.astype(jnp.float32)),
         task_job.astype(jnp.float32)[:, None]], axis=1,
    )
    placed, why, jst = _pass_call(
        tr, cf, nd, maxal, allocpos, tol, need, weights, block_size, interpret,
        absolute,
    )
    committed = (placed >= 0) & (jst[task_job] != -2.0)
    return jnp.where(committed, placed, -1), placed, why


@functools.partial(
    jax.jit,
    static_argnames=("weights", "block_size", "interpret", "absolute"),
)
def schedule_session_pallas_packed(
    taskrow_ext: jnp.ndarray,  # [T_act, R+3+2K] — resreq, class, active0, spread, job
    cf_u8: jnp.ndarray,  # [C, NS, 128] u8
    nd: jnp.ndarray,  # [3R+2+3K, NS, 128]
    tol: jnp.ndarray,  # [1, R]
    jobs2: jnp.ndarray,  # [2, J_pad] i32 — min_available | ready_count
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
    absolute: Tuple[bool, ...] = (),
):
    """Transfer-packed entry: the per-task job row and initial active
    mask ride inside the task rows and the two job vectors ride one
    buffer, so a session ships FIVE host→device transfers instead of
    eight — each extra transfer pays the device-link round trip.
    Semantics identical to schedule_session_pallas (device-side
    unpack + delegation)."""
    R = tol.shape[1]
    taskrow = taskrow_ext[:, :-1]
    active0 = taskrow_ext[:, R + 1] > 0.0
    task_job = taskrow_ext[:, -1].astype(jnp.int32)
    return schedule_session_pallas(
        taskrow,
        cf_u8,
        nd,
        tol,
        task_job,
        jobs2[0],
        jobs2[1],
        active0,
        weights=weights,
        block_size=block_size,
        interpret=interpret,
        absolute=absolute,
    )


def _node_planes(arr: np.ndarray, NK: int) -> np.ndarray:
    """[N_pad, R] → [R, NS, 128] f32 planes over the first NK nodes
    (zero-padded when the snapshot's node pad is narrower than NK)."""
    NS = NK // LANES
    n = min(NK, arr.shape[0])
    wide = np.zeros((NK, arr.shape[1]), dtype=np.float32)
    wide[:n] = arr[:n]
    return np.ascontiguousarray(wide.T).reshape(-1, NS, LANES)


def prepare_pallas_arrays(
    snap: PackedSnapshot, block_size: int = 256
) -> Tuple[dict, int, int]:
    """Host-side packing into the kernel's plane layout (with K spread
    classes: task rows gain thr[K] | inc[K] after the active column, the
    node array dom[K] | elig[K] | count0[K] after maxt; the job column
    is appended on the device).

    Nodes are cut to NK = ceil(n_nodes/128)*128 (instead of the pow2
    padded width) — every per-step op is O(NK), so the tighter width is a
    direct speedup.  Tasks are cut to T_act = ceil(n_tasks/TB)*TB.
    """
    TB = block_size
    assert TB % LANES == 0, "block_size must be a multiple of 128"
    NK = max(LANES, -(-max(snap.n_nodes, 1) // LANES) * LANES)
    NS = NK // LANES
    NV = min(NK, snap.node_idle.shape[0])  # valid (snapshot-backed) rows
    T_pad = snap.task_resreq.shape[0]
    # Always a multiple of TB (the kernel grid requires it); taskrow
    # copying below handles T_act on either side of the snapshot's pad.
    T_act = max(TB, -(-max(snap.n_tasks, 1) // TB) * TB)
    R = snap.task_resreq.shape[1]

    task_cls, class_sel, class_tol = _feasibility_classes(snap)
    # class codes: the first of node_ok, selector bits ⊆ node labels,
    # node taints ⊆ tolerations each (class, node) pair fails, or
    # N_EXPLAIN_REASONS where it passes — schedule_episodes' [C, N]
    # plane; padding nodes (none past n_nodes) read N_EXPLAIN_REASONS + 1.
    NV = min(NV, snap.n_nodes)
    C = class_sel.shape[0]
    cf = np.full((C, NK), N_EXPLAIN_REASONS + 1, dtype=np.int32)
    cf[:, :NV] = class_reason_codes(
        class_sel, class_tol, snap.node_label_bits[:NV],
        snap.node_taint_bits[:NV], snap.node_ok[:NV], xp=np,
    )

    K = snap.spread_max_skew.shape[0] if snap.has_spread else 0
    taskrow = np.zeros((T_act, R + 2 + 2 * K), dtype=np.float32)
    n_copy = min(T_act, T_pad)
    taskrow[:n_copy, :R] = snap.task_resreq[:n_copy]
    taskrow[:n_copy, R] = task_cls[:n_copy].astype(np.float32)
    # active column filled by the caller
    spread_planes = []
    if K:
        thr, inc = spread_task_columns(snap, T_act)
        taskrow[:, R + 2 : R + 2 + K] = thr
        taskrow[:, R + 2 + K :] = inc
        spread_planes = [p.reshape(K, NS, LANES) for p in spread_node_planes(snap, NK)]

    # One stacked node array: base | alloc | used0 | count0, maxt — a
    # single host→device transfer (u8 feasibility likewise shrinks its
    # transfer 4x; both matter through a high-latency device link).
    nd = np.concatenate(
        [
            _node_planes(snap.node_idle + snap.node_used, NK),
            _node_planes(snap.node_alloc, NK),
            _node_planes(snap.node_used, NK),
            _node_planes(
                np.stack(
                    [
                        snap.node_task_count.astype(np.float32),
                        snap.node_max_tasks.astype(np.float32),
                    ],
                    axis=1,
                ),
                NK,
            ),
            *spread_planes,
        ]
    )
    arrays = dict(
        taskrow=taskrow,
        cf_u8=np.ascontiguousarray(
            cf.astype(np.uint8).reshape(C, NS, LANES)
        ),
        nd=nd,
        tol=snap.tolerance.reshape(1, R).astype(np.float32),
    )
    return arrays, T_act, NK


def pallas_vmem_bytes(snap: PackedSnapshot, block_size: int = 256) -> int:
    """Estimated VMEM footprint of the allocate kernel (inputs +
    scratch), consulted by the dispatcher: the footprint scales with the
    feasibility-class count C and node width NK, which task×node area
    alone does not capture (ADVICE r2)."""
    R = snap.task_resreq.shape[1]
    NK = max(LANES, -(-max(snap.n_nodes, 1) // LANES) * LANES)
    _, class_sel, _ = _feasibility_classes(snap)
    K = snap.spread_max_skew.shape[0] if snap.has_spread else 0
    return kernel_vmem_bytes(class_sel.shape[0], NK, R, block_size, K,
                             snap.job_min_available.shape[0])


def kernel_vmem_bytes(C: int, NK: int, R: int, block_size: int = 256, K: int = 0,
                      J: int = 0) -> int:
    """:func:`pallas_vmem_bytes` from the kernel's shape alone."""
    # cf + nd + maxal/allocpos + scratch (used, cnt, masked-score plane)
    # + Mosaic's temporaries for the full-width recompute.  Compiling for
    # v5e at R=2..8, C=1 and 64, the scoped allocation ran to about
    # C + 7R + 17 planes against the C + 14R + 6 estimated here
    # (tests/test_tpu_compile.py pins the admitted edge at R=2..4).  Each
    # spread class adds three node planes, its count scratch and about
    # four temporaries of the per-step mask.  The copy of the node state
    # a gang's run began from adds R + 1 planes and one per class.
    n_planes = C + (3 * R + 2) + 2 * R + (R + 2) + (8 * R + 2) + 8 * K + (R + 1 + K)
    # task block streams as [TB, R+3+2K] → tiled to 128 lanes, and the
    # failing steps' reason counts as [P, TB], both double-buffered; the
    # job need and state planes are [J/128, 128] each
    job_planes = 2 * (-(-J // LANES)) * LANES * 4
    return (n_planes * NK * 4 + 2 * block_size * LANES * 4
            + 2 * N_EXPLAIN_REASONS * block_size * 4 + job_planes)


@functools.partial(
    jax.jit,
    static_argnames=(
        "T_rows", "R", "U", "C", "ND", "NS", "JP", "K",
        "weights", "block_size", "interpret", "absolute",
    ),
)
def schedule_session_pallas_buf(
    session_buf: jnp.ndarray,  # uint8 — header(i32) | tol | templates |
    #                            row_id(u16) | job(u16) | jobs2(i32)
    cluster_buf: jnp.ndarray,  # uint8 — cf_u8 | nd(f32)
    T_rows: int, R: int, U: int, C: int, ND: int, NS: int, JP: int,
    K: int = 0,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
    absolute: Tuple[bool, ...] = (),
):
    """Two-buffer entry: the per-SESSION payload and the per-CLUSTER
    payload (class feasibility + node planes) arrive as two byte
    buffers, bitcast-unpacked on device.  The cluster buffer is
    content-addressed and cached device-side by run_packed_pallas, so
    steady-state sessions ship ONE transfer — and that transfer carries
    DEDUPLICATED task-row templates plus u16 per-task indices instead of
    full f32 rows (gang replicas stamped from one PodTemplate share a
    row, so the 50k-task headline payload compresses ~6x; the device
    link's bandwidth was ~96% of session e2e)."""
    o = 0
    hdr = jax.lax.bitcast_convert_type(
        jax.lax.dynamic_slice_in_dim(session_buf, o, 4).reshape(1, 4), jnp.int32
    )
    n_act = hdr[0]
    o += 4
    W = R + 1 + 2 * K  # template row: resreq lanes, class, spread columns
    tol_b = jax.lax.dynamic_slice_in_dim(session_buf, o, R * 4); o += R * 4
    tpl_b = jax.lax.dynamic_slice_in_dim(session_buf, o, U * W * 4)
    o += U * W * 4
    rid_b = jax.lax.dynamic_slice_in_dim(session_buf, o, T_rows * 2)
    o += T_rows * 2
    tj_b = jax.lax.dynamic_slice_in_dim(session_buf, o, T_rows * 2)
    o += T_rows * 2
    j_b = jax.lax.dynamic_slice_in_dim(session_buf, o, 2 * JP * 4)

    tol = jax.lax.bitcast_convert_type(tol_b.reshape(-1, 4), jnp.float32).reshape(1, R)
    templates = jax.lax.bitcast_convert_type(
        tpl_b.reshape(-1, 4), jnp.float32
    ).reshape(U, W)
    row_id = jax.lax.bitcast_convert_type(
        rid_b.reshape(-1, 2), jnp.uint16
    ).astype(jnp.int32)
    task_job = jax.lax.bitcast_convert_type(
        tj_b.reshape(-1, 2), jnp.uint16
    ).astype(jnp.int32)
    jobs2 = jax.lax.bitcast_convert_type(
        j_b.reshape(-1, 4), jnp.int32
    ).reshape(2, JP)

    # reconstruct the full task rows device-side: template gather +
    # active column (first n_act tasks) + job column
    rows = templates[row_id]  # [T_rows, W]
    active = (jnp.arange(T_rows) < n_act).astype(jnp.float32)
    taskrow_ext = jnp.concatenate(
        [rows[:, : R + 1], active[:, None], rows[:, R + 1 :],
         task_job.astype(jnp.float32)[:, None]], axis=1
    )

    cf_u8 = jax.lax.dynamic_slice_in_dim(cluster_buf, 0, C * NS * LANES).reshape(
        C, NS, LANES
    )
    nd_b = jax.lax.dynamic_slice_in_dim(
        cluster_buf, C * NS * LANES, ND * NS * LANES * 4
    )
    nd = jax.lax.bitcast_convert_type(
        nd_b.reshape(-1, 4), jnp.float32
    ).reshape(ND, NS, LANES)

    return schedule_session_pallas_packed(
        taskrow_ext, cf_u8, nd, tol, jobs2,
        weights=weights, block_size=block_size,
        interpret=interpret, absolute=absolute,
    )


#: device-resident cluster planes, keyed by content fingerprint — nodes
#: change slowly relative to the 1s session cadence, so steady-state
#: sessions skip re-shipping them entirely (SURVEY §7 hard-part 5: the
#: per-cycle deep copy the reference pays, retired on the device side)
_CLUSTER_CACHE: "dict" = {}
_CLUSTER_CACHE_MAX = 4


def _cached_cluster_buf(cf_u8: np.ndarray, nd: np.ndarray):
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    h.update(cf_u8.tobytes())
    h.update(nd.tobytes())
    key = (cf_u8.shape, nd.shape, h.digest())
    hit = _CLUSTER_CACHE.get(key)
    if hit is not None:
        return hit
    buf = np.concatenate([
        np.ascontiguousarray(cf_u8).ravel().view(np.uint8),
        np.ascontiguousarray(nd).view(np.uint8).ravel(),
    ])
    dev = jax.device_put(jnp.asarray(buf))
    if len(_CLUSTER_CACHE) >= _CLUSTER_CACHE_MAX:
        _CLUSTER_CACHE.pop(next(iter(_CLUSTER_CACHE)))
    _CLUSTER_CACHE[key] = dev
    return dev


def _template_rows(rows: np.ndarray):
    """(first_idx, inverse) over distinct task rows.  Column-cascaded 1D
    uniques (the _feasibility_classes trick — ~5x cheaper than a
    void-key sort at 50k rows); float columns compare by BIT pattern,
    which equals value equality here (resreq lanes and class ids are
    non-negative finite, no -0.0).  Deliberately NOT memoized: the dedup
    is a real per-session host cost every cycle pays, and hiding it
    behind a cache would both misreport benchmarks and serve stale rows
    for in-place-mutated snapshots."""
    bits = rows.view(np.uint32)
    T, Wc = bits.shape
    code = np.zeros(T, dtype=np.int64)
    for c in range(Wc):
        u, inv = np.unique(bits[:, c], return_inverse=True)
        code = code * np.int64(len(u)) + inv
        if c < Wc - 1:
            _, code = np.unique(code, return_inverse=True)
            code = code.astype(np.int64)
    uc, inverse = np.unique(code, return_inverse=True)
    first = np.full(len(uc), T, dtype=np.int64)
    np.minimum.at(first, inverse, np.arange(T, dtype=np.int64))
    return first, inverse.astype(np.int64)


def _u_pad(U: int) -> int:
    p = 8
    while p < U:
        p *= 2
    return p


def pallas_session_payload_bytes(snap: PackedSnapshot, block_size: int = 256) -> int:
    """Steady-state per-session transfer volume for run_packed_pallas
    (the deduplicated session buffer incl. template padding; cluster
    planes ride the device-resident cache).  Used by bench.py's
    relay-floor estimate so the floor models what the session actually
    ships.  Builds only the task rows (not the node planes)."""
    TB = block_size
    T_pad = snap.task_resreq.shape[0]
    T_rows = max(TB, -(-max(snap.n_tasks, 1) // TB) * TB)
    R = snap.task_resreq.shape[1]
    task_cls, _, _ = _feasibility_classes(snap)
    K = snap.spread_max_skew.shape[0] if snap.has_spread else 0
    rows = np.zeros((T_rows, R + 1 + 2 * K), dtype=np.float32)
    n_copy = min(T_rows, T_pad)
    rows[:n_copy, :R] = snap.task_resreq[:n_copy]
    rows[:n_copy, R] = task_cls[:n_copy].astype(np.float32)
    if K:
        rows[:, R + 1 :] = np.concatenate(spread_task_columns(snap, T_rows), axis=1)
    first_idx, _ = _template_rows(rows)
    U = int(first_idx.shape[0])
    JP = snap.job_min_available.shape[0]
    n_tj = min(T_rows, snap.task_job.shape[0])
    if U >= 2**16 or JP >= 2**16 or int(snap.task_job[:n_tj].max(initial=0)) >= 2**16:
        # degenerate diversity: full f32 rows ship (5-transfer path)
        return T_rows * (R + 3 + 2 * K) * 4 + R * 4 + 2 * JP * 4
    return 4 + R * 4 + _u_pad(U) * (R + 1 + 2 * K) * 4 + T_rows * 4 + 2 * JP * 4


def make_session_dispatch(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
    prestage: bool = False,
):
    """Pack once; return ``(dispatch, T_act)`` where ``dispatch()``
    enqueues the fused session kernel and returns the (async) device
    result.  ``prestage=True`` device_puts the session buffer up front so
    repeated dispatches measure pure device compute — the bench pipelines
    K dispatches before one sync to amortize link RTT out of the compute
    estimate.  run_packed_pallas uses prestage=False: the
    per-session transfer is part of real session latency."""
    if not f32_lr_exact(snap):
        # Outside the f32 floor-division exactness envelope — the caller
        # (run_packed_auto) routes such sessions to the XLA int path.
        raise ValueError("node capacity outside f32-exact envelope")

    arrays, T_act, _ = prepare_pallas_arrays(snap, block_size)

    T_rows = arrays["taskrow"].shape[0]
    R = arrays["tol"].shape[1]
    K = (arrays["taskrow"].shape[1] - R - 2) // 2
    absolute = tuple(bool(a) for a in class_absolute(snap)) if K else ()
    n_act = min(snap.n_tasks, T_act)
    jobs2 = np.stack(
        [
            snap.job_min_available.astype(np.int32),
            snap.job_ready_count.astype(np.int32),
        ]
    )
    JP = jobs2.shape[1]

    # deduplicate (resreq lanes, class, spread columns) rows — every
    # column but the active one — into templates + u16 ids
    tr = arrays["taskrow"]
    rows = np.ascontiguousarray(np.concatenate([tr[:, : R + 1], tr[:, R + 2 :]], axis=1))
    first_idx, inv = _template_rows(rows)
    U = int(first_idx.shape[0])

    task_job16 = np.zeros(T_rows, dtype=np.uint16)
    n_tj = min(T_act, snap.task_job.shape[0])
    if U >= 2**16 or JP >= 2**16 or int(snap.task_job[:n_tj].max(initial=0)) >= 2**16:
        # degenerate row diversity — ship full rows the old 5-transfer way
        taskrow_ext = np.zeros((T_rows, R + 3 + 2 * K), np.float32)
        taskrow_ext[:, : R + 1] = rows[:, : R + 1]
        taskrow_ext[:, R + 2 : -1] = rows[:, R + 1 :]
        taskrow_ext[:n_act, R + 1] = 1.0
        taskrow_ext[:n_tj, -1] = snap.task_job[:n_tj].astype(np.float32)
        args5 = (taskrow_ext, arrays["cf_u8"], arrays["nd"],
                 arrays["tol"], jobs2)
        if prestage:
            args5 = tuple(jax.device_put(jnp.asarray(a)) for a in args5)

        def dispatch():
            return schedule_session_pallas_packed(
                *(jnp.asarray(a) for a in args5),
                weights=weights, block_size=block_size,
                interpret=interpret, absolute=absolute,
            )
    else:
        task_job16[:n_tj] = snap.task_job[:n_tj].astype(np.uint16)
        # pad U to a power-of-two bucket: U is a static jit arg AND sizes
        # the buffer, so an unpadded count would retrace the fused kernel
        # whenever the distinct-row count drifts between sessions (zero
        # template rows are inert — no row_id points at them)
        U_pad = _u_pad(U)
        templates = np.zeros((U_pad, R + 1 + 2 * K), dtype=np.float32)
        templates[:U] = rows[first_idx]
        session_buf = np.concatenate([
            np.array([n_act], dtype=np.int32).view(np.uint8),
            np.ascontiguousarray(arrays["tol"]).view(np.uint8).ravel(),
            templates.view(np.uint8).ravel(),
            inv.astype(np.uint16).view(np.uint8),
            task_job16.view(np.uint8),
            np.ascontiguousarray(jobs2).view(np.uint8).ravel(),
        ])
        cluster = _cached_cluster_buf(arrays["cf_u8"], arrays["nd"])
        if prestage:
            session_buf = jax.device_put(jnp.asarray(session_buf))
        kw = dict(
            T_rows=T_rows, R=R, U=U_pad, C=arrays["cf_u8"].shape[0],
            ND=arrays["nd"].shape[0], NS=arrays["nd"].shape[1], JP=JP, K=K,
            weights=weights, block_size=block_size,
            interpret=interpret, absolute=absolute,
        )

        def dispatch():
            return schedule_session_pallas_buf(
                jnp.asarray(session_buf), cluster, **kw)

    return dispatch, T_act


def run_outcome_pallas(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
) -> ScanOutcome:
    """Host wrapper: PackedSnapshot → ScanOutcome over the first
    ``n_tasks`` rows.  Packs, makes ONE fused device call (the per-job
    commit/discard included) and fetches the result.  The session ships
    as one byte buffer; cluster planes ride the device-resident cache."""
    dispatch, T_act = make_session_dispatch(
        snap, weights=weights, block_size=block_size, interpret=interpret,
    )
    assignment, placed, why = (np.asarray(x) for x in dispatch())
    n = min(snap.n_tasks, T_act)
    out = ScanOutcome(
        np.full(snap.n_tasks, -1, dtype=np.int32),
        np.full(snap.n_tasks, -2, dtype=np.int32),
        np.zeros((snap.n_tasks, N_EXPLAIN_REASONS), dtype=np.int32),
    )
    out.assignment[:n] = assignment[:n]
    out.placed[:n] = placed[:n]
    out.reasons[:n] = why[:n]
    return out


def run_packed_pallas(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 256,
    interpret: bool = False,
) -> np.ndarray:
    """PackedSnapshot → committed assignment[n_tasks] of
    :func:`run_outcome_pallas`."""
    return run_outcome_pallas(snap, weights, block_size, interpret).assignment
