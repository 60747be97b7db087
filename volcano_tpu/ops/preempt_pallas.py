"""Pallas TPU kernel for the preempt session pass.

Runs the ENTIRE in-queue preemption replay (the dense semantics of
ops/preempt_pack.py ``preempt_dense``, itself bindings-equivalent to the
host PreemptAction — reference pkg/scheduler/actions/preempt/
preempt.go:45-276) inside one ``pallas_call``:

  * victims live as node-major planes — K slots per node, each slot a
    [NS, 128] plane, slot order within a node = the eviction order —
    so per-attempt eligibility/sums/evictions are pure VPU plane ops,
    no gathers or scatters;
  * mutable state (future_idle, victim alive/gang-allowance, job
    ready/waiting counters, per-job task cursors, outputs) lives in
    VMEM scratch across the whole grid;
  * the host-packed static schedule streams in through the grid
    pipeline; each slot is one of BEGIN/ATTEMPT/END (phase 1, statement
    scoped — statement.go:309-337 rollback implemented as shadow-buffer
    save/restore) or BURN (phase 2, under-request sweep — see below);
  * node scores reuse the exact score block of the allocate kernel
    (pallas_session.score_planes) at static ``used`` — evict/pipeline
    never change it (see preempt_pack.py module doc).

Slot kinds: 0 BEGIN1, 1 ATTEMPT1, 2 END1, 5 BURN2, 9 pad.

Incremental repeated-row fast path (the round-4 allocate-kernel design,
ported): a successful attempt mutates node state at ONE node column
(evictions + the pipeline all land on the chosen node), so when attempt
k shares its (job, resreq row) with attempt k-1 — gang replicas are
schedule-contiguous and submit identical rows — the masked
validity+score plane is unchanged except in the [1, 128] sublane row
holding the previous pick.  The kernel keeps that plane in VMEM scratch
and recomputes only the dirty row.  The one non-local mutation is the
gang-allowance refresh after an eviction (it touches the victim job's
slots on EVERY node): a host-precomputed per-slot sensitivity flag
(``vsens`` — victim's job has an allowance that can actually change,
i.e. any sibling victim with min_available != 1) turns that into a
single row op; a sensitive eviction or a statement rollback invalidates
the cached plane and the next attempt recomputes in full.  Results are
bit-identical to the full recompute (same elementwise formulas).

Phase 2 (the under-request intra-job sweep, preempt.go:146-175)
compiles to a single BURN slot per (queue, job): under the supported
preemptable tier ({priority, gang, conformance} — enforced by
pack_preempt_session), an intra-job attempt can NEVER evict (victims of
the preemptor's own job have equal priority, and the priority plugin
admits strictly-lower only), so the host loop's net effect is exactly
"consume one pending task, break" — i.e. cursor += 1 when tasks remain.

Equivalence is proven against ``preempt_dense`` (and transitively the
host action) in tests/test_preempt_kernel.py; dispatch happens in
actions/jax_preempt.py via ops.dispatch.select_preempt_executor.
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
    DEFAULT_WEIGHTS,
    ScoreWeights,
)
from volcano_tpu.ops.pallas_session import LANES, score_planes
from volcano_tpu.ops.preempt_pack import PreemptPacked

INT_BIG = np.int32(2**31 - 1)

#: beyond this many distinct resreq rows, score inline instead of
#: unrolling per-class precompute at kernel init
SCORE_CLASS_CAP = 64

K_BEGIN1, K_ATT1, K_END1, K_BURN2, K_PAD = 0, 1, 2, 5, 9


def _score_class_rows(pk: PreemptPacked):
    """(distinct resreq rows, inverse) — memoized on the PreemptPacked
    (both the VMEM gate and array prep need it, once per session)."""
    cached = getattr(pk, "_score_class_cache", None)
    if cached is not None:
        return cached
    P = pk.base.n_tasks
    rows, inv = np.unique(pk.base.task_resreq[:P], axis=0, return_inverse=True)
    pk._score_class_cache = (rows, inv)
    return rows, inv


def _make_preempt_kernel(
    R: int, K: int, NS: int, JS: int, PS: int, SB: int, SC: int,
    weights: ScoreWeights,
):
    """Kernel factory — R resource lanes, K victim slots per node, NS node
    sublanes, JS job sublanes, PS preemptor sublanes, SB schedule slots
    per grid step, SC score-class planes (node scores are static for
    the whole pass — ``used`` never moves — so the per-class score plane
    is computed ONCE at init instead of ~35 VPU ops per attempt).
    ``SC`` is a PADDED bucket (bounds jit-cache churn); SC == 0 disables
    the precompute (too many distinct rows) and scores inline."""
    shape = (NS, LANES)

    def kernel(
        tol_ref,  # SMEM [1, R]
        sched_ref,  # SMEM [SB*4] i32 (grid-streamed, flat): slot s is
        #           (kind, job, task, pad) at s*4 — SMEM so slot headers
        #           are scalar reads, not one-hot plane reductions, and
        #           1-D so the window isn't lane-padded to 128
        ptask_ref,  # VMEM [P_pad, R+2] f32 — resreq lanes, feas class, score class
        screq_ref,  # VMEM [SC_pad, R] f32 — distinct resreq rows
        cf_ref,  # VMEM [C, NS, 128] f32 class feasibility (incl. node_ok)
        used_ref,  # VMEM [R, NS, 128] f32 (static across the pass)
        alloc_ref,  # VMEM [R, NS, 128] f32
        maxal_ref,  # VMEM [R, NS, 128] f32
        allocpos_ref,  # VMEM [R, NS, 128] f32
        fi0_ref,  # VMEM [R, NS, 128] f32 — future_idle at session open
        naux_ref,  # VMEM [2, NS, 128] f32 — ncount0, nmax
        vr_ref,  # VMEM [R*K, NS, 128] f32 — victim resreq (r*K + k)
        vjob_ref,  # VMEM [K, NS, 128] i32 — victim's job row
        vq_ref,  # VMEM [K, NS, 128] i32 — victim job's queue row
        vjp_ref,  # VMEM [K, NS, 128] i32 — victim job priority
        vjmin_ref,  # VMEM [K, NS, 128] f32 — victim job min_available
        vinit_ref,  # VMEM [2*K, NS, 128] f32 — galw0 | alive0
        vsens_ref,  # VMEM [K, NS, 128] f32 — evicting this victim can
        #           change a gang allowance somewhere (job has a sibling
        #           victim with min_available != 1) → invalidates the
        #           cached masked plane
        jobsf_ref,  # VMEM [2, JS, 128] f32 — ready0, waiting0
        jobsmem_ref,  # SMEM [3*JPAD] i32 — cursor0 | jqueue | jprio (flat)
        minav_ref,  # SMEM [JPAD] f32 — min_available as scalars
        evicted_out,  # out VMEM [K, NS, 128] i32
        pipelined_out,  # out VMEM [PS, 128] i32
        fi_s,  # scratch [R, NS, 128] f32
        ncnt_s,  # scratch [1, NS, 128] f32
        alive_s,  # scratch [K, NS, 128] f32
        galw_s,  # scratch [K, NS, 128] f32 — gang allowance per victim
        evic_s,  # scratch [K, NS, 128] i32
        ready_s,  # scratch [1, JS, 128] f32
        wait_s,  # scratch [1, JS, 128] f32
        cursor_s,  # SMEM scratch [JPAD] i32 — rollback-exempt, so pure
        #           scalar state (the host PQ pops have no undo)
        pipe_s,  # scratch [PS, 128] i32
        spre_s,  # scratch [SC_pad, NS, 128] f32 — per-class score planes
        masked_s,  # scratch [NS, 128] f32 — cached masked plane
        ctrl_s,  # SMEM scratch [5] i32 — valid, prev_job, prev_cls,
        #          prev_scl, dirty node (-1 = clean)
        fi_sh,  # shadow [R, NS, 128]
        ncnt_sh,  # shadow [1, NS, 128]
        alive_sh,  # shadow [K, NS, 128]
        galw_sh,  # shadow [K, NS, 128]
        evic_sh,  # shadow [K, NS, 128] i32
        ready_sh,  # shadow [1, JS, 128]
        wait_sh,  # shadow [1, JS, 128]
        pipe_sh,  # shadow [PS, 128] i32
    ):
        i = pl.program_id(0)
        G = pl.num_programs(0)

        @pl.when(i == 0)
        def _():
            fi_s[:] = fi0_ref[:]
            ncnt_s[:] = naux_ref[0:1]
            galw_s[:] = vinit_ref[0:K]
            alive_s[:] = vinit_ref[K : 2 * K]
            evic_s[:] = jnp.zeros((K, NS, LANES), jnp.int32)
            ready_s[:] = jobsf_ref[0:1]
            wait_s[:] = jobsf_ref[1:2]

            def _cp(k, _):
                cursor_s[k] = jobsmem_ref[k]
                return 0

            jax.lax.fori_loop(0, JS * LANES, _cp, 0)
            pipe_s[:] = jnp.full((PS, LANES), -1, jnp.int32)
            ctrl_s[0] = 0
            ctrl_s[4] = -1
            # precompute the static per-class score planes
            if SC:
                sc_lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
                for c in range(SC):
                    srow = screq_ref[c : c + 1, :]  # [1, R]
                    rr_c = [
                        jnp.sum(jnp.where(sc_lane == r, srow, 0.0))
                        for r in range(R)
                    ]
                    req_c = [rr_c[r] + used_ref[r] for r in range(R)]
                    spre_s[c] = score_planes(
                        rr_c,
                        req_c,
                        lambda r: alloc_ref[r],
                        lambda r: maxal_ref[r],
                        lambda r: allocpos_ref[r],
                        weights,
                        shape,
                    )

        idxp = (
            jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        )
        jidx = (
            jax.lax.broadcasted_iota(jnp.int32, (JS, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (JS, LANES), 1)
        )
        row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, R + 2), 1)

        # mutable job counters (ready/wait) live as VMEM planes (they are
        # shadow-copied on statement rollback); reads are one-hot sums.
        # STATIC job metadata and the rollback-exempt cursor live in SMEM
        # and are plain scalar loads.
        def jread_f(plane, j):
            return jnp.sum(jnp.where(jidx == j, plane, 0.0))

        JPAD = JS * LANES

        def jqueue_of(j):
            return jobsmem_ref[JPAD + j]

        def jprio_of(j):
            return jobsmem_ref[2 * JPAD + j]

        def pipelined_job(j):
            return (
                jread_f(wait_s[0], j) + jread_f(ready_s[0], j) >= minav_ref[j]
            )

        def save_shadow():
            fi_sh[:] = fi_s[:]
            ncnt_sh[:] = ncnt_s[:]
            alive_sh[:] = alive_s[:]
            galw_sh[:] = galw_s[:]
            evic_sh[:] = evic_s[:]
            ready_sh[:] = ready_s[:]
            wait_sh[:] = wait_s[:]
            pipe_sh[:] = pipe_s[:]

        def restore_shadow():
            fi_s[:] = fi_sh[:]
            ncnt_s[:] = ncnt_sh[:]
            alive_s[:] = alive_sh[:]
            galw_s[:] = galw_sh[:]
            evic_s[:] = evic_sh[:]
            ready_s[:] = ready_sh[:]
            wait_s[:] = wait_sh[:]
            pipe_s[:] = pipe_sh[:]
            ctrl_s[0] = 0  # rolled-back state invalidates the cached plane

        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def attempt(j, p, inter):
            """One _preempt try (preempt.go:181-259) for preemptor task p
            of job j.  ``inter``: phase-1 cross-job filter (same queue,
            different job) vs phase-2 intra-job filter.

            The masked validity+score plane lives in ``masked_s``: a full
            recompute happens only when the cached plane cannot be
            reused (different job/resreq row, or invalidated by rollback
            / a sensitive gang refresh); otherwise only the sublane row
            dirtied by the previous attempt is recomputed."""
            trow = ptask_ref[pl.ds(p, 1), :]  # [1, R+2]

            def col(r):
                return jnp.sum(jnp.where(row_lane == r, trow, 0.0))

            rr = [col(r) for r in range(R)]
            cls = col(R).astype(jnp.int32)
            scl = col(R + 1).astype(jnp.int32)
            pprio = jprio_of(j)
            jq = jqueue_of(j)

            def elig_view(k, rowslice):
                """Victim eligibility per slot k over a row view: alive ∩
                gang allowance ∩ strictly-lower job priority ∩ the
                phase's job/queue filter.  Fixed at attempt start —
                mid-attempt evictions don't re-rank (matches the host:
                victims list snapshot per node)."""
                e = (
                    (rowslice(alive_s, k) > 0.0)
                    & (rowslice(galw_s, k) > 0.0)
                    & (rowslice(vjp_ref, k) < pprio)
                )
                if inter:
                    e = e & (rowslice(vq_ref, k) == jq) & (
                        rowslice(vjob_ref, k) != j
                    )
                else:
                    e = e & (rowslice(vjob_ref, k) == j)
                return e

            def masked_rows(rowslice):
                """Masked validity+score over a row view ([NS|1, 128]) —
                the single copy of the validation arithmetic
                (preempt.go:261-276): victims exist + pod-count headroom
                + resreq fits future_idle + all eligible victims."""
                elig = [elig_view(k, rowslice) for k in range(K)]
                vsum = []
                for r in range(R):
                    acc = None
                    for k in range(K):
                        term = jnp.where(elig[k], rowslice(vr_ref, r * K + k), 0.0)
                        acc = term if acc is None else acc + term
                    vsum.append(acc)
                vcnt = None
                for k in range(K):
                    t = jnp.where(elig[k], 1.0, 0.0)
                    vcnt = t if vcnt is None else vcnt + t
                okl = None
                for r in range(R):
                    lane_ok = rr[r] < rowslice(fi_s, r) + vsum[r] + tol_ref[0, r]
                    if r >= 2:
                        lane_ok = lane_ok | (rr[r] <= tol_ref[0, r])
                    okl = lane_ok if okl is None else okl & lane_ok
                valid = (
                    (rowslice(cf_ref, cls) > 0.0)
                    & (rowslice(ncnt_s, 0) < rowslice(naux_ref, 1))
                    & (vcnt > 0.0)
                    & okl
                )
                # node scores at static used: precomputed per-class
                # plane, or inline when the class count exceeded the cap
                if SC:
                    total = rowslice(spre_s, scl)
                else:
                    req = [rr[r] + rowslice(used_ref, r) for r in range(R)]
                    total = score_planes(
                        rr,
                        req,
                        lambda r: rowslice(alloc_ref, r),
                        lambda r: rowslice(maxal_ref, r),
                        lambda r: rowslice(allocpos_ref, r),
                        weights,
                        valid.shape,
                    )
                return jnp.where(valid, total, -jnp.inf)

            if SC:
                same = (
                    (ctrl_s[0] > 0)
                    & (ctrl_s[1] == j)
                    & (ctrl_s[2] == cls)
                    & (ctrl_s[3] == scl)
                )
            else:
                same = jnp.bool_(False)

            @pl.when(jnp.logical_not(same))
            def _full():
                masked_s[:] = masked_rows(lambda ref, q: ref[q])

            @pl.when(same & (ctrl_s[4] >= 0))
            def _inc():
                dq = ctrl_s[4] // LANES
                masked_s[pl.ds(dq, 1), :] = masked_rows(
                    lambda ref, q: ref[q, pl.ds(dq, 1), :]
                )

            masked = masked_s[:]
            m = jnp.max(masked)
            okm = jnp.isfinite(m)
            nstar = jnp.min(jnp.where(masked == m, idxp, INT_BIG))

            @pl.when(okm)
            def _():
                bq = nstar // LANES
                selr = lane1 == nstar % LANES  # [1, 128] column mask

                def rowat(ref, q):
                    return ref[q, pl.ds(bq, 1), :]

                elig_row = [elig_view(k, rowat) for k in range(K)]
                # evict in slot order until the preemptor fits — exactly
                # the host's victims_queue drain (preempt.go:216-233),
                # all ops restricted to the chosen node's sublane row
                cum = [jnp.zeros((1, LANES), jnp.float32) for _ in range(R)]
                for k in range(K):
                    notfit = None
                    for r in range(R):
                        lane_bad = ~(
                            rr[r] < rowat(fi_s, r) + cum[r] + tol_ref[0, r]
                        )
                        if r >= 2:
                            lane_bad = lane_bad & ~(rr[r] <= tol_ref[0, r])
                        notfit = lane_bad if notfit is None else notfit | lane_bad
                    ev_k = elig_row[k] & selr & notfit  # ≤1 true element
                    for r in range(R):
                        cum[r] = cum[r] + jnp.where(ev_k, rowat(vr_ref, r * K + k), 0.0)
                    alive_s[k, pl.ds(bq, 1), :] = jnp.where(
                        ev_k, 0.0, rowat(alive_s, k)
                    )
                    evic_s[k, pl.ds(bq, 1), :] = jnp.where(
                        ev_k, 1, rowat(evic_s, k)
                    )
                    sens_k = jnp.max(jnp.where(ev_k, rowat(vsens_ref, k), 0.0))
                    ev_any = jnp.max(jnp.where(ev_k, 1, 0))

                    # gang bookkeeping for the evicted victim's job:
                    # ready -= 1, refresh its victims' allowances
                    # (gang.go:75-94 at the new ready count).  This is
                    # the one NON-LOCAL mutation — and for a
                    # non-sensitive job (every victim has min==1) the
                    # refresh provably rewrites identical values, and
                    # the ready count feeds nothing else (the pack
                    # guard refuses victim jobs that are also
                    # preemptors), so the whole block is skipped.
                    @pl.when((ev_any > 0) & (sens_k > 0.0))
                    def _():
                        j_e = jnp.sum(jnp.where(ev_k, rowat(vjob_ref, k), 0))
                        ready_s[0] = ready_s[0] - jnp.where(jidx == j_e, 1.0, 0.0)
                        rj = jread_f(ready_s[0], j_e)
                        for k2 in range(K):
                            refreshed = jnp.where(
                                (vjmin_ref[k2] == 1.0)
                                | (vjmin_ref[k2] <= rj - 1.0),
                                1.0,
                                0.0,
                            )
                            galw_s[k2] = jnp.where(
                                vjob_ref[k2] == j_e, refreshed, galw_s[k2]
                            )
                        # the cached masked plane is stale beyond this row
                        ctrl_s[0] = jnp.int32(-2)

                for r in range(R):
                    fi_s[r, pl.ds(bq, 1), :] = rowat(fi_s, r) + cum[r]

                # final fit at nstar (guaranteed by validation, kept as
                # the literal host check) → pipeline
                fitp = None
                for r in range(R):
                    lane_ok = rr[r] < rowat(fi_s, r) + tol_ref[0, r]
                    if r >= 2:
                        lane_ok = lane_ok | (rr[r] <= tol_ref[0, r])
                    fitp = lane_ok if fitp is None else fitp & lane_ok
                okfit = jnp.max(jnp.where(selr & fitp, 1, 0)) > 0

                @pl.when(okfit)
                def _():
                    for r in range(R):
                        fi_s[r, pl.ds(bq, 1), :] = rowat(fi_s, r) - jnp.where(
                            selr, rr[r], 0.0
                        )
                    ncnt_s[0, pl.ds(bq, 1), :] = rowat(ncnt_s, 0) + jnp.where(
                        selr, 1.0, 0.0
                    )
                    wait_s[0] = wait_s[0] + jnp.where(jidx == j, 1.0, 0.0)
                    pq = p // LANES
                    pipe_s[pl.ds(pq, 1), :] = jnp.where(
                        lane1 == p % LANES, nstar, pipe_s[pl.ds(pq, 1), :]
                    )

            # cache bookkeeping: valid unless a sensitive refresh fired
            # (ctrl_s[0] == -2 sentinel written inside the drain); dirty
            # column = the touched node on success, clean otherwise
            invalidated = ctrl_s[0] == -2
            ctrl_s[0] = jnp.where(invalidated, 0, 1)
            ctrl_s[1] = j
            ctrl_s[2] = cls
            ctrl_s[3] = scl
            ctrl_s[4] = jnp.where(okm, nstar, jnp.int32(-1))

        # ---- schedule slot loop ----
        def slot(s, _):
            kind = sched_ref[s * 4 + 0]
            j = sched_ref[s * 4 + 1]
            p = sched_ref[s * 4 + 2]

            @pl.when(kind == K_BEGIN1)
            def _():
                save_shadow()

            @pl.when(kind == K_ATT1)
            def _():
                cur = cursor_s[j]
                fire = (cur == p) & ~pipelined_job(j)

                @pl.when(fire)
                def _():
                    cursor_s[j] = cur + 1
                    attempt(j, p, inter=True)

            @pl.when(kind == K_END1)
            def _():
                @pl.when(~pipelined_job(j))
                def _():
                    restore_shadow()

            @pl.when(kind == K_BURN2)
            def _():
                # phase-2 sweep for one job: consume one pending task if
                # any remain (see module docstring — the attempt itself
                # provably fails under the supported tier, so only the
                # cursor moves).  Slot col 2 carries job_ptask_end.
                cur = cursor_s[j]

                @pl.when(cur < p)
                def _():
                    cursor_s[j] = cur + 1

            return 0

        jax.lax.fori_loop(0, SB, slot, 0)

        @pl.when(i == G - 1)
        def _():
            evicted_out[:] = evic_s[:]
            pipelined_out[:] = pipe_s[:]

    return kernel


def _node_plane(vals: np.ndarray, NK: int) -> np.ndarray:
    """[N] → [NS, 128] f32/i32 plane (zero pad)."""
    NS = NK // LANES
    out = np.zeros(NK, dtype=vals.dtype)
    n = min(NK, vals.shape[0])
    out[:n] = vals[:n]
    return out.reshape(NS, LANES)


def build_schedule_slots(pk: PreemptPacked) -> np.ndarray:
    """Expand pk.schedule (phase, job) rows into kernel slots [S, 4] i32.
    Phase 1: BEGIN1, one ATT1 per job task offset (the cursor guard makes
    consumed offsets no-ops), END1.  Phase 2: a single BURN slot per
    (queue, job) carrying job_ptask_end in col 2 — see the module
    docstring for why the under-request sweep reduces to a cursor burn."""
    if pk.schedule.shape[0] == 0:
        return np.zeros((0, 4), np.int32)
    phases = pk.schedule[:, 0].astype(np.int64)
    jrows = pk.schedule[:, 1].astype(np.int64)
    starts = pk.job_ptask_start[jrows].astype(np.int64)
    ends = pk.job_ptask_end[jrows].astype(np.int64)
    ntasks = np.maximum(ends - starts, 0)
    # slots per schedule row: phase 1 → BEGIN + tasks + END; phase 2 → 1
    row_slots = np.where(phases == 1, ntasks + 2, 1)
    offsets = np.concatenate([[0], np.cumsum(row_slots)])
    S = int(offsets[-1])
    out = np.zeros((S, 4), dtype=np.int32)

    p1 = phases == 1
    out[offsets[:-1][p1], 0] = K_BEGIN1
    out[offsets[:-1][p1], 1] = jrows[p1]
    end_pos = offsets[1:][p1] - 1
    out[end_pos, 0] = K_END1
    out[end_pos, 1] = jrows[p1]
    # ATT1 runs: for each phase-1 row, positions offset+1 .. offset+n
    att_total = int(ntasks[p1].sum())
    if att_total:
        att_rows = np.repeat(np.flatnonzero(p1), ntasks[p1])
        within = np.arange(att_total) - np.repeat(
            np.concatenate([[0], np.cumsum(ntasks[p1])])[:-1], ntasks[p1]
        )
        att_pos = (offsets[:-1][p1].repeat(ntasks[p1]) + 1 + within).astype(np.int64)
        out[att_pos, 0] = K_ATT1
        out[att_pos, 1] = jrows[att_rows]
        out[att_pos, 2] = (starts[att_rows] + within).astype(np.int32)
    p2 = ~p1
    out[offsets[:-1][p2], 0] = K_BURN2
    out[offsets[:-1][p2], 1] = jrows[p2]
    out[offsets[:-1][p2], 2] = ends[p2].astype(np.int32)
    return out


def prepare_preempt_arrays(pk: PreemptPacked) -> Tuple[dict, dict, np.ndarray]:
    """Host-side packing of a PreemptPacked into the kernel's plane
    layout → (arrays, dims, vic_slot) where vic_slot[i] is victim i's
    k-slot on its node (needed to unpack the evicted output planes)."""
    base = pk.base
    R = base.task_resreq.shape[1]
    P = max(base.n_tasks, 1)
    N = base.n_nodes
    NK = max(LANES, -(-max(N, 1) // LANES) * LANES)
    NS = NK // LANES
    NV = min(NK, base.node_idle.shape[0])

    # victim slots: k-th victim of each node, in eviction order (the
    # order pack_preempt_session appended them).  Fully vectorized —
    # the Python per-victim loop was ~0.5s at 90k victims, dominating
    # the whole device pass.
    V = pk.n_victims
    vnode = pk.vic_node[:V].astype(np.int64)
    # slot index = position within the victim's node group, preserving
    # input order (stable argsort of node, then rank within group)
    order = np.argsort(vnode, kind="stable")
    sorted_nodes = vnode[order]
    vic_slot = np.zeros(max(V, 1), dtype=np.int64)
    if V:
        new_grp = np.concatenate([[True], sorted_nodes[1:] != sorted_nodes[:-1]])
        starts = np.flatnonzero(new_grp)
        group_start = np.repeat(starts, np.diff(np.append(starts, V)))
        vic_slot[order] = np.arange(V) - group_start
    per_node_max = np.bincount(vnode, minlength=1).max(initial=0) if V else 0
    K = int(max(1, per_node_max))

    # Only vr + vjob ship (the other victim planes — vq/vjp/vjmin/galw0/
    # alive0/vsens — derive on DEVICE from the tiny per-job tables via
    # gathers: every transferred byte rides the device link, and victim
    # planes were ~2/3 of the pass's bytes).  Empty slots carry vjob=-1.
    vr = np.zeros((R * K, NK), dtype=np.float32)
    vjob = np.full((K, NK), -1, dtype=np.int32)
    job_sens = np.zeros(max(pk.n_jobs, 1), dtype=bool)
    if V:
        ks = vic_slot[:V]
        jrows = pk.vic_job[:V]
        for r in range(R):
            vr[r * K + ks, vnode] = pk.vic_resreq[:V, r]
        vjob[ks, vnode] = jrows
        # sensitivity: evicting a victim of job j can change an allowance
        # iff some victim of j has min_available != 1 (allowances of
        # min==1 victims refresh to 1 — a no-op)
        np.logical_or.at(job_sens, jrows, pk.job_min_avail[jrows] != 1)
    vr = vr.reshape(R * K, NS, LANES)
    vjob = vjob.reshape(K, NS, LANES)

    # class feasibility planes (same construction as the allocate kernel)
    task_cls, class_sel, class_tol = _feasibility_classes(base)
    node_labels = base.node_label_bits[:NV]
    node_taints = base.node_taint_bits[:NV]
    sel_ok = ((class_sel[:, None, :] & ~node_labels[None, :, :]) == 0).all(-1)
    tol_ok = ((node_taints[None, :, :] & ~class_tol[:, None, :]) == 0).all(-1)
    C = class_sel.shape[0]
    cf = np.zeros((C, NK), dtype=np.float32)
    cf[:, :NV] = sel_ok & tol_ok & base.node_ok[None, :NV]

    P_pad = -(-P // 8) * 8
    ptask = np.zeros((P_pad, R + 2), dtype=np.float32)
    n_copy = min(P_pad, base.task_resreq.shape[0])
    ptask[:n_copy, :R] = base.task_resreq[:n_copy]
    ptask[: min(P_pad, task_cls.shape[0]), R] = task_cls[
        : min(P_pad, task_cls.shape[0])
    ].astype(np.float32)

    # score classes: distinct resreq rows (node scores are static per
    # pass, so one plane per distinct row is computed at kernel init).
    # SC is bucketed to a power of two (bounds jit-cache churn on
    # heterogeneous request mixes) and capped: past the cap the kernel
    # scores inline (SC=0) instead of unrolling a huge init loop.
    screq_rows, sc_inv = _score_class_rows(pk)
    n_classes = screq_rows.shape[0]
    if n_classes <= SCORE_CLASS_CAP:
        SC = 8
        while SC < n_classes:
            SC *= 2
        ptask[:P, R + 1] = sc_inv.astype(np.float32)
    else:
        SC = 0
    screq = np.zeros((max(SC, 8), R), dtype=np.float32)
    if SC:
        screq[:n_classes] = screq_rows

    def planes(arr2d):  # [N_pad, R] → [R, NS, 128]
        wide = np.zeros((NK, R), dtype=np.float32)
        n = min(NK, arr2d.shape[0])
        wide[:n] = arr2d[:n]
        return np.ascontiguousarray(wide.T).reshape(R, NS, LANES)

    alloc = planes(base.node_alloc)
    used = planes(base.node_used)

    J = max(pk.n_jobs, 1)
    JS = -(-J // LANES)

    def jflat(vals, dtype):
        out = np.zeros(JS * LANES, dtype=dtype)
        out[: vals.shape[0]] = vals
        return out

    def jplane(vals, dtype):
        return jflat(vals, dtype).reshape(JS, LANES)

    jobsf = np.stack(
        [
            jplane(pk.job_ready0.astype(np.float32), np.float32),
            jplane(pk.job_waiting0.astype(np.float32), np.float32),
        ]
    )
    jobsmem = np.concatenate(
        [
            jflat(pk.job_ptask_start.astype(np.int32), np.int32),
            jflat(pk.job_queue.astype(np.int32), np.int32),
            jflat(
                np.clip(pk.job_prio, -(2**31), 2**31 - 1).astype(np.int32),
                np.int32,
            ),
        ]
    )
    minav = jflat(pk.job_min_avail.astype(np.float32), np.float32)

    PS = -(-P // LANES)
    naux = np.stack(
        [
            _node_plane(base.node_task_count.astype(np.float32), NK),
            _node_plane(base.node_max_tasks.astype(np.float32), NK),
        ]
    )
    # Single stacked f32/i32 node-plane buffers: ONE host→device transfer
    # each instead of ~14 (each transfer pays the device-link round trip;
    # maxal/allocpos are derived on device from alloc).  Row layout:
    #   f32: cf[C] | used[R] | alloc[R] | fi0[R] | naux[2] | vr[R*K]
    #   (victim metadata planes — vq/vjp/vjmin/galw0/alive0/vsens — are
    #   DERIVED on device from vjob + the per-job tables; see
    #   _preempt_call)
    #   i32: vjob[K] (-1 = empty slot)
    fstack = np.concatenate(
        [
            np.ascontiguousarray(cf.reshape(C, NS, LANES)),
            used,
            alloc,
            planes(pk.node_fi0),
            naux,
            vr,
        ]
    )
    arrays = dict(
        tol=base.tolerance.reshape(1, R).astype(np.float32),
        ptask=ptask,
        screq=screq,
        fstack=fstack,
        istack=vjob,
        jobsf=jobsf,
        jobsmem=jobsmem,
        minav=minav,
        jsens=jflat(job_sens.astype(np.float32), np.float32),
    )
    dims = dict(R=R, K=K, NS=NS, JS=JS, PS=PS, C=C, NK=NK, SC=SC)
    return arrays, dims, vic_slot


@functools.partial(
    jax.jit,
    static_argnames=(
        "R", "K", "C", "NS", "JS", "PS", "SB", "SC", "S4", "P_pad",
        "SC_rows", "weights", "interpret"
    ),
)
def _preempt_call(
    buf,  # uint8 [total] — EVERY kernel operand in one transfer (each
    #       host→device array pays its own round trip)
    R, K, C, NS, JS, PS, SB, SC, S4, P_pad, SC_rows, weights, interpret,
):
    S = S4 // 4  # sched is flat [S_pad*4]
    G = S // SB
    kernel = _make_preempt_kernel(R, K, NS, JS, PS, SB, SC, weights)
    JPAD = JS * LANES
    FROWS = C + 3 * R + 2 + R * K

    # device-side unpack: byte slices bitcast to f32/i32 (XLA ops)
    off = [0]

    def take(n_elems, dtype):
        nbytes = n_elems * 4
        sl = jax.lax.dynamic_slice_in_dim(buf, off[0], nbytes)
        off[0] += nbytes
        return jax.lax.bitcast_convert_type(sl.reshape(-1, 4), dtype)

    tol = take(R, jnp.float32).reshape(1, R)
    ptask = take(P_pad * (R + 2), jnp.float32).reshape(P_pad, R + 2)
    screq = take(SC_rows * R, jnp.float32).reshape(SC_rows, R)
    fstack = take(FROWS * NS * LANES, jnp.float32).reshape(FROWS, NS, LANES)
    jobsf = take(2 * JS * LANES, jnp.float32).reshape(2, JS, LANES)
    minav = take(JPAD, jnp.float32)
    jsens = take(JPAD, jnp.float32)
    sched = take(S4, jnp.int32)
    vjob = take(K * NS * LANES, jnp.int32).reshape(K, NS, LANES)
    jobsmem = take(3 * JPAD, jnp.int32)

    o = 0
    cf = fstack[o : o + C]; o += C
    used = fstack[o : o + R]; o += R
    alloc = fstack[o : o + R]; o += R
    fi0 = fstack[o : o + R]; o += R
    naux = fstack[o : o + 2]; o += 2
    vr = fstack[o : o + R * K]; o += R * K
    maxal = jnp.maximum(alloc, 1.0)
    allocpos = (alloc > 0.0).astype(jnp.float32)

    # derived victim planes (gathers from the per-job tables — shipping
    # them cost ~2/3 of the pass's transfer bytes); empty slots have
    # vjob == -1 and derive to the same inert values the host packed
    jq_vec = jobsmem[JPAD : 2 * JPAD]
    jp_vec = jobsmem[2 * JPAD : 3 * JPAD]
    ready_vec = jobsf[0].reshape(-1)
    occupied = vjob >= 0
    safe_j = jnp.maximum(vjob, 0)
    vq = jnp.where(occupied, jq_vec[safe_j], -2)
    vjp = jnp.where(occupied, jp_vec[safe_j], 0)
    vjmin = jnp.where(occupied, minav[safe_j], 0.0)
    alive0 = occupied.astype(jnp.float32)
    galw0 = jnp.where(
        occupied
        & ((vjmin <= ready_vec[safe_j] - 1.0) | (vjmin == 1.0)),
        1.0,
        0.0,
    )
    vinit = jnp.concatenate([galw0, alive0], axis=0)
    vsens = jnp.where(occupied, jsens[safe_j], 0.0)

    full = lambda *shape: pl.BlockSpec(
        shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.VMEM
    )
    evicted, pipelined = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, R), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((SB * 4,), lambda i: (i,), memory_space=pltpu.SMEM),
            full(*ptask.shape),
            full(*screq.shape),
            full(C, NS, LANES),
            full(R, NS, LANES),
            full(R, NS, LANES),
            full(R, NS, LANES),
            full(R, NS, LANES),
            full(R, NS, LANES),
            full(2, NS, LANES),
            full(R * K, NS, LANES),
            full(K, NS, LANES),
            full(K, NS, LANES),
            full(K, NS, LANES),
            full(K, NS, LANES),
            full(2 * K, NS, LANES),
            full(K, NS, LANES),
            full(2, JS, LANES),
            pl.BlockSpec(
                (3 * JS * LANES,), lambda i: (0,), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (JS * LANES,), lambda i: (0,), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=[
            full(K, NS, LANES),
            full(PS, LANES),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, NS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((PS, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, NS, LANES), jnp.float32),
            pltpu.VMEM((1, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.int32),
            pltpu.VMEM((1, JS, LANES), jnp.float32),
            pltpu.VMEM((1, JS, LANES), jnp.float32),
            pltpu.SMEM((JS * LANES,), jnp.int32),
            pltpu.VMEM((PS, LANES), jnp.int32),
            pltpu.VMEM((screq.shape[0], NS, LANES), jnp.float32),
            pltpu.VMEM((NS, LANES), jnp.float32),
            pltpu.SMEM((5,), jnp.int32),
            pltpu.VMEM((R, NS, LANES), jnp.float32),
            pltpu.VMEM((1, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.float32),
            pltpu.VMEM((K, NS, LANES), jnp.int32),
            pltpu.VMEM((1, JS, LANES), jnp.float32),
            pltpu.VMEM((1, JS, LANES), jnp.float32),
            pltpu.VMEM((PS, LANES), jnp.int32),
        ],
        compiler_params=pallas_compiler_params(),
        interpret=interpret,
    )(
        tol, sched, ptask, screq, cf, used, alloc, maxal, allocpos, fi0, naux,
        vr, vjob, vq, vjp, vjmin, vinit, vsens, jobsf, jobsmem, minav,
    )
    # ONE fused output fetch: [K*NS + PS, 128] i32
    return jnp.concatenate(
        [evicted.reshape(K * NS, LANES), pipelined], axis=0
    )


def preempt_vmem_bytes(pk: PreemptPacked) -> int:
    """Estimated kernel VMEM footprint (inputs + scratch + shadows), used
    by the dispatcher to gate the Pallas route."""
    base = pk.base
    R = base.task_resreq.shape[1]
    N = max(base.n_nodes, 1)
    NK = max(LANES, -(-N // LANES) * LANES)
    per_node = np.bincount(
        pk.vic_node[: pk.n_victims], minlength=1
    ) if pk.n_victims else np.zeros(1, np.int64)
    K = int(max(1, per_node.max(initial=1)))
    J = max(pk.n_jobs, 1)
    JS = -(-J // LANES)
    P = max(base.n_tasks, 1)
    PS = -(-P // LANES)
    task_cls, class_sel, _ = _feasibility_classes(base)
    C = class_sel.shape[0]
    n_classes = _score_class_rows(pk)[0].shape[0]
    if n_classes > SCORE_CLASS_CAP:
        SC_pad = 8  # inline-score mode: only the dummy screq pad remains
    else:
        SC_pad = 8
        while SC_pad < n_classes:
            SC_pad *= 2
    plane = NK * 4
    n_planes = (
        C + 5 * R + 2  # cf + used/alloc/maxal/allocpos/fi0 + naux
        + R * K + 7 * K  # victim planes (vr, vjob/vq/vjp/vjmin, vinit×2, vsens)
        + (R + 1 + 3 * K) * 2  # node scratch + shadows
        + SC_pad  # precomputed per-class score plane scratch (padded)
        + 1  # cached masked plane
    )
    # jobsf (2 rows) + ready/wait scratch and shadows (4 rows of [1,JS,128])
    job_planes = (2 + 4) * JS * LANES * 4
    pipe = 2 * PS * LANES * 4
    ptask = P * LANES * 4  # [P_pad, R+1] tiles to 128 lanes
    return n_planes * plane + job_planes + pipe + ptask + K * plane


def preempt_smem_bytes(pk: PreemptPacked) -> int:
    """Estimated SMEM footprint: the flat schedule block (double
    buffered), job metadata scalars, cursor scratch, minav — TPU scalar
    memory is ~1 MB, so large-J sessions must be gated separately from
    VMEM (the dispatcher checks both)."""
    J = max(pk.n_jobs, 1)
    JPAD = -(-J // LANES) * LANES
    sched_block = 1024 * 4 * 4 * 2  # SB slots × 4 cols × i32 × double buffer
    return sched_block + (3 * JPAD + JPAD) * 4 + JPAD * 4


def make_preempt_dispatch(
    pk: PreemptPacked,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_slots: int = 1024,
    interpret: bool = False,
    prestage: bool = False,
):
    """Pack once; return ``(dispatch, dims, vic_slot)`` where
    ``dispatch()`` enqueues the fused preempt kernel and returns the
    (async) device result — or ``None`` when the session is trivially
    empty.  ``prestage=True`` device_puts the transfer buffer so repeated
    dispatches measure pure device compute (bench pipelines K dispatches
    before one sync to amortize link RTT); run_preempt_pallas uses
    prestage=False — the per-session transfer is part of real session
    latency."""
    packed = pack_preempt_call(pk, weights, block_slots, interpret)
    if packed is None:
        return None
    buf, kw, dims, vic_slot = packed
    if prestage:
        buf = jax.device_put(jnp.asarray(buf))

    def dispatch():
        return _preempt_call(jnp.asarray(buf), **kw)

    return dispatch, dims, vic_slot


def pack_preempt_call(
    pk: PreemptPacked,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_slots: int = 1024,
    interpret: bool = False,
):
    """Host half of :func:`make_preempt_dispatch`: ``(buf, kw, dims,
    vic_slot)`` — the one transfer buffer and the static arguments of
    ``_preempt_call`` — or ``None`` for a trivially empty session."""
    slots = build_schedule_slots(pk)
    if pk.base.n_tasks == 0 or slots.shape[0] == 0:
        return None

    arrays, dims, vic_slot = prepare_preempt_arrays(pk)
    S = slots.shape[0]
    SB = min(block_slots, -(-S // 8) * 8)
    S_pad = -(-S // SB) * SB
    sched = np.full((S_pad, 4), 0, dtype=np.int32)
    sched[:, 0] = K_PAD
    sched[:S] = slots
    sched = np.ascontiguousarray(sched.reshape(-1))  # flat for SMEM

    # single transfer buffer: f32 parts then i32 parts, as raw bytes
    buf = np.concatenate([
        np.ascontiguousarray(arrays["tol"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["ptask"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["screq"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["fstack"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["jobsf"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["minav"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["jsens"]).view(np.uint8).ravel(),
        sched.view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["istack"]).view(np.uint8).ravel(),
        np.ascontiguousarray(arrays["jobsmem"]).view(np.uint8).ravel(),
    ])
    kw = dict(
        R=dims["R"], K=dims["K"], C=dims["C"], NS=dims["NS"], JS=dims["JS"],
        PS=dims["PS"], SB=SB, SC=dims["SC"], S4=int(sched.shape[0]),
        P_pad=int(arrays["ptask"].shape[0]),
        SC_rows=int(arrays["screq"].shape[0]),
        weights=weights, interpret=interpret,
    )
    return buf, kw, dims, vic_slot


def run_preempt_pallas(
    pk: PreemptPacked,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_slots: int = 1024,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """PreemptPacked → (evicted[V] bool, pipelined_node[P] i32, -1=none).

    Packs to planes, makes ONE device call that replays the whole
    preempt pass, unpacks.  Semantics ≡ preempt_dense ≡ host action."""
    base = pk.base
    P = base.n_tasks
    V = pk.n_victims
    evicted = np.zeros(max(V, 1), dtype=bool)[:V]
    pipelined = np.full(max(P, 1), -1, dtype=np.int32)[:P]
    made = make_preempt_dispatch(
        pk, weights=weights, block_slots=block_slots, interpret=interpret,
    )
    if made is None:
        return evicted, pipelined
    dispatch, dims, vic_slot = made

    out = np.asarray(dispatch())
    K, NS = dims["K"], dims["NS"]
    ev_planes = out[: K * NS].reshape(K, NS, LANES)
    pipe_flat = out[K * NS :].reshape(-1)

    if V:
        sub = pk.vic_node[:V] // LANES
        lane = pk.vic_node[:V] % LANES
        evicted = ev_planes[vic_slot[:V], sub, lane] > 0
    pipelined = pipe_flat[:P].astype(np.int32)
    return evicted, pipelined
