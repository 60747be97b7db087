"""Blocked greedy assignment — the fast exact formulation of the session
kernel.

The plain kernel (ops/kernels.py schedule_episodes) scans tasks one by one,
paying a full [N]-wide mask+score+argmax per step; at 16k nodes the
per-iteration cost is ~30-60µs, so 50k tasks take seconds.  This module
restructures the same sequential-greedy semantics into blocks:

  1. Per block of B tasks: ONE wide [B, N] feasibility+score computation
     at block-start state (parallel, MXU-friendly), top-K candidate nodes
     per task, plus each task's best score/index among NON-candidates
     ("outside"), all at block-start state.
  2. A small inner scan resolves the block task-by-task over only the
     M = B·K tracked candidate slots — ops are [M]-sized, not [N]-sized.
  3. EXACTNESS INVARIANT: every placement inside a block lands on a
     tracked node, so untracked nodes keep their block-start scores.  The
     per-task decision compares the tracked current max against the
     outside static max (exact, not a bound).  If the outside value would
     win, the block STOPS at that task; the host-visible while_loop
     resolves that one task with a full-width step at current state and
     starts a fresh block.  Outcome: identical chosen sequence to the
     naive scan, including the lowest-node-index tie-break.

Result: sequential work per task shrinks from O(N) to O(B·K) with rare
full-width fallbacks, while the O(T·N) score arithmetic runs in wide
parallel blocks where the TPU is fast.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from volcano_tpu.ops.kernels import (
    _feasibility_classes,
    DEFAULT_WEIGHTS,
    f32_lr_exact,
    MAX_PRIORITY,
    node_scores,
    run_packed,
    ScoreWeights,
    step_delta_ext,
)
from volcano_tpu.ops.packing import PackedSnapshot

INT_BIG = np.int32(2**31 - 1)


def _block_scores(weights, tolerance, base, node_alloc, node_max_tasks,
                  used_ext, resreq_blk, class_feas_blk, active_blk):
    """[B, N] feasibility + masked scores at current state."""
    used = used_ext[:, :-1]
    count = used_ext[:, -1]
    idle = base - used
    scalar_lane = jnp.arange(resreq_blk.shape[-1]) >= 2
    fit = jnp.all(
        (resreq_blk[:, None, :] < idle[None, :, :] + tolerance[None, None, :])
        | (scalar_lane[None, None, :] & (resreq_blk[:, None, :] <= tolerance[None, None, :])),
        axis=-1,
    )
    feasible = fit & (count < node_max_tasks)[None, :] & class_feas_blk & active_blk[:, None]
    score = node_scores(resreq_blk, used, node_alloc, weights)
    return jnp.where(feasible, score, -jnp.inf)


def make_inner_step(tracked, base_t, alloc_t, maxt_t, real, tolerance,
                    weights, R):
    """The per-task decision body for resolving one block over compact
    tracked slots — the SINGLE copy shared by the single-chip kernel
    below and the sharded mesh kernel (ops/sharded.py), so tie-break /
    tolerance / stop-rule fixes propagate to both.

    ``tracked`` must be sorted ascending by node id (global id for the
    sharded path) so that argmax-first IS the lowest-node-index
    tie-break; dummy slots carry ``real=False`` and the largest ids.
    Scan xs: (resreq, tf_row, out_max_b, out_arg_b, act)."""

    def inner(carry, xs):
        U, stopped = carry
        resreq, tf_row, out_max_b, out_arg_b, act = xs

        u = U[:, :-1]
        cnt = U[:, -1]
        idle_t = base_t - u
        # Unrolled lane reduce (R is small and static; avoids a reduce
        # op per step — per-op scan overhead dominates).
        fit = jnp.ones((u.shape[0],), bool)
        for r in range(R):
            lane_ok = resreq[r] < idle_t[:, r] + tolerance[r]
            if r >= 2:
                lane_ok = lane_ok | (resreq[r] <= tolerance[r])
            fit = fit & lane_ok
        feas = fit & (cnt < maxt_t) & tf_row & act & real
        s = node_scores(resreq[None, :], u, alloc_t, weights)[0]
        s = jnp.where(feas, s, -jnp.inf)

        # tracked is SORTED ascending, so the first max position is the
        # lowest node index among maxima — one argmax does both the max
        # and the tie-break.
        pos = jnp.argmax(s)
        maxv = s[pos]
        t_ok = jnp.isfinite(maxv)
        t_node = tracked[pos]

        out_finite = jnp.isfinite(out_max_b)
        outside_better = out_finite & (
            (out_max_b > maxv) | ((out_max_b == maxv) & (out_arg_b < t_node))
        )

        place = t_ok & ~outside_better & ~stopped
        stop_now = ~stopped & outside_better
        consumed = ~stopped & ~stop_now

        U = U.at[pos].add(
            jnp.where(place, 1.0, 0.0)
            * jnp.concatenate([resreq, jnp.ones((1,), resreq.dtype)])
        )
        chosen = jnp.where(place, t_node, -1)
        return (U, stopped | stop_now), (chosen, consumed)

    return inner


def one_pass(run_pass, n_tasks, t_total) -> np.ndarray:
    """The blocked and sharded wrappers' single greedy pass over the
    first ``n_tasks`` rows: ``run_pass(active)`` → (chosen,
    job_assigned).  Their sessions hold one row per job and need at most
    one placement a job (``PackedSnapshot.episodic`` is False), so what
    the pass places is what commits."""
    active = np.zeros(t_total, dtype=bool)
    active[:n_tasks] = True
    chosen, _ = run_pass(jnp.asarray(active))
    return np.asarray(chosen, dtype=np.int32)[:n_tasks]


@functools.partial(
    jax.jit, static_argnames=("weights", "block_size", "top_k")
)
def schedule_pass_blocked(
    task_resreq: jnp.ndarray,  # [T_pad, R] (padded by an extra block)
    task_job: jnp.ndarray,
    task_feas_class: jnp.ndarray,
    class_sel_bits: jnp.ndarray,
    class_tol_bits: jnp.ndarray,
    node_idle: jnp.ndarray,  # [Nw, R] — last row must be a dummy node
    node_used: jnp.ndarray,
    node_alloc: jnp.ndarray,
    node_label_bits: jnp.ndarray,
    node_taint_bits: jnp.ndarray,
    node_ok: jnp.ndarray,
    node_task_count: jnp.ndarray,
    node_max_tasks: jnp.ndarray,
    job_min_available: jnp.ndarray,
    tolerance: jnp.ndarray,
    active: jnp.ndarray,  # [T_pad]
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 64,
    top_k: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One greedy pass, block formulation → (chosen[T_pad], job_assigned)."""
    T = task_resreq.shape[0]
    Nw = node_idle.shape[0]
    R = task_resreq.shape[1]
    B, K = block_size, top_k
    M = B * K
    SENTINEL = jnp.int32(Nw - 1)  # the dummy node row

    sel_ok = jnp.all(
        (class_sel_bits[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1
    )
    tol_ok = jnp.all(
        (node_taint_bits[None, :, :] & ~class_tol_bits[:, None, :]) == 0, axis=-1
    )
    class_feasible = sel_ok & tol_ok & node_ok[None, :]  # [C, Nw]

    base = node_idle + node_used
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )

    def full_step(used_ext, resreq, cls, act):
        """Exact single-task step at full width (the stop-task resolver)."""
        s = _block_scores(
            weights, tolerance, base, node_alloc, node_max_tasks,
            used_ext, resreq[None, :], class_feasible[cls][None, :], act[None],
        )[0]
        # jnp.argmax picks the first (lowest-index) maximum.
        best = jnp.argmax(s)
        ok = jnp.isfinite(s[best])
        used_ext = used_ext.at[best].add(
            jnp.where(ok, 1.0, 0.0) * jnp.concatenate([resreq, jnp.ones((1,), resreq.dtype)])
        )
        return used_ext, jnp.where(ok, best.astype(jnp.int32), -1)

    def run_block(used_ext, cursor):
        """Resolve up to B tasks starting at cursor; returns consumed count."""
        resreq_blk = jax.lax.dynamic_slice(task_resreq, (cursor, 0), (B, R))
        cls_blk = jax.lax.dynamic_slice(task_feas_class, (cursor,), (B,))
        act_blk = jax.lax.dynamic_slice(active, (cursor,), (B,))

        cf_blk = class_feasible[cls_blk]  # [B, Nw]
        S = _block_scores(
            weights, tolerance, base, node_alloc, node_max_tasks,
            used_ext, resreq_blk, cf_blk, act_blk,
        )  # [B, Nw]

        _, top_idx = jax.lax.top_k(S, K)  # [B, K]
        flat = jnp.sort(top_idx.reshape(-1).astype(jnp.int32))
        dup = jnp.concatenate([jnp.zeros((1,), bool), flat[1:] == flat[:-1]])
        tracked = jnp.where(dup, SENTINEL, flat)  # [M], unique reals + sentinels

        in_tracked = jnp.zeros((Nw,), bool).at[tracked].set(True)
        S_out = jnp.where(in_tracked[None, :], -jnp.inf, S)
        out_max = jnp.max(S_out, axis=1)  # [B]
        out_arg = jnp.argmax(S_out, axis=1).astype(jnp.int32)  # first max → lowest idx

        # Compact tracked state.
        U0 = used_ext[tracked]  # [M, R+1]
        base_t = base[tracked]
        alloc_t = node_alloc[tracked]
        maxt_t = node_max_tasks[tracked]
        real = tracked != SENTINEL  # sentinel slots never place
        tf_blk = cf_blk[:, tracked]  # [B, M] static feas on tracked

        inner = make_inner_step(
            tracked, base_t, alloc_t, maxt_t, real, tolerance, weights, R
        )
        (U, _), (chosen_blk, consumed_blk) = jax.lax.scan(
            inner,
            (U0, jnp.zeros((), bool)),
            (resreq_blk, tf_blk, out_max, out_arg, act_blk),
        )

        # Write compact state back (sentinel slots carry unchanged dummy
        # rows; duplicate sentinel writes are identical values).
        used_ext = used_ext.at[tracked].set(U)
        n_consumed = jnp.sum(consumed_blk.astype(jnp.int32))
        # Chosen entries past the stop point are already -1/masked via
        # consumed; keep only consumed prefix.
        chosen_blk = jnp.where(consumed_blk, chosen_blk, -1)
        return used_ext, chosen_blk, n_consumed

    def cond(state):
        _, cursor, _ = state
        return cursor < T

    def body(state):
        used_ext, cursor, chosen_out = state
        used_ext, chosen_blk, n_consumed = run_block(used_ext, cursor)
        chosen_out = jax.lax.dynamic_update_slice(
            chosen_out,
            jnp.where(
                jnp.arange(B) < n_consumed,
                chosen_blk,
                jax.lax.dynamic_slice(chosen_out, (cursor,), (B,)),
            ),
            (cursor,),
        )
        cursor = cursor + n_consumed

        # Stopped before the block drained → resolve ONE task full-width.
        def resolve(args):
            used_ext, cursor, chosen_out = args
            idx = jnp.minimum(cursor, T - 1)
            used_ext, chosen1 = full_step(
                used_ext,
                task_resreq[idx],
                task_feas_class[idx],
                active[idx],
            )
            chosen_out = chosen_out.at[idx].set(chosen1)
            return used_ext, cursor + 1, chosen_out

        state = (used_ext, cursor, chosen_out)
        return jax.lax.cond(n_consumed < B, resolve, lambda a: a, state)

    init = (
        used_ext0,
        jnp.int32(0),
        jnp.full((T,), -1, dtype=jnp.int32),
    )
    used_ext, _, chosen = jax.lax.while_loop(cond, body, init)
    # Gang accounting post-hoc: one segment-sum instead of a scatter per
    # scan step.
    job_assigned = jnp.zeros_like(job_min_available).at[task_job].add(
        (chosen >= 0).astype(job_min_available.dtype)
    )
    return chosen, job_assigned


def task_block_padding(snap: PackedSnapshot, block_size: int):
    """(T_blk, pad_tasks) — the task padding both blocked wrappers use.
    T_blk = T_pad rounded to the block size PLUS one block of headroom,
    so a dynamic_slice after an unaligned stop-resolve never clamps into
    live tasks.  The single copy — ops/sharded.py imports this too."""
    B = block_size
    T_pad = snap.task_resreq.shape[0]
    T_blk = T_pad + (-T_pad) % B + B

    def pad_tasks(arr, fill=0):
        out = np.full((T_blk, *arr.shape[1:]), fill, dtype=arr.dtype)
        out[:T_pad] = arr
        return out

    return T_blk, pad_tasks


def prepare_blocked_arrays(snap: PackedSnapshot, block_size: int = 64):
    """Host-side array prep: dummy node row + task padding to block size."""
    T_blk, pad_tasks = task_block_padding(snap, block_size)

    task_feas_class, class_sel, class_tol = _feasibility_classes(snap)

    # One guaranteed-infeasible dummy node row at the end (the sentinel).
    def pad_nodes(arr, fill=0):
        out = np.full((arr.shape[0] + 1, *arr.shape[1:]), fill, dtype=arr.dtype)
        out[:-1] = arr
        return out

    arrays = dict(
        task_resreq=pad_tasks(snap.task_resreq),
        task_job=pad_tasks(snap.task_job),
        task_feas_class=pad_tasks(task_feas_class),
        class_sel_bits=class_sel,
        class_tol_bits=class_tol,
        node_idle=pad_nodes(snap.node_idle),
        node_used=pad_nodes(snap.node_used),
        node_alloc=pad_nodes(snap.node_alloc),
        node_label_bits=pad_nodes(snap.node_label_bits),
        node_taint_bits=pad_nodes(snap.node_taint_bits),
        node_ok=pad_nodes(snap.node_ok, fill=False),
        node_task_count=pad_nodes(snap.node_task_count),
        node_max_tasks=pad_nodes(snap.node_max_tasks),
        job_min_available=snap.job_min_available,
        tolerance=snap.tolerance,
    )
    return arrays, T_blk


def _prepare_blocked_dev(snap: PackedSnapshot, block_size: int):
    """Device-side equivalent of prepare_blocked_arrays: identical pad
    values (task blocks zero-filled, one infeasible sentinel node row),
    concatenated on device from the staged planes."""
    from volcano_tpu.ops.device_stage import device_plane

    T_blk, _ = task_block_padding(snap, block_size)
    T_pad = snap.task_resreq.shape[0]
    task_feas_class, class_sel, class_tol = _feasibility_classes(snap)

    def pad_tasks(arr, fill=0):
        arr = jnp.asarray(arr)
        pad = jnp.full((T_blk - T_pad, *arr.shape[1:]), fill, arr.dtype)
        return jnp.concatenate([arr, pad], axis=0)

    def pad_nodes(arr, fill=0):
        arr = jnp.asarray(arr)
        pad = jnp.full((1, *arr.shape[1:]), fill, arr.dtype)
        return jnp.concatenate([arr, pad], axis=0)

    dev = dict(
        task_resreq=pad_tasks(device_plane(snap, "task_resreq")),
        task_job=pad_tasks(device_plane(snap, "task_job")),
        task_feas_class=pad_tasks(task_feas_class),
        class_sel_bits=jnp.asarray(class_sel),
        class_tol_bits=jnp.asarray(class_tol),
        node_idle=pad_nodes(device_plane(snap, "node_idle")),
        node_used=pad_nodes(device_plane(snap, "node_used")),
        node_alloc=pad_nodes(device_plane(snap, "node_alloc")),
        node_label_bits=pad_nodes(device_plane(snap, "node_label_bits")),
        node_taint_bits=pad_nodes(device_plane(snap, "node_taint_bits")),
        node_ok=pad_nodes(device_plane(snap, "node_ok"), fill=False),
        node_task_count=pad_nodes(device_plane(snap, "node_task_count")),
        node_max_tasks=pad_nodes(device_plane(snap, "node_max_tasks")),
        job_min_available=jnp.asarray(device_plane(snap, "job_min_available")),
        tolerance=jnp.asarray(device_plane(snap, "tolerance")),
    )
    return dev, T_blk


def run_packed_blocked(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 64,
    top_k: int = 8,
) -> np.ndarray:
    """Host wrapper: PackedSnapshot → assignment[n_tasks] from one
    blocked pass.

    Sessions with hard topology spread or multi-task gangs run the plain
    scan instead: the blocked pass keeps each block's top-K candidates
    valid on the premise that a placement changes one node, and a
    placement there moves the counts of a whole domain, or a gang's
    discard moves many (the dispatcher never picks this executor for
    them)."""
    if snap.has_spread or snap.episodic:
        return run_packed(snap, weights=weights)
    if not f32_lr_exact(snap):
        weights = weights._replace(lr_int_exact=True)

    if getattr(snap, "device_planes", None):
        # staged session (ops/device_stage.py): planes are already
        # device-resident — pad on device so the host ships nothing but
        # the dirty-row scatters already applied by the stager
        dev, T_blk = _prepare_blocked_dev(snap, block_size)
    else:
        arrays, T_blk = prepare_blocked_arrays(snap, block_size)
        dev = {k: jnp.asarray(v) for k, v in arrays.items()}

    def run_pass(active):
        return schedule_pass_blocked(
            dev["task_resreq"],
            dev["task_job"],
            dev["task_feas_class"],
            dev["class_sel_bits"],
            dev["class_tol_bits"],
            dev["node_idle"],
            dev["node_used"],
            dev["node_alloc"],
            dev["node_label_bits"],
            dev["node_taint_bits"],
            dev["node_ok"],
            dev["node_task_count"],
            dev["node_max_tasks"],
            dev["job_min_available"],
            dev["tolerance"],
            active,
            weights=weights,
            block_size=block_size,
            top_k=top_k,
        )

    return one_pass(run_pass, snap.n_tasks, T_blk)
