"""Multi-chip session kernel: node axis sharded over a device mesh,
blocked formulation (one collective round per task-BLOCK, not per task).

Scale-out design (SURVEY.md §5 "long-context" analogue): the session's
scale axis is tasks × nodes.  Tasks are a sequential scan (allocation
feedback), so the parallel axis is nodes.  The round-1/2 formulation ran
one full-width step + one all_gather per task — 50k ICI collectives at
the headline shape, the exact per-step-overhead design the single-chip
path escaped.  This version shards the BLOCKED formulation
(ops/blocked.py) instead:

  1. Per block of B tasks, each device computes [B, N_loc] feasibility +
     scores at block-start state over its LOCAL node shard (the wide,
     MXU-friendly part — this is what sharding is for), takes local
     top-K candidates per task plus the local outside max/argmax.
  2. ONE all-gather round ships the tiny candidate pack (ids, state
     rows, static planes, outside pairs) — O(B·K·R) scalars per device.
  3. Every device then runs the IDENTICAL replicated inner scan over the
     gathered M = n_dev·B·K candidate slots (sorted by global node id,
     so argmax-first = lowest-global-index tie-break), resolving the
     block task-by-task with the same exactness invariant as
     ops/blocked.py: placements land only on tracked slots, untracked
     nodes keep block-start scores, and the outside comparison is exact
     — if an untracked node would win, the block STOPS and that one
     task is resolved full-width (one extra collective, rare).
  4. Each device writes back the slot rows it owns; state never leaves
     the owning shard except as gathered candidates.

Deterministic tie-break is preserved end-to-end: candidate slots are
sorted by TRUE global node index before the replicated scan, the
outside argmax carries the lowest global index achieving the max, and
the full-width fallback reduces (score, lowest-local) pairs picking the
lowest shard among equal maxima — identical bindings to run_packed /
run_packed_blocked / the Pallas kernel (tests/test_sharded.py asserts
this at 10k nodes on an 8-virtual-device mesh).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from volcano_tpu.ops.blocked import (
    _block_scores,
    one_pass,
    INT_BIG,
    make_inner_step,
    run_packed_blocked,
    task_block_padding,
)
from volcano_tpu.ops.kernels import (
    _feasibility_classes,
    DEFAULT_WEIGHTS,
    f32_lr_exact,
    ScoreWeights,
)
from volcano_tpu.ops.packing import PackedSnapshot

AXIS = "nodes"


def _sharded_blocked_kernel(
    task_resreq,  # [T_blk, R] replicated
    task_job,  # [T_blk]
    task_feas_class,  # [T_blk]
    class_sel_bits,  # [C, W] replicated
    class_tol_bits,  # [C, W]
    node_idle,  # local shard [n_loc1, R] (last row = dummy)
    node_used,
    node_alloc,
    node_label_bits,
    node_taint_bits,
    node_ok,
    node_task_count,
    node_max_tasks,
    job_min_available,
    tolerance,
    active,  # [T_blk] replicated
    weights: ScoreWeights,
    block_size: int,
    top_k: int,
):
    """shard_map body: one blocked greedy pass → (chosen[T_blk] global
    node ids, job_assigned).  All replicated values evolve identically on
    every shard (inputs to the replicated scan are gathered, hence
    bit-identical)."""
    my = jax.lax.axis_index(AXIS)
    n_dev = jax.lax.axis_size(AXIS)
    n_loc1 = node_idle.shape[0]
    n_loc = n_loc1 - 1  # real rows; row n_loc is the infeasible dummy
    T = task_resreq.shape[0]
    R = task_resreq.shape[1]
    B = block_size
    K = min(top_k, n_loc1)  # tiny shards can't track more than they own
    DUMMY_LOCAL = jnp.int32(n_loc)

    sel_ok = jnp.all(
        (class_sel_bits[:, None, :] & ~node_label_bits[None, :, :]) == 0, axis=-1
    )
    tol_ok = jnp.all(
        (node_taint_bits[None, :, :] & ~class_tol_bits[:, None, :]) == 0, axis=-1
    )
    class_feasible = sel_ok & tol_ok & node_ok[None, :]  # [C, n_loc1]

    base = node_idle + node_used
    used_ext0 = jnp.concatenate(
        [node_used, node_task_count.astype(node_used.dtype)[:, None]], axis=1
    )

    def to_global(local_idx):
        """Local row → true global node id (dummy → INT_BIG)."""
        return jnp.where(
            local_idx >= n_loc, INT_BIG, my * n_loc + local_idx
        ).astype(jnp.int32)

    def full_step(used_ext, resreq, cls, act):
        """Exact single-task step at full width — the stop-task resolver.
        One (score, global-argmax) all-gather; lowest shard among equal
        maxima wins, preserving the global lowest-index tie-break."""
        s = _block_scores(
            weights, tolerance, base, node_alloc, node_max_tasks,
            used_ext, resreq[None, :], class_feasible[cls][None, :], act[None],
        )[0]
        best_local = jnp.argmax(s)  # first max → lowest local index
        best_score = s[best_local]
        all_scores = jax.lax.all_gather(best_score, AXIS)  # [n_dev]
        all_globals = jax.lax.all_gather(to_global(best_local), AXIS)
        winner = jnp.argmax(all_scores)  # first max → lowest shard
        ok = jnp.isfinite(all_scores[winner])
        mine = (winner == my) & ok
        delta = jnp.concatenate([resreq, jnp.ones((1,), resreq.dtype)])
        used_ext = used_ext.at[best_local].add(
            jnp.where(mine, 1.0, 0.0) * delta
        )
        chosen = jnp.where(ok, all_globals[winner], -1)
        return used_ext, chosen

    def run_block(used_ext, cursor):
        resreq_blk = jax.lax.dynamic_slice(task_resreq, (cursor, 0), (B, R))
        cls_blk = jax.lax.dynamic_slice(task_feas_class, (cursor,), (B,))
        act_blk = jax.lax.dynamic_slice(active, (cursor,), (B,))

        cf_blk = class_feasible[cls_blk]  # [B, n_loc1]
        S = _block_scores(
            weights, tolerance, base, node_alloc, node_max_tasks,
            used_ext, resreq_blk, cf_blk, act_blk,
        )  # [B, n_loc1]

        _, top_idx = jax.lax.top_k(S, K)  # [B, K] local indices
        flat = jnp.sort(top_idx.reshape(-1).astype(jnp.int32))
        dup = jnp.concatenate([jnp.zeros((1,), bool), flat[1:] == flat[:-1]])
        tracked_loc = jnp.where(dup, DUMMY_LOCAL, flat)  # [M_loc]

        in_tracked = jnp.zeros((n_loc1,), bool).at[tracked_loc].set(True)
        S_out = jnp.where(in_tracked[None, :], -jnp.inf, S)
        out_max_loc = jnp.max(S_out, axis=1)  # [B]
        out_arg_loc = to_global(jnp.argmax(S_out, axis=1).astype(jnp.int32))

        # ---- gather the candidate pack (the one collective round) ----
        ids_g = jax.lax.all_gather(to_global(tracked_loc), AXIS).reshape(-1)
        U_g = jax.lax.all_gather(used_ext[tracked_loc], AXIS).reshape(-1, R + 1)
        base_g = jax.lax.all_gather(base[tracked_loc], AXIS).reshape(-1, R)
        alloc_g = jax.lax.all_gather(node_alloc[tracked_loc], AXIS).reshape(-1, R)
        maxt_g = jax.lax.all_gather(node_max_tasks[tracked_loc], AXIS).reshape(-1)
        tf_g = jax.lax.all_gather(
            cf_blk[:, tracked_loc], AXIS, axis=1
        ).reshape(B, -1)
        out_max_all = jax.lax.all_gather(out_max_loc, AXIS)  # [n_dev, B]
        out_arg_all = jax.lax.all_gather(out_arg_loc, AXIS)  # [n_dev, B]

        # global outside: max score, lowest global id among shard maxima
        out_max = jnp.max(out_max_all, axis=0)  # [B]
        out_arg = jnp.min(
            jnp.where(out_max_all == out_max[None, :], out_arg_all, INT_BIG),
            axis=0,
        )

        # sort slots by global id → argmax-first = lowest-global-index
        perm = jnp.argsort(ids_g)
        tracked = ids_g[perm]  # [M_g], dummies (INT_BIG) at the end
        U0 = U_g[perm]
        base_t = base_g[perm]
        alloc_t = alloc_g[perm]
        maxt_t = maxt_g[perm]
        tf_blk_g = tf_g[:, perm]
        real = tracked != INT_BIG

        # the per-task decision body is the SAME code object as the
        # single-chip blocked kernel's (blocked.make_inner_step) — the
        # bindings-equivalence invariant cannot drift between them
        inner = make_inner_step(
            tracked, base_t, alloc_t, maxt_t, real, tolerance, weights, R
        )
        (U, _), (chosen_blk, consumed_blk) = jax.lax.scan(
            inner,
            (U0, jnp.zeros((), bool)),
            (resreq_blk, tf_blk_g, out_max, out_arg, act_blk),
        )

        # ---- writeback: each shard keeps the slot rows it owns ----
        own = (tracked >= my * n_loc) & (tracked < (my + 1) * n_loc)
        local_target = jnp.where(own, tracked - my * n_loc, DUMMY_LOCAL)
        used_ext = used_ext.at[local_target].set(
            jnp.where(own[:, None], U, used_ext[local_target])
        )

        n_consumed = jnp.sum(consumed_blk.astype(jnp.int32))
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
    _, _, chosen = jax.lax.while_loop(cond, body, init)
    job_assigned = jnp.zeros_like(job_min_available).at[task_job].add(
        (chosen >= 0).astype(job_min_available.dtype)
    )
    return chosen, job_assigned


#: partition of each argument of the sharded pass, in call order:
#: node-axis arrays split over AXIS, task/class/job arrays replicated
_IN_SPECS = (
    P(None, None),  # task_resreq
    P(None),  # task_job
    P(None),  # task_feas_class
    P(None, None),  # class_sel_bits
    P(None, None),  # class_tol_bits
    P(AXIS, None),  # node_idle
    P(AXIS, None),  # node_used
    P(AXIS, None),  # node_alloc
    P(AXIS, None),  # node_label_bits
    P(AXIS, None),  # node_taint_bits
    P(AXIS),  # node_ok
    P(AXIS),  # node_task_count
    P(AXIS),  # node_max_tasks
    P(None),  # job_min_available
    P(None),  # tolerance
    P(None),  # active
)


def make_sharded_session(
    mesh: Mesh,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 64,
    top_k: int = 8,
):
    """Build the jitted node-sharded blocked pass for ``mesh``
    (arguments partitioned as ``_IN_SPECS``).  Returns fn(arrays…) →
    (chosen global node ids, job_assigned)."""
    body = functools.partial(
        _sharded_blocked_kernel,
        weights=weights,
        block_size=block_size,
        top_k=top_k,
    )
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=_IN_SPECS,
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    return jax.jit(sharded)


def sharded_session_inputs(snap: PackedSnapshot, n_dev: int, block_size: int = 64):
    """Host arrays of one sharded session, in ``_IN_SPECS`` order minus
    the trailing per-round ``active`` mask → (arrays, T_blk)."""
    task_feas_class, class_sel, class_tol = _feasibility_classes(snap)
    T_blk, pad_tasks = task_block_padding(snap, block_size)
    arrays = [
        pad_tasks(snap.task_resreq),
        pad_tasks(snap.task_job),
        pad_tasks(task_feas_class),
        class_sel,
        class_tol,
        *_shard_nodes_with_dummies(snap, n_dev),
        snap.job_min_available,
        snap.tolerance,
    ]
    return arrays, T_blk


def _shard_nodes_with_dummies(snap: PackedSnapshot, n_dev: int):
    """Rearrange node arrays into n_dev chunks of n_loc real rows + one
    trailing infeasible dummy row each → global width n_dev*(n_loc+1).
    Global id mapping: (shard s, local i) ↔ true node s*n_loc + i.
    Returned in ``_IN_SPECS`` order."""
    N_pad = snap.node_idle.shape[0]
    if N_pad % n_dev:
        raise ValueError(
            f"padded node count {N_pad} not divisible by mesh size {n_dev}"
        )
    n_loc = N_pad // n_dev

    def rearrange(arr, fill=0):
        shaped = arr.reshape(n_dev, n_loc, *arr.shape[1:])
        dummy = np.full((n_dev, 1, *arr.shape[1:]), fill, dtype=arr.dtype)
        return np.concatenate([shaped, dummy], axis=1).reshape(
            n_dev * (n_loc + 1), *arr.shape[1:]
        )

    return [
        rearrange(snap.node_idle),
        rearrange(snap.node_used),
        rearrange(snap.node_alloc),
        rearrange(snap.node_label_bits),
        rearrange(snap.node_taint_bits),
        rearrange(snap.node_ok, fill=False),
        rearrange(snap.node_task_count),
        rearrange(snap.node_max_tasks),
    ]


def run_packed_sharded(
    snap: PackedSnapshot,
    mesh: Mesh,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    block_size: int = 64,
    top_k: int = 8,
) -> np.ndarray:
    """Host wrapper: PackedSnapshot → assignment[n_tasks] from one
    sharded blocked pass (run_packed_blocked's protocol).  Sessions with
    hard topology spread or multi-task gangs take run_packed_blocked's
    way to the plain scan."""
    if snap.has_spread or snap.episodic:
        return run_packed_blocked(snap, weights=weights)
    n_dev = mesh.devices.size
    if not f32_lr_exact(snap):
        weights = weights._replace(lr_int_exact=True)

    arrays, T_blk = sharded_session_inputs(snap, n_dev, block_size)
    fn = make_sharded_session(
        mesh, weights=weights, block_size=block_size, top_k=top_k
    )
    # Place each array with its final sharding — every device receives
    # only its node shard.
    dev = [
        jax.device_put(a, NamedSharding(mesh, spec))
        for a, spec in zip(arrays, _IN_SPECS)
    ]

    return one_pass(lambda active: fn(*dev, active), snap.n_tasks, T_blk)
