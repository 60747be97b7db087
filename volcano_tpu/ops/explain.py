"""Device-derived scheduling explainability.

The reference system's most-used observability surface is the
unschedulable-explanation pipeline: per-node predicate failures are
histogrammed into "0/N nodes are available: ..." messages
(unschedule_info.go) and recorded as pod Events and ``Unschedulable``
conditions (cache.go:832-867).  The device kernels already materialize
every ingredient — the predicate component planes of
``ops/kernels._component_planes`` — and then AND them away.  This module
keeps them: an ``explain`` pass reduces the planes on-device to a
per-task×reason node-count matrix (``kernels.explain_counts``) and
synthesizes reference-identical :class:`FitErrors` from it, so a
device-scheduled cycle explains a pending task without the O(T×N) host
predicate sweep the fallback path would pay.

Layers on top:

  * jax-allocate (and the jax-preempt/jax-reclaim no-victim paths)
    populate ``job.nodes_fit_errors`` from the counts, feeding the
    existing Unschedulable event + pod-condition writeback in
    ``cache.record_job_status_event`` unchanged.
  * the most recent cycle's explanation is parked in
    :func:`set_last_explain` for the scheduler's ``GET /explain`` debug
    endpoint and the trace journal's per-cycle reason summary.
  * full per-pair reason planes (node-level attribution, [T, N]) are
    retained only when asked (``retain_planes``) — the hot path ships
    one [T, P] matrix back, P = 5, or 8 in a session with hard topology
    spread constraints or required anti-affinity (their three reasons).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from volcano_tpu.api.unschedule_info import (
    FitErrors,
    NODE_POD_NUMBER_EXCEEDED,
    NODE_RESOURCE_FIT_FAILED,
    NODE_SELECTOR_MISMATCH,
    NODE_TAINT_UNTOLERATED,
    NODE_UNSCHEDULABLE,
    POD_AFFINITY_MISMATCH,
    SPREAD_CONSTRAINTS_MISMATCH,
    SPREAD_LABEL_MISSING,
)
from volcano_tpu.ops.kernels import explain_counts, N_EXPLAIN_REASONS
from volcano_tpu.ops.packing import PackedSnapshot

#: reason strings by kernel plane index (kernels.R_FIT..R_TOL) — the
#: host first-failure precedence the kernel mirrors.
EXPLAIN_REASONS = (
    NODE_RESOURCE_FIT_FAILED,
    NODE_POD_NUMBER_EXCEEDED,
    NODE_UNSCHEDULABLE,
    NODE_SELECTOR_MISMATCH,
    NODE_TAINT_UNTOLERATED,
    SPREAD_LABEL_MISSING,
    SPREAD_CONSTRAINTS_MISMATCH,
    POD_AFFINITY_MISMATCH,
)

assert len(EXPLAIN_REASONS) == N_EXPLAIN_REASONS


class ExplainResult:
    """Reason counts for one packed session.

    ``counts[t, p]`` — valid nodes whose FIRST failing predicate for
    ordered task ``t`` is ``EXPLAIN_REASONS[p]``; ``reasons`` is the
    per-pair [T, N] plane (int8 reason index, ``N_EXPLAIN_REASONS`` =
    feasible) when retention was requested, else None."""

    __slots__ = ("counts", "n_nodes", "reasons")

    def __init__(
        self, counts: np.ndarray, n_nodes: int,
        reasons: Optional[np.ndarray] = None,
    ):
        self.counts = counts
        self.n_nodes = n_nodes
        self.reasons = reasons

    def all_infeasible(self, i: int) -> bool:
        """Does the device prove task ``i`` fits NO node at all?"""
        return self.n_nodes > 0 and int(self.counts[i].sum()) >= self.n_nodes

    def histogram(self, i: int) -> Dict[str, int]:
        return {
            EXPLAIN_REASONS[p]: int(c)
            for p, c in enumerate(self.counts[i])
            if c > 0
        }

    def fit_errors(self, i: int) -> FitErrors:
        """Reference-identical FitErrors for task ``i`` — ``.error()``
        renders byte-equal to the host path's aggregate message for the
        same snapshot (tests/test_explain.py pins it)."""
        fe = FitErrors()
        fe.set_histogram(int(self.counts[i].sum()), self.histogram(i))
        return fe

    def node_reasons(self, i: int, node_names: List[str]) -> Dict[str, str]:
        """node name → failing reason for task ``i`` (plane-retention
        runs only)."""
        if self.reasons is None:
            return {}
        out: Dict[str, str] = {}
        for n, code in enumerate(self.reasons[i][: len(node_names)]):
            if code < N_EXPLAIN_REASONS:
                out[node_names[n]] = EXPLAIN_REASONS[code]
        return out


#: wall-clock ms of the most recent run_explain in this process — read
#: right after the call by the cycle loop (bench/phase stats), same
#: single-threaded discipline as dispatch state
last_run_ms: float = 0.0


def run_explain(
    snap: PackedSnapshot,
    retain_planes: bool = False,
    task_rows: Optional[np.ndarray] = None,
) -> ExplainResult:
    """PackedSnapshot → ExplainResult via the jitted on-device reduction.

    ``task_rows`` restricts the reduction to those task rows (the
    callers pass the UNPLACED rows — explaining 8 stuck tasks of a 50k
    session must not pay a [50k, N] reduction).  The subset is padded
    to a power-of-two bucket so a steady trickle of stuck tasks hits
    the jit cache; rows outside the subset come back all-zero (reads as
    "not proven infeasible", which sends consumers to the host sweep —
    conservative, never wrong).

    Runs in the scheduler process, beside the kernels, as a
    ``vtpu/allocate.explain`` span, whose duration feeds the
    explain-overhead histogram."""
    from volcano_tpu import trace
    from volcano_tpu.metrics import metrics

    rec = trace.get_recorder()
    if rec.enabled:
        rec.event(
            "dispatch:explain", "kernel",
            tasks=snap.n_tasks, nodes=snap.n_nodes,
            rows=(len(task_rows) if task_rows is not None else snap.n_tasks),
        )

    rows = None
    if task_rows is not None:
        rows = np.asarray(task_rows, dtype=np.int64)
        if rows.size == 0:
            return ExplainResult(
                np.zeros((snap.n_tasks, N_EXPLAIN_REASONS), dtype=np.int32),
                snap.n_nodes,
                np.full((snap.n_tasks, snap.n_nodes), N_EXPLAIN_REASONS,
                        dtype=np.int8) if retain_planes else None,
            )
    global last_run_ms
    with trace.span("vtpu/allocate.explain", "explain", label="explain") as sp:
        result = _reduce_reasons(snap, retain_planes, rows)
    last_run_ms = sp.seconds * 1e3
    metrics.update_explain_duration(sp.seconds)
    return result


def _reduce_reasons(
    snap: PackedSnapshot, retain_planes: bool, rows: Optional[np.ndarray]
) -> ExplainResult:
    import jax.numpy as jnp

    from volcano_tpu.ops.device_stage import device_plane as _dp
    from volcano_tpu.ops.packing import (
        _bucket,
        class_absolute,
        spread_node_planes,
        spread_task_columns,
    )

    padded = None
    if rows is not None:
        padded = np.zeros(_bucket(len(rows)), dtype=np.int64)
        padded[: len(rows)] = rows
        task_resreq = np.asarray(snap.task_resreq)[padded]
        task_sel = np.asarray(snap.task_sel_bits)[padded]
        task_tol = np.asarray(snap.task_tol_bits)[padded]
    else:
        task_resreq = _dp(snap, "task_resreq")
        task_sel = _dp(snap, "task_sel_bits")
        task_tol = _dp(snap, "task_tol_bits")
    spread = None
    if snap.has_spread:
        thr, _inc = spread_task_columns(snap, snap.task_resreq.shape[0])
        spread = tuple(jnp.asarray(a) for a in (
            thr if padded is None else thr[padded],
            *spread_node_planes(snap, snap.node_idle.shape[0]),
            class_absolute(snap),
        ))

    reasons, counts = explain_counts(
        jnp.asarray(task_resreq),
        jnp.asarray(task_sel),
        jnp.asarray(task_tol),
        jnp.asarray(_dp(snap, "node_idle")),
        jnp.asarray(_dp(snap, "node_label_bits")),
        jnp.asarray(_dp(snap, "node_taint_bits")),
        jnp.asarray(_dp(snap, "node_ok")),
        jnp.asarray(_dp(snap, "node_task_count")),
        jnp.asarray(_dp(snap, "node_max_tasks")),
        jnp.asarray(_dp(snap, "tolerance")),
        jnp.int32(snap.n_nodes),
        spread=spread,
    )
    if rows is None:
        counts_np = np.asarray(counts)[: snap.n_tasks]
        planes_np = (
            np.asarray(reasons)[: snap.n_tasks, : snap.n_nodes]
            if retain_planes
            else None
        )
    else:
        counts = np.asarray(counts)
        counts_np = np.zeros((snap.n_tasks, counts.shape[1]), dtype=np.int32)
        counts_np[rows] = counts[: len(rows)]
        planes_np = None
        if retain_planes:
            planes_np = np.full(
                (snap.n_tasks, snap.n_nodes), N_EXPLAIN_REASONS, dtype=np.int8
            )
            planes_np[rows] = np.asarray(reasons)[: len(rows), : snap.n_nodes]
    return ExplainResult(counts_np, snap.n_nodes, planes_np)


def task_exactly_encoded(snap: PackedSnapshot, i: int) -> bool:
    """May device counts for row ``i`` be trusted as the host truth?
    Requires the row's predicates to be bitset-exact (no rich affinity),
    its hard topology spread constraints and required inter-pod terms
    encoded, no registry overflow (every row suspect then), and
    MiB-exact memory lanes (the fit plane rounds otherwise)."""
    if getattr(snap, "registry_overflow", False) or not snap.memory_exact:
        return False
    spread_host = getattr(snap, "task_spread_host", None)
    if spread_host is not None and spread_host[i]:
        return False
    needs_host = getattr(snap, "task_needs_host", None)
    if needs_host is None:
        # remote/journal snapshots don't carry per-row bookkeeping —
        # fall back to the session-level flag
        return not snap.needs_host_validation
    return not bool(needs_host[i])


def explain_enabled() -> bool:
    """Process-wide default for device-derived explanations (the
    VTPU_NO_EXPLAIN escape hatch; actions may override per-instance)."""
    import os

    return not os.environ.get("VTPU_NO_EXPLAIN")


def session_explain_compatible(ssn) -> bool:
    """May device reason counts stand in for this session's host
    predicate chain?  Requires the predicates plugin (without it the
    host chain has none of the selector/taint/unschedulable checks the
    planes encode) and NO opt-in pressure predicates — the host chain
    raises 'node(s) had memory pressure' etc. BETWEEN the pod-count and
    unschedulable checks, a reason the device planes cannot see, so a
    pressure-enabled session's synthesized messages could name the
    wrong cause.  The single gate shared by jax-allocate's context and
    the no-victim synthesis."""
    if "predicates" not in ssn.predicate_fns:
        return False
    pred = ssn.plugins.get("predicates")
    if pred is not None and (
        getattr(pred, "memory_pressure_enable", False)
        or getattr(pred, "disk_pressure_enable", False)
        or getattr(pred, "pid_pressure_enable", False)
    ):
        return False
    return True


def synthesize_no_victim_explanations(ssn, pk) -> int:
    """The jax-preempt / jax-reclaim no-victim path: the device found
    nothing to evict, so the preemptors stay Pending with no recorded
    reason.  For every packed preemptor the device can PROVE fits no
    node at the current state, synthesize the reference FitErrors into
    ``job.nodes_fit_errors`` so the Unschedulable event + pod-condition
    writeback fires exactly as on a host-scheduled cycle.  Returns the
    number of tasks explained.

    The pack is fresh (the action packs, dispatches, and lands here
    before any Statement mutation), so the counts reflect the live
    session state."""
    from volcano_tpu.metrics import metrics

    if not explain_enabled() or not session_explain_compatible(ssn):
        return 0
    base = pk.base
    if base.n_nodes == 0 or base.n_tasks == 0:
        return 0
    result = run_explain(base)
    explained = 0
    for i in range(base.n_tasks):
        if not task_exactly_encoded(base, i):
            continue
        if not result.all_infeasible(i):
            continue
        job = ssn.jobs.get(pk.job_uids[base.task_job[i]])
        if job is None:
            continue
        uid = pk.ptask_uids[i]
        if uid in job.nodes_fit_errors:
            continue
        job.nodes_fit_errors[uid] = result.fit_errors(i)
        ssn.touched_jobs.add(job.uid)
        for reason in result.histogram(i):
            metrics.register_unschedulable_reason(reason)
        explained += 1
    if explained and ssn._trace.enabled:
        ssn._trace.event(
            "explain-no-victim", "action", tasks=explained,
        )
    return explained


# ---- last-cycle explanation (the /explain debug surface) ----

_last_lock = threading.Lock()
_last: Optional[Dict[str, Any]] = None  # guarded-by: _last_lock


def set_last_explain(info: Optional[Dict[str, Any]]) -> None:
    """Park the most recent cycle's explanation summary: consumed by the
    scheduler's ``GET /explain`` endpoint and tests.  Same
    single-writer discipline as dispatch state (the cycle loop), but
    read from serving threads — hence the lock."""
    global _last
    with _last_lock:
        _last = info


def last_explain() -> Optional[Dict[str, Any]]:
    with _last_lock:
        return _last
