"""Kernel auto-dispatch: pick the fastest exact formulation for the
session shape and backend.  The one place the ``jax-*`` actions run
their kernels: :func:`run_allocate` for jax-allocate and
:func:`run_preempt_auto` for jax-preempt, both in this process, on the
chip the scheduler holds.

Three formulations share one semantics (identical bindings, proven by
tests/test_blocked.py and tests/test_pallas.py); a session with
multi-task gangs (``PackedSnapshot.episodic``) takes only those that
carry Volcano's per-job commit/discard in the scan — pallas, the plain
scan and native:

  * ``run_packed_pallas`` — the whole greedy scan inside one Pallas TPU
    kernel, node state VMEM-resident (ops/pallas_session.py).  ~50x the
    XLA scan at 50k x 10k.  TPU only, and only within the f32
    floor-division exactness envelope (node capacity * 10 < 2^24).
  * ``run_packed_blocked`` — blocked top-K candidate tracking with exact
    outside-max stop/fallback (ops/blocked.py).  Best off-TPU at scale;
    one row per job only.
  * ``run_packed`` — the plain lax.scan (ops/kernels.py).  Smallest
    compile, fine for small sessions and the reference for equivalence.
"""

from __future__ import annotations

import numpy as np

from volcano_tpu.ops.chip_memory import SMEM_BUDGET, VMEM_BUDGET
from volcano_tpu.ops.kernels import (
    DEFAULT_WEIGHTS,
    f32_lr_exact,
    run_packed,
    ScoreWeights,
)
from volcano_tpu.ops.packing import PackedSnapshot

#: sessions below this task*node area keep the plain scan (compile cost
#: of the fancier kernels outweighs the win)
_SMALL_AREA = 1_000_000

#: node count above which a multi-device session shards the node axis
#: instead of running the single-chip blocked formulation
_SHARD_MIN_NODES = 2_048

#: degradation ladder: which rung a failing/tripped executor falls to.
#: blocked and xla-scan are the floor (plain XLA formulations with no
#: exotic lowering) — they carry no breaker and their failures propagate.
_FALLBACK = {"native": "xla-scan", "pallas": "blocked", "sharded": "blocked"}


def _fallback(executor: str, snap: PackedSnapshot) -> str:
    """The rung below ``executor`` for this session: the plain scan for
    a session with multi-task gangs or spread classes, which the blocked
    formulations do not carry."""
    rung = _FALLBACK[executor]
    if rung == "blocked" and (snap.episodic or snap.has_spread):
        return "xla-scan"
    return rung


def _breaker(name: str):
    """Executor breaker: 3 consecutive failures open it, half-open
    re-probe after 30s promotes the executor back on success."""
    from volcano_tpu.faults.breaker import get_breaker

    return get_breaker(name, failure_threshold=3, cooldown_s=30.0)


def _assignment_valid(snap: PackedSnapshot, out) -> bool:
    """Cheap sanity gate on an upper-rung executor's output: the right
    length and every value a real node index or -1.  A kernel that
    silently produced garbage (NaN score planes argmax to arbitrary
    indices) degrades like a raised error instead of binding tasks to
    nonexistent nodes."""
    arr = np.asarray(out)
    if arr.ndim != 1 or arr.shape[0] < snap.n_tasks:
        return False
    head = arr[: snap.n_tasks]
    return bool(((head >= -1) & (head < snap.n_nodes)).all())


class _CorruptOutput(RuntimeError):
    """Upper-rung executor returned an invalid assignment."""


class _PhaseAbandoned(RuntimeError):
    """This dispatch runs on a watchdog worker whose cycle already
    completed on the host path — unwind without touching breakers,
    fallback counters, last-executor notes, or running any fallback."""


def _tpu_available() -> bool:
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - jax init failure
        return False


def _device_count() -> int:
    try:
        import jax

        return len(jax.devices())
    except Exception:  # pragma: no cover - jax init failure
        return 1


def select_executor(
    snap: PackedSnapshot, weights: ScoreWeights = DEFAULT_WEIGHTS
) -> str:
    """Which executor run_packed_auto will use: 'native' | 'pallas' |
    'sharded' | 'blocked' | 'xla-scan'.

    Multi-chip policy (BASELINE config 5 'pmap over v5e-8'; the scale
    coping the reference does with 16-way goroutines + subsampling,
    scheduler_helper.go:42-117): sessions too big for one chip's VMEM —
    or beyond the single-chip node-width threshold — shard the node axis
    over the mesh when ≥2 devices exist; single-chip otherwise.

    Hard topology spread is encoded by the Pallas kernel and the plain
    scan only: such a session takes ``pallas`` where it may, else
    ``xla-scan`` — never ``native`` or the blocked formulations, whose
    candidate tracking assumes a placement changes one node.  A session
    with multi-task gangs takes ``pallas``, else the plain scan, or
    ``native`` below the small-area cut: the blocked formulations do not
    roll a gang back."""
    area = max(snap.n_tasks, 1) * max(snap.n_nodes, 1)
    spread = snap.has_spread
    if area < _SMALL_AREA:
        if weights == DEFAULT_WEIGHTS and not spread:
            from volcano_tpu import native

            if native.load() is not None:
                return "native"
        return "xla-scan"
    if f32_lr_exact(snap) and _tpu_available():
        from volcano_tpu.ops.pallas_session import pallas_vmem_bytes

        if pallas_vmem_bytes(snap) <= VMEM_BUDGET:
            return "pallas"
    if spread or snap.episodic:
        return "xla-scan"
    if _device_count() >= 2 and snap.n_nodes >= _SHARD_MIN_NODES:
        return "sharded"
    return "blocked"


def preempt_f32_exact(pk) -> bool:
    """f32 exactness for the PREEMPT arrays: base node planes AND the
    preempt-specific lanes the kernel arithmetics on.  Gating on
    ``pk.base`` alone (ADVICE r3) missed sessions whose victims or
    future-idle exceed the floor-division envelope while node_alloc does
    not (e.g. releasing pods inflating future_idle).  The bound must hold
    for the ACCUMULATED plane — the kernel adds evicted victims' resreqs
    back into future-idle, so the worst case per node is
    fi0 + sum(victim resreqs on that node), not any single element."""
    import numpy as np

    from volcano_tpu.ops.kernels import MAX_PRIORITY

    limit = 2**24 / MAX_PRIORITY
    if not f32_lr_exact(pk.base):
        return False
    nv = max(pk.n_victims, 0)
    worst = pk.node_fi0[:, :2].astype(np.float64).copy()
    if nv:
        vic_node = pk.vic_node[:nv]
        np.add.at(worst[:, 0], vic_node, pk.vic_resreq[:nv, 0].astype(np.float64))
        np.add.at(worst[:, 1], vic_node, pk.vic_resreq[:nv, 1].astype(np.float64))
    return float(worst.max(initial=0.0)) < limit


def select_preempt_executor(pk) -> str:
    """Executor for the preempt pass: 'pallas' | 'dense'.  Same decision
    shape as select_executor — pallas only on TPU, inside the f32
    envelope, and within the VMEM budget (the preempt kernel's footprint
    additionally scales with K = max victims per node)."""
    base = pk.base
    area = max(base.n_tasks, 1) * max(base.n_nodes, 1)
    if area < _SMALL_AREA:
        return "dense"
    # the Pallas kernel models the classic {priority, gang, conformance}
    # preemptable tier only; drf-preemptable (and weakened-filter)
    # sessions run the dense formulation
    if not (pk.use_prio and pk.use_gang and pk.use_conf) or pk.use_drf:
        return "dense"
    if preempt_f32_exact(pk) and _tpu_available():
        from volcano_tpu.ops.preempt_pallas import (
            preempt_smem_bytes,
            preempt_vmem_bytes,
        )

        if (
            preempt_vmem_bytes(pk) <= VMEM_BUDGET
            and preempt_smem_bytes(pk) <= SMEM_BUDGET
        ):
            return "pallas"
    return "dense"


def run_preempt_auto(pk, weights: ScoreWeights = DEFAULT_WEIGHTS):
    """PreemptPacked → (evicted, pipelined), fastest exact path: pallas
    when eligible, degrading to the dense formulation on runtime
    failure.  The single copy of the preempt dispatch, and the
    jax-preempt action's entry.  The cycle watchdog does not bound it:
    preempt has no host-completion seam to hand an abandoned device
    pass to.  The pallas rung sits behind a circuit breaker: repeated
    failures stop re-attempting (and re-paying the failure latency)
    every cycle; a half-open probe later promotes it back."""
    from volcano_tpu import faults, trace
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops.preempt_pack import preempt_dense

    executor = select_preempt_executor(pk)
    if executor == "pallas" and not _breaker("preempt-pallas").allow():
        # demote BEFORE the trace event below, so the journal names the
        # executor that actually runs during the open window
        metrics.register_executor_fallback(
            "preempt-pallas", "dense", "circuit-open"
        )
        executor = "dense"
    rec = trace.get_recorder()
    if rec.enabled:
        rec.event(
            "dispatch:preempt", "kernel",
            executor=executor,
            tasks=pk.base.n_tasks, victims=pk.n_victims,
        )
    if executor == "pallas":
        from volcano_tpu.ops.preempt_pallas import run_preempt_pallas

        br = _breaker("preempt-pallas")
        fp = faults.get_plane()
        try:
            if fp.enabled and fp.should("device.lowering"):
                raise RuntimeError("fault-injected lowering failure")
            out = run_preempt_pallas(pk, weights=weights)
            br.record_success()
            return out
        except Exception as e:  # noqa: BLE001 — degrade, don't abort
            from volcano_tpu.utils.logging import get_logger

            br.record_failure(str(e))
            metrics.register_executor_fallback(
                "preempt-pallas", "dense", "error"
            )
            get_logger(__name__).error(
                "pallas preempt failed (%s); dense fallback", e
            )
    return preempt_dense(pk, weights=weights)


#: executor run_packed_auto last actually EXECUTED — unlike the
#: select_executor pick, this reflects mid-session degradations
#: (native→xla-scan, pallas/sharded→blocked).  Single-threaded cycle
#: loop state: read it right after the call, same thread (the trace
#: capture in jax_allocate does).
_last_executor = ""


def last_executor() -> str:
    return _last_executor


def _note(executor: str) -> str:
    global _last_executor
    _last_executor = executor
    return executor


#: ScanOutcome of the last run_packed_auto — what the scan placed and
#: rolled back, and the failing steps' reason counts where the
#: formulation reports them.  Same read-right-after-the-call discipline.
_last_outcome = None


def last_outcome():
    return _last_outcome


def run_packed_auto(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
) -> np.ndarray:
    """PackedSnapshot → committed assignment[T], fastest exact path for
    the shape; the whole outcome is :func:`last_outcome`.

    Dispatches on :func:`select_executor` — the single copy of the
    decision tree — so what runs always matches what callers (e.g.
    bench.py's ``executor`` field) report.  Upper rungs (native, pallas,
    sharded) sit behind per-executor circuit breakers: a tripped rung is
    skipped without being attempted (no failure latency every cycle)
    until its half-open probe succeeds; every demotion counts in
    ``volcano_executor_fallbacks_total`` and the outputs of upper rungs
    pass a validity gate so silently-corrupt kernels degrade like raised
    errors."""
    global _last_outcome
    _last_outcome = None
    outcome = _run_outcome(snap, weights)
    _last_outcome = outcome
    return outcome.assignment


def _run_outcome(snap: PackedSnapshot, weights: ScoreWeights):
    from volcano_tpu.ops.kernels import ScanOutcome, run_outcome

    executor = select_executor(snap, weights)
    from volcano_tpu import faults, trace
    from volcano_tpu.metrics import metrics

    fp = faults.get_plane()
    if executor in _FALLBACK and not _breaker(executor).allow():
        metrics.register_executor_fallback(
            executor, _fallback(executor, snap), "circuit-open"
        )
        executor = _fallback(executor, snap)
    rec = trace.get_recorder()
    if rec.enabled:
        rec.event(
            "dispatch:allocate", "kernel",
            executor=executor, tasks=snap.n_tasks, nodes=snap.n_nodes,
        )
    if fp.enabled and fp.should("device.slow"):
        import time

        time.sleep(fp.param_ms("device.slow") / 1e3)
    _note(executor)

    def attempt(run):
        """One upper-rung attempt under its breaker: injected lowering
        failures, the corrupt-output gate, and success/failure
        accounting all live here once."""
        from volcano_tpu.faults import watchdog

        br = _breaker(executor)
        if fp.enabled and fp.should("device.lowering"):
            raise RuntimeError("fault-injected lowering failure")
        out = run()
        if watchdog.abandoned():
            # the cycle watchdog gave up on this worker mid-run: the
            # cycle already completed on the host path — this (late)
            # result is garbage to it, and recording a verdict now
            # would race the next live cycle's breaker state
            raise _PhaseAbandoned(executor)
        if fp.enabled and fp.should("device.nan"):
            out = ScanOutcome.of_assignment(np.full(
                np.asarray(out.assignment).shape, np.iinfo(np.int32).max,
                dtype=np.int32,
            ))
        if not _assignment_valid(snap, out.assignment):
            raise _CorruptOutput(f"{executor} returned an invalid assignment")
        br.record_success()
        return out

    def degrade(e: Exception) -> str:
        from volcano_tpu.faults import watchdog
        from volcano_tpu.utils.logging import get_logger

        if isinstance(e, _PhaseAbandoned) or watchdog.abandoned():
            # abandoned worker: no breaker verdict, no fallback count,
            # no _note overwrite, and — by raising before the caller's
            # fallback line — no duplicate fallback allocate competing
            # with the next cycle for the device
            raise _PhaseAbandoned(executor)
        fallback = _fallback(executor, snap)
        _breaker(executor).record_failure(str(e))
        metrics.register_executor_fallback(
            executor, fallback,
            "corrupt-output" if isinstance(e, _CorruptOutput) else "error",
        )
        get_logger(__name__).error(
            "%s allocate failed (%s); %s fallback", executor, e, fallback
        )
        return _note(fallback)

    def floor(rung: str):
        if rung == "blocked":
            from volcano_tpu.ops.blocked import run_packed_blocked

            return ScanOutcome.of_assignment(run_packed_blocked(snap, weights=weights))
        return run_outcome(snap, weights=weights)

    if executor == "native":
        from volcano_tpu import native

        try:
            return attempt(lambda: native.baseline_outcome(snap))
        except (RuntimeError, ValueError) as e:
            # Native executor hit an internal error mid-session — degrade
            # to the exact XLA scan rather than failing the session.
            return floor(degrade(e))
    if executor == "pallas":
        from volcano_tpu.ops.pallas_session import run_outcome_pallas

        try:
            return attempt(lambda: run_outcome_pallas(snap, weights=weights))
        except Exception as e:  # noqa: BLE001 — e.g. VMEM overflow at lowering
            return floor(degrade(e))
    if executor == "sharded":
        import jax
        from jax.sharding import Mesh

        from volcano_tpu.ops.sharded import run_packed_sharded

        devices = jax.devices()
        # the node axis shards evenly with dummy padding inside
        # run_packed_sharded; the mesh is 1-D over all devices
        mesh = Mesh(np.array(devices), ("nodes",))
        try:
            return attempt(lambda: ScanOutcome.of_assignment(
                run_packed_sharded(snap, mesh, weights=weights)
            ))
        except Exception as e:  # noqa: BLE001 — degrade like the other paths
            return floor(degrade(e))
    return floor(executor)


#: reason counts of the last run_allocate(explain=True) — [T, P] int32
#: aligned with the snapshot's ordered tasks, or None when the session
#: needed no explanation (everything placed) or explain was off — and
#: the wall-clock ms of their reduction.  Same read-right-after-the-call
#: discipline as _last_executor.
_last_explain_counts = None
_last_explain_ms = None
#: True when those counts were taken at each row's failing step of the
#: scan, False when at the pack's node state
_last_explain_at_step = False


def last_explain_at_step() -> bool:
    return _last_explain_at_step


def last_explain_counts():
    return _last_explain_counts


def last_explain_ms():
    return _last_explain_ms


def run_allocate(
    snap: PackedSnapshot,
    weights: ScoreWeights = DEFAULT_WEIGHTS,
    explain: bool = False,
):
    """The jax-allocate action's entry: :func:`run_packed_auto` under
    the cycle watchdog's remaining budget → ScanOutcome.  With
    ``explain``, the reason counts of the rows that fit no node are read
    back with :func:`last_explain_counts`: those of the failing steps
    where the formulation reports them (:func:`last_explain_at_step`),
    else a reduction over the unplaced rows at the pack's node state.
    Lazy: a fully placed session computes nothing extra."""
    from volcano_tpu.faults import watchdog
    from volcano_tpu.ops.kernels import ScanOutcome

    global _last_explain_counts, _last_explain_ms, _last_explain_at_step
    # cleared up front: an aborted call (deadline, error) must not leave
    # a previous session's counts readable as this session's
    _last_explain_counts = None
    _last_explain_ms = None
    _last_explain_at_step = False
    # run_packed_auto resolves through the module globals at call time,
    # so a wrapper installed on the module attribute sees every dispatch
    out = watchdog.run_with_deadline(
        lambda: run_packed_auto(snap, weights=weights),
        watchdog.remaining_s(),
        "allocate",
    )
    outcome = last_outcome()
    if outcome is None or outcome.assignment is not out:
        outcome = ScanOutcome.of_assignment(np.asarray(out)[: snap.n_tasks])
    if explain:
        failed = outcome.placed[: snap.n_tasks] == -1
        if outcome.reasons is not None:
            if failed.any():
                _last_explain_counts = outcome.reasons
                _last_explain_at_step = True
        else:
            unplaced = np.nonzero(failed)[0]
            if unplaced.size:
                from volcano_tpu.ops import explain as _explain

                _last_explain_counts = _explain.run_explain(
                    snap, task_rows=unplaced
                ).counts
                _last_explain_ms = _explain.last_run_ms
    return outcome


def warmup_kernels(n_tasks: int = 4096, n_nodes: int = 1024,
                   gang_size: int = 8, micro_shapes: bool = True) -> str:
    """Populate the jit cache for the session kernels at a
    representative shape bucket (first TPU compile is ~20-40s; every
    same-bucket session after is cache-hit) and log the duration.
    Returns the executor auto-dispatch SELECTED — if the run degraded to
    a fallback mid-warmup, the dispatcher logged that error itself.
    Behind the scheduler daemon's ``--warmup`` flag.

    ``micro_shapes`` additionally compiles the minimum task bucket at
    the same node count: event-driven micro-cycles score a handful of
    freshly-arrived tasks per wake ([64, N] sessions, usually the
    small-area scan path rather than the headline formulation), and
    without this the FIRST event after startup pays that compile inside
    its submit→bind latency."""
    import time

    from volcano_tpu.ops.synthetic import generate_snapshot
    from volcano_tpu.utils.logging import get_logger

    snap = generate_snapshot(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=gang_size
    )
    executor = select_executor(snap)
    t0 = time.monotonic()
    run_packed_auto(snap)
    if micro_shapes and n_tasks > 64:
        micro_snap = generate_snapshot(
            n_tasks=48, n_nodes=n_nodes, gang_size=1
        )
        run_packed_auto(micro_snap)
    get_logger(__name__).info(
        "warmup compile (%s) done in %.1fs", executor, time.monotonic() - t0
    )
    return executor
