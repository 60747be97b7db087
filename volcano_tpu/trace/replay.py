"""Deterministic re-execution of recorded cycles.

``verify()`` promotes the bench scripts' ad-hoc ``identical_bindings``
check into a first-class API: load a captured PackedSnapshot from the
journal, re-run it through a chosen executor, and diff the resulting
assignment against the recorded one.  All executors share one exact
semantics (ops/dispatch.py module docstring), so any diff is a real
regression — a kernel change that moved bindings, a nondeterministic
tie-break, or a corrupted capture.

Executors:

  * ``native``  — the C++ host baseline (volcano_tpu.native); raises
                  RuntimeError when the toolchain isn't available.
  * ``jax``     — the plain XLA scan (ops/kernels.run_packed), the
                  reference formulation.  Runs everywhere.
  * ``blocked`` — the blocked top-K formulation (ops/blocked.py).
  * ``pallas``  — the fused TPU kernel (TPU only).
  * ``auto``    — whatever ops/dispatch.select_executor picks here.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

EXECUTORS = ("native", "jax", "blocked", "pallas", "auto")

#: ops/dispatch.select_executor vocabulary → replayable executor names
#: (the xla-scan and mesh-sharded formulations both replay through the
#: single-chip reference scan — identical bindings by contract)
_DISPATCH_ALIASES = {"xla-scan": "jax", "sharded": "jax"}


def replay_executor_name(dispatch_name: str) -> str:
    """Translate a dispatch-layer executor pick into the name
    ``run_snapshot`` accepts, for journaling."""
    return _DISPATCH_ALIASES.get(dispatch_name, dispatch_name)


@dataclass
class ReplayResult:
    cycle: int
    executor: str
    recorded_executor: str
    n_tasks: int
    n_placed_recorded: int
    n_placed_replayed: int
    n_diffs: int
    #: (task index, recorded node index, replayed node index) per mismatch
    diffs: List[Tuple[int, int, int]] = field(default_factory=list)

    @property
    def match(self) -> bool:
        return self.n_diffs == 0

    def summary(self) -> str:
        verdict = "IDENTICAL" if self.match else f"{self.n_diffs} DIFFS"
        return (
            f"cycle {self.cycle}: recorded[{self.recorded_executor}] vs "
            f"replayed[{self.executor}] over {self.n_tasks} tasks "
            f"({self.n_placed_recorded}/{self.n_placed_replayed} placed): "
            f"{verdict}"
        )


def run_snapshot(snap, executor: str = "jax", weights=None):
    """PackedSnapshot → assignment[T] through the named executor."""
    from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS

    weights = weights or DEFAULT_WEIGHTS
    if executor == "native":
        from volcano_tpu import native

        if native.load() is None:
            raise RuntimeError("native executor unavailable (no C++ toolchain)")
        if weights != DEFAULT_WEIGHTS:
            # silently dropping the recorded weights would turn every
            # diff into a spurious "kernel regression" verdict
            raise RuntimeError(
                "native executor scores with DEFAULT_WEIGHTS only; this "
                "capture recorded non-default weights — replay it with "
                "the jax/blocked executor instead"
            )
        return native.baseline_allocate(snap)
    if executor == "jax":
        from volcano_tpu.ops.kernels import run_packed

        return run_packed(snap, weights=weights)
    if executor == "blocked":
        from volcano_tpu.ops.blocked import run_packed_blocked

        return run_packed_blocked(snap, weights=weights)
    if executor == "pallas":
        from volcano_tpu.ops.pallas_session import run_packed_pallas

        return run_packed_pallas(snap, weights=weights)
    if executor == "auto":
        from volcano_tpu.ops.dispatch import run_packed_auto

        return run_packed_auto(snap, weights=weights)
    raise ValueError(f"unknown executor {executor!r}; expected one of {EXECUTORS}")


def _as_journal(journal):
    from volcano_tpu.trace.journal import Journal

    if isinstance(journal, str):
        return Journal(journal)
    return journal


def replay(
    journal, cycle: Optional[int] = None, executor: str = "jax"
) -> ReplayResult:
    """Re-run a recorded cycle's snapshot and diff against its recorded
    assignment.  ``journal`` is a Journal or a directory path; ``cycle``
    defaults to the newest cycle with a snapshot."""
    journal = _as_journal(journal)
    if cycle is None:
        snaps = journal.snapshot_cycles()
        if not snaps:
            raise FileNotFoundError(
                f"no snapshot captures in journal {journal.root!r} "
                "(was the recorder's snapshot_every knob set?)"
            )
        cycle = snaps[-1]
    snap, extras = journal.read_snapshot(cycle)
    recorded = np.asarray(extras["assignment"], dtype=np.int64)
    # re-run with the kernel parameters the capture recorded (older
    # journals without them fall back to the defaults)
    weights = None
    if "weights" in extras:
        from volcano_tpu.ops.kernels import ScoreWeights

        lanes = [float(v) for v in np.asarray(extras["weights"]).ravel()]
        if len(lanes) == len(ScoreWeights._fields):
            weights = ScoreWeights(*lanes[:-1], lr_int_exact=bool(lanes[-1]))
        else:
            # a diff produced under substituted weights is NOT a kernel
            # regression — without this warning it would read as one
            import warnings

            warnings.warn(
                f"journal cycle {cycle}: recorded {len(lanes)} weight "
                f"lanes but ScoreWeights now has "
                f"{len(ScoreWeights._fields)} fields; replaying with "
                "DEFAULT_WEIGHTS — binding diffs may reflect the weight "
                "substitution, not a kernel regression",
                RuntimeWarning,
                stacklevel=2,
            )
    # journals written before the per-job commit/discard moved into the
    # scan may carry a ``gang_rounds`` extra: it bounds nothing now
    replayed = np.asarray(
        run_snapshot(snap, executor=executor, weights=weights),
        dtype=np.int64,
    )

    n = snap.n_tasks
    rec_n, rep_n = recorded[:n], replayed[:n]
    mismatch = np.nonzero(rec_n != rep_n)[0]
    return ReplayResult(
        cycle=cycle,
        executor=executor,
        recorded_executor=extras.get("executor", ""),
        n_tasks=n,
        n_placed_recorded=int((rec_n >= 0).sum()),
        n_placed_replayed=int((rep_n >= 0).sum()),
        n_diffs=len(mismatch),
        diffs=[(int(i), int(rec_n[i]), int(rep_n[i])) for i in mismatch],
    )


def verify(
    journal, cycle: Optional[int] = None, executor: str = "jax"
) -> ReplayResult:
    """The first-class ``identical_bindings`` check: replay and return the
    diff result (``result.match`` is the old boolean)."""
    return replay(journal, cycle=cycle, executor=executor)
