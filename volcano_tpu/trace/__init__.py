"""volcano_tpu.trace — program spans, and the cycle record/replay journal.

:func:`span` is the program's one span primitive: every layer boundary
of the scheduling cycle opens one (the names are :data:`SPAN_NAMES`).
It puts the region on the profiler's clock, beside the device's ops,
and feeds the same interval to the cycle recorder and the flight
recorder when they are on.

The journal has three pieces:

  * **recorder** — thread-safe span/event capture per scheduling cycle
    (recorder.py), zero-cost when disabled.
  * **journal**  — JSONL event log + sampled npz PackedSnapshot captures
    in a bounded on-disk ring (journal.py).
  * **replayer** — deterministic re-execution of a captured snapshot
    through any executor, diffed against the recorded bindings
    (replay.py ``verify()``), plus Chrome trace_event timeline export
    (export.py).

Usage::

    from volcano_tpu import trace

    trace.enable("/var/log/vtpu-trace", snapshot_every=10)
    ...  # scheduler cycles record themselves
    result = trace.replay.verify("/var/log/vtpu-trace", executor="jax")
    assert result.match

Instrumented code always goes through :func:`get_recorder`; with tracing
off that returns the shared ``NullRecorder`` whose calls are no-ops.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

from volcano_tpu.trace import export, journal, replay  # noqa: F401
from volcano_tpu.trace.export import (
    chrome_trace,
    export_chrome_trace,
    export_merged_chrome_trace,
    merge_chrome_traces,
)
from volcano_tpu.trace.journal import Journal
from volcano_tpu.trace.recorder import NullRecorder, TraceRecorder
from volcano_tpu.trace.replay import ReplayResult, run_snapshot, verify

_NULL = NullRecorder()
_recorder = _NULL

#: correlation id of the scheduling cycle currently executing in this
#: process (-1 outside a cycle).  Set by the scheduler loop every
#: run_once — independent of whether a recorder is installed — and
#: attached to outbound VBUS request frames (bus/remote.py) so a
#: pending task can be followed scheduler → bus → controllers across
#: process boundaries.
_current_cycle: int = -1


def set_current_cycle(cycle_id: int) -> None:
    global _current_cycle
    _current_cycle = cycle_id


def current_cycle() -> int:
    return _current_cycle


def get_recorder():
    """The active recorder — NullRecorder unless :func:`enable` (or
    :func:`set_recorder`) installed a live one."""
    return _recorder


def set_recorder(rec: Optional[TraceRecorder]) -> None:
    global _recorder
    _recorder = rec if rec is not None else _NULL


def enable(
    journal_dir: Optional[str] = None,
    snapshot_every: int = 0,
    keep: int = 64,
) -> TraceRecorder:
    """Install a live recorder.  With ``journal_dir`` set, completed
    cycles append to the bounded on-disk ring there; ``snapshot_every=N``
    additionally captures the packed session + kernel assignment every
    Nth cycle for replay."""
    jr = Journal(journal_dir, keep=keep) if journal_dir else None
    rec = TraceRecorder(journal=jr, snapshot_every=snapshot_every)
    set_recorder(rec)
    return rec


def disable() -> None:
    set_recorder(None)


# ---- spans on the profiler's clock ----

#: Every name :func:`span` is opened with.  ``{action}`` and ``{plugin}``
#: stand for the action's and the plugin's registered name.  All carry
#: the ``vtpu/`` prefix, so no program span takes the name of an
#: annotation some other tool puts around the same calls.
SPAN_NAMES = (
    "vtpu/cycle",  # Scheduler.run_once
    "vtpu/cache.snapshot",  # SchedulerCache.snapshot
    "vtpu/session.open",  # framework.open_session
    "vtpu/plugin.{plugin}.open",
    "vtpu/session.close",  # framework.close_session
    "vtpu/plugin.{plugin}.close",
    "vtpu/session.job_updater",  # JobUpdater.update_all in close_session
    "vtpu/session.job_writeback",  # the due status writes, inside vtpu/session.job_updater
    "vtpu/action.{action}",  # each action of run_once
    "vtpu/allocate.prestage",  # jax-allocate: node planes staged before ORDER
    "vtpu/allocate.order",  # jax-allocate ORDER
    "vtpu/allocate.pack",
    "vtpu/allocate.spread_pack",  # the topology spread planes, inside the pack
    "vtpu/allocate.affinity_pack",  # the anti-affinity classes, inside the pack
    "vtpu/allocate.dispatch",  # the kernel's executor call
    "vtpu/allocate.explain",  # the reason-count reduction (ops/explain)
    "vtpu/allocate.apply",  # jax-allocate APPLY
    "vtpu/allocate.fast_apply",  # the bulk commit inside APPLY
    "vtpu/allocate.gang_discard",  # recording the discarded gangs, inside the bulk commit
    "vtpu/allocate.explain_tail",  # APPLY's pack and reduction of the tasks ORDER left out
    "vtpu/allocate.host_sweep",  # one host predicate sweep, one task
    "vtpu/preempt.pack",
    "vtpu/preempt.device",  # jax-preempt's kernel outcome
    "vtpu/preempt.apply",  # evictions, pipelines and their commit
    "vtpu/statement.commit",  # Statement.commit
    "vtpu/cache.flush",  # SchedulerCache.flush
    "vtpu/cache.bind_batch",  # SchedulerCache.bind_batch (args binds, jobs, nodes)
    "vtpu/gc",  # a generation-2 collection (utils/gcutil.py)
    "vtpu/gc.quiesce",  # the collector policy's thaw, collect and freeze, after vtpu/cycle
)

#: ``jax.profiler.TraceAnnotation`` once this process has imported JAX;
#: a process that never does (controllers, apiserver) runs no profiler
#: and takes the null path
_annotation = None
_obs = None  # volcano_tpu.obs.spans, bound at the first span


def annotation_class():
    """The profiler annotation class, or None before JAX is imported
    here.  Imports nothing: safe inside a gc callback."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            _annotation = profiler.TraceAnnotation
    return _annotation


def _obs_spans():
    global _obs
    if _obs is None:
        from volcano_tpu.obs import spans

        _obs = spans
    return _obs


class Span:
    """One region, timed once: the clock is read at entry and at exit,
    and the one interval goes to every sink that is on — a
    ``jax.profiler.TraceAnnotation`` named ``name`` (a no-op in C++ when
    no profiler session runs), the cycle recorder, and the flight
    recorder.  ``label`` is the name the recorder and the flight
    recorder know the region by where that predates the profiler names
    (``open_session``, ``action:<name>``); it defaults to ``name``.
    ``seconds`` holds the duration once the region exits."""

    __slots__ = ("name", "cat", "label", "args", "seconds", "_t0", "_ann", "_obs")

    def __init__(self, name: str, cat: str, label: Optional[str], args: dict):
        self.name = name
        self.cat = cat
        self.label = label or name
        self.args = args
        self.seconds = 0.0
        self._ann = None

    def set(self, **args) -> None:
        """Add args known only inside the region (what it counted)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        cls = annotation_class()
        if cls is not None:
            self._ann = cls(self.name, **self.args)
            self._ann.__enter__()
        self._obs = _obs_spans().span(self.label, cat=self.cat, args=self.args)
        self._obs.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if _recorder.enabled:
            _recorder.complete(self.label, self.cat, self._t0, seconds, **self.args)
        self._obs.end(seconds, exc_type)
        return False


def span(name: str, cat: str = "span", label: Optional[str] = None, **args) -> Span:
    """A :class:`Span` named ``name`` (one of :data:`SPAN_NAMES`)."""
    return Span(name, cat, label, args)


__all__ = [
    "Journal",
    "NullRecorder",
    "ReplayResult",
    "SPAN_NAMES",
    "Span",
    "TraceRecorder",
    "chrome_trace",
    "current_cycle",
    "set_current_cycle",
    "disable",
    "enable",
    "export_chrome_trace",
    "export_merged_chrome_trace",
    "get_recorder",
    "merge_chrome_traces",
    "replay",
    "run_snapshot",
    "set_recorder",
    "span",
    "verify",
]
