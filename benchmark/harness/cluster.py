"""The system under test, driven in process: a ``SchedulerCache`` fed
through its event handlers (what the informers call), a ``Scheduler``
whose conf comes from the configuration's file, and ``run_once`` called
back to back — the daemon's loop at zero period.

A cycle here is ``run_once`` followed by ``cache.flush()``: it ends when
every bind and eviction it decided has been delivered.

``Probes`` observe, without changing, what the program does in a cycle:
which executor each device dispatch ran on, whether jax-preempt fell back
to the host action, and the pipelined placements that committed.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

ACTION_HISTOGRAM = "volcano_action_scheduling_latency_microseconds"


class Recorder:
    """Binder and evictor of the benchmark: every delivered effect with
    its host-clock stamp and the cycle that decided it."""

    def __init__(self):
        self.lock = threading.Lock()
        self.cycle = -1
        self.binds: List[tuple] = []  # (pod uid, node name, t, cycle)
        self.evicts: List[tuple] = []  # (pod uid, node name, t, cycle)

    def binder(self):
        from volcano_tpu.cache.interface import Binder

        rec = self

        class _Binder(Binder):
            def bind(self, task, hostname):
                with rec.lock:
                    rec.binds.append((task.uid, hostname, time.perf_counter(), rec.cycle))

        return _Binder()

    def evictor(self):
        from volcano_tpu.cache.interface import Evictor

        rec = self

        class _Evictor(Evictor):
            def evict(self, task):
                with rec.lock:
                    rec.evicts.append(
                        (task.uid, task.node_name, time.perf_counter(), rec.cycle)
                    )

        return _Evictor()


def scheduler_conf_yaml(sched: dict) -> str:
    lines = [f'actions: "{sched["actions"]}"', "tiers:"]
    for tier in sched["tiers"]:
        lines.append("- plugins:")
        lines.extend(f"  - name: {name}" for name in tier)
    return "\n".join(lines) + "\n"


def build(objects: dict, sched: dict, recorder: Recorder, conf_path: str):
    """(cache, scheduler) fed with ``objects`` (``nodes``, ``queues``,
    ``priority_classes``, ``pod_groups``, ``pods``) through the cache's
    event handlers, with the daemon's default flags.  ``sched`` is the
    configuration's ``scheduler`` block; its conf is written to
    ``conf_path`` for the scheduler's hot-reloading conf loader."""
    from volcano_tpu.cache import SchedulerCache
    from volcano_tpu.scheduler.scheduler import Scheduler

    cache = SchedulerCache(binder=recorder.binder(), evictor=recorder.evictor())
    for pc in objects.get("priority_classes", []):
        cache.add_priority_class(pc)
    for q in objects["queues"]:
        cache.add_queue(q)
    for n in objects["nodes"]:
        cache.add_node(n)
    for pg in objects["pod_groups"]:
        cache.add_pod_group(pg)
    for p in objects["pods"]:
        cache.add_pod(p)
    with open(conf_path, "w") as f:
        f.write(scheduler_conf_yaml(sched))
    scheduler = Scheduler(cache, scheduler_conf_path=conf_path, period=0.0)
    return cache, scheduler


def capture_task_infos(cache) -> dict:
    """uid → pristine TaskInfo clone (bench/_profsetup.py)."""
    return {
        t.uid: t.clone() for job in cache.jobs.values() for t in job.tasks.values()
    }


def revert(cache, pristine: dict) -> None:
    """Return every task whose status or node moved back to its pristine
    clone, through the cache's internal event mutations — what a
    status-only ``update_pod`` pair does (bench/_profsetup.py
    ``revert_binds``, widened to evicted victims)."""
    with cache._mutex:
        for job in list(cache.jobs.values()):
            for t in list(job.tasks.values()):
                orig = pristine.get(t.uid)
                if orig is None:
                    continue
                if t.status != orig.status or t.node_name != orig.node_name:
                    cache._delete_task(t)
                    cache._add_task(orig.clone())


class Probes:
    """Wrappers installed in this process around the program's dispatch
    and commit points.  They record and pass everything through."""

    def __init__(self, annotate: bool):
        self.executors: List[str] = []
        self.preempt_executors: List[str] = []
        self.host_preempts = 0
        self.pipelined: Dict[str, str] = {}
        #: the sessions handed to the preempt kernel this cycle, for work counts
        self.pks: list = []
        self._install()
        if annotate:
            self._annotate()

    def begin(self) -> None:
        self.executors = []
        self.preempt_executors = []
        self.host_preempts = 0
        self.pipelined = {}
        self.pks = []

    @staticmethod
    def _wrap(owner, name, make):
        orig = getattr(owner, name)
        setattr(owner, name, make(orig))

    def _install(self) -> None:
        from volcano_tpu.actions import preempt as host_preempt
        from volcano_tpu.framework import statement
        from volcano_tpu.ops import dispatch, preempt_pack, preempt_pallas

        probes = self

        def allocate(orig):
            def run_packed_auto(*a, **kw):
                out = orig(*a, **kw)
                probes.executors.append(dispatch.last_executor())
                return out
            return run_packed_auto

        def tag(orig, label):
            def wrapped(*a, **kw):
                probes.preempt_executors.append(label)
                probes.pks.append(a[0])
                return orig(*a, **kw)
            return wrapped

        def host(orig):
            def execute(self_, ssn):
                probes.host_preempts += 1
                return orig(self_, ssn)
            return execute

        def commit(orig):
            def wrapped(self_):
                for name, args in self_.operations:
                    if name == "pipeline":
                        probes.pipelined[args[0].uid] = args[1]
                return orig(self_)
            return wrapped

        self._wrap(dispatch, "run_packed_auto", allocate)
        self._wrap(preempt_pallas, "run_preempt_pallas", lambda o: tag(o, "pallas"))
        self._wrap(preempt_pack, "preempt_dense", lambda o: tag(o, "dense"))
        self._wrap(host_preempt.PreemptAction, "execute", host)
        self._wrap(statement.Statement, "commit", commit)

    @staticmethod
    def _annotate() -> None:
        """Host spans on the profiler's clock around each layer the
        cycle passes through, so device idle gaps can be attributed."""
        import jax

        from volcano_tpu.actions import backfill, enqueue, jax_allocate, jax_preempt
        from volcano_tpu.ops import preempt_pack
        from volcano_tpu.scheduler import scheduler as sched_mod

        def span(label, orig):
            def wrapped(*a, **kw):
                with jax.profiler.TraceAnnotation(label):
                    return orig(*a, **kw)
            return wrapped

        points = [
            (sched_mod, "open_session", "session_open"),
            (sched_mod, "close_session", "session_close"),
            (enqueue.EnqueueAction, "execute", "action:enqueue"),
            (jax_allocate.JaxAllocateAction, "execute", "action:jax-allocate"),
            (jax_allocate, "compute_task_order", "allocate:order"),
            (jax_allocate.JaxAllocateAction, "_kernel_proposals", "allocate:pack+kernel"),
            (jax_allocate.JaxAllocateAction, "_apply", "allocate:apply"),
            (jax_preempt.JaxPreemptAction, "execute", "action:jax-preempt"),
            (preempt_pack, "pack_preempt_session", "preempt:pack"),
            (jax_preempt.JaxPreemptAction, "_device_outcome", "preempt:kernel"),
            (backfill.BackfillAction, "execute", "action:backfill"),
        ]
        for owner, name, label in points:
            Probes._wrap(owner, name, lambda o, label=label: span(label, o))


@dataclass
class Cycle:
    """What one cycle did and cost (host clock, program counters)."""

    index: int
    start: float
    wall_ms: float
    open_ms: float = 0.0
    action_ms: Dict[str, float] = field(default_factory=dict)
    phases: Dict[str, float] = field(default_factory=dict)
    executors: List[str] = field(default_factory=list)
    preempt_executors: List[str] = field(default_factory=list)
    host_preempts: int = 0
    fallbacks: float = 0.0
    compiles: int = 0
    binds: int = 0
    evicts: int = 0
    pipelined: Dict[str, str] = field(default_factory=dict)
    #: the packed sessions the preempt kernel saw, until
    #: :meth:`count_work` turns them into shapes
    pks: list = field(default_factory=list)
    preempt_shapes: List[dict] = field(default_factory=list)

    def count_work(self) -> None:
        """Shapes of each preempt kernel call, for the work counts of
        ``benchmark/work``; drops the sessions."""
        for pk in self.pks:
            self.preempt_shapes.append({
                "preemptors": pk.base.n_tasks, "nodes": pk.base.n_nodes,
                "victims": pk.n_victims, "resources": int(pk.base.task_resreq.shape[1]),
            })
        self.pks = []

    def faults(self, expect: Dict[str, str], check_executors: bool = True) -> List[str]:
        """Why this cycle counts as failed, if it does: a dispatch of
        allocate or preempt the cycle had to make and did not, or made on
        another executor than ``expect`` names (skipped with
        ``check_executors=False`` on the CPU, where only the fallbacks
        count), a jax-preempt host fallback, or an executor fallback."""
        out = []
        if check_executors:
            for kind, got in (("allocate", self.executors), ("preempt", self.preempt_executors)):
                want = expect.get(kind)
                if want is not None and (not got or any(e != want for e in got)):
                    out.append(f"{kind} executors {got}, not {want}")
        if self.host_preempts:
            out.append(f"{self.host_preempts} host preempt fallbacks")
        if self.fallbacks:
            out.append(f"{self.fallbacks} executor fallbacks")
        return out


class Driver:
    """Runs cycles of one scheduler and accounts for each."""

    def __init__(self, cache, scheduler, recorder: Recorder, probes: Probes,
                 compile_log, action_names: List[str]):
        self.cache = cache
        self.scheduler = scheduler
        self.recorder = recorder
        self.probes = probes
        self.compile_log = compile_log
        self.action_names = action_names
        self.count = 0

    def _action_sums(self) -> Dict[str, float]:
        from volcano_tpu.metrics.metrics import registry

        out = {}
        for name in self.action_names:
            snap = registry.histogram_snapshot(ACTION_HISTOGRAM, {"action": name})
            out[name] = snap["sum"] if snap else 0.0
        return out

    def cycle(self, annotate: Optional[str] = None) -> Cycle:
        from volcano_tpu.actions import jax_allocate
        from volcano_tpu.metrics import metrics

        sched, rec = self.scheduler, self.recorder
        self.probes.begin()
        fb0 = metrics.executor_fallbacks_total()
        open0, act0 = sched.session_open_seconds, self._action_sums()
        mark = self.compile_log.mark()
        nb0, ne0 = len(rec.binds), len(rec.evicts)
        with rec.lock:
            rec.cycle = self.count
        ctx = contextlib.nullcontext()
        if annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(annotate)
        t0 = time.perf_counter()
        with ctx:
            sched.run_once()
            self.cache.flush()
        t1 = time.perf_counter()
        act1 = self._action_sums()
        c = Cycle(
            index=self.count,
            start=t0,
            wall_ms=(t1 - t0) * 1e3,
            open_ms=(sched.session_open_seconds - open0) * 1e3,
            action_ms={k: (act1[k] - act0[k]) / 1e3 for k in act1},
            phases={
                k: v for k, v in jax_allocate.last_phase_stats.items()
                if isinstance(v, (int, float))
            },
            executors=list(self.probes.executors),
            preempt_executors=list(self.probes.preempt_executors),
            host_preempts=self.probes.host_preempts,
            fallbacks=metrics.executor_fallbacks_total() - fb0,
            compiles=self.compile_log.since(mark)["compiles"],
            binds=len(rec.binds) - nb0,
            evicts=len(rec.evicts) - ne0,
            pipelined=dict(self.probes.pipelined),
            pks=list(self.probes.pks),
        )
        self.count += 1
        return c
