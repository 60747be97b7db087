"""The collector policy at the cycle boundary (``utils/gcutil.after_cycle``):
a cache at or above ``FREEZE_FLOOR`` resident pods + nodes is frozen out of
full collections at its first cycle, and frozen again once the unfrozen old
generation reaches a quarter of what was frozen; a smaller one never is.

Every test that lets the policy freeze thaws in a ``finally``."""

from __future__ import annotations

import gc
import weakref

import pytest

from volcano_tpu import trace
from volcano_tpu.metrics import metrics
from volcano_tpu.scheduler.scheduler import Scheduler
from volcano_tpu.trace import TraceRecorder
from volcano_tpu.utils import gcutil

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache


def _cluster(jobs: int = 6):
    """Three 4-cpu nodes and ``jobs`` one-pod jobs of 1 cpu each, so the
    first cycle binds some and leaves the rest pending."""
    nodes = [build_node(f"n{i}", {"cpu": "4", "memory": "8Gi"}) for i in range(3)]
    pods = [build_pod("ns", f"p{j}", "", {"cpu": "1", "memory": "1Gi"}, group=f"g{j}")
            for j in range(jobs)]
    groups = [build_pod_group("ns", f"g{j}", 1) for j in range(jobs)]
    cache = make_cache(nodes=nodes, pods=pods, pod_groups=groups,
                       queues=[build_queue("default")])
    return cache, Scheduler(cache, period=0.0)


def _quiesces(reason: str) -> float:
    key = ("volcano_gc_quiesce_total", (("reason", reason),))
    with metrics.registry._lock:
        return metrics.registry._counters.get(key, 0.0)


def _frozen_after_collect() -> int:
    """The permanent generation after a full collection: what is frozen,
    and the immortal objects a collection moves there on its own."""
    gc.collect()
    return gc.get_freeze_count()


def _frozen_gauge() -> float:
    with metrics.registry._lock:
        return metrics.registry._gauges.get(("volcano_gc_frozen_objects", ()), 0.0)


@pytest.fixture
def policy(monkeypatch):
    """The policy with its floor lowered below a test cluster; the heap
    thawed afterwards, and the policy's record of it restored."""
    monkeypatch.setattr(gcutil, "FREEZE_FLOOR", 1)
    monkeypatch.setattr(gcutil, "_frozen", 0)
    try:
        yield
    finally:
        gc.unfreeze()


def test_cluster_above_the_floor_freezes_at_its_first_cycle_and_binds_the_same(monkeypatch):
    frozen0 = _frozen_after_collect()
    below_cache, below = _cluster()
    below.run_once()
    below_cache.flush()
    assert _frozen_after_collect() == frozen0
    first0 = _quiesces("first")

    monkeypatch.setattr(gcutil, "FREEZE_FLOOR", 1)
    monkeypatch.setattr(gcutil, "_frozen", 0)
    try:
        cache, sched = _cluster()
        sched.run_once()
        cache.flush()
        assert gc.get_freeze_count() > frozen0
        assert gcutil._frozen > frozen0
        assert _quiesces("first") == first0 + 1
        assert cache.binder.binds == below_cache.binder.binds
        assert len(cache.binder.binds) == 6
    finally:
        gc.unfreeze()
    assert _frozen_after_collect() == frozen0


def test_a_test_sized_cluster_never_freezes():
    cache, sched = _cluster(jobs=40)
    assert cache.resident_objects() == 43 < gcutil.FREEZE_FLOOR
    quiesces0 = _quiesces("first") + _quiesces("growth")
    frozen0 = _frozen_after_collect()
    for _ in range(4):
        gc.collect()  # a full collection before each boundary
        sched.run_once()
        cache.flush()
    assert _frozen_after_collect() == frozen0
    assert gcutil._frozen == 0
    assert _quiesces("first") + _quiesces("growth") == quiesces0


class _Ring:
    """An object kept alive only by a reference cycle through itself."""

    def __init__(self):
        self.me = self


def test_growth_requiesces_and_reclaims_frozen_cycles(policy):
    cache, sched = _cluster()
    ring = _Ring()
    ref = weakref.ref(ring)
    sched.run_once()  # first: freezes the ring while it is alive
    assert gcutil._frozen > 0
    del ring
    gc.collect()
    assert ref() is not None  # dead, but frozen out of the collection

    growth0 = _quiesces("growth")
    sched.run_once()  # a full collection ran, the old generation is small
    assert _quiesces("growth") == growth0
    assert ref() is not None

    # a quarter of the frozen count, promoted into the old generation by
    # a full collection
    grown = [[] for _ in range(gcutil._frozen // 4 + 1)]
    sched.run_once()  # no full collection since the last boundary
    assert _quiesces("growth") == growth0
    gc.collect()
    assert len(gc.get_objects(2)) * 4 >= gcutil._frozen
    sched.run_once()
    assert _quiesces("growth") == growth0 + 1
    assert ref() is None
    del grown  # held through the quiesce, which froze it


def test_each_quiesce_is_counted_and_spanned(policy):
    assert gcutil.QUIESCE_SPAN in trace.SPAN_NAMES
    rec = TraceRecorder()
    trace.set_recorder(rec)
    try:
        cache, sched = _cluster()
        first0 = _quiesces("first")
        sched.run_once()
        events = rec.last_cycle()["events"]
    finally:
        trace.set_recorder(None)
    assert _quiesces("first") == first0 + 1
    assert _frozen_gauge() == gcutil._frozen > 0
    (quiesce,) = [e for e in events if e["name"] == gcutil.QUIESCE_SPAN]
    assert quiesce["args"] == {"reason": "first"}
    (cycle,) = [e for e in events if e["name"] == "cycle:full"]
    # after the cycle's span, inside its journal
    assert quiesce["ts"] >= cycle["ts"] + cycle["dur"]
