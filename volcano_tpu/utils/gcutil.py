"""The collector policy at the cycle boundary, and the pause hook.

A generation-2 collection walks every object the collector tracks.  In
a scheduler most of them are long-lived: the cache's pods, nodes and
jobs, and the modules the process imported.  Each session clones the
cache, the clones are promoted while the cycle runs, and CPython's own
trigger (a full collection once the objects promoted since the last one
pass a quarter of those it left) then re-walks the whole cache graph
one to two times a cycle, nearly all of it still alive.

Quiesce (:func:`gc_quiesce`): thaw, collect, freeze.  Freezing moves
the survivors into the permanent generation, out of the collector's
working set.  Thawing first reclaims objects frozen by an earlier
quiesce that have since died in a reference cycle: their reclaim is
delayed to the next quiesce, never dropped.

The policy (:func:`after_cycle`, called by ``Scheduler.run_once`` at
the end of every cycle, outside the cycle's span and in no lock) reads
only what it can observe:

* **first** — nothing frozen by the policy yet, and the cache holds at
  least :data:`FREEZE_FLOOR` resident pods + nodes: quiesce.
* **growth** — something frozen, a generation-2 collection ran since
  the last boundary, and the unfrozen old generation has reached a
  quarter of what the last quiesce froze: quiesce again.  This is
  CPython's rule for a full collection, applied to what the freeze left
  out.  It bounds memory: the frozen set never grows between quiesces
  and held only live objects when frozen, and the unfrozen old
  generation is quiesced at a quarter of it, so the tracked heap stays
  within 1.25 times the live heap at the last quiesce, plus one cycle's
  promotions.  A frozen object that dies in a reference cycle is
  replaced, if the cluster keeps its size, by a live one in the old
  generation: a quarter of the heap of such replacements brings the
  quiesce that reclaims them.  An outside ``gc.unfreeze()`` puts the
  whole heap back in the old generation, so the next boundary after a
  full collection freezes it again.

Until the first quiesce, each boundary counts the cache's pods + nodes
(under the cache's mutex, released before any quiesce).  After it, a
boundary with no generation-2 collection since the last one reads one
flag; one after such a collection lists the old generation.

The pause hook (:func:`install_gc_hook`, once per process) adds every
collection's pause to ``volcano_gc_pause_seconds{generation}``, puts
each generation-2 collection on the profiler's clock as a ``vtpu/gc``
span, and raises the flag the growth rule reads.  The callback takes
no lock, and nothing quiesces inside it: a collection can start inside
any allocation, including one made while this thread holds a lock the
cycle recorder or the metrics registry would need.
"""

from __future__ import annotations

import gc
import time
from typing import Callable

GC_SPAN = "vtpu/gc"
QUIESCE_SPAN = "vtpu/gc.quiesce"

#: resident pods + nodes from which the policy freezes the heap.  Measured
#: on the CPU with PreemptionBasic caches of 300 to 6,000 pods + nodes
#: after two cycles: each resident pod or node keeps 41–43 tracked
#: objects and adds 22–31 µs to every full collection, and the process
#: with JAX and the program imported and no cache tracks ~90k objects
#: and collects them in 20–30 ms.  At 2,000 the cache's graph is as large
#: as the rest of the process, and above it most of every full
#: collection; below it a freeze saves little.  At 6,000, frozen, a full
#: collection walked 24k objects in 6.5 ms instead of 337k in 214 ms.
FREEZE_FLOOR = 2_000

_t0 = 0.0
_annotation = None  # the open vtpu/gc annotation of a running gen-2 collection
_hook = None
#: a generation-2 collection ended since the policy last looked
_full_seen = False
#: objects the policy's last quiesce froze; 0 before its first
_frozen = 0


def gc_quiesce() -> None:
    gc.unfreeze()
    gc.collect()
    gc.freeze()


def after_cycle(resident: Callable[[], int]) -> None:
    """Quiesce if the policy calls for it.  ``resident`` gives the
    cache's resident pods + nodes; it is called only while the policy
    has frozen nothing."""
    global _full_seen, _frozen
    if _frozen:
        if not _full_seen:
            return
        _full_seen = False
        if 4 * len(gc.get_objects(2)) < _frozen:
            return
        reason = "growth"
    elif resident() >= FREEZE_FLOOR:
        reason = "first"
    else:
        return
    from volcano_tpu import trace
    from volcano_tpu.metrics import metrics

    with trace.span(QUIESCE_SPAN, "gc", reason=reason):
        gc_quiesce()
    _full_seen = False
    _frozen = gc.get_freeze_count()
    metrics.register_gc_quiesce(reason, _frozen)


def install_gc_hook() -> None:
    """Install the pause hook; later calls do nothing."""
    global _hook
    if _hook is not None:
        return
    # bound now: the callback itself imports nothing
    from volcano_tpu import trace
    from volcano_tpu.metrics.metrics import gc_pause_histograms

    annotation_class = trace.annotation_class
    pauses = gc_pause_histograms()

    def on_gc(phase: str, info: dict) -> None:
        global _t0, _annotation, _full_seen
        generation = info["generation"]
        if phase == "start":
            if generation == 2:
                cls = annotation_class()
                if cls is not None:
                    _annotation = cls(GC_SPAN)
                    _annotation.__enter__()
            _t0 = time.perf_counter()
            return
        pauses[generation].observe(time.perf_counter() - _t0)
        if generation == 2:
            _full_seen = True
        if _annotation is not None:
            _annotation.__exit__(None, None, None)
            _annotation = None

    _hook = on_gc
    gc.callbacks.append(on_gc)
