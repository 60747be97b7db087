"""GC quiesce, and the pause hook.

Quiesce: thaw, collect, freeze.  Long-lived cluster state (a 50k-pod
cache graph is millions of objects) makes every gen-2 collection inside
a hot region re-traverse it all; freezing survivors into the permanent
generation removes them from the collector's working set.  Thaw first
so objects frozen by a PREVIOUS quiesce that have since died in a cycle
are reclaimed — delayed by one quiesce interval, never leaked.  Used by
the scheduler loop (--gc-quiesce-period) and by bench.py between
configs.

The pause hook (:func:`install_gc_hook`, once per process) adds every
collection's pause to ``volcano_gc_pause_seconds{generation}`` and puts
each generation-2 collection on the profiler's clock as a ``vtpu/gc``
span, so a slow session open can be told apart from a collection inside
it.  The callback takes no lock: a collection can start inside any
allocation, including one made while this thread holds a lock the
cycle recorder or the metrics registry would need.
"""

from __future__ import annotations

import gc
import time

GC_SPAN = "vtpu/gc"

_t0 = 0.0
_annotation = None  # the open vtpu/gc annotation of a running gen-2 collection
_hook = None


def gc_quiesce() -> None:
    gc.unfreeze()
    gc.collect()
    gc.freeze()


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
        global _t0, _annotation
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
        if _annotation is not None:
            _annotation.__exit__(None, None, None)
            _annotation = None

    _hook = on_gc
    gc.callbacks.append(on_gc)
