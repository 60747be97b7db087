"""Traffic ``full_cycle``: every cycle sees the configuration's whole
pending set at once and decides it in full.

Between cycles the harness returns every task the cycle moved (bound,
evicted) to its state at set-up, through the cache's event mutations, off
the clock: each cycle then starts from the same cluster, and one run of
the plain reference gives the outcome every cycle must reproduce.

Parameters (``params`` of the cell's file):

* ``warm_cycles`` — cycles run in set-up, after the first (compiling)
  one, so that every program the window runs is compiled and loaded;
* ``compare`` — which outcomes are compared with the reference:
  ``binds``, ``evicts``, ``pipelined``;
* ``executors`` — the executor each kernel's dispatch must run on in
  every cycle (``allocate``, ``preempt``): the one the program's
  dispatcher picks for the cell's shapes; a cycle that dispatches a
  kernel elsewhere, or not at all, counts as failed.
"""

from __future__ import annotations

import gc
import os
import time

from harness import cluster, core
from harness.compare import mismatch

LABELS = {
    "cycle", "restore", "session_open", "session_close", "action:enqueue",
    "action:jax-allocate", "allocate:order", "allocate:pack+kernel",
    "allocate:apply", "action:jax-preempt", "preempt:pack", "preempt:kernel",
    "action:backfill",
}


class Traffic:
    def __init__(self, cell, require_pallas: bool = True):
        self.cell = cell
        self.require_pallas = require_pallas
        self.recorder = cluster.Recorder()
        self.probes = cluster.Probes(annotate=cell.trace)
        self.cycles = []
        self.outcomes = []
        self.faults = []
        self.attempted = 0
        self.failed = 0
        self.restore_ms = []
        self.warm = []

    # ---- set-up ----

    def setup(self) -> None:
        cfg = self.cell.config
        t0 = time.perf_counter()
        self.world = self.cell.generator.full_cycle(cfg, self.cell.seed)
        t1 = time.perf_counter()
        self.cache, self.scheduler = cluster.build(
            self.world.objects, cfg["scheduler"], self.recorder,
            os.path.join(self.cell.scratch, "scheduler.conf"),
        )
        self.world.objects = None  # the cache holds what it needs
        self.pristine = cluster.capture_task_infos(self.cache)
        t2 = time.perf_counter()
        actions = [a.strip() for a in cfg["scheduler"]["actions"].split(",")]
        self.driver = cluster.Driver(
            self.cache, self.scheduler, self.recorder, self.probes,
            self.cell.compile_log, actions,
        )
        mark = self.cell.compile_log.mark()
        for _ in range(1 + self.cell.params["warm_cycles"]):
            c = self.driver.cycle()
            self.warm.append({"wall_ms": c.wall_ms, "compiles": c.compiles,
                              "binds": c.binds, "evicts": c.evicts,
                              "faults": self._faults(c)})
            core.log("warm", index=c.index, wall_ms=c.wall_ms, open_ms=c.open_ms,
                     action_ms=c.action_ms, phases=c.phases, compiles=c.compiles)
            self._restore()
        self.setup_detail = {
            "generate_s": t1 - t0,
            "feed_s": t2 - t1,
            "warm_cycles": self.warm,
            "compile": self.cell.compile_log.since(mark),
        }

    def setup_info(self) -> dict:
        return self.setup_detail

    def _faults(self, c) -> list:
        return c.faults(self.cell.params["executors"], self.require_pallas)

    def _restore(self) -> None:
        t0 = time.perf_counter()
        if self.cell.trace:
            import jax

            with jax.profiler.TraceAnnotation("restore"):
                cluster.revert(self.cache, self.pristine)
        else:
            cluster.revert(self.cache, self.pristine)
        self.restore_ms.append((time.perf_counter() - t0) * 1e3)

    # ---- the window ----

    def window(self) -> None:
        rec = self.recorder
        mark = self.cell.compile_log.mark()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.cell.seconds:
            nb, ne = len(rec.binds), len(rec.evicts)
            c = self.driver.cycle(annotate="cycle" if self.cell.trace else None)
            self.cycles.append(c)
            self.outcomes.append({
                "binds": {u: n for u, n, _t, _c in rec.binds[nb:]},
                "evicts": {u: n for u, n, _t, _c in rec.evicts[ne:]},
                "pipelined": c.pipelined,
            })
            faults = self._faults(c)
            if faults:
                self.faults.append((c.index, faults))
            self._restore()
        self.window_s = time.perf_counter() - t0
        self.window_compile = self.cell.compile_log.since(mark)
        self.attempted = len(self.cycles)
        self.failed = len(self.faults)
        for c in self.cycles:
            core.log("cycle", index=c.index, wall_ms=c.wall_ms, open_ms=c.open_ms,
                     action_ms=c.action_ms,
                     phases=c.phases, executors=c.executors,
                     preempt_executors=c.preempt_executors, compiles=c.compiles,
                     binds=c.binds, evicts=c.evicts, pipelined=len(c.pipelined))
        core.log("window", seconds=self.window_s, cycles=len(self.cycles),
                 restore_ms=self.restore_ms, compile=self.window_compile,
                 faults=self.faults)

    def after_window(self) -> None:
        pass

    def release(self) -> None:
        self.cache.stop_commit_plane()
        for c in self.cycles:
            c.count_work()
        self.cache = self.scheduler = self.driver = self.pristine = None
        self.probes.pks = []
        gc.collect()

    # ---- results ----

    def end_to_end(self) -> dict:
        walls = [c.wall_ms for c in self.cycles]
        return {"cycle_ms": sum(walls) / len(walls)} if walls else {}

    def span_labels(self) -> set:
        return LABELS

    def view(self, trace_summary: dict, device: dict):
        from harness.view import RunView

        return RunView(self.cycles, trace_summary, device)

    def check(self) -> dict:
        """Every cycle's outcome against the one the reference gives:
        the count of pods whose node differs, or that one side placed
        and the other did not, summed over the window's cycles."""
        name = self.cell.generator.node_name
        expected = self.cell.reference.expected_full_cycle(self.world)
        checks = {}
        for kind in self.cell.params["compare"]:
            want = {u: name(n) for u, n in expected[kind].items()}
            wrong = sum(mismatch(want, got[kind]) for got in self.outcomes)
            for i, got in enumerate(self.outcomes):
                diff = sorted(u for u in set(want) | set(got[kind]) if want.get(u) != got[kind].get(u))
                if diff:
                    core.log("mismatch", outcome=kind, cycle=i, count=len(diff),
                             first=[[u, want.get(u), got[kind].get(u)] for u in diff[:12]])
            checks[f"{kind}_mismatch"] = {"value": wrong, "limit": self.cell.params["limits"][kind]}
            core.log("expected", outcome=kind, count=len(want),
                     per_cycle=[len(o[kind]) for o in self.outcomes])
        checks["cycles_unchecked"] = {"value": 0 if self.outcomes else 1, "limit": 0}
        return checks
