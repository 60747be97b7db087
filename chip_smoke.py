#!/usr/bin/env python3
"""Bring-up smoke: the scheduler's device path on a TPU, checked against
independent references.  Not a benchmark: the times it prints are
informational.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded session on four chips

From a sandbox without a chip, run it through the chip tool
(``chiprun -- python3 chip_smoke.py``).  It runs in ONE process, the
only one touching the chip, and needs the repository checkout around it.

With no option, two phases at BASELINE sizes (ops/synthetic.py):

* allocate, ``50k_pods_10k_nodes_gang_predicates``: the real
  ``jax-allocate`` action inside live Sessions over one scheduler cache
  (bench.py's setup: bench/_profsetup.py) — one cold cycle, then warm
  cycles with the binds reverted between them.  Every cycle must bind
  every pod, exactly as ``native.baseline_allocate`` does on the same
  packed session, on the ``pallas`` executor.
* preempt, ``100k_pods_10k_nodes_preempt``: ``run_preempt_auto`` on the
  ``pallas`` executor; evictions and pipelined placements must equal
  ``preempt_dense`` and ``native.baseline_preempt``.

With ``--chips 4``, only ``run_packed_sharded`` at 50k×10k on a 4-device
mesh: bindings must equal the single-chip native reference, and a
profiler trace of the warm run must show the program executing on every
device.

Any degradation fails the smoke: a dispatcher fallback or a failed
device staging moves ``volcano_executor_fallbacks_total``.  Without a
TPU it exits 1; every failure exits non-zero before the last line.  The
last line of stdout is the contract:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
HEADLINE = "50k_pods_10k_nodes_gang_predicates"
PREEMPT = "100k_pods_10k_nodes_preempt"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(phase: str, **fields) -> None:
    """One informational line (not a metric)."""
    print(f"smoke {phase} " + json.dumps(fields), flush=True)


class CompileLog:
    """Backend compile seconds per jitted function, and persistent
    compile-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds: dict = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def take(self) -> dict:
        """Compiles since the last take: the slowest functions, the
        total, and the cache hits."""
        slowest = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:5]
        out = {
            "compile_s": {name: s for name, s in slowest},
            "compile_s_total": sum(self.seconds.values()),
            "compiles": len(self.seconds),
            "cache_hits": self.cache_hits,
        }
        self.seconds, self.cache_hits = {}, 0
        return out


def native_bindings(ssn, native) -> dict:
    """``ns/name → node`` that ``native.baseline_allocate`` gives on the
    session's packed form — task order and node order as jax-allocate
    packs them."""
    from volcano_tpu.actions.jax_allocate import compute_task_order
    from volcano_tpu.ops.packing import pack_session

    ordered = compute_task_order(ssn)
    jobs = {}
    for t in ordered:
        job = ssn.jobs.get(t.job)
        if job is not None:
            jobs.setdefault(job.uid, job)
    nodes = [ssn.nodes[n] for n in sorted(ssn.nodes)]
    snap = pack_session(ordered, list(jobs.values()), nodes)
    assignment = native.baseline_allocate(snap)
    return {
        f"{t.namespace}/{t.name}": nodes[a].name
        for t, a in zip(ordered, assignment)
        if a >= 0
    }


def allocate_phase(kwargs: dict, warm_cycles: int = 2) -> dict:
    """The jax-allocate action through live Sessions; returns info."""
    from _profsetup import TIERS, capture_task_infos, make_cache_builder, revert_binds

    from volcano_tpu import native
    from volcano_tpu.actions.jax_allocate import JaxAllocateAction
    from volcano_tpu.framework import close_session, open_session
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops import dispatch

    t0 = time.perf_counter()
    cache = make_cache_builder(**kwargs)()
    cache.enable_pipelined_commit()
    pristine = capture_task_infos(cache)
    setup_s = time.perf_counter() - t0
    action = JaxAllocateAction()
    fallbacks0 = metrics.executor_fallbacks_total()
    cycle_ms = []
    try:
        for cycle in range(1 + warm_cycles):
            binds0 = len(cache.binder.binds)
            ssn = open_session(cache, TIERS, [])
            expected = native_bindings(ssn, native)
            t0 = time.perf_counter()
            action.execute(ssn)
            cycle_ms.append((time.perf_counter() - t0) * 1e3)
            close_session(ssn)
            cache.flush()
            executor = dispatch.last_executor()
            check(executor == "pallas", f"allocate cycle {cycle} ran on {executor!r}")
            got = dict(cache.binder.binds[binds0:])
            check(
                len(got) == len(pristine),
                f"allocate cycle {cycle}: {len(got)} of {len(pristine)} pods bound",
            )
            wrong = sum(got.get(k) != v for k, v in expected.items())
            check(
                got == expected,
                f"allocate cycle {cycle}: {wrong} bindings differ from "
                "native.baseline_allocate",
            )
            revert_binds(cache, pristine)
    finally:
        cache.stop_commit_plane()
    fallbacks = metrics.executor_fallbacks_total() - fallbacks0
    check(fallbacks == 0, f"allocate: {fallbacks} executor fallbacks")
    return {
        "executor": "pallas",
        "pods": len(pristine),
        "bound_each_cycle": len(pristine),
        "bindings_match_native": True,
        "cluster_setup_s": setup_s,
        "cold_cycle_ms": cycle_ms[0],
        "warm_cycle_ms": cycle_ms[1:],
        "executor_fallbacks": fallbacks,
    }


def preempt_phase(kwargs: dict) -> dict:
    """run_preempt_auto against both references; returns info."""
    from volcano_tpu import native
    from volcano_tpu.metrics import metrics
    from volcano_tpu.ops.dispatch import run_preempt_auto, select_preempt_executor
    from volcano_tpu.ops.preempt_pack import preempt_dense
    from volcano_tpu.ops.synthetic import generate_preempt_packed

    pk = generate_preempt_packed(**kwargs)
    executor = select_preempt_executor(pk)
    check(executor == "pallas", f"preempt dispatch picked {executor!r}")
    fallbacks0 = metrics.executor_fallbacks_total()
    runs_ms = []
    for _ in range(2):
        t0 = time.perf_counter()
        evicted, pipelined = run_preempt_auto(pk)
        runs_ms.append((time.perf_counter() - t0) * 1e3)
    fallbacks = metrics.executor_fallbacks_total() - fallbacks0
    check(fallbacks == 0, f"preempt: {fallbacks} executor fallbacks")
    t0 = time.perf_counter()
    ev_d, pipe_d = preempt_dense(pk)
    dense_s = time.perf_counter() - t0
    ev_n, pipe_n = native.baseline_preempt(pk)
    check(
        np.array_equal(evicted, ev_d) and np.array_equal(pipelined, pipe_d),
        "preempt: pallas differs from preempt_dense",
    )
    check(
        np.array_equal(evicted, ev_n) and np.array_equal(pipelined, pipe_n),
        "preempt: pallas differs from native.baseline_preempt",
    )
    n_evicted, n_placed = int(evicted.sum()), int((pipelined >= 0).sum())
    check(n_evicted > 0 and n_placed > 0, "preempt: the pass evicted nothing")
    return {
        "executor": executor,
        "preemptors": pk.base.n_tasks,
        "victims": pk.n_victims,
        "evicted": n_evicted,
        "pipelined": n_placed,
        "match_dense_and_native": True,
        "cold_ms": runs_ms[0],
        "warm_ms": runs_ms[1],
        "dense_reference_s": dense_s,
        "executor_fallbacks": fallbacks,
    }


def device_busy(trace_dir: str) -> dict:
    """TPU ordinal → program executions and busy milliseconds, from the
    ``XLA Modules`` line of each ``/device:TPU:<ordinal>`` plane of a
    profiler trace."""
    import jax

    busy = {}
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            m = re.match(r"/device:TPU:(\d+)$", plane.name)
            if not m:
                continue
            modules = [line for line in plane.lines if line.name == "XLA Modules"]
            durations = [e.duration_ns for line in modules for e in line.events]
            ordinal = int(m.group(1))
            busy[ordinal] = {"executions": len(durations), "busy_ms": sum(durations) / 1e6}
            if not modules:  # shows up idle, with the lines it has
                busy[ordinal]["lines"] = [line.name for line in plane.lines]
    return busy


def sharded_phase(kwargs: dict, devices) -> dict:
    """run_packed_sharded on a mesh of ``devices`` vs native; returns
    info.  The jobs are of one pod each: the sharded formulation takes no
    session with multi-task gangs (ops/dispatch.py)."""
    import jax
    from jax.sharding import Mesh

    from volcano_tpu import native
    from volcano_tpu.ops.sharded import run_packed_sharded
    from volcano_tpu.ops.synthetic import generate_snapshot

    snap = generate_snapshot(**{**kwargs, "gang_size": 1})
    mesh = Mesh(np.array(devices), ("nodes",))
    reference = native.baseline_allocate(snap)
    t0 = time.perf_counter()
    cold = run_packed_sharded(snap, mesh)
    cold_ms = (time.perf_counter() - t0) * 1e3
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        warm = run_packed_sharded(snap, mesh)
        warm_ms = (time.perf_counter() - t0) * 1e3
        jax.profiler.stop_trace()
        busy = device_busy(trace_dir)
    for name, got in (("cold", cold), ("warm", warm)):
        wrong = int((got != reference).sum())
        check(wrong == 0, f"sharded {name} run: {wrong} bindings differ from native")
    check(int((reference >= 0).sum()) == snap.n_tasks, "sharded: not every task placed")
    worked = [d for d, b in busy.items() if b["executions"] > 0 and b["busy_ms"] > 0]
    check(
        len(worked) >= len(devices),
        f"sharded: only {len(worked)} of {len(devices)} devices show work: {busy}",
    )
    return {
        "devices": len(devices),
        "tasks": snap.n_tasks,
        "nodes": snap.n_nodes,
        "bindings_match_native": True,
        "device_busy": busy,
        "peak_bytes": [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices],
        "cold_ms": cold_ms,
        "warm_ms_traced": warm_ms,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded session on a 4-chip mesh",
    )
    args = parser.parse_args(argv)

    sys.path[:0] = [REPO, os.path.join(REPO, "bench")]
    try:
        import volcano_tpu
    except ImportError:
        volcano_tpu = None
    if volcano_tpu is None or not volcano_tpu.__file__.startswith(REPO + os.sep):
        print("chip_smoke: run from a checkout of volcano-tpu", file=sys.stderr)
        return 2
    from volcano_tpu.ops.synthetic import BASELINE_CONFIGS
    from volcano_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU, JAX found {device}", file=sys.stderr)
        return 1
    compiles = CompileLog()
    info("device", **device, compile_cache=cache_dir, jax=jax.__version__)
    try:
        if args.chips == 4:
            check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
            out = sharded_phase(BASELINE_CONFIGS[HEADLINE], devices[:4])
            info("sharded", **out, **compiles.take())
        else:
            out = allocate_phase(BASELINE_CONFIGS[HEADLINE])
            info("allocate", **out, **compiles.take())
            preempt_kw = {
                k: v for k, v in BASELINE_CONFIGS[PREEMPT].items() if k != "preempt"
            }
            out = preempt_phase(preempt_kw)
            info("preempt", **out, **compiles.take())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
