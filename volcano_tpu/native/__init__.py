"""Native (C++) runtime components, driven via ctypes.

``baseline_allocate`` is the host-native greedy allocate loop — the
performance stand-in for the reference's Go allocate action (this
environment has no Go toolchain; C++ with a 16-thread node sweep matches
the reference's 16-goroutine ParallelizeUntil design,
scheduler_helper.go:110-111).  bench.py measures it as the "stock
reference" column.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "baseline.cpp")
#: build outputs (gitignored): one shared object per source content
_BUILD_DIR = os.path.join(_HERE, "_build")

_lib = None


def _build() -> Optional[str]:
    """Compile the shared object on demand, keyed by a hash of the
    committed source — a stale or foreign binary is never loaded.  The
    write goes through a temp name and an atomic rename, so processes
    building at once each see a whole file."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"baseline-{digest}.so")
    if os.path.exists(so):
        return so
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
        os.close(fd)
        try:
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
                 _SRC, "-o", tmp],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return so
    except (OSError, subprocess.CalledProcessError):
        return None


def load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    so = _build()
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    f32 = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u32 = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.baseline_allocate.argtypes = [
        f32, i32, u32, u32,              # task arrays
        f32, f32, f32, u32, u32, u8,     # node arrays
        i32, i32,                        # counts/max
        i32, i32,                        # job arrays
        f32,                             # tolerance
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int,
        i32, i32, i32,                   # out assignment / placed / reasons
    ]
    lib.baseline_allocate.restype = ctypes.c_int
    i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.baseline_preempt.argtypes = [
        f32, u32, u32,                   # preemptor task arrays
        f32, f32, f32, u32, u32, u8,     # node arrays (used/alloc/fi0/bits/ok)
        i32, i32,                        # node count/max
        f32, i32, i32,                   # victim arrays
        i64, i32, i32, i32, i32, i32, i32,  # job tables
        i32,                             # schedule [S,2]
        f32,                             # tolerance
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        u8, i32,                         # out evicted / pipelined
    ]
    lib.baseline_preempt.restype = ctypes.c_int
    _lib = lib
    return lib


def baseline_outcome(snap, n_threads: int = 16):
    """Run the native greedy allocate on a PackedSnapshot → ScanOutcome
    (ops/kernels.py) over the first ``n_tasks`` rows, with the per-job
    commit/discard and the failing steps' reason counts."""
    from volcano_tpu.ops.kernels import N_EXPLAIN_REASONS, ScanOutcome

    lib = load()
    if lib is None:
        raise RuntimeError("native baseline unavailable (g++ missing?)")
    J = snap.job_min_available.shape[0]
    R = snap.task_resreq.shape[1]
    W = snap.task_sel_bits.shape[1]
    T = snap.n_tasks
    out = np.full(T, -1, dtype=np.int32)
    placed = np.full(T, -1, dtype=np.int32)
    reasons = np.zeros((T, N_EXPLAIN_REASONS), dtype=np.int32)
    rc = lib.baseline_allocate(
        np.ascontiguousarray(snap.task_resreq[:T]),
        np.ascontiguousarray(snap.task_job[:T]),
        np.ascontiguousarray(snap.task_sel_bits[:T]),
        np.ascontiguousarray(snap.task_tol_bits[:T]),
        np.ascontiguousarray(snap.node_idle[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_used[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_alloc[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_label_bits[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_taint_bits[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_ok[: snap.n_nodes].astype(np.uint8)),
        np.ascontiguousarray(snap.node_task_count[: snap.n_nodes]),
        np.ascontiguousarray(snap.node_max_tasks[: snap.n_nodes]),
        np.ascontiguousarray(snap.job_min_available),
        np.ascontiguousarray(snap.job_ready_count),
        np.ascontiguousarray(snap.tolerance),
        T,
        snap.n_nodes,
        J,
        R,
        W,
        n_threads,
        out,
        placed,
        reasons,
    )
    if rc != 0:
        raise RuntimeError(f"baseline_allocate failed: {rc}")
    return ScanOutcome(out, placed, reasons)


def baseline_allocate(snap, n_threads: int = 16) -> np.ndarray:
    """Run the native greedy allocate on a PackedSnapshot → committed
    assignment[n_tasks]."""
    return baseline_outcome(snap, n_threads).assignment


def baseline_preempt(pk, n_threads: int = 16):
    """Run the native greedy preempt on a PreemptPacked →
    (evicted[V] bool, pipelined_node[P] i32).  Semantics mirror
    ops/preempt_pack.preempt_dense (the host PreemptAction replay)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native baseline unavailable (g++ missing?)")
    base = pk.base
    P = base.n_tasks
    N = base.n_nodes
    V = pk.n_victims
    J = pk.n_jobs
    R = base.task_resreq.shape[1]
    W = base.task_sel_bits.shape[1]
    S = pk.schedule.shape[0]
    evicted = np.zeros(max(V, 1), dtype=np.uint8)
    pipelined = np.full(max(P, 1), -1, dtype=np.int32)
    if P == 0 or S == 0:
        return evicted[:V].astype(bool), pipelined[:P]
    rc = lib.baseline_preempt(
        np.ascontiguousarray(base.task_resreq[:P]),
        np.ascontiguousarray(base.task_sel_bits[:P]),
        np.ascontiguousarray(base.task_tol_bits[:P]),
        np.ascontiguousarray(base.node_used[:N]),
        np.ascontiguousarray(base.node_alloc[:N]),
        np.ascontiguousarray(pk.node_fi0[:N]),
        np.ascontiguousarray(base.node_label_bits[:N]),
        np.ascontiguousarray(base.node_taint_bits[:N]),
        np.ascontiguousarray(base.node_ok[:N].astype(np.uint8)),
        np.ascontiguousarray(base.node_task_count[:N]),
        np.ascontiguousarray(base.node_max_tasks[:N]),
        np.ascontiguousarray(pk.vic_resreq[: max(V, 1)]),
        np.ascontiguousarray(pk.vic_node[: max(V, 1)]),
        np.ascontiguousarray(pk.vic_job[: max(V, 1)]),
        np.ascontiguousarray(pk.job_prio.astype(np.int64)),
        np.ascontiguousarray(pk.job_min_avail),
        np.ascontiguousarray(pk.job_ready0),
        np.ascontiguousarray(pk.job_waiting0),
        np.ascontiguousarray(pk.job_queue),
        np.ascontiguousarray(pk.job_ptask_start),
        np.ascontiguousarray(pk.job_ptask_end),
        np.ascontiguousarray(pk.schedule),
        np.ascontiguousarray(base.tolerance),
        P, N, V, J, R, W, S, n_threads,
        evicted, pipelined,
    )
    if rc != 0:
        raise RuntimeError(f"baseline_preempt failed: {rc}")
    return evicted[:V].astype(bool), pipelined[:P]
