"""Pallas session-kernel equivalence: the VMEM-resident full scan must
reproduce the plain XLA scan's assignments exactly — tie-breaks, gang
discards, taints/labels, capacity pressure — since it is the kernel the
TPU path actually runs (ops/dispatch.py).  CPU CI uses interpret mode."""

from __future__ import annotations

import numpy as np
import pytest

from volcano_tpu.ops.kernels import run_packed
from volcano_tpu.ops.pallas_session import run_packed_pallas
from volcano_tpu.ops.synthetic import generate_snapshot


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_matches_plain_random(seed):
    snap = generate_snapshot(n_tasks=300, n_nodes=150, gang_size=4, seed=seed)
    got = run_packed_pallas(snap, block_size=128, interpret=True)
    assert (run_packed(snap) == got).all()


def test_pallas_matches_plain_with_predicates():
    snap = generate_snapshot(
        n_tasks=256, n_nodes=130, gang_size=8, seed=3,
        label_classes=4, taint_fraction=0.25,
    )
    got = run_packed_pallas(snap, block_size=128, interpret=True)
    assert (run_packed(snap) == got).all()


def test_pallas_matches_plain_capacity_pressure():
    """Tight capacity: infeasible tasks, gang discards and rollbacks."""
    snap = generate_snapshot(
        n_tasks=400, n_nodes=16, gang_size=5, seed=4,
        node_cpu_milli=16_000, node_mem_mib=32_768,
    )
    plain = run_packed(snap)
    got = run_packed_pallas(snap, block_size=128, interpret=True)
    assert (plain == got).all()
    assert (plain == -1).any()  # pressure actually discards gangs


def test_pallas_matches_plain_single_node():
    snap = generate_snapshot(n_tasks=64, n_nodes=1, gang_size=2, seed=5)
    got = run_packed_pallas(snap, block_size=128, interpret=True)
    assert (run_packed(snap) == got).all()


def test_pallas_rejects_beyond_f32_envelope():
    snap = generate_snapshot(
        n_tasks=16, n_nodes=4, gang_size=2, seed=6,
        node_cpu_milli=2_000_000, node_mem_mib=4_000_000,
    )
    with pytest.raises(ValueError):
        run_packed_pallas(snap, block_size=128, interpret=True)


def test_auto_dispatch_small_native_matches_plain():
    """Small default-weight sessions route to the native C++ executor
    (select_executor → 'native'); its bindings must equal the XLA scan."""
    from volcano_tpu.ops.dispatch import run_packed_auto, select_executor

    snap = generate_snapshot(n_tasks=100, n_nodes=20, gang_size=4, seed=7)
    if select_executor(snap) != "native":
        pytest.skip("native executor unavailable (no g++)")
    assert (run_packed_auto(snap) == run_packed(snap)).all()


def test_auto_dispatch_small_custom_weights_uses_plain():
    """Non-default weights bypass the native executor (its weights are
    baked in) and take the XLA scan."""
    from volcano_tpu.ops.dispatch import run_packed_auto, select_executor
    from volcano_tpu.ops.kernels import ScoreWeights

    w = ScoreWeights(binpack_weight=2.0)
    snap = generate_snapshot(n_tasks=100, n_nodes=20, gang_size=4, seed=7)
    assert select_executor(snap, w) == "xla-scan"
    assert (run_packed_auto(snap, weights=w) == run_packed(snap, weights=w)).all()


def test_make_session_dispatch_prestaged_matches_wrapper():
    # the bench's compute probe (make_session_dispatch prestage=True)
    # must enqueue the SAME kernel as run_packed_pallas — prestaging only
    # moves the transfer, never the math
    from volcano_tpu.ops.pallas_session import make_session_dispatch

    snap = generate_snapshot(n_tasks=300, n_nodes=150, gang_size=4, seed=3)
    want = run_packed_pallas(snap, block_size=128, interpret=True)

    dispatch, T_act = make_session_dispatch(
        snap, block_size=128, interpret=True, prestage=True)
    out = np.asarray(dispatch()[0])
    got = np.full(snap.n_tasks, -1, dtype=np.int32)
    n = min(snap.n_tasks, T_act)
    got[:n] = out[:n]
    assert (want == got).all()
    # repeated dispatches (the pipelined-slope probe) stay identical
    out2 = np.asarray(dispatch()[0])
    assert (np.asarray(out) == np.asarray(out2)).all()


def test_warmup_kernels_runs_auto_path():
    # the --warmup flag on vtpu-scheduler: compiles
    # whatever executor auto-dispatch selects for the bucket, and returns
    # its name
    from volcano_tpu.ops.dispatch import select_executor, warmup_kernels
    from volcano_tpu.ops.synthetic import generate_snapshot

    executor = warmup_kernels(n_tasks=256, n_nodes=64, gang_size=4)
    snap = generate_snapshot(n_tasks=256, n_nodes=64, gang_size=4)
    assert executor == select_executor(snap)


def test_scheduler_warmup_flag_compiles_in_process(monkeypatch):
    """vtpu-scheduler --warmup compiles the kernels in its own process
    before the daemon starts; without the flag nothing compiles."""
    from volcano_tpu.cmd import scheduler as scheduler_cmd
    from volcano_tpu.ops import dispatch
    from volcano_tpu.scheduler import util as sched_util

    calls = []
    monkeypatch.setattr(dispatch, "warmup_kernels",
                        lambda: calls.append("warmup"))
    monkeypatch.setattr(scheduler_cmd, "SchedulerDaemon",
                        lambda *a, **kw: calls.append("daemon"))
    monkeypatch.setattr(scheduler_cmd, "serve_forever", lambda daemon: 0)
    # main() installs the subsampling options process-wide
    monkeypatch.setattr(sched_util, "server_opts", sched_util.server_opts)

    assert scheduler_cmd.main(["--warmup"]) == 0
    assert calls == ["warmup", "daemon"]
    calls.clear()
    assert scheduler_cmd.main([]) == 0
    assert calls == ["daemon"]
