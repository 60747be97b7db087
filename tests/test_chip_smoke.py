"""chip_smoke.py without a chip: it refuses to run, and its phases —
rehearsed at a tiny size with the Pallas kernels in interpret mode and
the dispatcher told it has a TPU — pass their own checks."""

from __future__ import annotations

import functools
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_refuses_without_a_tpu():
    proc = _run(SMOKE, REPO)
    assert proc.returncode == 1
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_outside_a_checkout(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(str(alone), str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.fixture
def smoke(monkeypatch):
    """The chip_smoke module, with the dispatcher's Pallas rungs open on
    the CPU through the interpreter."""
    from volcano_tpu.ops import dispatch, pallas_session, preempt_pallas

    monkeypatch.syspath_prepend(os.path.join(REPO, "bench"))
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setattr(dispatch, "_tpu_available", lambda: True)
    monkeypatch.setattr(dispatch, "_SMALL_AREA", 0)
    monkeypatch.setattr(
        pallas_session, "run_outcome_pallas",
        functools.partial(pallas_session.run_outcome_pallas, interpret=True),
    )
    monkeypatch.setattr(
        preempt_pallas, "run_preempt_pallas",
        functools.partial(preempt_pallas.run_preempt_pallas, interpret=True),
    )
    import chip_smoke

    return chip_smoke


def test_allocate_phase_rehearsal(smoke):
    out = smoke.allocate_phase(
        dict(n_tasks=256, n_nodes=64, gang_size=8, label_classes=4,
             taint_fraction=0.1),
        warm_cycles=1,
    )
    assert out["executor"] == "pallas" and out["executor_fallbacks"] == 0
    assert out["pods"] == 256 and len(out["warm_cycle_ms"]) == 1


def test_preempt_phase_rehearsal(smoke):
    out = smoke.preempt_phase(dict(n_victims=720, n_nodes=80, n_preemptors=80))
    assert out["executor"] == "pallas" and out["match_dense_and_native"]


def test_sharded_phase_rehearsal(smoke, monkeypatch):
    # the CPU backend's trace has no device planes to count
    monkeypatch.setattr(
        smoke, "device_busy",
        lambda trace_dir: {i: {"executions": 1, "busy_ms": 1.0} for i in range(4)},
    )
    out = smoke.sharded_phase(
        dict(n_tasks=512, n_nodes=256, gang_size=8), jax.devices()[:4]
    )
    assert out["bindings_match_native"] and out["devices"] == 4
