"""Scale equivalence gate (VERDICT r2 #6): seeded large-shape identity
between the session formulations, so perf work can't silently break
exactness.  The plain lax.scan (run_packed) is the reference formulation
— proven bindings-identical to the host action at small shapes in
tests/test_jax_allocate.py — so chaining these identities extends host
equivalence to scale:

  plain ≡ blocked ≡ sharded(8-device mesh)   at 10k tasks × 1k nodes
  plain ≡ pallas(interpret)                  at 2k tasks × 1k nodes
  blocked ≡ sharded                          at 4k tasks × 10k nodes
                                             (≥10k nodes, VERDICT #3)

Real-TPU compiled-Mosaic equivalence at the full 50k × 10k headline
shape is asserted on the chip by chip_smoke.py; interpret mode here
covers the kernel logic itself on CPU CI."""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from volcano_tpu.ops.blocked import run_packed_blocked
from volcano_tpu.ops.kernels import run_packed
from volcano_tpu.ops.sharded import run_packed_sharded
from volcano_tpu.ops.synthetic import (
    generate_preempt_packed,
    generate_snapshot,
)

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs a multi-device backend")
    return Mesh(np.array(devices).reshape(len(devices)), ("nodes",))


def test_plain_blocked_sharded_10k_tasks_1k_nodes(mesh):
    snap = generate_snapshot(
        n_tasks=10_000, n_nodes=1_000, gang_size=4, seed=7,
        label_classes=4, taint_fraction=0.1,
    )
    plain = run_packed(snap)
    assert np.array_equal(plain, run_packed_blocked(snap))
    assert np.array_equal(plain, run_packed_sharded(snap, mesh))
    assert (plain >= 0).sum() > 5_000  # the scenario actually places


def test_pallas_interpret_matches_plain_2k_tasks_1k_nodes():
    from volcano_tpu.ops.pallas_session import run_packed_pallas

    snap = generate_snapshot(
        n_tasks=2_048, n_nodes=1_000, gang_size=8, seed=11,
        label_classes=4, taint_fraction=0.1,
    )
    plain = run_packed(snap)
    pallas = run_packed_pallas(snap, interpret=True)
    assert np.array_equal(plain, pallas)
    assert (plain >= 0).sum() > 1_000


def test_sharded_10k_nodes(mesh):
    """VERDICT #3 done criterion: the sharded mesh kernel reproduces the
    fast single-chip formulation exactly at ≥10k nodes."""
    snap = generate_snapshot(n_tasks=4_096, n_nodes=10_000, gang_size=8, seed=3)
    assert np.array_equal(run_packed_blocked(snap), run_packed_sharded(snap, mesh))


def test_preempt_dense_native_pallas_mid_scale():
    """Preempt formulations agree at a mid scale with queue spread and
    gang-blocked victim jobs (the bench asserts the same at 100k/10k on
    real TPU every round)."""
    from volcano_tpu import native
    from volcano_tpu.ops.preempt_pack import preempt_dense
    from volcano_tpu.ops.preempt_pallas import run_preempt_pallas

    pk = generate_preempt_packed(
        n_victims=3_600, n_nodes=400, n_preemptors=400, seed=5
    )
    ev_d, pipe_d = preempt_dense(pk)
    ev_n, pipe_n = native.baseline_preempt(pk)
    assert np.array_equal(ev_d, ev_n) and np.array_equal(pipe_d, pipe_n)
    ev_p, pipe_p = run_preempt_pallas(pk, interpret=True)
    assert np.array_equal(ev_d, ev_p) and np.array_equal(pipe_d, pipe_p)
    assert ev_d.sum() > 100  # real preemption pressure
