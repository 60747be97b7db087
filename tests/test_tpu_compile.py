"""Compile the main-path kernels for a described TPU v5e, with no chip.

The TPU compiler is installed here, so it can compile for a v5e that is
described and not attached (on-chip-measurement guide §2).  What it
refuses here — more scoped VMEM or SMEM than a kernel may use, a shape
Mosaic cannot tile — the chip would refuse too, and the dispatcher would
then quietly degrade the session to a slower rung.  So every shape the
dispatcher admits to a Pallas rung must compile.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every xdist
worker imports this file.  Nothing here runs a kernel.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from volcano_tpu.ops.chip_memory import SMEM_BUDGET, VMEM_BUDGET
from volcano_tpu.ops.pallas_session import (
    kernel_vmem_bytes,
    LANES,
    prepare_pallas_arrays,
    schedule_session_pallas,
)
from volcano_tpu.ops.synthetic import (
    BASELINE_CONFIGS,
    generate_preempt_packed,
    generate_snapshot,
)

HEADLINE = BASELINE_CONFIGS["50k_pods_10k_nodes_gang_predicates"]
PREEMPT = {
    k: v for k, v in BASELINE_CONFIGS["100k_pods_10k_nodes_preempt"].items()
    if k != "preempt"
}


@pytest.fixture(scope="module")
def topo():
    # libtpu writes no log files (the fd-leak guard would see them open)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "4")
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "4")
    topologies = pytest.importorskip("jax.experimental.topologies")

    # the persistent compile cache is off (conftest): a compile for a
    # described chip could be written to it but never read back.  A
    # topology that cannot be described (libtpu missing or held) fails
    # every test here: these compiles are the only check, short of a
    # chip, that admission and compilation agree.
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_allocate(one_chip, T: int, C: int, NS: int, R: int = 2, J: int = 8192,
                      K: int = 0, absolute=()):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jax.clear_caches()  # the scoped-VMEM limit is read at trace time
    return schedule_session_pallas.lower(
        spec((T, R + 2 + 2 * K), jnp.float32),
        spec((C, NS, LANES), jnp.uint8),
        spec((3 * R + 2 + 3 * K, NS, LANES), jnp.float32),
        spec((1, R), jnp.float32),
        spec((T,), jnp.int32),
        spec((J,), jnp.int32),
        spec((J,), jnp.int32),
        spec((T,), jnp.bool_),
        absolute=absolute,
    ).compile()


def test_allocate_kernel_at_headline_shape(one_chip):
    snap = generate_snapshot(**HEADLINE)
    arrays, T_act, NK = prepare_pallas_arrays(snap)
    C = arrays["cf_u8"].shape[0]
    compiled = _compile_allocate(one_chip, T_act, C, NK // LANES)
    assert "tpu_custom_call" in compiled.as_text()


def test_allocate_kernel_at_gang_backlog_shape(one_chip):
    """The gang-backlog cell: 11,200 workers in 1,200 gangs over 5,000
    nodes, beside 5,000 one-pod resident jobs — the per-job state planes
    and the copy of the node state a gang begins from."""
    compiled = _compile_allocate(one_chip, 11264, 1, 40, J=8192)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R", [2, 3, 4])
@pytest.mark.parametrize("C", [1, 64])
def test_allocate_kernel_at_admission_edge(one_chip, C, R):
    """The widest node axis the dispatcher admits for C classes and R
    resources."""
    NS = 1
    while kernel_vmem_bytes(C, (NS + 1) * LANES, R) <= VMEM_BUDGET:
        NS += 1
    compiled = _compile_allocate(one_chip, 50176, C, NS, R)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("K", [1, 8])
def test_allocate_kernel_with_spread_classes(one_chip, K):
    """Hard topology spread: K classes' planes, count scratch and
    per-step minimum, at the 2,000 x 5,000 shape of scheduler_perf's
    TopologySpreading and at the widest node axis admitted for C=1."""
    compiled = _compile_allocate(one_chip, 2048, 1, 40, K=K)
    assert "tpu_custom_call" in compiled.as_text()
    NS = 1
    while kernel_vmem_bytes(1, (NS + 1) * LANES, 2, K=K) <= VMEM_BUDGET:
        NS += 1
    compiled = _compile_allocate(one_chip, 2048, 1, NS, K=K)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("absolute", [(True, True), (False, True, True)])
def test_allocate_kernel_with_anti_affinity_classes(one_chip, absolute):
    """Required anti-affinity: classes of the absolute kind (bound, no
    minimum), alone and beside a spread class, at the 1,000 x 5,000
    shape of scheduler_perf's SchedulingPodAntiAffinity."""
    compiled = _compile_allocate(one_chip, 1024, 1, 40, K=len(absolute), absolute=absolute)
    assert "tpu_custom_call" in compiled.as_text()


def test_allocate_kernel_above_default_scoped_vmem(one_chip):
    """C=64, NS=800 needs ~33 MiB of scoped VMEM: refused under
    Mosaic's 16 MiB default, admitted and compiled under the budget."""
    assert kernel_vmem_bytes(64, 800 * LANES, 2) <= VMEM_BUDGET
    compiled = _compile_allocate(one_chip, 50176, 64, 800)
    assert "tpu_custom_call" in compiled.as_text()


def _compile_preempt(one_chip, pk):
    from volcano_tpu.ops.preempt_pallas import _preempt_call, pack_preempt_call

    buf, kw, _dims, _ = pack_preempt_call(pk)
    jax.clear_caches()
    return _preempt_call.lower(
        jax.ShapeDtypeStruct(buf.shape, buf.dtype, sharding=one_chip), **kw
    ).compile()


@pytest.mark.parametrize(
    "overrides",
    [
        pytest.param({}, id="baseline-100k"),
        # 37.6k jobs: the preempt kernel's SMEM estimate just under
        # SMEM_BUDGET (the chip has 1 MiB of SMEM)
        pytest.param(
            {"n_victims": 72_700, "victim_job_size": 2}, id="smem-edge"
        ),
    ],
)
def test_preempt_kernel(one_chip, overrides):
    from volcano_tpu.ops.preempt_pallas import (
        preempt_smem_bytes,
        preempt_vmem_bytes,
    )

    pk = generate_preempt_packed(**{**PREEMPT, **overrides})
    assert preempt_vmem_bytes(pk) <= VMEM_BUDGET
    assert preempt_smem_bytes(pk) <= SMEM_BUDGET
    compiled = _compile_preempt(one_chip, pk)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_session_on_four_chips(topo):
    """The node-sharded session at 50k×10k on a 2x2 mesh: each device
    holds a quarter of the node planes, and the blocks meet through
    all-gathers."""
    from volcano_tpu.ops.sharded import (
        _IN_SPECS,
        make_sharded_session,
        sharded_session_inputs,
    )

    mesh = Mesh(np.array(topo.devices[:4]), ("nodes",))
    snap = generate_snapshot(**HEADLINE)
    arrays, T_blk = sharded_session_inputs(snap, 4)
    arrays.append(np.zeros(T_blk, dtype=bool))  # active
    args = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, s))
        for a, s in zip(arrays, _IN_SPECS)
    ]
    compiled = make_sharded_session(mesh).lower(*args).compile()
    assert "all-gather" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    node_bytes = sum(a.nbytes for a, s in zip(arrays, _IN_SPECS) if s[0])
    whole = sum(a.nbytes for a in arrays)
    assert per_device < whole - node_bytes + node_bytes // 2
