"""On-chip memory budgets of the Pallas kernels (TPU v5e).

One pair of numbers for both sides: the dispatcher admits a session to a
Pallas rung only when the kernel's estimate fits under them
(ops/dispatch.py), and both kernels compile with the VMEM budget as
their scoped-VMEM limit (:func:`pallas_compiler_params`) — Mosaic's own
default (16 MiB) would refuse most admitted shapes at compile time.
"""

from __future__ import annotations

#: VMEM budget.  v5e VMEM is 128 MiB; leave headroom for Mosaic's own
#: buffers and the double-buffered grid pipeline.
VMEM_BUDGET = 96 * 1024 * 1024

#: SMEM (scalar memory) budget — 1 MiB on v5e; the preempt kernel's
#: per-job scalar state must fit (large-J sessions fall back to dense).
SMEM_BUDGET = 768 * 1024


def pallas_compiler_params():
    """Mosaic compiler parameters shared by every Pallas kernel."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)
