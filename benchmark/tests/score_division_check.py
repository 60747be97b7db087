#!/usr/bin/env python3
"""Are the allocate kernel's score divisions IEEE float32 on the chip?

    python3 benchmark/tests/score_division_check.py [--interpret]

``ops/pallas_session.py`` ``score_planes`` divides in float32 for the
binpack lanes (``req * w / max(alloc, 1)``) and the balanced fractions
(``req / max(alloc, 1)``).  This runs those divisions plain (``raw_*``)
and through ``ops/kernels.py`` ``div_rn`` (the residual correction the
score now takes), and the whole score, inside a Pallas kernel over every
node state the ``gang-training-backlog`` cell can reach in a cycle —
used cpu in steps of 500m, used memory in steps of 1 GiB, on its 4 cpu /
32 GiB nodes — for each worker request it has, and compares each
element with numpy's float32 (IEEE round-to-nearest) division in the
same order.  Prints one JSON line: the elements compared and how many
differ in each plane.  Without ``--interpret`` it needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MAX_PRIORITY = 10.0


def node_states(cfg):
    """(used cpu, used memory) planes over every reachable state."""
    cpu = np.arange(0, cfg["node_cpu_milli"] + 1, 500, dtype=np.float32)
    mem = np.arange(0, cfg["node_mem_mib"] + 1, 1024, dtype=np.float32)
    uc, um = np.meshgrid(cpu, mem, indexing="ij")
    return uc.ravel(), um.ravel()


def numpy_planes(rr, used, alloc):
    """binpack lanes, balanced fractions and total score in float32, in
    score_planes' operation order (default weights)."""
    f = np.float32
    out = {}
    req = [f(rr[r]) + used[r] for r in range(2)]
    maxal = [np.maximum(alloc[r], f(1.0)) for r in range(2)]
    bp = None
    ws = f(0.0)
    for r in range(2):
        reqmask = rr[r] > 0
        valid = reqmask & (alloc[r] > 0) & (req[r] <= alloc[r])
        lane = np.where(valid, (req[r] * f(1.0)) / maxal[r], f(0.0)).astype(np.float32)
        out[f"binpack_lane{r}"] = lane
        bp = lane if bp is None else bp + lane
        ws = ws + (f(1.0) if reqmask else f(0.0))
    s_bp = (np.where(ws > 0, bp / ws, f(0.0)) * f(MAX_PRIORITY)).astype(np.float32)
    lr = None
    fracs = []
    for r in range(2):
        cap, c = alloc[r], maxal[r]
        p = (cap - req[r]) * f(MAX_PRIORITY)
        q = np.floor(p / c)
        q = q + ((q + f(1.0)) * c <= p) - (q * c > p)
        lane = np.where((alloc[r] > 0) & (req[r] <= cap), q, f(0.0))
        lr = lane if lr is None else lr + lane
        frac = np.where(alloc[r] > 0, req[r] / c, f(1.0)).astype(np.float32)
        out[f"balanced_frac{r}"] = frac
        fracs.append(frac)
    s_lr = np.floor(lr * f(0.5))
    diff = np.abs(fracs[0] - fracs[1])
    s_bal = np.floor((f(1.0) - diff) * f(MAX_PRIORITY))
    s_bal = np.where((fracs[0] >= 1) | (fracs[1] >= 1), f(0.0), s_bal)
    out["total"] = (s_bp + s_lr + s_bal).astype(np.float32)
    return out


def device_planes(rr, used, alloc, interpret):
    """The same planes from inside a Pallas kernel, through
    score_planes for the total."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from volcano_tpu.ops.kernels import DEFAULT_WEIGHTS, div_rn
    from volcano_tpu.ops.pallas_session import LANES, score_planes

    n = used[0].size
    ns = -(-n // (8 * LANES)) * 8
    width = ns * LANES

    def plane(x, fill):
        out = np.full(width, fill, dtype=np.float32)
        out[:n] = x
        return out.reshape(ns, LANES)

    ins = [plane(used[0], 0), plane(used[1], 0), plane(alloc[0], 1), plane(alloc[1], 1)]

    def kernel(uc_ref, um_ref, ac_ref, am_ref, o_ref):
        used_r = [uc_ref[:], um_ref[:]]
        alloc_r = [ac_ref[:], am_ref[:]]
        req = [rr[r] + used_r[r] for r in range(2)]
        maxal = [jnp.maximum(alloc_r[r], 1.0) for r in range(2)]
        for r in range(2):
            valid = (req[r] <= alloc_r[r]) & (alloc_r[r] > 0.0)
            o_ref[r] = jnp.where(valid, req[r] * 1.0 / maxal[r], 0.0)
            o_ref[2 + r] = jnp.where(alloc_r[r] > 0.0, req[r] / maxal[r], 1.0)
            o_ref[4 + r] = jnp.where(valid, div_rn(req[r] * 1.0, maxal[r]), 0.0)
            o_ref[6 + r] = jnp.where(alloc_r[r] > 0.0, div_rn(req[r], maxal[r]), 1.0)
        o_ref[8] = score_planes(
            list(rr), req, lambda r: alloc_r[r], lambda r: maxal[r],
            lambda r: (alloc_r[r] > 0.0).astype(jnp.float32), DEFAULT_WEIGHTS,
            (ns, LANES),
        )

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((9, ns, LANES), jnp.float32),
        interpret=interpret,
    )(*(jnp.asarray(x) for x in ins))
    got = np.asarray(got).reshape(9, width)[:, :n]
    names = ["raw_binpack_lane0", "raw_binpack_lane1", "raw_balanced_frac0",
             "raw_balanced_frac1", "binpack_lane0", "binpack_lane1", "balanced_frac0",
             "balanced_frac1", "total"]
    return dict(zip(names, got))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--interpret", action="store_true")
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    import jax

    if not args.interpret and jax.default_backend() != "tpu":
        print("score_division_check: no TPU", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "configs", "gang-training-backlog.json")) as f:
        cfg = json.load(f)
    uc, um = node_states(cfg)
    alloc = [np.full_like(uc, cfg["node_cpu_milli"]), np.full_like(um, cfg["node_mem_mib"])]
    result = {"device": jax.devices()[0].device_kind, "compared": 0, "differ": {}, "first": {}}
    for g in cfg["gangs"]:
        rr = (float(g["cpu_milli"]), float(g["mem_mib"]))
        want = numpy_planes(rr, [uc, um], alloc)
        got = device_planes(rr, [uc, um], alloc, args.interpret)
        result["compared"] += uc.size
        for name in got:
            w = want[name.replace("raw_", "")]
            bad = np.nonzero(got[name].view(np.uint32) != w.view(np.uint32))[0]
            result["differ"][name] = result["differ"].get(name, 0) + int(bad.size)
            if bad.size and name not in result["first"]:
                i = int(bad[0])
                result["first"][name] = [rr, float(uc[i]), float(um[i]), float(w[i]), float(got[name][i])]
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
