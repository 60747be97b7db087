"""The ``spread-full-cycle`` cell rehearsed on the CPU: a miniature of
``topology-spreading`` (60 nodes, 40 measured pods, maxSkew 1) driven
end to end through the program equals ``reference/spread.py``, the
control turns ``correct`` false, and the allocate kernel's work counts
match counts made by hand.

The miniature is registered in ``test_rehearsal.SMALL`` beside the other
cells' sizes, so that the rehearsal's check of the named files covers
the cell too."""

import numpy as np
import pytest

import test_rehearsal
from controls import control_hook
from harness import core
from test_rehearsal import small_run
from work import allocate_kernel

CELL = "spread-full-cycle"
test_rehearsal.SMALL[CELL] = (
    {"nodes": 60, "init_pods": 60, "measure_pods": 40,
     "spread": {**core.load_json("configs", "topology-spreading.json")["spread"],
                "max_skew": 1}},
    {},
)
SEEDS = (13, 2**31 + 11, 987654321013)


def small_config():
    cfg = core.load_json("configs", "topology-spreading.json")
    cfg.update(test_rehearsal.SMALL[CELL][0])
    return cfg


@pytest.mark.parametrize("seed", SEEDS)
def test_cell_matches_reference(seed):
    out = small_run(CELL, seed=seed)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["binds_mismatch"]["value"] == 0
    assert "setup_s" in out["metrics"] and "cycle_ms" in out["metrics"]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_turns_correct_false(seed):
    record = {}
    out = small_run(CELL, seed=seed, hook=control_hook(record))
    assert not out["correct"], out["checks"]
    assert out["checks"]["binds_mismatch"]["value"] > 0
    assert all(c["value"] <= c["limit"] for c in record["program"].values()), record


def test_reference_spreads_and_the_constraint_bites():
    """Every measured pod binds; no zone ends more than maxSkew blue pods
    above another; and without the constraint the placements differ."""
    from generators import topology_spreading
    from reference import spread

    cfg = small_config()
    for seed in SEEDS:
        world = topology_spreading.full_cycle(cfg, seed)
        assert (np.bincount(world.init_node, minlength=cfg["nodes"]) == 1).all()
        binds = spread.expected_full_cycle(world)["binds"]
        assert len(binds) == cfg["measure_pods"]
        per_zone = np.bincount([world.zone[n] for n in binds.values()],
                               minlength=len(cfg["zones"]))
        present = np.bincount(world.zone, minlength=len(cfg["zones"])) > 0
        assert per_zone[present].max() - per_zone[present].min() <= cfg["spread"]["max_skew"]
        assert spread.expected_full_cycle(world, control=True)["binds"] != binds


def test_allocate_kernel_count_by_hand():
    # R = 2: 80 ops a (task, node) pair (test_work_counts); a spread class
    # adds 2 + 6 + 3 = 11 at each node
    assert allocate_kernel.spread_ops_per_node() == 11
    got = allocate_kernel.count(tasks=2, nodes=3, resources=2, classes=1)
    # ops 2 x 3 x (80 + 11); bytes read 2 x (2+2+2) x 4 + 3 x (1 + (6+2+3) x 4),
    # written 2 x 4
    assert got == {"ops": 2 * 3 * 91, "bytes": 48 + 3 * 45 + 8}
    assert allocate_kernel.count(2, 3, 2)["ops"] == 2 * 3 * 80
