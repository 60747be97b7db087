"""The ``antiaffinity-full-cycle`` cell rehearsed on the CPU: a miniature
of ``pod-anti-affinity`` (80 nodes, 16 init pods, 24 measured) driven end
to end through the program equals ``reference/anti_affinity.py``, the
control turns ``correct`` false, and the allocate kernel's work counts
with anti-affinity classes match counts made by hand.

The miniature is registered in ``test_rehearsal.SMALL`` beside the other
cells' sizes, so that the rehearsal's check of the named files covers
the cell too."""

import numpy as np
import pytest

import test_rehearsal
from controls import control_hook
from harness import core
from test_rehearsal import small_run
from work import allocate_kernel, allocate_kernel_affinity

CELL = "antiaffinity-full-cycle"
test_rehearsal.SMALL[CELL] = ({"nodes": 80, "init_pods": 16, "measure_pods": 24}, {})
SEEDS = (17, 2**31 + 13, 987654321017)


def small_config():
    cfg = core.load_json("configs", "pod-anti-affinity.json")
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


def test_reference_keeps_green_pods_apart():
    """Every measured pod binds, no node ends with two pods, and without
    the terms binpack stacks pods onto nodes that hold one."""
    from generators import pod_anti_affinity
    from reference import anti_affinity

    cfg = small_config()
    for seed in SEEDS:
        world = pod_anti_affinity.full_cycle(cfg, seed)
        assert len(set(world.init_node.tolist())) == cfg["init_pods"]
        binds = anti_affinity.expected_full_cycle(world)["binds"]
        assert len(binds) == cfg["measure_pods"]
        held = np.bincount(np.concatenate([world.init_node, list(binds.values())]),
                           minlength=cfg["nodes"])
        assert held.max() == 1
        control = anti_affinity.expected_full_cycle(world, control=True)["binds"]
        assert np.bincount(list(control.values()) + world.init_node.tolist()).max() > 1


def test_generator_refuses_a_program_without_device_anti_affinity(monkeypatch):
    from generators import pod_anti_affinity

    from volcano_tpu.ops import packing

    monkeypatch.delattr(packing, "affinity_row_encodable")
    with pytest.raises(core.Refused, match="anti-affinity"):
        pod_anti_affinity.full_cycle(small_config(), 1)


def test_allocate_kernel_affinity_count_by_hand():
    # R = 2: 80 ops a (task, node) pair (test_work_counts); an absolute
    # class adds 3 + 3 = 6 at each node, a spread class 11
    assert allocate_kernel_affinity.affinity_ops_per_node() == 6
    got = allocate_kernel_affinity.count(tasks=2, nodes=3, resources=2, affinity_classes=2)
    # ops 2 x 3 x (80 + 2 x 6); bytes read 2 x (2+2+4) x 4 + 3 x (1 + (6+2+6) x 4),
    # written 2 x 4
    assert got == {"ops": 2 * 3 * 92, "bytes": 64 + 3 * 57 + 8}
    mixed = allocate_kernel_affinity.count(2, 3, 2, spread_classes=1, affinity_classes=1)
    assert mixed["ops"] == 2 * 3 * (80 + 11 + 6)
    assert allocate_kernel_affinity.count(2, 3, 2) == allocate_kernel.count(2, 3, 2)
