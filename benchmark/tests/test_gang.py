"""The ``gang-backlog-full-cycle`` cell rehearsed on the CPU: a miniature
of ``gang-training-backlog`` (50 part-filled nodes, 15 gangs of 16, 8 and
4 workers asking more cpu than is free) driven end to end through the
program equals ``reference/gang.py``, the control (a worker that fits
nowhere bound over capacity) turns ``correct`` false, and the allocate kernel's work
counts for gangs match counts made by hand.

The miniature is registered in ``test_rehearsal.SMALL`` beside the other
cells' sizes, so that the rehearsal's check of the named files covers
the cell too."""

import numpy as np
import pytest

import test_rehearsal
from controls import control_hook
from harness import core
from test_rehearsal import small_run
from work import allocate_kernel, allocate_kernel_gang

CELL = "gang-backlog-full-cycle"
test_rehearsal.SMALL[CELL] = (
    {"nodes": 50, "gangs": [
        {"count": 5, "workers": 16, "cpu_milli": 2000, "mem_mib": 8192},
        {"count": 5, "workers": 8, "cpu_milli": 1000, "mem_mib": 4096},
        {"count": 5, "workers": 4, "cpu_milli": 500, "mem_mib": 2048},
    ]},
    {},
)
SEEDS = (19, 2**31 + 17, 987654321019)


def small_config():
    cfg = core.load_json("configs", "gang-training-backlog.json")
    cfg.update(test_rehearsal.SMALL[CELL][0])
    return cfg


@pytest.mark.parametrize("seed", SEEDS)
def test_cell_matches_reference(seed):
    out = small_run(CELL, seed=seed)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["binds_mismatch"]["value"] == 0
    assert out["checks"]["pipelined_mismatch"]["value"] == 0
    assert "setup_s" in out["metrics"] and "cycle_ms" in out["metrics"]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_turns_correct_false(seed):
    record = {}
    out = small_run(CELL, seed=seed, hook=control_hook(record))
    assert not out["correct"], out["checks"]
    assert out["checks"]["binds_mismatch"]["value"] > 0
    assert all(c["value"] <= c["limit"] for c in record["program"].values()), record


def test_reference_commits_whole_gangs_and_discards_the_rest():
    """Every gang binds whole or not at all, some are discarded — the
    backlog asks more than is free — and without capacity the control
    binds every gang whole, some over a node's cpu."""
    from generators import gang_training_backlog
    from reference import gang

    cfg = small_config()
    for seed in SEEDS:
        world = gang_training_backlog.full_cycle(cfg, seed)
        binds = gang.expected_full_cycle(world)["binds"]
        whole = [g for g in world.gangs if g.worker_uid(0) in binds]
        assert all(all(g.worker_uid(k) in binds for k in range(g.workers)) for g in whole)
        assert 0 < len(whole) < len(world.gangs)
        assert len(binds) == sum(g.workers for g in whole)
        used = np.bincount(list(binds.values()), minlength=world.n_nodes)
        assert (used + world.resident <= cfg["node_max_pods"]).all()
        control = gang.expected_full_cycle(world, control=True)["binds"]
        assert len(control) == sum(g.workers for g in world.gangs)
        cpu = world.resident * cfg["resident_cpu_milli"]
        for g in world.gangs:
            for k in range(g.workers):
                cpu[control[g.worker_uid(k)]] += g.cpu_milli
        assert (cpu > cfg["node_cpu_milli"]).any()


def test_generator_refuses_a_program_without_device_gangs(monkeypatch):
    from generators import gang_training_backlog

    from volcano_tpu.ops import kernels

    monkeypatch.delattr(kernels, "schedule_episodes")
    with pytest.raises(core.Refused, match="gangs"):
        gang_training_backlog.full_cycle(small_config(), 1)


def test_allocate_kernel_gang_count_by_hand():
    # R = 2: 80 ops a (task, node) pair (test_work_counts); a failing
    # row's reasons 4 + 2 x 8 = 20 at each node; a state copy 2 at each
    # node of each of R + 1 planes
    assert allocate_kernel_gang.reason_ops_per_node() == 20
    plain = allocate_kernel.count(2, 3, 2)
    got = allocate_kernel_gang.count(tasks=4, nodes=3, resources=2, skipped=2,
                                     gang_runs=1, rollback_runs=1, failed=1)
    assert got["ops"] == plain["ops"] + 2 * 3 * 3 * 2 + 1 * 3 * 20
    # bytes: two skipped rows read (2+2) x 4, the job column of 4 rows,
    # and the written chosen of 2 skipped rows and 8 reasons a row
    assert got["bytes"] == plain["bytes"] + 2 * 4 * 4 + 4 * 4 + (2 + 4 * 8) * 4
    assert allocate_kernel_gang.count(2, 3, 2)["ops"] == plain["ops"]
