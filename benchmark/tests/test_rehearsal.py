"""The harness rehearsed without the chip: files found by name, the
preempt generator accepted by the program's packer, every cell driven
end to end at a small size, and ``run.py`` refusing without a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from harness import core

ROOT = core.ROOT
SMALL = {
    "preempt-full-cycle": ({"nodes": 100, "low_pods": 400, "high_pods": 100}, {}),
}


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def small_run(cell, seed=20260101, seconds=1.0, hook=None):
    config, params = SMALL[cell]

    def shrink(traffic):
        traffic.cell.config.update(config)
        traffic.cell.params.update(params)
        if hook is not None:
            hook(traffic)

    return core.run(cell, seed, seconds, False, time.perf_counter(),
                    require_accelerator=False, hook=shrink)


def test_every_named_file_is_found():
    bench = benchmark_json()
    for cfg in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
        c = core.load_json("configs", f"{cfg['name']}.json")
        core.load_module("generators", c["generator"])
        core.load_module("reference", c["reference"])
    for w in bench["workloads"]:
        spec = core.load_json("workloads", f"{w['name']}.json")
        assert spec["config"] == w["config"] and spec["traffic"] == w["traffic"]
        assert spec["chips"] == w["chips"]
        core.load_module("traffic", spec["traffic"])
    for m in bench["per_layer"]:
        assert callable(core.load_module("metrics", m["name"]).read)
    assert {w["name"] for w in bench["workloads"]} <= set(SMALL)


def test_preempt_generator_packs():
    """A session of the tiny preemption cluster passes
    ``pack_preempt_session`` (none of its refusals) with victims and
    preemptors in it."""
    from generators import preemption_basic
    from harness import cluster

    cfg = core.load_json("configs", "preemption-basic.json")
    cfg.update(SMALL["preempt-full-cycle"][0])
    world = preemption_basic.full_cycle(cfg, 7)
    cache, _ = cluster.build(world.objects, cfg["scheduler"], cluster.Recorder(), os.devnull)
    from volcano_tpu.conf import PluginOption, Tier
    from volcano_tpu.framework import close_session, open_session
    from volcano_tpu.ops.preempt_pack import pack_preempt_session

    tiers = [Tier(plugins=[PluginOption(name=n) for n in tier])
             for tier in cfg["scheduler"]["tiers"]]
    ssn = open_session(cache, tiers, [])
    try:
        pk = pack_preempt_session(ssn)
    finally:
        close_session(ssn)
    assert pk.n_victims == cfg["low_pods"]
    assert pk.base.n_tasks == cfg["high_pods"]
    assert pk.use_prio and pk.use_gang and pk.use_conf and not pk.use_drf


def test_seeds_give_the_same_work():
    """Seeds move pods between nodes and reorder the feed, and leave the
    work alone: four init pods on every node, and as many evictions and
    placements in the reference."""
    import numpy as np

    from generators import preemption_basic
    from reference import preempt

    cfg = core.load_json("configs", "preemption-basic.json")
    cfg.update(SMALL["preempt-full-cycle"][0])
    outs = []
    for seed in (1, 2**31 + 7):
        world = preemption_basic.full_cycle(cfg, seed)
        assert (np.bincount(world.low_node, minlength=cfg["nodes"]) == 4).all()
        outs.append(preempt.expected_full_cycle(world))
    assert [len(o["evicts"]) for o in outs] == [3 * cfg["high_pods"]] * 2
    assert [len(o["pipelined"]) for o in outs] == [cfg["high_pods"]] * 2
    assert outs[0]["evicts"] != outs[1]["evicts"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_runs_and_matches_reference(cell):
    out = small_run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


def test_run_py_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "preempt-full-cycle", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_py_refuses_outside_a_checkout(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "preempt-full-cycle",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
