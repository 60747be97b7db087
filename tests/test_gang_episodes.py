"""Volcano's per-job gang commit/discard in the allocate scan.

jax-allocate on each formulation that takes a session with multi-task
gangs — the plain scan, native and the Pallas kernel (interpret mode on
the CPU) — against the host ``allocate`` action (the Statement loop) on
seeded sessions built so that gangs fail at their first task, roll back
partial placements (gangs larger than the holes left), end post-ready
jobs whose later tasks must be skipped, and sit beside topology spread
and anti-affinity classes whose counts a rollback must undo.  The whole
session state must match field by field, fit errors included."""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

import volcano_tpu.actions.jax_allocate as ja
from volcano_tpu.actions.allocate import AllocateAction
from volcano_tpu.actions.jax_allocate import JaxAllocateAction
from volcano_tpu.apis import core
from volcano_tpu.framework import close_session, open_session

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache, tiers
from tests.test_fast_apply import _assert_state_equal

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
TIERS = lambda: tiers(
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder", "binpack"],
)


def force_executor(monkeypatch, executor):
    """Route every allocate dispatch to ``executor``; the Pallas kernel
    runs in interpret mode."""
    from volcano_tpu import native
    from volcano_tpu.ops import dispatch, pallas_session

    if executor == "native" and native.load() is None:
        pytest.skip("native executor unavailable (no g++)")
    monkeypatch.setattr(dispatch, "select_executor", lambda snap, weights=None: executor)
    if executor == "pallas":
        real = pallas_session.run_outcome_pallas
        monkeypatch.setattr(
            pallas_session, "run_outcome_pallas",
            lambda snap, weights=None: real(snap, weights, block_size=128, interpret=True),
        )


def gang_cluster(seed, kind="plain"):
    """Ten part-filled nodes and a backlog larger than their room: gangs
    of 2-5 workers with minAvailable = replicas, two jobs of 4 with
    minAvailable 1 whose later tasks can fail, all in one queue.
    ``spread``: the workers of gangs g0-g3 spread over zones with
    maxSkew 1; ``anti``: those of gangs g0-g3 repel each other on a
    hostname (few enough classes for the kernel to carry them all);
    ``queues``: the backlog alternates between two weighted queues."""
    rng = random.Random(seed)
    nodes = []
    for i in range(10):
        cpu = rng.choice([4, 6, 8])
        nodes.append(build_node(
            f"n{i:02d}", {"cpu": str(cpu), "memory": "32Gi"},
            labels={ZONE: f"z{i % 3}", HOST: f"n{i:02d}"},
        ))
    pods, pgs = [], []
    for i in range(10):
        for k in range(rng.randrange(3)):
            name = f"run-{i}-{k}"
            pods.append(build_pod("ns", name, f"n{i:02d}", {"cpu": "1", "memory": "2Gi"},
                                  phase="Running", group=f"pg-{name}"))
            pgs.append(build_pod_group("ns", f"pg-{name}", 1, queue="q"))
    jobs = [(f"g{j}", rng.randrange(2, 6), None) for j in range(9)]
    jobs += [(f"m{j}", 4, 1) for j in range(2)]
    rng.shuffle(jobs)
    for name, size, min_avail in jobs:
        cpu = rng.choice(["1", "2", "3"]) if min_avail is None else rng.choice(["2", "3", "4"])
        for w in range(size):
            p = build_pod("ns", f"{name}-w{w}", "", {"cpu": cpu, "memory": "4Gi"},
                          group=f"pg-{name}", labels={"app": name})
            constrained = name in ("g0", "g1", "g2", "g3")
            if kind == "spread" and constrained:
                p.spec.topology_spread_constraints = [core.TopologySpreadConstraint(
                    max_skew=1, topology_key=ZONE, when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"app": name}},
                )]
            if kind == "anti" and constrained:
                p.spec.affinity = {"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": {"app": name}},
                        "topologyKey": HOST,
                    }],
                }}
            pods.append(p)
        queue = "q2" if kind == "queues" and len(pgs) % 2 else "q"
        pgs.append(build_pod_group("ns", f"pg-{name}", min_avail or size, queue=queue))
    queues = [build_queue("q", weight=2)]
    if kind == "queues":
        queues.append(build_queue("q2", weight=1))
    return dict(nodes=nodes, pods=pods, pod_groups=pgs, queues=queues)


def _run(cluster, action):
    cache = make_cache(**copy.deepcopy(cluster))
    ssn = open_session(cache, TIERS(), [])
    action.execute(ssn)
    return cache, ssn


def _fit_errors(ssn):
    return {
        (uid, t): fe.error()
        for uid, job in ssn.jobs.items()
        for t, fe in job.nodes_fit_errors.items()
    }


CASES = [
    (seed, executor, kind)
    for kind, seeds in (("plain", (1, 2, 3)), ("spread", (1, 2, 3)), ("anti", (2, 3, 8)))
    for seed in seeds
    for executor in ("xla-scan", "native", "pallas")
    if not (executor == "native" and kind != "plain")  # native carries no classes
]


@pytest.mark.parametrize("seed,executor,kind", CASES)
def test_jax_allocate_matches_host_allocate_on_gangs(seed, executor, kind, monkeypatch):
    cluster = gang_cluster(seed, kind)
    host = _run(cluster, AllocateAction())
    force_executor(monkeypatch, executor)
    dev = _run(cluster, JaxAllocateAction())
    stats = dict(ja.last_phase_stats)
    _assert_state_equal(dev, host)
    assert _fit_errors(dev[1]) == _fit_errors(host[1])
    assert _fit_errors(host[1])  # some gang failed somewhere
    # applied from the device, with no host sweep
    assert stats["gang_outcomes_host"] == 0 and stats["apply_host_sweep"] == 0
    assert stats["gang_discarded"] > 0 and stats["gang_committed"] > 0
    for ssn in (dev[1], host[1]):
        close_session(ssn)


@pytest.mark.parametrize("seed", [1, 2, 5])
def test_several_queues_take_the_host_chooser_after_a_discard(seed, monkeypatch):
    """Two weighted queues: a discard moves the shares that pick the next
    queue, so every row after the first gang that did not fully place
    takes the host chooser, counted by reason — and the session still
    ends as under the host action."""
    from volcano_tpu.metrics import metrics

    def cut_rows():
        key = ("volcano_allocate_order_cut_rows_total", (("reason", "queue_share"),))
        with metrics.registry._lock:
            return metrics.registry._counters.get(key, 0.0)

    cluster = gang_cluster(seed, "queues")
    host = _run(cluster, AllocateAction())
    force_executor(monkeypatch, "xla-scan")
    before = cut_rows()
    dev = _run(cluster, JaxAllocateAction())
    _assert_state_equal(dev, host)
    assert _fit_errors(dev[1]) == _fit_errors(host[1])
    assert cut_rows() > before
    assert ja.last_phase_stats["gang_outcomes_host"] > 0
    for ssn in (dev[1], host[1]):
        close_session(ssn)


def test_gang_cluster_rolls_gangs_back():
    """The seeds above include partial gangs the scan rolls back — in
    every kind, constrained gangs too, so class counts are undone — and
    post-ready tasks skipped after their job ended."""
    from volcano_tpu.ops.kernels import NOT_REACHED

    rolled = skipped = 0
    seen = []
    rolled_kinds = set()

    def capture(orig):
        def wrapped(self, ssn, ordered, nodes=None, pc=None):
            out = orig(self, ssn, ordered, nodes, pc)
            seen.append((list(ordered), self._decided[1]))
            return out
        return wrapped

    orig = JaxAllocateAction._kernel_proposals
    JaxAllocateAction._kernel_proposals = capture(orig)
    try:
        for seed, kind in sorted({(seed, kind) for seed, _e, kind in CASES}):
            n = len(seen)
            _run(gang_cluster(seed, kind), JaxAllocateAction())
            for ordered, outcome in seen[n:]:
                if any(p >= 0 and a < 0 and t.name[:2] in ("g0", "g1", "g2", "g3")
                       for t, p, a in zip(ordered, outcome.placed, outcome.assignment)):
                    rolled_kinds.add(kind)
    finally:
        JaxAllocateAction._kernel_proposals = orig
    assert rolled_kinds == {"plain", "spread", "anti"}
    for ordered, outcome in seen:
        rolled += int(((outcome.placed >= 0) & (outcome.assignment < 0)).sum())
        post_ready = {t.job for t in ordered if t.name.startswith("m")}
        skipped += sum(
            1 for t, p in zip(ordered, outcome.placed)
            if p == NOT_REACHED and t.job in post_ready
        )
    assert rolled > 0 and skipped > 0
