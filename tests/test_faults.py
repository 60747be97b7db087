"""Fault plane + graceful degradation units: spec parser round-trip,
deterministic firing streams, circuit-breaker state machine, cycle
watchdog, executor degradation ladder with half-open re-promotion,
/healthz degraded reporting, and the bounded resync queue's
poison-task quarantine.  The multi-seam integration runs live in
tests/test_chaos.py."""

from __future__ import annotations

import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from volcano_tpu import faults
from volcano_tpu.faults.breaker import CircuitBreaker, CLOSED, HALF_OPEN, OPEN
from volcano_tpu.faults.watchdog import CycleDeadlineExceeded
from volcano_tpu.metrics import metrics

from tests.builders import build_node, build_pod, build_pod_group, build_queue
from tests.scheduler_helpers import make_cache


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with the plane disabled and the
    breaker registry empty — faults are process-global state."""
    faults.configure(None)
    faults.reset_breakers()
    faults.configure_deadline(None)
    yield
    faults.configure(None)
    faults.reset_breakers()
    faults.configure_deadline(None)


def _counter(name, **labels):
    key = (f"volcano_{name}", tuple(sorted(labels.items())))
    return metrics.registry._counters.get(key, 0.0)


# ---- spec parser ----


class TestFaultSpec:
    def test_round_trip(self):
        spec = faults.parse_faults(
            "seed=42;bus.disconnect=0.05;device.nan=0.1:count=2;"
            "device.slow=1:ms=50:after=3"
        )
        assert spec.seed == 42
        assert spec.rules["bus.disconnect"].probability == 0.05
        assert spec.rules["device.nan"].count == 2
        assert spec.rules["device.slow"].ms == 50.0
        assert spec.rules["device.slow"].after == 3
        assert faults.parse_faults(spec.format()) == spec

    def test_round_trip_is_fixpoint(self):
        spec = faults.parse_faults("seed=7;cache.bind_fail=0.25:count=10")
        assert faults.parse_faults(spec.format()).format() == spec.format()

    def test_empty_spec(self):
        spec = faults.parse_faults("")
        assert spec.seed == 0 and not spec.rules

    @pytest.mark.parametrize("bad", [
        "bogus",
        "p=1.5",
        "p=-0.1",
        "p=0.5:count=-1",
        "p=0.5:unknown=3",
        "p=0.5:count",
        "seed=x",
        "seed=42:count=2",
        "seed=42:bus.disconnect=0.05",
        "a=0.5;a=0.6",
    ])
    def test_malformed_rejected(self, bad):
        with pytest.raises(ValueError):
            faults.parse_faults(bad)

    def test_deterministic_across_planes(self):
        spec = "seed=99;x.y=0.3;a.b=0.7"
        p1 = faults.FaultPlane(faults.parse_faults(spec))
        p2 = faults.FaultPlane(faults.parse_faults(spec))
        s1 = [p1.should("x.y") for _ in range(50)]
        # interleave another point's evaluations on the second plane —
        # per-point streams are independent, so x.y must not shift
        s2 = []
        for _ in range(50):
            p2.should("a.b")
            s2.append(p2.should("x.y"))
        assert s1 == s2
        assert any(s1) and not all(s1)

    def test_count_and_after(self):
        plane = faults.FaultPlane(
            faults.parse_faults("seed=1;p.q=1:count=2:after=3")
        )
        fires = [plane.should("p.q") for _ in range(10)]
        assert fires == [False] * 3 + [True, True] + [False] * 5
        assert plane.fired() == {"p.q": 2}

    def test_unknown_point_never_fires(self):
        plane = faults.FaultPlane(faults.parse_faults("seed=1;p.q=1"))
        assert plane.should("other.point") is False

    def test_configure_installs_and_clears(self):
        faults.configure("seed=3;x.x=1")
        assert faults.get_plane().enabled
        assert faults.get_plane().should("x.x")
        faults.configure(None)
        assert not faults.get_plane().enabled

    def test_firing_counts_metric(self):
        before = _counter("faults_injected_total", point="m.n")
        faults.configure("seed=1;m.n=1")
        faults.get_plane().should("m.n")
        assert _counter("faults_injected_total", point="m.n") == before + 1


# ---- circuit breaker ----


class TestCircuitBreaker:
    def test_trips_after_threshold(self):
        br = CircuitBreaker("t", failure_threshold=3, cooldown_s=60)
        assert br.state == CLOSED
        br.record_failure("e1")
        br.record_failure("e2")
        assert br.state == CLOSED and br.allow()
        br.record_failure("e3")
        assert br.state == OPEN
        assert not br.allow()

    def test_success_resets_failure_count(self):
        br = CircuitBreaker("t", failure_threshold=2, cooldown_s=60)
        br.record_failure("e")
        br.record_success()
        br.record_failure("e")
        assert br.state == CLOSED  # the streak was broken

    def test_half_open_single_probe_then_promote(self):
        br = CircuitBreaker("t", failure_threshold=1, cooldown_s=0.05)
        br.record_failure("down")
        assert not br.allow()
        time.sleep(0.06)
        assert br.allow()  # the one half-open probe
        assert br.state == HALF_OPEN
        assert not br.allow()  # everyone else keeps falling back
        br.record_success()
        assert br.state == CLOSED and br.allow()

    def test_half_open_failure_reopens(self):
        br = CircuitBreaker("t", failure_threshold=1, cooldown_s=0.05)
        br.record_failure("down")
        time.sleep(0.06)
        assert br.allow()
        br.record_failure("still down")
        assert br.state == OPEN
        assert not br.allow()  # cooldown restarted

    def test_registry_and_degraded_reasons(self):
        br = faults.get_breaker("exec-a", failure_threshold=1)
        assert faults.get_breaker("exec-a") is br
        assert faults.degraded_reasons() == []
        br.record_failure("kaboom")
        reasons = faults.degraded_reasons()
        assert len(reasons) == 1
        assert "exec-a" in reasons[0] and "kaboom" in reasons[0]

    def test_state_gauge(self):
        br = faults.get_breaker("exec-g", failure_threshold=1)
        br.record_failure("x")
        key = ("volcano_circuit_breaker_open", (("executor", "exec-g"),))
        assert metrics.registry._gauges[key] == 1.0
        br.record_success()
        assert metrics.registry._gauges[key] == 0.0


# ---- cycle watchdog ----


class TestWatchdog:
    def test_disabled_runs_inline(self):
        # no deadline → same thread, no worker
        tid = {}
        out = faults.run_with_deadline(
            lambda: tid.setdefault("t", threading.get_ident()) and 41 + 1,
            None, "test",
        )
        assert out == 42 and tid["t"] == threading.get_ident()

    def test_result_and_exception_passthrough(self):
        assert faults.run_with_deadline(lambda: "ok", 5.0, "t") == "ok"
        with pytest.raises(KeyError):
            faults.run_with_deadline(
                lambda: (_ for _ in ()).throw(KeyError("boom")), 5.0, "t"
            )

    def test_overrun_raises(self):
        with pytest.raises(CycleDeadlineExceeded):
            faults.run_with_deadline(lambda: time.sleep(1.0), 0.05, "t")

    def test_exhausted_budget_raises_immediately(self):
        with pytest.raises(CycleDeadlineExceeded):
            faults.run_with_deadline(lambda: "never", 0.0, "t")

    def test_cycle_budget_accounting(self):
        faults.configure_deadline(100.0)  # 100 ms
        faults.begin_cycle()
        r1 = faults.remaining_s()
        assert r1 is not None and 0 < r1 <= 0.1
        time.sleep(0.03)
        r2 = faults.remaining_s()
        assert r2 < r1
        faults.configure_deadline(None)
        assert faults.remaining_s() is None


# ---- executor degradation ladder (dispatch) ----


def _small_snapshot():
    from __graft_entry__ import _tiny_snapshot

    return _tiny_snapshot()


def _small_preempt():
    from volcano_tpu.ops.synthetic import generate_preempt_packed

    return generate_preempt_packed(n_victims=64, n_nodes=16, n_preemptors=16)


class _Rung:
    """One dispatch entry forced onto an upper rung over a tiny session:
    ``run()`` dispatches, ``reference`` is the exact answer, ``breaker``
    guards the rung and ``floor`` is the rung it degrades to.  The
    preempt Pallas kernel is stood in for by the dense formulation —
    these tests are about the ladder, not the kernel — and counts the
    calls that reach it in ``calls``."""

    def __init__(self, entry, rung, monkeypatch):
        from volcano_tpu.ops import dispatch, preempt_pallas
        from volcano_tpu.ops.kernels import run_packed
        from volcano_tpu.ops.preempt_pack import preempt_dense

        self.calls = 0
        if entry == "allocate":
            snap = _small_snapshot()
            monkeypatch.setattr(
                dispatch, "select_executor", lambda snap, weights=None: rung
            )
            self.run = lambda: dispatch.run_packed_auto(snap)
            self.reference = run_packed(snap)
            self.breaker = rung
            self.floor = dispatch._fallback(rung, snap)
            return
        pk = _small_preempt()

        def stand_in(pk, weights=None):
            self.calls += 1
            return preempt_dense(pk)

        monkeypatch.setattr(dispatch, "select_preempt_executor", lambda pk: rung)
        monkeypatch.setattr(preempt_pallas, "run_preempt_pallas", stand_in)
        self.run = lambda: dispatch.run_preempt_auto(pk)
        self.reference = preempt_dense(pk)
        self.breaker = "preempt-pallas"
        self.floor = "dense"

    def fallbacks(self, cause):
        return _counter("executor_fallbacks_total",
                        **{"from": self.breaker, "to": self.floor,
                           "cause": cause})


class TestDispatchDegradation:
    def _force_pallas(self, monkeypatch):
        from volcano_tpu.ops import dispatch

        monkeypatch.setattr(
            dispatch, "select_executor", lambda snap, weights=None: "pallas"
        )

    @pytest.mark.parametrize("entry", ["allocate", "preempt"])
    def test_injected_lowering_failure_degrades_exactly(self, entry,
                                                        monkeypatch):
        from volcano_tpu.ops import dispatch

        rung = _Rung(entry, "pallas", monkeypatch)
        faults.configure("seed=1;device.lowering=1:count=1")
        before = rung.fallbacks("error")
        np.testing.assert_equal(rung.run(), rung.reference)
        if entry == "allocate":
            assert dispatch.last_executor() == rung.floor
        assert rung.fallbacks("error") == before + 1
        # 1 < threshold
        assert faults.get_breaker(rung.breaker).state == CLOSED

    @pytest.mark.parametrize("entry", ["allocate", "preempt"])
    def test_breaker_trips_and_skips_the_broken_rung(self, entry,
                                                     monkeypatch):
        rung = _Rung(entry, "pallas", monkeypatch)
        faults.configure("seed=1;device.lowering=1:count=3")
        for _ in range(3):
            rung.run()
        assert faults.get_breaker(rung.breaker).state == OPEN
        # 4th call: the rung is skipped WITHOUT attempting (the
        # injection budget is exhausted, so an attempt would succeed —
        # the circuit-open fallback proves it was never tried)
        before = rung.fallbacks("circuit-open")
        np.testing.assert_equal(rung.run(), rung.reference)
        assert rung.fallbacks("circuit-open") == before + 1
        assert rung.calls == 0
        assert faults.degraded_reasons()  # visible to /healthz

    @pytest.mark.parametrize("entry, upper", [
        ("allocate", "native"), ("preempt", "pallas"),
    ])
    def test_half_open_probe_repromotes_the_rung(self, entry, upper,
                                                 monkeypatch):
        """A tripped rung stays skipped through its cooldown, then one
        half-open probe runs it again and, on success, closes the
        breaker: the ladder recovers with no operator action."""
        from volcano_tpu.ops import dispatch

        rung = _Rung(entry, upper, monkeypatch)
        # first fetch fixes the breaker's parameters: a short cooldown
        br = faults.get_breaker(rung.breaker, failure_threshold=3,
                                cooldown_s=0.05)
        faults.configure("seed=1;device.lowering=1:count=3")
        for _ in range(3):
            np.testing.assert_equal(rung.run(), rung.reference)
        assert br.state == OPEN
        before = rung.fallbacks("circuit-open")
        np.testing.assert_equal(rung.run(), rung.reference)
        assert rung.fallbacks("circuit-open") == before + 1
        time.sleep(0.06)
        np.testing.assert_equal(rung.run(), rung.reference)
        assert br.state == CLOSED
        assert not faults.degraded_reasons()
        if entry == "allocate":
            assert dispatch.last_executor() == upper
        else:
            assert rung.calls == 1

    def test_corrupt_output_caught_by_validity_gate(self, monkeypatch):
        from volcano_tpu.ops import dispatch, pallas_session
        from volcano_tpu.ops.kernels import run_packed

        from volcano_tpu.ops.kernels import ScanOutcome

        snap = _small_snapshot()
        reference = run_packed(snap)
        self._force_pallas(monkeypatch)
        # the kernel "succeeds" but NaN score planes argmax'd to garbage
        monkeypatch.setattr(
            pallas_session, "run_outcome_pallas",
            lambda s, weights=None: ScanOutcome.of_assignment(np.zeros(
                s.task_resreq.shape[0], dtype=np.int32
            )),
        )
        faults.configure("seed=1;device.nan=1:count=1")
        out = dispatch.run_packed_auto(snap)
        np.testing.assert_array_equal(out, reference)
        assert _counter("executor_fallbacks_total",
                        **{"from": "pallas", "to": dispatch._fallback("pallas", snap),
                           "cause": "corrupt-output"}) >= 1

    def test_assignment_validity_gate(self):
        from volcano_tpu.ops.dispatch import _assignment_valid

        snap = _small_snapshot()
        good = np.full(snap.task_resreq.shape[0], -1, dtype=np.int32)
        assert _assignment_valid(snap, good)
        bad = good.copy()
        bad[0] = snap.n_nodes  # out of range
        assert not _assignment_valid(snap, bad)
        assert not _assignment_valid(snap, good[:2])  # truncated
        assert not _assignment_valid(snap, np.zeros((4, 4)))  # wrong rank

    def test_abandoned_worker_skips_fallback_and_state_writes(
        self, monkeypatch
    ):
        """A device phase the watchdog abandoned must not, when it
        finally fails, record a breaker verdict, count a fallback, or
        run the full fallback allocate against the next live cycle."""
        from volcano_tpu.ops import blocked, dispatch, kernels, pallas_session

        snap = _small_snapshot()
        self._force_pallas(monkeypatch)
        floor = dispatch._fallback("pallas", snap)

        def slow_then_fail(s, weights=None):
            time.sleep(0.2)
            raise RuntimeError("late lowering failure")

        ran_fallback = []
        monkeypatch.setattr(pallas_session, "run_outcome_pallas",
                            slow_then_fail)
        monkeypatch.setattr(
            blocked, "run_packed_blocked",
            lambda *a, **k: ran_fallback.append(1) or
            np.full(snap.task_resreq.shape[0], -1, dtype=np.int32),
        )
        monkeypatch.setattr(
            kernels, "run_outcome",
            lambda *a, **k: ran_fallback.append(1) or kernels.ScanOutcome.of_assignment(
                np.full(snap.n_tasks, -1, dtype=np.int32)),
        )
        before = _counter("executor_fallbacks_total",
                          **{"from": "pallas", "to": floor,
                             "cause": "error"})
        with pytest.raises(CycleDeadlineExceeded):
            faults.run_with_deadline(
                lambda: dispatch.run_packed_auto(snap), 0.05, "t"
            )
        time.sleep(0.3)  # let the abandoned worker hit its failure
        assert ran_fallback == []
        assert faults.get_breaker("pallas").state == CLOSED
        assert _counter("executor_fallbacks_total",
                        **{"from": "pallas", "to": floor,
                           "cause": "error"}) == before

    def test_device_slow_injects_latency(self):
        from volcano_tpu.ops import dispatch

        snap = _small_snapshot()
        baseline = dispatch.run_packed_auto(snap)  # warm the jit cache
        faults.configure("seed=1;device.slow=1:count=1:ms=120")
        t0 = time.monotonic()
        out = dispatch.run_packed_auto(snap)
        assert time.monotonic() - t0 >= 0.12
        np.testing.assert_array_equal(out, baseline)


# ---- /healthz degraded ----


class TestHealthzDegraded:
    def test_degraded_reason_in_body(self):
        from volcano_tpu.serving.http import ServingServer

        server = ServingServer(host="127.0.0.1", port=0).start()
        try:
            url = f"http://127.0.0.1:{server.port}/healthz"
            assert urllib.request.urlopen(url).read() == b"ok"
            faults.get_breaker("pallas").record_failure("vmem overflow")
            faults.get_breaker("pallas").record_failure("vmem overflow")
            faults.get_breaker("pallas").record_failure("vmem overflow")
            body = urllib.request.urlopen(url).read().decode()
            assert body.startswith("degraded: ")
            assert "pallas" in body and "vmem overflow" in body
            faults.get_breaker("pallas").record_success()
            assert urllib.request.urlopen(url).read() == b"ok"
        finally:
            server.stop()


# ---- resync queue: bounded retry + poison quarantine ----


class _FlakyClient:
    """get_pod fails ``fail_times`` times, then serves ``pod``."""

    def __init__(self, pod=None, fail_times=10**9):
        self.pod = pod
        self.fail_times = fail_times
        self.calls = 0

    def get_pod(self, namespace, name):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ConnectionError("apiserver unreachable")
        return self.pod

    def watch(self, cache):
        pass


def _cache_with_bound_pod(client):
    node = build_node("n0", {"cpu": "8", "memory": "16Gi"})
    pod = build_pod("ns", "p0", "", {"cpu": "1", "memory": "1Gi"}, group="pg")
    cache = make_cache(
        nodes=[node], pods=[pod],
        pod_groups=[build_pod_group("ns", "pg", 1)],
        queues=[build_queue("default")],
    )
    cache.client = client
    task = next(iter(cache.jobs.values())).tasks[pod.metadata.uid]
    return cache, task, pod


class TestResyncQuarantine:
    def test_dedupe(self):
        cache, task, _ = _cache_with_bound_pod(_FlakyClient())
        cache._RESYNC_BACKOFF_BASE = 0.0
        cache.resync_task(task)
        cache.resync_task(task)
        assert len(cache.err_tasks) + (task.uid in cache.quarantined_tasks) == 1

    def test_bounded_retries_then_quarantine(self):
        client = _FlakyClient()
        cache, task, _ = _cache_with_bound_pod(client)
        cache._RESYNC_BACKOFF_BASE = 0.0
        cache.resync_task(task)  # attempt 1 happens inline
        for _ in range(10):
            cache.process_due_resyncs()
        assert client.calls == cache._RESYNC_MAX_RETRIES
        assert task.uid in cache.quarantined_tasks
        assert cache.err_tasks == []
        key = ("volcano_resync_quarantined_tasks", ())
        assert metrics.registry._gauges[key] >= 1.0
        # quarantined: further resync_task calls don't requeue
        cache.resync_task(task)
        assert cache.err_tasks == []

    def test_fresh_truth_clears_quarantine(self):
        client = _FlakyClient()
        cache, task, pod = _cache_with_bound_pod(client)
        cache._RESYNC_BACKOFF_BASE = 0.0
        cache.resync_task(task)
        for _ in range(10):
            cache.process_due_resyncs()
        assert task.uid in cache.quarantined_tasks
        # the pod's watch event is the quarantine's exit
        cache.update_pod(pod, pod)
        assert task.uid not in cache.quarantined_tasks
        key = ("volcano_resync_quarantined_tasks", ())
        assert metrics.registry._gauges[key] == 0.0

    def test_quarantine_cooldown_reenters_the_queue(self):
        """An unchanged pod never produces the watch event that is the
        quarantine's fast exit — after the cooldown the task re-enters
        the queue with a fresh attempt budget (slow retry lane)."""
        pod = build_pod("ns", "p0", "n0", {"cpu": "1", "memory": "1Gi"},
                        group="pg")
        client = _FlakyClient(pod=pod, fail_times=5)
        cache, task, _ = _cache_with_bound_pod(client)
        cache._RESYNC_BACKOFF_BASE = 0.0
        cache._QUARANTINE_COOLDOWN = 0.05
        cache.resync_task(task)
        for _ in range(10):
            cache.process_due_resyncs()
        assert task.uid in cache.quarantined_tasks
        time.sleep(0.06)
        for _ in range(3):
            cache.process_due_resyncs()
        assert task.uid not in cache.quarantined_tasks
        assert cache.err_tasks == []  # the retry after cooldown succeeded
        assert client.calls == 6

    def test_transient_failure_recovers_before_quarantine(self):
        pod = build_pod("ns", "p0", "n0", {"cpu": "1", "memory": "1Gi"},
                        group="pg")
        client = _FlakyClient(pod=pod, fail_times=2)
        cache, task, _ = _cache_with_bound_pod(client)
        cache._RESYNC_BACKOFF_BASE = 0.0
        cache.resync_task(task)
        for _ in range(5):
            cache.process_due_resyncs()
        assert task.uid not in cache.quarantined_tasks
        assert cache.err_tasks == []
        assert client.calls == 3  # 2 failures + the success

    def test_injected_bind_failure_feeds_resync(self):
        cache, task, _ = _cache_with_bound_pod(_FlakyClient())
        cache.client = None  # keep resync queued, not processed
        faults.configure("seed=1;cache.bind_fail=1:count=1")
        cache.bind(task, "n0")
        cache.flush()
        assert cache.binder.binds == {}  # the injection fired pre-binder
        assert len(cache.err_tasks) == 1

    def test_resync_marks_row_dirty_on_success(self):
        pod = build_pod("ns", "p0", "", {"cpu": "1", "memory": "1Gi"},
                        group="pg")
        client = _FlakyClient(pod=pod, fail_times=0)
        cache, task, _ = _cache_with_bound_pod(client)
        cache.resync_task(task)
        assert task.uid in cache._dirty_tasks


# ---- bus injection points ----


class TestBusFaults:
    def test_force_relist_recovers_via_reconcile(self):
        """A 410-storm (every resume refused) degrades to relists — the
        informer caches still converge, with the relist counter as the
        audit trail."""
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.bus.server import BusServer
        from volcano_tpu.client import APIServer

        api = APIServer()
        server = BusServer(api).start()
        client = RemoteAPIServer(f"tcp://127.0.0.1:{server.port}")
        try:
            assert client.wait_ready(10)
            seen = []
            client.watch("Node", lambda e, old, new: seen.append(e))
            api.create(build_node("n0", {"cpu": "1", "memory": "1Gi"}))
            deadline = time.monotonic() + 5
            while len(seen) < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert seen  # stream live
            before = _counter("bus_relists_total", kind="Node")
            faults.configure("seed=1;bus.force_relist=1:count=1")
            # break the connection so the watch re-establishes (resume →
            # forced 410 → relist); a raw shutdown (not teardown) lets
            # the reader thread observe the loss and trigger reconnect
            client._sock.shutdown(socket.SHUT_RDWR)
            api.create(build_node("n1", {"cpu": "1", "memory": "1Gi"}))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if _counter("bus_relists_total", kind="Node") > before and \
                        len(seen) >= 2:
                    break
                time.sleep(0.02)
            assert _counter("bus_relists_total", kind="Node") > before
            assert len(seen) == 2  # no duplicates, no losses
        finally:
            client.close()
            server.stop()
