"""Anomaly-aware diagnostics plane (ISSUE 19): tail-based trace
retention, the SLO burn-rate watchdog over the in-process metrics
time-series, incident bundles + the cluster capture boost, clock-skew
correction from paired bus spans, and the vtctl top/incidents
surfaces.

The tier-1 cross-process pin runs the scheduler in THIS process
against a real persistent ``vtpu-apiserver`` (carrying a seeded
``bus.delay`` schedule) and a real ``vtpu-controllers`` OS process,
all tail-sampling at 1%: the bus.delay-anomalous trace is kept WHOLE
across all three processes while steady traces drop at the configured
rate, and the chaos twin stays bit-identical with tail mode on."""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time
import zlib

import pytest

from volcano_tpu import faults, obs
from volcano_tpu.apis import core
from volcano_tpu.client import APIServer, VolcanoClient
from volcano_tpu.metrics import metrics
from volcano_tpu.metrics import scrape as mscrape
from volcano_tpu.metrics.timeseries import TimeSeriesRing
from volcano_tpu.obs.channel import SpanExporter
from volcano_tpu.obs.incident import IncidentManager, set_capture_boost
from volcano_tpu.obs.slo import (
    Alert,
    BurnRateWatchdog,
    resolve_slos,
)
from volcano_tpu.obs.tail import TailConfig, TailSampler


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    metrics.registry.reset()
    yield
    obs.disable()
    metrics.registry.reset()
    faults.configure(None)


def _wait(pred, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _coin(tid: str, sample: float = 0.01) -> bool:
    """The channel's head coin, for picking names on a known side."""
    return (zlib.crc32(tid.encode()) % 10_000) < sample * 10_000


def _drop_name(prefix: str, ns: str = "default",
               sample: float = 0.01) -> str:
    """A pod name whose trace id the head coin DROPS at ``sample`` —
    any keep of it must come from anomaly evidence, not the coin."""
    for i in range(100_000):
        name = f"{prefix}{i}"
        if not _coin(obs.trace_id_for(ns, name), sample):
            return name
    raise AssertionError("no coin-dropped name found")


def _rec(tid, name="op", dur=1000.0, sid=None, root=False, args=None,
         ts=1e6):
    r = {"t": tid, "s": sid or f"{tid}:{name}:{dur}", "p": "",
         "name": name, "cat": "span", "ts": ts, "dur": dur, "tid": 1}
    if root:
        r["_root"] = True
    if args:
        r["args"] = dict(args)
    return r


# ---- tail sampler (unit) ----

class TestTailSampler:
    def test_error_tag_keeps_whole_buffer_immediately(self):
        ts = TailSampler(lambda t: False, TailConfig())
        assert ts.offer(_rec("aa", "op", 1000.0)) == []
        out = ts.offer(_rec("aa", "op", 1000.0, sid="a2",
                            args={"error": "RuntimeError"}))
        assert [r["s"] for r in out] == ["aa:op:1000.0", "a2"]
        assert ts.kept_traces == 1 and ts.anomaly_keeps == 1
        assert ts.keep("aa")
        # decided traces bypass the pool entirely from now on
        assert ts.offer(_rec("aa", "op", 5.0, sid="a3")) != []
        assert ts.drain_decisions() == {"aa": True}
        assert ts.drain_decisions() == {}
        r = metrics.registry.render()
        assert ('volcano_telemetry_tail_decisions_total'
                '{result="keep"} 1') in r

    def test_fallback_and_degraded_tags_are_anomalies(self):
        ts = TailSampler(lambda t: False, TailConfig())
        assert ts.offer(_rec("f1", args={"fallback": "gang-arrival"}))
        assert ts.offer(_rec("d1", args={"degraded": "breaker"}))
        assert ts.anomaly_keeps == 2

    def test_duration_floor_breach_keeps(self):
        ts = TailSampler(lambda t: False, TailConfig())  # floor 25 ms
        assert ts.offer(_rec("slow", "rpc", dur=30_000.0)) != []
        assert ts.offer(_rec("fast", "rpc", dur=10_000.0)) == []
        assert ts.anomaly_keeps == 1 and ts.pending_count() == 1

    def test_threshold_seeds_from_windowed_p99_per_kind(self):
        cfg = TailConfig(min_kind_samples=16)
        ts = TailSampler(lambda t: False, cfg)
        # 40 steady ~10ms observations of "rpc": once past the warmup
        # the threshold is 4 x p99 = 40ms, no longer the 25ms floor
        for i in range(40):
            assert ts.offer(_rec(f"w{i}", "rpc", dur=10_000.0)) == []
        assert ts.offer(_rec("x1", "rpc", dur=30_000.0)) == []  # < 4xp99
        assert ts.offer(_rec("x2", "rpc", dur=41_000.0)) != []  # breach
        # a different span kind still sits at its floor
        assert ts.offer(_rec("y1", "other", dur=30_000.0)) != []
        assert ts.anomaly_keeps == 2

    def test_coin_decides_at_settle(self):
        ts = TailSampler(lambda t: t == "keep", TailConfig(settle_s=0.0))
        ts.offer(_rec("keep", root=True))
        ts.offer(_rec("drop", root=True))
        out = ts.sweep()
        assert {r["t"] for r in out} == {"keep"}
        assert ts.kept_traces == 1 and ts.dropped_traces == 1
        assert ts.keep("keep") and not ts.keep("drop")
        # memoized DROP suppresses later spans of the trace
        assert ts.offer(_rec("drop", sid="late")) == []
        assert ts.drain_decisions() == {"keep": True, "drop": False}
        r = metrics.registry.render()
        assert 'volcano_telemetry_tail_decisions_total{result="drop"} 1' in r

    def test_rootless_trace_never_settles_by_coin(self):
        ts = TailSampler(lambda t: True, TailConfig(settle_s=0.0))
        ts.offer(_rec("orphan"))  # no root landed
        assert ts.sweep() == []
        assert ts.pending_count() == 1

    def test_runaway_trace_evicts_pool_full(self):
        cfg = TailConfig(max_spans_per_trace=4)
        ts = TailSampler(lambda t: False, cfg)
        for i in range(4):
            ts.offer(_rec("big", sid=f"b{i}"))
        assert ts.offer(_rec("big", sid="b4")) == []  # head coin drops
        assert ts.evicted_traces == 1 and not ts.keep("big")
        r = metrics.registry.render()
        assert ('volcano_telemetry_tail_evictions_total'
                '{reason="pool-full"} 1') in r

    def test_pool_overflow_evicts_oldest_with_head_decision(self):
        cfg = TailConfig(max_traces=2)
        keep_first = lambda t: t == "t1"  # noqa: E731
        ts = TailSampler(keep_first, cfg)
        ts.offer(_rec("t1", sid="s1"))
        ts.offer(_rec("t2", sid="s2"))
        out = ts.offer(_rec("t3", sid="s3"))
        # t1 was evicted for room — head decision kept its spans
        assert [r["s"] for r in out] == ["s1"]
        assert ts.pending_count() == 2 and ts.evicted_traces == 1
        assert ts.keep("t1")

    def test_never_completed_trace_times_out_to_head_decision(self):
        ts = TailSampler(lambda t: False,
                         TailConfig(pending_timeout_s=0.0))
        ts.offer(_rec("stuck"))
        assert ts.sweep() == []
        assert ts.evicted_traces == 1 and not ts.keep("stuck")
        r = metrics.registry.render()
        assert ('volcano_telemetry_tail_evictions_total'
                '{reason="timeout"} 1') in r

    def test_apply_remote_resolves_pending_both_ways(self):
        ts = TailSampler(lambda t: False, TailConfig())
        ts.offer(_rec("r1", sid="r1a"))
        out = ts.apply_remote({"r1": True})
        assert [r["s"] for r in out] == ["r1a"]
        assert ts.keep("r1")
        # remote decisions are memoized, never re-published (no echo)
        assert ts.drain_decisions() == {}
        ts.offer(_rec("r2"))
        assert ts.apply_remote({"r2": False}) == []
        assert not ts.keep("r2")

    def test_local_anomaly_keep_beats_remote_coin_drop(self):
        ts = TailSampler(lambda t: False, TailConfig())
        ts.offer(_rec("ev", args={"error": "X"}))
        assert ts.keep("ev")
        ts.apply_remote({"ev": False})
        assert ts.keep("ev"), "evidence-keep must survive a remote drop"

    def test_decision_memo_is_bounded(self):
        ts = TailSampler(lambda t: False, TailConfig(decision_memo=64))
        ts.apply_remote({f"m{i}": False for i in range(70)})
        assert len(ts._decided) == 64


# ---- exporter integration (tail mode on the real channel) ----

class TestTailExporter:
    def _tail_exporter(self, api, sample=0.01, **cfg):
        exp = obs.enable(api, identity="d0", sample=sample,
                         flush_interval=3600, tail=True)
        exp.tail = TailSampler(exp._coin, TailConfig(**cfg))
        return exp

    def test_steady_trace_dropped_anomalous_kept_whole(self):
        api = APIServer()
        exp = self._tail_exporter(api, settle_s=0.0)
        tid_s = obs.trace_id_for("default", _drop_name("steady-"))
        with obs.span("bind", cat="scheduler", trace_id=tid_s):
            with obs.span("child"):
                pass
        exp.tick()  # sweep settles by coin (drop), then flush
        assert tid_s not in {s.get("t") for s in obs.collect_spans(api)}
        assert exp.tail.dropped_traces == 1

        tid_a = obs.trace_id_for("default", _drop_name("anom-"))
        with pytest.raises(RuntimeError):
            with obs.span("bind", cat="scheduler", trace_id=tid_a):
                with obs.span("child"):
                    raise RuntimeError("boom")
        exp.tick()
        sel = [s for s in obs.collect_spans(api) if s.get("t") == tid_a]
        assert {s["name"] for s in sel} == {"bind", "child"}
        assert any(s.get("args", {}).get("error") == "RuntimeError"
                   for s in sel)
        # the transient completion marker never reaches the bus
        assert not any("_root" in s for s in obs.collect_spans(api))
        assert exp.tail.anomaly_keeps >= 1

    def test_slow_span_duration_is_an_anomaly(self):
        api = APIServer()
        exp = self._tail_exporter(api, settle_s=0.0, floor_ms=5.0)
        tid = obs.trace_id_for("default", _drop_name("slow-"))
        with obs.span("bind", cat="scheduler", trace_id=tid):
            time.sleep(0.02)
        exp.tick()
        assert tid in {s.get("t") for s in obs.collect_spans(api)}

    def test_decisions_propagate_between_exporters(self):
        api = APIServer()
        e1 = SpanExporter(api, "d1", sample=0.01, flush_interval=3600,
                          tail=True)
        e2 = SpanExporter(api, "d2", sample=0.01, flush_interval=3600,
                          tail=True)
        e1.tail = TailSampler(e1._coin, TailConfig(settle_s=0.0))
        e2.tail = TailSampler(e2._coin, TailConfig(settle_s=0.0))
        t_keep = obs.trace_id_for("default", _drop_name("xk-"))
        t_drop = obs.trace_id_for("default", _drop_name("xd-"))
        # d2 holds child spans it cannot decide (rootless, no anomaly)
        e2.emit(_rec(t_keep, "bus:bind", sid="d2-k"))
        e2.emit(_rec(t_drop, "wal:fsync", sid="d2-d"))
        # d1 holds the evidence for one and settles the other by coin
        e1.emit(_rec(t_keep, "bind", sid="d1-k", args={"error": "X"}))
        e1.emit(_rec(t_drop, "bind:landed", sid="d1-d", root=True))
        e1.tick()  # sweep + publish vtpu-tail-d1 + flush
        e2.tick()  # apply peer decisions, ship resolved spans
        sids = {s["s"] for s in obs.collect_spans(api)}
        assert {"d1-k", "d2-k"} <= sids
        assert "d2-d" not in sids and "d1-d" not in sids
        assert e2.tail.keep(t_keep) and not e2.tail.keep(t_drop)
        assert e2.tail.pending_count() == 0

    def test_capture_boost_keeps_everything_and_polls(self):
        api = APIServer()
        exp = self._tail_exporter(api, sample=0.0, settle_s=0.0)
        tid = obs.trace_id_for("default", "boosted-pod")
        exp.set_boost({"until": time.time() + 30, "by": "t",
                       "reason": "test", "ts": time.time()})
        assert exp.boost_active() and exp.keep(tid)
        assert "volcano_capture_boost_active 1" in metrics.registry.render()
        with obs.span("bind", cat="scheduler", trace_id=tid):
            pass
        # boosted spans bypass the pending pool entirely
        assert exp.tail.pending_count() == 0
        # the poll finds no cluster record backing the local boost —
        # the CM is authoritative, so the cache clears on the beat
        exp.tick()
        assert tid in {s.get("t") for s in obs.collect_spans(api)}
        assert not exp.boost_active()
        assert "volcano_capture_boost_active 0" in metrics.registry.render()
        with obs.span("bind2", cat="scheduler", trace_id=tid + "x"):
            pass
        assert exp.tail.pending_count() == 1  # back to buffering

    def test_flusher_poll_picks_up_cluster_boost_record(self):
        api = APIServer()
        exp = self._tail_exporter(api, sample=0.0)
        assert not exp.boost_active()
        set_capture_boost(api, "vtctl", "manual", ttl_s=30.0)
        exp.tick()  # poll beat
        assert exp.boost_active()
        rec = exp.boost_record()
        assert rec["reason"] == "manual" and rec["by"] == "vtctl"
        # an expired record ages out on the next poll
        cm = api.get("ConfigMap", obs.NAMESPACE, obs.BOOST_NAME)
        cm.data = {obs.BOOST_KEY: json.dumps(
            {"until": time.time() - 1, "by": "vtctl", "reason": "manual"})}
        api.update(cm)
        exp.tick()
        assert not exp.boost_active()


# ---- capture boost CAS ----

class TestCaptureBoostCAS:
    def test_never_shortens_a_live_boost(self):
        api = APIServer()
        b1 = set_capture_boost(api, "a", "r1", ttl_s=100.0, now=1000.0)
        assert b1["until"] == 1100.0
        # a shorter re-trigger keeps the existing record
        b2 = set_capture_boost(api, "b", "r2", ttl_s=10.0, now=1005.0)
        assert b2["by"] == "a" and b2["until"] == 1100.0
        # a later expiry extends it
        b3 = set_capture_boost(api, "b", "r2", ttl_s=300.0, now=1010.0)
        assert b3["until"] == 1310.0
        rec = json.loads(api.get(
            "ConfigMap", obs.NAMESPACE, obs.BOOST_NAME
        ).data[obs.BOOST_KEY])
        assert rec["until"] == 1310.0 and rec["by"] == "b"


# ---- metrics time-series ring ----

class TestTimeSeriesRing:
    def test_windowed_delta_and_dump(self):
        ring = TimeSeriesRing()
        metrics.register_commit_failure("io")
        ring.tick(now=1000.0)
        for _ in range(30):
            metrics.register_commit_failure("io")
        ring.tick(now=1030.0)
        w = ring.window(60.0, now=1030.0)
        assert w is not None
        assert w.value("volcano_commit_failures_total") == 30.0
        # no sample old enough inside a 10s window
        assert ring.window(10.0, now=1030.0) is None
        assert len(ring) == 2 and ring.span_seconds() == 30.0
        dump = ring.dump()
        assert len(dump) == 2
        assert "volcano_commit_failures_total" in dump[1][1]

    def test_single_sample_has_no_window(self):
        ring = TimeSeriesRing()
        ring.tick(now=1.0)
        assert ring.window(60.0, now=1.0) is None

    def test_capacity_bounds_the_ring(self):
        ring = TimeSeriesRing(capacity=4)
        for i in range(10):
            ring.tick(now=float(i))
        assert len(ring) == 4


# ---- burn-rate watchdog ----

class TestBurnRateWatchdog:
    def test_breach_fires_once_then_clears(self):
        fired = []
        ring = TimeSeriesRing()
        wd = BurnRateWatchdog(
            ring, slos=resolve_slos("submit-bind-p99=50"),
            fast_window_s=60.0, slow_window_s=300.0,
            on_breach=fired.append,
        )
        ring.tick(now=1000.0)
        for _ in range(50):
            metrics.observe_submit_to_bind(0.5)  # 500ms against 50ms
        alerts = wd.run_once(now=1030.0)
        assert [a.name for a in alerts] == ["submit-bind-p99"]
        assert len(fired) == 1
        assert fired[0].burn_fast >= 1.0 and fired[0].burn_slow >= 1.0
        assert fired[0].value > 50.0
        assert wd.degraded_reasons() == ["slo-burn:submit-bind-p99"]
        r = metrics.registry.render()
        assert 'volcano_slo_burn{slo="submit-bind-p99",window="fast"}' in r
        # still breaching: edge-triggered, no second capture
        wd.run_once(now=1035.0)
        assert len(fired) == 1 and wd.breaches == 1
        # signal stops: the fast window empties, the alert clears
        ring.tick(now=1100.0)
        assert wd.evaluate(now=1100.0) == []
        assert wd.active_alerts() == [] and wd.degraded_reasons() == []

    def test_fast_spike_without_slow_confirmation_is_noise(self):
        ring = TimeSeriesRing()
        slos = [s for s in resolve_slos("")
                if s.name == "commit-failures"]
        wd = BurnRateWatchdog(ring, slos=slos, fast_window_s=60.0,
                              slow_window_s=300.0)
        ring.tick(now=1000.0)
        for _ in range(30):
            metrics.register_commit_failure("io")
        ring.tick(now=1030.0)
        assert wd.evaluate(now=1030.0) == []
        s = mscrape.parse_metrics(metrics.registry.render())
        burns = {
            dict(ls)["window"]: v
            for (n, ls), v in s.series.items() if n == "volcano_slo_burn"
        }
        # 30 failures / 60s = 0.5/s -> burn 2.5 fast; /300s -> 0.5 slow
        assert burns["fast"] == pytest.approx(2.5)
        assert burns["slow"] == pytest.approx(0.5)

    def test_gauge_slo_takes_max_not_sum(self):
        from volcano_tpu.obs.slo import _gauge_max

        s = mscrape.parse_metrics(
            'volcano_circuit_breaker_open{name="a"} 0.5\n'
            'volcano_circuit_breaker_open{name="b"} 0.5\n'
        )
        # Scrape.value would sum to 1.0 and fake a tripped breaker
        assert s.value("volcano_circuit_breaker_open") == 1.0
        assert _gauge_max(s, "volcano_circuit_breaker_open", {}) == 0.5

    def test_resolve_slos_overrides_known_ignores_garbage(self):
        slos = {s.name: s for s in resolve_slos(
            "submit-bind-p99=50, bogus=1, micro-cycle-p99=abc")}
        assert slos["submit-bind-p99"].objective == 50.0
        assert slos["micro-cycle-p99"].objective == 250.0
        assert "bogus" not in slos
        assert set(slos) == {s.name for s in resolve_slos("")}

    def test_alert_to_dict_is_stored_fields_only(self):
        a = Alert("x", 1.23456, 2.0, 3.0, 4.0, 100.0)
        d = a.to_dict()
        assert d == {"name": "x", "burnFast": 1.2346, "burnSlow": 2.0,
                     "value": 3.0, "objective": 4.0, "since": 100.0}


# ---- incident manager ----

class TestIncidentManager:
    def _manager(self, api, tmp_path, **kw):
        ring = TimeSeriesRing()
        ring.tick(now=1.0)
        ring.tick(now=2.0)
        kw.setdefault("settle_s", 0.0)
        return IncidentManager(api, "d0", str(tmp_path / "inc"),
                               metrics_ring=ring, **kw)

    def _bundles(self, tmp_path):
        d = tmp_path / "inc"
        return sorted(p.name for p in d.iterdir()) if d.exists() else []

    def test_breach_writes_one_bundle_and_arms_the_boost(self, tmp_path):
        api = APIServer()
        exp = obs.enable(api, identity="d0", flush_interval=3600)
        with obs.span("bind:landed", cat="scheduler",
                      trace_id="ff00aa11"):
            pass
        exp.flush_all()
        mgr = self._manager(api, tmp_path, cooldown_s=60.0,
                            boost_ttl_s=30.0)
        alert = Alert("submit-bind-p99", 12.7, 3.2, 636.8, 50.0, 1030.0)
        mgr.on_alert(alert)  # settle 0 -> synchronous capture
        bundles = self._bundles(tmp_path)
        assert len(bundles) == 1
        assert not any(b.startswith(".tmp") for b in bundles)
        bdir = tmp_path / "inc" / bundles[0]
        meta = json.loads((bdir / "meta.json").read_text())
        assert meta["reason"] == "slo-burn:submit-bind-p99"
        assert meta["alerts"][0]["name"] == "submit-bind-p99"
        assert meta["boost"]["reason"] == "slo-burn:submit-bind-p99"
        assert meta["errors"] == {}
        assert {"spans.json", "bus_status.json", "shard_map.json",
                "metrics.jsonl", "meta.json"} <= set(meta["files"])
        spans = json.loads((bdir / "spans.json").read_text())
        assert any(s["name"] == "bind:landed" for s in spans)
        assert meta["spanCount"] == len(spans)
        # the boost record reached the bus; the local exporter boosted
        # without waiting a poll tick
        rec = json.loads(api.get(
            "ConfigMap", obs.NAMESPACE, obs.BOOST_NAME
        ).data[obs.BOOST_KEY])
        assert rec["reason"] == "slo-burn:submit-bind-p99"
        assert exp.boost_active()
        # a re-fire inside the cooldown re-arms the boost, no 2nd bundle
        mgr.on_alert(alert)
        assert len(self._bundles(tmp_path)) == 1
        assert mgr.captured == 1 and mgr.suppressed_triggers == 1
        r = metrics.registry.render()
        assert "volcano_incidents_captured_total" in r
        # the published summary is fleet-readable
        recs = obs.list_incidents(api)
        assert len(recs) == 1
        assert recs[0]["object"].startswith("vtpu-incident-d0-")
        assert recs[0]["meta"]["reason"] == meta["reason"]
        assert any(s["name"] == "bind:landed" for s in recs[0]["spans"])

    def test_distinct_triggers_are_independent_episodes(self, tmp_path):
        api = APIServer()
        mgr = self._manager(api, tmp_path, cooldown_s=60.0)
        mgr.trigger("breaker-open", sync=True)
        mgr.trigger("drift-divergence", sync=True)
        assert len(self._bundles(tmp_path)) == 2
        assert mgr.suppressed_triggers == 0

    def test_bundle_ring_prunes_oldest(self, tmp_path):
        api = APIServer()
        mgr = self._manager(api, tmp_path, ring=2, cooldown_s=0.0)
        for i in range(4):
            mgr.capture(f"t{i}")
        bundles = self._bundles(tmp_path)
        assert len(bundles) == 2
        assert bundles[-1].endswith("-t3")

    def test_capture_survives_missing_sources(self, tmp_path):
        class _BrokenAPI:
            def list(self, *a, **k):
                raise RuntimeError("bus down")

            def get(self, *a, **k):
                raise RuntimeError("bus down")

            def create(self, *a, **k):
                raise RuntimeError("bus down")

        mgr = IncidentManager(_BrokenAPI(), "d0", str(tmp_path / "inc"),
                              settle_s=0.0,
                              journal_dir=str(tmp_path / "nope"))
        path = mgr.capture("manual")
        meta = json.loads(
            (tmp_path / "inc" / os.path.basename(path) /
             "meta.json").read_text())
        assert "spans.json" in meta["errors"]
        assert meta["reason"] == "manual"


# ---- vtctl surfaces ----

class TestVtctlIncidents:
    def _seed_incident(self, api, tmp_path):
        exp = obs.enable(api, identity="d0", flush_interval=3600)
        with obs.span("bind:landed", cat="scheduler",
                      trace_id="ff00aa11"):
            pass
        exp.flush_all()
        mgr = IncidentManager(api, "d0", str(tmp_path / "inc"),
                              settle_s=0.0)
        mgr.capture("slo-burn:submit-bind-p99", alerts=[
            {"name": "submit-bind-p99", "burnFast": 2.0}])

    def test_list_show_collect(self, tmp_path):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        api = APIServer()
        self._seed_incident(api, tmp_path)
        out = io.StringIO()
        assert vtctl_main(["incidents", "list"], api=api, out=out) == 0
        text = out.getvalue()
        assert "TRIGGER" in text and "slo-burn:submit-bind-p99" in text
        assert "d0" in text

        out = io.StringIO()
        assert vtctl_main(["incidents", "show"], api=api, out=out) == 0
        text = out.getvalue()
        assert '"reason": "slo-burn:submit-bind-p99"' in text
        assert "bind:landed" in text  # the breach-window waterfall

        out = io.StringIO()
        dest = tmp_path / "got"
        assert vtctl_main(
            ["incidents", "collect", "--out", str(dest)],
            api=api, out=out,
        ) == 0
        files = list(dest.iterdir())
        assert len(files) == 1
        rec = json.loads(files[0].read_text())
        assert rec["meta"]["identity"] == "d0"

        # the singular alias routes identically
        out = io.StringIO()
        assert vtctl_main(["incident", "list"], api=api, out=out) == 0
        assert "slo-burn:submit-bind-p99" in out.getvalue()

    def test_empty_store_list_ok_show_errors(self, tmp_path):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        api = APIServer()
        out = io.StringIO()
        assert vtctl_main(["incidents", "list"], api=api, out=out) == 0
        assert "no incident bundles" in out.getvalue()
        out = io.StringIO()
        assert vtctl_main(["incidents", "show"], api=api, out=out) == 1

    def test_operator_capture_boosts_then_bundles(self, tmp_path):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        api = APIServer()
        out = io.StringIO()
        rc = vtctl_main(
            ["incidents", "capture", "--dir", str(tmp_path / "inc"),
             "--settle", "0"],
            api=api, out=out,
        )
        assert rc == 0
        assert "bundle:" in out.getvalue()
        assert len(list((tmp_path / "inc").iterdir())) == 1
        rec = json.loads(api.get(
            "ConfigMap", obs.NAMESPACE, obs.BOOST_NAME
        ).data[obs.BOOST_KEY])
        assert rec["reason"] == "manual" and rec["by"] == "vtctl"
        # and it is now fleet-visible
        out = io.StringIO()
        assert vtctl_main(["incidents", "list"], api=api, out=out) == 0
        assert "manual" in out.getvalue()


class TestVtctlTopBurn:
    def _cluster(self, burn_a=0.4, burn_b=2.5):
        from volcano_tpu.federation.leases import (
            NAMESPACE as SM_NS,
            SHARD_MAP_KEY,
            SHARD_MAP_NAME,
        )
        from volcano_tpu.metrics.metrics import _Registry
        from volcano_tpu.serving.http import ServingServer

        servers = []
        for ident, burn in (("shard-a", burn_a), ("shard-b", burn_b)):
            reg = _Registry()
            reg.set_identity(daemon="scheduler", shard=ident)
            h = reg.histogram(
                "volcano_submit_to_bind_latency_milliseconds", {},
                buckets=[5.0, 10.0, 20.0],
            )
            for v in (4.0, 8.0, 16.0):
                h.observe(v)
            reg.inc("volcano_pod_schedule_successes", {}, 3)
            reg.set_gauge("volcano_slo_burn",
                          {"slo": "submit-bind-p99", "window": "fast"},
                          burn)
            reg.set_gauge("volcano_slo_burn",
                          {"slo": "submit-bind-p99", "window": "slow"},
                          burn * 10)  # slow must not leak into BURN
            servers.append(ServingServer(registry=reg).start())
        api = APIServer()
        rec = {
            "nShards": 2, "members": {}, "shards": {},
            "stats": {
                "shard-a": {"metricsAddr": f"127.0.0.1:{servers[0].port}"},
                "shard-b": {"metricsAddr": f"127.0.0.1:{servers[1].port}"},
            },
        }
        api.create(core.ConfigMap(
            metadata=core.ObjectMeta(name=SHARD_MAP_NAME, namespace=SM_NS),
            data={SHARD_MAP_KEY: json.dumps(rec)},
        ))
        return api, servers

    def test_burn_column_and_json(self):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        api, servers = self._cluster()
        try:
            out = io.StringIO()
            assert vtctl_main(["top"], api=api, out=out) == 0
            text = out.getvalue()
            assert "BURN" in text
            line_b = next(l for l in text.splitlines() if "shard-b" in l)
            assert "2.50" in line_b

            out = io.StringIO()
            assert vtctl_main(["top", "--json"], api=api, out=out) == 0
            doc = json.loads(out.getvalue())
            members = doc["members"]
            assert members["shard-a"]["burn"] == pytest.approx(0.4)
            assert members["shard-b"]["burn"] == pytest.approx(2.5)
            # cluster burn is the fleet MAX (a sum would dilute or
            # double-count a single burning member)
            assert doc["cluster"]["burn"] == pytest.approx(2.5)
            assert doc["cluster"]["binds"] == 6
        finally:
            for s in servers:
                s.stop()

    def test_watch_emits_bounded_frames(self):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        api, servers = self._cluster()
        try:
            out = io.StringIO()
            rc = vtctl_main(
                ["top", "--watch", "0.01", "--count", "2"],
                api=api, out=out,
            )
            assert rc == 0
            assert out.getvalue().count("CLUSTER") == 2
        finally:
            for s in servers:
                s.stop()


# ---- clock-skew correction ----

def _busspan(sid, name, daemon, pid, ts, dur, parent="", cat="bus",
             tid="tt00tt00"):
    return {"t": tid, "s": sid, "p": parent, "name": name, "cat": cat,
            "daemon": daemon, "pid": pid, "ts": ts, "dur": dur}


class TestClockSkew:
    def test_estimates_offset_from_rtt_midpoints(self):
        # the anchor is the earliest process in the trace: the client
        client = _busspan("A1", "bus:bind", "sched", 11,
                          1_000_000.0, 10_000.0)
        # server's clock runs 50ms AHEAD: symmetric rpc -> the span
        # midpoints name the same instant on two clocks
        server = _busspan("B1", "bus:bind", "api", 22,
                          1_052_000.0, 6_000.0, parent="A1")
        offs = obs.estimate_skew([client, server])
        assert offs[("sched", 11)] == 0.0
        assert offs[("api", 22)] == pytest.approx(-50_000.0)
        fixed = {s["s"]: s for s in obs.apply_skew([client, server], offs)}
        assert fixed["B1"]["ts"] == pytest.approx(1_002_000.0)
        assert fixed["A1"]["ts"] == 1_000_000.0

    def test_median_rejects_asymmetric_outlier(self):
        spans = [_busspan("A1", "bus:get", "sched", 11,
                          1_000_000.0, 10_000.0)]  # unpaired: ignored
        for i, off in enumerate((50_000.0, 50_000.0, 950_000.0)):
            spans.append(_busspan(f"C{i}", "bus:get", "sched", 11,
                                  1_000_000.0 + i, 10_000.0))
            spans.append(_busspan(f"S{i}", "bus:get", "api", 22,
                                  1_005_000.0 + i + off - 3_000.0,
                                  6_000.0, parent=f"C{i}"))
        offs = obs.estimate_skew(spans)
        assert offs[("api", 22)] == pytest.approx(-50_000.0)

    def test_chained_hops_propagate_from_anchor(self):
        spans = [
            _busspan("A1", "bus:bind", "sched", 11, 1_000_000.0, 10_000.0),
            # api runs 50ms ahead of the anchor
            _busspan("B1", "bus:bind", "api", 22, 1_052_000.0, 6_000.0,
                     parent="A1"),
            # api -> ctrl hop: ctrl runs a further 20ms ahead of api
            _busspan("B2", "bus:status", "api", 22, 1_060_000.0, 8_000.0),
            _busspan("D1", "bus:status", "ctrl", 33, 1_082_000.0, 4_000.0,
                     parent="B2"),
        ]
        offs = obs.estimate_skew(spans)
        assert offs[("sched", 11)] == 0.0
        assert offs[("api", 22)] == pytest.approx(-50_000.0)
        # ctrl offset composes through the api hop
        assert offs[("ctrl", 33)] == pytest.approx(-70_000.0)

    def test_no_pairs_no_correction(self):
        spans = [
            _busspan("A1", "cycle", "sched", 11, 0.0, 10.0, cat="scheduler"),
            _busspan("A2", "bind", "sched", 11, 1.0, 2.0, parent="A1",
                     cat="scheduler"),
        ]
        assert obs.estimate_skew(spans) == {}
        out = io.StringIO()
        obs.render_waterfall(spans, out)
        assert "clock skew corrected" not in out.getvalue()

    def test_waterfall_reports_and_applies_correction(self):
        client = _busspan("A1", "bus:bind", "sched", 11,
                          1_000_000.0, 10_000.0)
        server = _busspan("B1", "bus:bind", "api", 22,
                          1_052_000.0, 6_000.0, parent="A1")
        out = io.StringIO()
        obs.render_waterfall([client, server], out)
        text = out.getvalue()
        assert "clock skew corrected" in text
        assert "api/22 -50.00ms" in text
        # skew={} disables the estimate: raw wall clocks, no header
        out = io.StringIO()
        obs.render_waterfall([client, server], out, skew={})
        assert "clock skew corrected" not in out.getvalue()

    def test_remote_client_emits_paired_bus_span(self):
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.bus.server import BusServer

        store = APIServer()
        srv = BusServer(store).start()
        remote = RemoteAPIServer(f"tcp://127.0.0.1:{srv.port}",
                                 timeout=5.0)
        try:
            assert remote.wait_ready(5)
            exp = obs.enable(remote, identity="cli-0",
                             flush_interval=3600)
            with obs.span("outer", trace_id="aabbccdd") as outer:
                remote.get("Pod", "default", "nope")
            exp.flush_all()
            spans = [s for s in obs.collect_spans(remote)
                     if s["name"] == "bus:get"]
            # one client-side + one server-side span, linked, same name
            assert len(spans) == 2
            by_parent = {s["p"]: s for s in spans}
            client = by_parent[outer.span_id]
            server = by_parent[client["s"]]
            assert client["cat"] == "bus" and server["cat"] == "bus"
            assert client["t"] == server["t"] == "aabbccdd"
            assert client["dur"] >= server["dur"]
        finally:
            obs.disable()
            remote.close()
            srv.stop()

    def test_remote_client_span_records_error(self):
        from volcano_tpu.bus.remote import RemoteAPIServer
        from volcano_tpu.bus.server import BusServer

        store = APIServer()
        store.create(core.ConfigMap(metadata=core.ObjectMeta(
            name="dup", namespace="default")))
        srv = BusServer(store).start()
        remote = RemoteAPIServer(f"tcp://127.0.0.1:{srv.port}",
                                 timeout=5.0)
        try:
            assert remote.wait_ready(5)
            exp = obs.enable(remote, identity="cli-0",
                             flush_interval=3600)
            with obs.span("outer", trace_id="aabbccdd"):
                with pytest.raises(Exception):
                    remote.create(core.ConfigMap(metadata=core.ObjectMeta(
                        name="dup", namespace="default")))
            exp.flush_all()
            clients = [s for s in obs.collect_spans(remote)
                       if s["name"] == "bus:create"
                       and "error" in (s.get("args") or {})]
            assert clients, "client bus span must tag the failed rpc"
        finally:
            obs.disable()
            remote.close()
            srv.stop()


# ---- the 3-OS-process retention pin (tier-1) ----

def _spawn_env(extra_env, module, *args):
    return subprocess.Popen(
        [sys.executable, "-m", module, *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env={**os.environ, **extra_env, "JAX_PLATFORMS": "cpu"},
    )


class TestTailRetentionThreeProcesses:
    def test_bus_delay_anomalous_trace_kept_whole(self, tmp_path,
                                                  monkeypatch):
        """Scheduler (this process) + persistent apiserver carrying a
        seeded ``bus.delay`` schedule + controllers, every exporter
        tail-sampling at 1%: the trace that catches a delayed rpc is
        kept WHOLE across all three processes (completion-time
        decisions propagate through ``vtpu-tail-*``), while steady
        traces drop at the configured rate."""
        from volcano_tpu.apis import batch
        from volcano_tpu.bus import connect_bus
        from volcano_tpu.cache import SchedulerCache
        from volcano_tpu.client import SchedulerClient
        from volcano_tpu.cmd.local_up import seed_cluster
        from volcano_tpu.scheduler.scheduler import Scheduler

        port = _free_port()
        bus_url = f"tcp://127.0.0.1:{port}"
        # children: tail on at 1%, 60ms duration floor.  Their pod
        # spans are ROOTLESS halves (adopt() never roots), so they hold
        # them pending and resolve via the root owner's published
        # decision; settle/timeout sit far beyond the test horizon so
        # the only decisions in play are evidence-driven
        child_env = {
            "VTPU_TELEMETRY_SAMPLE": "0.01",
            "VTPU_TELEMETRY_TAIL": "1",
            "VTPU_TAIL_FLOOR_MS": "60",
            "VTPU_TAIL_SETTLE": "3600",
            "VTPU_TAIL_TIMEOUT": "3600",
        }
        procs = [_spawn_env(
            child_env, "volcano_tpu.cmd.apiserver",
            "--port", str(port), "--listen-port", "0",
            "--data-dir", str(tmp_path / "wal"),
            "--flight-recorder",
            "--faults", "seed=5;bus.delay=0.25:ms=120",
        )]
        # this process: same floor, but never settle/evict locally —
        # only anomaly evidence (or a peer) may decide, so the probe
        # below is deterministic
        monkeypatch.setenv("VTPU_TAIL_FLOOR_MS", "60")
        monkeypatch.setenv("VTPU_TAIL_SETTLE", "3600")
        monkeypatch.setenv("VTPU_TAIL_TIMEOUT", "3600")
        api = sched_remote = None
        cache = None
        try:
            api = connect_bus(bus_url, wait=30.0)
            seed_cluster(api, nodes=2, node_cpu="16", node_mem="32Gi")
            procs.append(_spawn_env(
                child_env, "volcano_tpu.cmd.controllers",
                "--bus", bus_url, "--listen-port", "0",
                "--period", "0.05", "--flight-recorder",
                "--leader-elect-id", "ctrl-0",
            ))
            sched_remote = connect_bus(bus_url, wait=10.0)
            exp = obs.enable(sched_remote, identity="sched-0",
                             flush_interval=0.05, sample=0.01,
                             tail=True)
            cache = SchedulerCache(client=SchedulerClient(sched_remote),
                                   scheduler_name="volcano-tpu")
            scheduler = Scheduler(cache, period=0.05)
            cache.run()
            cache.wait_for_cache_sync()

            # a job whose every pod trace the 1% coin DROPS: any keep
            # below is anomaly-driven by construction
            replicas = 6
            job = next(
                f"st{i}" for i in range(100_000)
                if not any(_coin(obs.trace_id_for(
                    "default", f"st{i}-t-{k}")) for k in range(replicas))
            )
            VolcanoClient(api).create_job(batch.Job(
                metadata=core.ObjectMeta(name=job, namespace="default"),
                spec=batch.JobSpec(
                    min_available=replicas, queue="default",
                    scheduler_name="volcano-tpu",
                    tasks=[batch.TaskSpec(
                        name="t", replicas=replicas,
                        template=core.PodTemplateSpec(spec=core.PodSpec(
                            containers=[core.Container(
                                name="c", image="busybox",
                                resources={"requests": {
                                    "cpu": "1", "memory": "1Gi"}},
                            )],
                        )),
                    )],
                ),
            ))

            def all_bound():
                scheduler.run_once()
                return all(
                    (p := api.get("Pod", "default", f"{job}-t-{k}"))
                    is not None and bool(p.spec.node_name)
                    for k in range(replicas)
                )

            assert _wait(all_bound, timeout=90.0, interval=0.1), (
                "pods never bound over the faulted 3-process topology"
            )

            # probe a (still-undecided) trace with real rpcs until the
            # apiserver's seeded bus.delay lands one: the client-side
            # bus:get span then breaches the 60ms floor and the whole
            # pending buffer for that trace is kept + published
            anom = f"{job}-t-0"

            def probe_until_delayed(tid):
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    t0 = time.perf_counter()
                    with obs.span("probe:get", cat="probe",
                                  trace_id=tid):
                        sched_remote.get("Pod", "default", anom)
                    if time.perf_counter() - t0 >= 0.1:
                        return True
                return False

            tid = obs.trace_id_for("default", anom)
            assert probe_until_delayed(tid), (
                "seeded bus.delay never landed on a probe rpc")
            assert _wait(lambda: exp.tail.anomaly_keeps >= 1,
                         timeout=10.0), "delayed rpc not flagged anomalous"

            # the pod trace converges: this process's bind-path spans
            # plus the apiserver's halves resolved via the published
            # vtpu-tail decision
            def trace_spans():
                return [s for s in obs.collect_spans(api)
                        if s.get("t") == tid]

            assert _wait(
                lambda: len({s.get("daemon") for s in trace_spans()}) >= 2,
                timeout=30.0, interval=0.25,
            ), ("anomalous pod trace never crossed processes: "
                + str(sorted({s.get("daemon") for s in trace_spans()})))
            sel = trace_spans()
            names = {s["name"] for s in sel}
            assert "bind:landed" in names, names
            assert any(s.get("cat") == "bus"
                       and s.get("dur", 0.0) >= 60_000.0
                       for s in sel), "kept trace lacks the slow rpc"
            assert not any("_root" in s for s in sel)

            # the controller leg rides the owning Job's identity
            # (controller:status) — flag that trace anomalous too and
            # the union waterfall spans all three daemons
            assert probe_until_delayed(obs.trace_id_for("default", job))
            idents = obs.related_identities(api, "default", anom)

            def union():
                return obs.select_union(obs.collect_spans(api), idents)

            assert _wait(
                lambda: len({s.get("daemon") for s in union()}) >= 3,
                timeout=30.0, interval=0.25,
            ), ("union waterfall never spanned 3 daemons: "
                + str(sorted({s.get("daemon") for s in union()})))
            assert "controller:status" in {s["name"] for s in union()}

            # steady traces: every one is coin-dropped and none grew
            # anomaly evidence -> absent from the durable segments
            steady = {obs.trace_id_for("default", f"{job}-t-{k}")
                      for k in range(1, replicas)}
            exported = {s.get("t") for s in obs.collect_spans(api)}
            assert steady.isdisjoint(exported), (
                "steady traces must drop at the configured rate"
            )
        finally:
            obs.disable()
            if cache is not None:
                cache.stop_commit_plane()
            if sched_remote is not None:
                sched_remote.close()
            if api is not None:
                api.close()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---- chaos twin: bit-identical with tail mode ON ----

class TestChaosTwinWithTail:
    def test_binding_map_bit_identical_with_tail_on(self, tmp_path):
        """The PR 13 pin, upgraded: the chaos twin runs with TAIL mode
        on both sides (sample < 1 so the pending pool actually
        engages) — buffering and completion-time decisions must never
        perturb a scheduling outcome."""
        from tests.test_chaos import ChaosCluster, _submit_mixed_workload

        maps = {}
        for label, spec in (
            ("faulty", "seed=77;bus.disconnect=0.05:count=3;"
                       "bus.delay=0.08:count=5:ms=5;"
                       "bus.client_drop=0.05:count=4;"
                       "cache.bind_fail=0.1:count=3"),
            ("clean", None),
        ):
            cluster = ChaosCluster(tmp_path, f"tail-{label}")
            try:
                obs.enable(cluster.remote, identity=f"sched-{label}",
                           flush_interval=0.05, sample=0.05, tail=True)
                _submit_mixed_workload(cluster)
                faults.configure(spec)
                cluster.run_cycles(10)
                faults.configure(None)
                assert _wait(
                    lambda: (cluster.cycle() or True)
                    and cluster.all_placed(),
                    timeout=30.0, interval=0.05,
                ), f"{label}: pods still unplaced with tail mode on"
                cluster.assert_no_duplicate_binds()
                assert cluster.cycle_errors == 0
                maps[label] = cluster.binding_map()
            finally:
                obs.disable()
                cluster.close()
                faults.configure(None)
                faults.reset_breakers()
        pinned = {k: v for k, v in maps["faulty"].items()
                  if "pinned" in k}
        pinned_clean = {k: v for k, v in maps["clean"].items()
                        if "pinned" in k}
        assert pinned == pinned_clean and len(pinned) == 4
        assert set(maps["faulty"]) == set(maps["clean"])
