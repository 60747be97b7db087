"""Shared daemon scaffolding: serving surface + leader election + the
guarded work loop.  SchedulerDaemon and ControllersDaemon differ only in
their work body and construction; everything else (loop, crash-stop
semantics, liveness) lives here once.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from typing import Optional

from volcano_tpu.client import APIServer
from volcano_tpu.serving import LeaderElector, ServingServer
from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)


class BaseDaemon:
    """Work loop + serving + optional leader election.

    Subclasses set ``LOCK_NAME``/``NAME`` and implement ``_work()`` (one
    cycle).  The loop is exception-guarded — a failing cycle is logged
    and retried, never silently killing the thread — and ``/healthz``
    reflects actual loop liveness, not just process liveness."""

    LOCK_NAME = "vtpu-daemon"
    NAME = "daemon"

    def __init__(
        self,
        api: APIServer,
        period: float = 0.2,
        listen_host: str = "127.0.0.1",
        listen_port: int = 0,
        leader_elect: bool = False,
        identity: Optional[str] = None,
        lease_duration: float = 2.0,
        retry_period: float = 0.2,
        debug_enabled: bool = False,
        explain_source=None,
        flight_recorder: Optional[bool] = None,
        watchdog: Optional[bool] = None,
        incident_dir: Optional[str] = None,
    ):
        self.api = api
        self.period = period
        self.identity = identity or f"{self.NAME}-{uuid.uuid4().hex[:8]}"
        #: cluster-wide flight recorder (volcano_tpu/obs): span batches
        #: export to the bus as telemetry segments.  None = follow
        #: VTPU_FLIGHT_RECORDER (so local_up/chaos harnesses flip every
        #: daemon with one env var)
        if flight_recorder is None:
            flight_recorder = os.environ.get(
                "VTPU_FLIGHT_RECORDER", ""
            ) not in ("", "0")
        self.flight_recorder = flight_recorder
        self._obs_exporter = None
        #: SLO burn-rate watchdog (obs/slo.py) + incident bundles
        #: (obs/incident.py).  None = follow VTPU_WATCHDOG /
        #: VTPU_INCIDENT_DIR so the drill harnesses flip every daemon
        #: with env vars, same shape as the flight recorder flag.
        if watchdog is None:
            watchdog = os.environ.get("VTPU_WATCHDOG", "") not in ("", "0")
        if incident_dir is None:
            incident_dir = os.environ.get("VTPU_INCIDENT_DIR", "")
        self.watchdog_enabled = watchdog
        self.incident_dir = incident_dir
        self.watchdog = None
        self.incidents = None
        #: uniform identity labels merged into every /metrics series
        #: (vtctl top's federation contract); subclasses refine
        self.identity_labels = {
            "daemon": self.NAME.replace("vtpu-", ""),
            "role": self.NAME.replace("vtpu-", ""),
        }
        if self.watchdog_enabled:
            from volcano_tpu.metrics.timeseries import TimeSeriesRing
            from volcano_tpu.obs.incident import IncidentManager
            from volcano_tpu.obs.slo import BurnRateWatchdog

            ring = TimeSeriesRing()
            self.incidents = IncidentManager(
                api,
                self.identity,
                self.incident_dir
                or os.path.join("/tmp", f"vtpu-incidents-{self.identity}"),
                cooldown_s=float(
                    os.environ.get("VTPU_INCIDENT_COOLDOWN", "60")),
                boost_ttl_s=float(os.environ.get("VTPU_BOOST_TTL", "30")),
                metrics_ring=ring,
                journal_dir=os.environ.get("VTPU_TRACE_JOURNAL", ""),
                explain_source=explain_source,
            )
            self.watchdog = BurnRateWatchdog(
                ring=ring,
                fast_window_s=float(
                    os.environ.get("VTPU_SLO_FAST_WINDOW", "60")),
                slow_window_s=float(
                    os.environ.get("VTPU_SLO_SLOW_WINDOW", "300")),
                period=float(os.environ.get("VTPU_WATCHDOG_PERIOD", "5")),
                on_breach=self.incidents.on_alert,
            )
        self.serving = ServingServer(
            host=listen_host, port=listen_port, health_check=self.healthy,
            debug_enabled=debug_enabled, explain_source=explain_source,
            degraded_source=self._degraded,
        )
        self.elector: Optional[LeaderElector] = None
        if leader_elect:
            self.elector = LeaderElector(
                api,
                self.LOCK_NAME,
                self.identity,
                lease_duration=lease_duration,
                retry_period=retry_period,
            )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: cycles this instance actually ran (leadership observability)
        self.cycles = 0
        self.last_error: Optional[str] = None

    # ---- subclass API ----

    def _work(self) -> None:
        raise NotImplementedError

    def _on_start(self) -> None:
        """Hook before the loop thread starts (e.g. cache informers)."""

    # ---- loop ----

    def _loop(self) -> None:
        while not self._stop.is_set():
            if self.elector is None or self.elector.is_leader:
                try:
                    self._work()
                    self.cycles += 1
                    self.last_error = None
                except Exception as e:  # noqa: BLE001 — keep the loop alive
                    self.last_error = str(e)
                    log.error("%s cycle failed: %s", self.NAME, e)
            self._stop.wait(self.period)

    def _degraded(self) -> Optional[str]:
        """/healthz degraded body: open breakers (the serving default)
        plus the watchdog's active ``slo-burn:<name>`` breaches."""
        from volcano_tpu.serving.http import _default_degraded

        reasons = []
        breakers = _default_degraded()
        if breakers:
            reasons.append(breakers)
        if self.watchdog is not None:
            reasons.extend(self.watchdog.degraded_reasons())
        return "; ".join(reasons) if reasons else None

    def healthy(self) -> bool:
        """Liveness for /healthz: the loop thread must be running (or
        not yet started)."""
        return self._thread is None or self._thread.is_alive()

    def start(self):
        from volcano_tpu.metrics import metrics

        metrics.set_identity(**self.identity_labels)
        if self.flight_recorder:
            from volcano_tpu import obs

            self._obs_exporter = obs.enable(self.api, identity=self.identity)
        if self.watchdog is not None:
            self.watchdog.start()
        self.serving.start()
        self._on_start()
        if self.elector is not None:
            self.elector.start()
        self._thread = threading.Thread(
            target=self._loop, name=f"{self.NAME}-{self.identity}", daemon=True
        )
        self._thread.start()
        log.info("%s %s serving on :%d", self.NAME, self.identity, self.serving.port)
        return self

    def stop(self, crash: bool = False) -> None:
        """Stop the daemon.  ``crash=True`` skips the graceful lease
        release, leaving standbys to take over after expiry."""
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)
        if self.elector is not None:
            self.elector.stop(release=not crash)
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._obs_exporter is not None:
            from volcano_tpu import obs

            if obs.get_exporter() is self._obs_exporter:
                obs.disable()  # final flush rides the exporter stop
            else:
                self._obs_exporter.stop()
            self._obs_exporter = None
        self.serving.stop()


def apply_faults(spec: str) -> None:
    """``--faults`` → the process-global fault plane (a parse error is
    a clean exit: a typo'd schedule must not run a different chaos
    plan).  An empty flag leaves VTPU_FAULTS env resolution intact.
    Lives here — not in cmd.scheduler — so store-only daemons
    (vtpu-apiserver) don't drag the scheduler
    stack in for a flag helper."""
    if not spec:
        return
    from volcano_tpu import faults

    try:
        faults.configure(spec)
    except ValueError as e:
        raise SystemExit(f"--faults: {e}") from e


def serve_forever(daemon: BaseDaemon) -> int:
    """Blocking main body shared by the binaries."""
    daemon.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        daemon.stop()
    return 0
