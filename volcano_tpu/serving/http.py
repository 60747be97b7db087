"""HTTP serving: /healthz + /metrics.

Reference: the scheduler binary starts a Prometheus handler on
--listen-address (cmd/scheduler/app/server.go:96-99) and a healthz
endpoint (pkg/apis/helpers/helpers.go:195 StartHealthz); controllers and
admission do the same.  Here one small threaded server carries both:

  GET /healthz     → 200 "ok"      (liveness)
  GET /metrics     → Prometheus text exposition of metrics.registry
  GET /trace/last  → Chrome trace_event JSON of the last completed
                     scheduling cycle (404 when tracing is disabled or
                     no cycle has finished yet) — open it in
                     chrome://tracing / Perfetto.  Forensics, so gated
                     like /debug/stacks: loopback always, non-loopback
                     only with debug_enabled
  GET /explain     → JSON "why is my job pending": unschedulable jobs,
                     their per-task fit-error messages and reason
                     histograms (serving/explain.py).  Narrow with
                     ?namespace=&job=.  Scheduler daemon only; gated
                     like /debug/stacks

No third-party client library — metrics._Registry.render() already
emits the text format.
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from volcano_tpu.metrics import metrics


class _Handler(BaseHTTPRequestHandler):
    server_version = "volcano-tpu"

    def _deny_unless_debug(self) -> bool:
        """One gate for every forensics endpoint (/debug/stacks,
        /trace/last): answer an empty 404 and return True unless the
        client is loopback or debug serving is explicitly enabled."""
        if debug_allowed(
            getattr(self.server, "debug_enabled", False),
            self.client_address[0],
        ):
            return False
        self.send_response(404)
        self.send_header("Content-Length", "0")
        self.end_headers()
        return True

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
        if self.path == "/healthz":
            check = getattr(self.server, "health_check", None)
            if check is not None and not check():
                body = b"unhealthy"
                self.send_response(503)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            # degraded ≠ unhealthy: the daemon is alive and scheduling,
            # but a fallback rung is carrying the load (open circuit
            # breaker on an executor rung).  200 so
            # liveness probes don't restart a working pod; the body
            # names the reason so operators and the chaos harness see
            # the demotion.
            degraded = getattr(self.server, "degraded_source", None)
            reason = degraded() if degraded is not None else None
            body = f"degraded: {reason}".encode() if reason else b"ok"
            ctype = "text/plain"
        elif self.path == "/metrics":
            body = self.server.registry.render().encode()
            ctype = "text/plain; version=0.0.4"
        elif self.path == "/trace/last":
            # scheduling forensics (task uids, node placements, evict
            # reasons) — same sensitivity class as /debug/stacks, same
            # gate: loopback always, non-loopback only with debug_enabled
            if self._deny_unless_debug():
                return
            import json

            from volcano_tpu import trace
            from volcano_tpu.trace.export import chrome_trace

            rec = getattr(self.server, "recorder", None) or trace.get_recorder()
            record = rec.last_cycle()
            if record is None:
                body = b"no recorded cycle (is tracing enabled?)"
                self.send_response(404)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            body = json.dumps(chrome_trace(record)).encode()
            ctype = "application/json"
        elif self.path == "/explain" or self.path.startswith("/explain?"):
            # unschedulability forensics (job/task names, node names,
            # failure reasons) — same sensitivity class and gate as
            # /debug/stacks
            if self._deny_unless_debug():
                return
            source = getattr(self.server, "explain_source", None)
            if source is None:
                body = b"no explain source (scheduler daemon only)"
                self.send_response(404)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            import json
            from urllib.parse import parse_qs, urlsplit

            query = parse_qs(urlsplit(self.path).query)
            data = source(
                query.get("namespace", [""])[0], query.get("job", [""])[0]
            )
            if data is None:
                body = b"job not found or nothing recorded"
                self.send_response(404)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            body = json.dumps(data).encode()
            ctype = "application/json"
        elif self.path == "/debug/stacks":
            # the pprof-goroutine analogue (cmd/scheduler/main.go:25
            # imports net/http/pprof): live thread stacks for hang
            # forensics.  Stack dumps leak internals (paths, job names,
            # lock state), so off-loopback binds must opt in explicitly
            # via debug_enabled — a metrics port exposed cluster-wide
            # must not also expose forensics.
            if self._deny_unless_debug():
                return
            import sys
            import threading
            import traceback

            frames = sys._current_frames()
            parts = []
            for t in threading.enumerate():
                frame = frames.get(t.ident)
                parts.append(f"--- {t.name} (daemon={t.daemon}) ---")
                if frame is not None:
                    parts.append("".join(traceback.format_stack(frame)))
            body = "\n".join(parts).encode()
            ctype = "text/plain"
        else:
            self.send_response(404)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):  # silence per-request stderr noise
        pass


def _default_degraded() -> Optional[str]:
    """Default /healthz degraded source: every open circuit breaker in
    the process (executor demotions)."""
    from volcano_tpu.faults.breaker import degraded_reasons

    reasons = degraded_reasons()
    return "; ".join(reasons) if reasons else None


def debug_allowed(debug_enabled: bool, client_ip: str) -> bool:
    """/debug/stacks policy: loopback always, anything else only with
    the explicit opt-in."""
    return debug_enabled or client_ip in ("127.0.0.1", "::1")


class ServingServer:
    """Threaded healthz+metrics server.  ``port=0`` binds an ephemeral
    port (read it back from ``.port`` after start)."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        registry=None,
        health_check=None,
        debug_enabled: bool = False,
        recorder=None,
        explain_source=None,
        degraded_source=None,
    ):
        self._host = host
        self._port = port
        self._registry = registry if registry is not None else metrics.registry
        #: optional () -> bool; False turns /healthz into a 503 (liveness
        #: must reflect the daemon's loop, not just the process)
        self._health_check = health_check
        #: serve /debug/stacks to non-loopback clients (off by default)
        self._debug_enabled = debug_enabled
        #: trace recorder serving /trace/last; None = the process-global
        #: recorder at request time (trace.get_recorder())
        self._recorder = recorder
        #: optional (namespace, job) -> dict|None backing /explain —
        #: the scheduler daemon wires serving/explain.explain_jobs here
        self._explain_source = explain_source
        #: optional () -> Optional[str]; a non-empty reason turns
        #: /healthz's 200 body into "degraded: <reason>".  None = the
        #: process-global breaker registry (volcano_tpu.faults.breaker)
        self._degraded_source = (
            degraded_source
            if degraded_source is not None
            else _default_degraded
        )
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        assert self._httpd is not None, "server not started"
        return self._httpd.server_address[1]

    @property
    def host(self) -> str:
        return self._host

    def start(self) -> "ServingServer":
        self._httpd = ThreadingHTTPServer((self._host, self._port), _Handler)
        self._httpd.registry = self._registry
        self._httpd.health_check = self._health_check
        self._httpd.debug_enabled = self._debug_enabled
        self._httpd.recorder = self._recorder
        self._httpd.explain_source = self._explain_source
        self._httpd.degraded_source = self._degraded_source
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="vtpu-serving", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
