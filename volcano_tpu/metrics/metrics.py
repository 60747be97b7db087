"""Scheduler metrics — the reference's Prometheus catalog, host-side.

Reference: pkg/scheduler/metrics/metrics.go:38-191.  Same metric names under
the ``volcano`` namespace; implemented as in-process histograms/counters with
an optional Prometheus text exposition (no hard dependency on a client lib).
The TPU build adds kernel phase timings (compile/transfer/execute) under the
same registry.
"""

from __future__ import annotations

import bisect
import threading
from collections import defaultdict
from typing import Dict, List, Tuple

_NAMESPACE = "volcano"

# 5ms × 2^k buckets, like prometheus.ExponentialBuckets(5, 2, 10) in ms.
_LATENCY_BUCKETS_MS = [5.0 * (2**k) for k in range(10)]

# Microsecond histograms need a wider exponential range: 5µs × 2^k up to
# ~160ms, so both a 20µs plugin callback and a 100ms action land inside
# the bucketed range rather than in +Inf.
_LATENCY_BUCKETS_US = [5.0 * (2**k) for k in range(16)]

# GC pauses: 100µs × 4^k up to ~26 s, in seconds.
_GC_PAUSE_BUCKETS_S = [1e-4 * (4**k) for k in range(10)]

# Job-level end-to-end latency (creation → first scheduled cycle) is
# seconds-to-minutes scale: 100ms × 2^k up to ~14 minutes.
_JOB_LATENCY_BUCKETS_MS = [100.0 * (2**k) for k in range(14)]


class _Histogram:
    def __init__(self, name: str, help_: str, buckets: List[float]):
        self.name = name
        self.help = help_
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.sum = 0.0
        self.total = 0

    def observe(self, value: float) -> None:
        idx = bisect.bisect_left(self.buckets, value)
        self.counts[idx] += 1
        self.sum += value
        self.total += 1


class _Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._histograms: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], _Histogram] = {}  # guarded-by: self._lock
        self._counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = defaultdict(float)  # guarded-by: self._lock
        self._gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}  # guarded-by: self._lock
        #: uniform identity labels merged into EVERY rendered series
        #: (daemon / shard / replica_index / role) so federated scrapes
        #: aggregated by ``vtctl top`` stay distinguishable without
        #: scrape-config tricks; empty until set_identity() — tests and
        #: library embedders see unchanged output
        self._identity: Tuple[Tuple[str, str], ...] = ()  # guarded-by: self._lock
        #: histograms observed without the lock, by a caller that may run
        #: while this thread already holds it (a gc callback fires inside
        #: any allocation).  Fixed in size once made; reset() zeroes them
        #: in place, since their observers keep a reference.
        self._unlocked: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], _Histogram] = {}  # guarded-by: self._lock

    def histogram(
        self,
        name: str,
        labels: Dict[str, str],
        help_: str = "",
        buckets: List[float] = None,
    ) -> _Histogram:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = _Histogram(name, help_, buckets or _LATENCY_BUCKETS_MS)
                self._histograms[key] = h
            return h

    def unlocked_histogram(
        self, name: str, labels: Dict[str, str], buckets: List[float]
    ) -> _Histogram:
        """A histogram whose ``observe`` its one writer calls without the
        lock (writes must not overlap: gc callbacks never do).  Rendered
        once it holds an observation."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._unlocked.get(key)
            if h is None:
                h = self._unlocked[key] = _Histogram(name, "", buckets)
            return h

    @staticmethod
    def _read(h: _Histogram, unlocked: bool):
        """(buckets, counts, sum, total) of one histogram; None for an
        unlocked one never observed.  An unlocked histogram's counts are
        copied first and its total taken from the copy, so a collection
        during the read cannot make its buckets and count disagree."""
        counts = list(h.counts)
        if not unlocked:
            return h.buckets, counts, h.sum, h.total
        total = sum(counts)
        return (h.buckets, counts, h.sum, total) if total else None

    def inc(self, name: str, labels: Dict[str, str], value: float = 1.0) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] += value

    def set_gauge(self, name: str, labels: Dict[str, str], value: float) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._gauges[key] = value

    def set_identity(self, **labels: str) -> None:
        """Install the uniform identity labels (non-empty values only);
        they merge into every series at render time, so a role flip
        (follower → leader) retags the whole exposition at the next
        scrape."""
        with self._lock:
            self._identity = tuple(
                sorted((k, v) for k, v in labels.items() if v)
            )

    def refresh_identity_role(self, role: str) -> None:
        """Replace just the ``role`` identity label — called from the
        replication role transitions (update_repl_role) so BOTH
        directions retag: a deposed leader's series must stop claiming
        role="leader" the moment it demotes, not only flip on
        promotion.  No-op when no identity is installed (library
        embedders, tests)."""
        with self._lock:
            if not self._identity or not role:
                return
            self._identity = tuple(sorted(
                [(k, v) for k, v in self._identity if k != "role"]
                + [("role", role)]
            ))

    def render(self) -> str:
        """Prometheus text exposition format."""
        lines: List[str] = []
        identity: Tuple[Tuple[str, str], ...] = ()

        def merge(labels: Tuple[Tuple[str, str], ...]):
            if not identity:
                return labels
            keys = {k for k, _v in labels}
            return tuple(sorted(
                labels + tuple((k, v) for k, v in identity if k not in keys)
            ))

        def fmt_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
            labels = merge(labels)
            if not labels:
                return ""
            inner = ",".join(f'{k}="{v}"' for k, v in labels)
            return "{" + inner + "}"

        with self._lock:
            identity = self._identity
            views = [(k, self._read(h, False)) for k, h in self._histograms.items()]
            views += [(k, self._read(h, True)) for k, h in self._unlocked.items()]
            for (name, labels), view in sorted(v for v in views if v[1] is not None):
                bounds, counts, sum_, total = view
                cumulative = 0
                for bound, c in zip(bounds, counts):
                    cumulative += c
                    le = labels + (("le", str(bound)),)
                    lines.append(f"{name}_bucket{fmt_labels(le)} {cumulative}")
                le = labels + (("le", "+Inf"),)
                lines.append(f"{name}_bucket{fmt_labels(le)} {total}")
                lines.append(f"{name}_sum{fmt_labels(labels)} {sum_}")
                lines.append(f"{name}_count{fmt_labels(labels)} {total}")
            for (name, labels), v in sorted(self._counters.items()):
                lines.append(f"{name}{fmt_labels(labels)} {v}")
            for (name, labels), v in sorted(self._gauges.items()):
                lines.append(f"{name}{fmt_labels(labels)} {v}")
        return "\n".join(lines) + "\n"

    def histogram_snapshot(self, name: str, labels=None):
        """Cumulative scrape-shaped snapshot of one histogram —
        ``{"buckets": [(le, cumulative)...], "sum", "count"}``, the
        exact shape ``metrics/scrape.py`` parses from /metrics text, so
        the shard autoscaler's windowed quantiles reuse
        ``merge_histograms``/``histogram_quantile`` unchanged.  None
        when the series was never observed."""
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            h = self._histograms.get(key)
            if h is not None:
                view = self._read(h, False)
            else:
                h = self._unlocked.get(key)
                view = self._read(h, True) if h is not None else None
        if view is None:
            return None
        bounds, counts, sum_, total = view
        cumulative = 0
        buckets = []
        for bound, c in zip(bounds, counts):
            cumulative += c
            buckets.append((str(bound), float(cumulative)))
        buckets.append(("+Inf", float(total)))
        return {"buckets": buckets, "sum": sum_, "count": float(total)}

    def reset(self) -> None:
        with self._lock:
            for h in self._unlocked.values():
                h.counts[:] = [0] * len(h.counts)
                h.sum, h.total = 0.0, 0
            self._histograms.clear()
            self._counters.clear()
            self._gauges.clear()
            self._identity = ()


registry = _Registry()


# ---- daemon identity + build info (the federated-scrape contract) ----
# Every daemon stamps who it is once at startup; the registry merges
# the labels into every rendered series, so `vtctl top` can aggregate
# N schedulers + M apiserver replicas without scrape-config tricks.

#: bounded role vocabulary for the identity label (MTR001 discipline)
_IDENTITY_ROLES = (
    "scheduler", "controllers", "admission", "apiserver",
    "leader", "follower", "standalone", "init", "removed",
)


def set_identity(
    daemon: str,
    shard: str = "",
    replica_index: str = "",
    role: str = "",
) -> None:
    """Install the uniform identity labels and the
    ``volcano_build_info`` gauge.  role ∈ the _IDENTITY_ROLES
    vocabulary (daemon kind, or leader/follower for apiserver
    replicas); empty labels are omitted rather than rendered blank.
    Call again on a role flip (promotion) — the whole exposition
    retags at the next scrape."""
    if role and role not in _IDENTITY_ROLES:
        role = "other"
    registry.set_identity(
        daemon=daemon, shard=shard, replica_index=replica_index, role=role
    )
    from volcano_tpu import __version__

    # label-vocab: version — the package __version__, one value per build
    registry.set_gauge(
        f"{_NAMESPACE}_build_info", {"version": __version__}, 1.0
    )


# ---- label-cardinality bound (MTR001: metric hygiene) ----
# Some reference metrics carry a JOB label (gang.go's per-job
# unschedulable gauges).  Job names are operator input — an unbounded
# vocabulary that would mint one series per job forever.  This helper
# is the declared bound: the first _LABEL_CARDINALITY_CAP distinct
# values keep their own series, everything after lands under "other"
# (with an eviction counter so saturation is visible, not silent).

_LABEL_CARDINALITY_CAP = 256
_label_values_lock = threading.Lock()
_label_values: Dict[Tuple[str, str], set] = {}  # guarded-by: _label_values_lock


def bounded_label(metric: str, label: str, value: str) -> str:
    """Admit ``value`` into the metric's label vocabulary, or collapse
    it to "other" once the per-(metric, label) cap is reached."""
    key = (metric, label)
    with _label_values_lock:
        seen = _label_values.setdefault(key, set())
        if value in seen:
            return value
        if len(seen) < _LABEL_CARDINALITY_CAP:
            seen.add(value)
            return value
    registry.inc(
        f"{_NAMESPACE}_metric_label_overflow_total", {"metric": metric}
    )  # label-vocab: metric — the fixed set of bounded_label call sites
    return "other"


# ---- update helpers (metrics.go:124-171) ----
# Unit discipline (metrics.go:47-72): *_microseconds histograms observe
# seconds × 1e6, *_milliseconds histograms seconds × 1e3.  The first
# four releases observed ms into the µs histograms — every exported
# plugin/action/task latency was 1000× off (tests/test_metrics.py pins
# the units now).

def update_plugin_duration(plugin_name: str, seconds: float) -> None:
    # label-vocab: plugin — the registered plugin-builder names
    # (framework/plugins.py factory registry), a static set
    registry.histogram(
        f"{_NAMESPACE}_plugin_scheduling_latency_microseconds",
        {"plugin": plugin_name},
        buckets=_LATENCY_BUCKETS_US,
    ).observe(seconds * 1e6)


def update_action_duration(action_name: str, seconds: float) -> None:
    # label-vocab: action — the registered action names
    # (framework/plugins.py action registry), a static set
    registry.histogram(
        f"{_NAMESPACE}_action_scheduling_latency_microseconds",
        {"action": action_name},
        buckets=_LATENCY_BUCKETS_US,
    ).observe(seconds * 1e6)


def update_e2e_duration(seconds: float) -> None:
    registry.histogram(
        f"{_NAMESPACE}_e2e_scheduling_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def update_job_schedule_duration(seconds: float) -> None:
    """Per-job end-to-end scheduling latency (creation → first scheduled
    cycle), the reference's e2e_job_scheduling_latency_milliseconds."""
    registry.histogram(
        f"{_NAMESPACE}_e2e_job_scheduling_latency_milliseconds",
        {},
        buckets=_JOB_LATENCY_BUCKETS_MS,
    ).observe(seconds * 1e3)


def update_task_schedule_duration(seconds: float) -> None:
    registry.histogram(
        f"{_NAMESPACE}_task_scheduling_latency_microseconds",
        {},
        buckets=_LATENCY_BUCKETS_US,
    ).observe(seconds * 1e6)


def register_schedule_attempt(result: str) -> None:
    """metrics.go schedule_attempts_total: one count per job scheduling
    attempt, result ∈ {scheduled, unschedulable, error}."""
    registry.inc(f"{_NAMESPACE}_schedule_attempts_total", {"result": result})


def register_job_status_writes(path: str, n: int) -> None:
    """volcano_job_status_writes_total{path}: jobs whose status writeback
    was due at session close, by where the writes went, path ∈ {inline,
    pool, plane} (framework/job_updater)."""
    registry.inc(f"{_NAMESPACE}_job_status_writes_total", {"path": path}, n)


def register_snapshot_clones(counts: Dict[str, int]) -> None:
    """volcano_snapshot_clones_total{kind,path}: the objects a session
    snapshot handed out, kind ∈ {job, node}, path ∈ {reused, cloned} —
    reused is a clone the previous session handed back untouched
    (cache/cache.py snapshot), cloned a fresh one."""
    for kind in ("job", "node"):
        for path in ("reused", "cloned"):
            registry.inc(
                f"{_NAMESPACE}_snapshot_clones_total",
                {"kind": kind, "path": path}, counts[f"{kind}s_{path}"],
            )


def update_pod_schedule_status(status: str, count: int = 1) -> None:
    """metrics.go pod_schedule_successes/errors: pods whose bind effect
    landed (or failed to land) on the bus.  status ∈ {successes,
    errors} — the status names the metric, not a label, exactly the
    reference's two-counter shape."""
    registry.inc(f"{_NAMESPACE}_pod_schedule_{status}", {}, count)


def update_preemption_victims_count(count: int) -> None:
    registry.inc(f"{_NAMESPACE}_total_preemption_victims", {}, count)


def register_preemption_attempts() -> None:
    registry.inc(f"{_NAMESPACE}_total_preemption_attempts", {})


def register_unschedulable_reason(reason: str, tasks: int = 1) -> None:
    """volcano_unschedulable_task_reasons{reason}: tasks that stayed
    pending this cycle with ``reason`` in their fit-error histogram —
    the per-reason face of the Unschedulable event stream.  Recorded by
    both the host predicate sweep and the device explain synthesis, so
    the metric is path-independent.

    Host fit-error reasons can interpolate object names ('pvc "ns/x"
    not found') — an unbounded label value would mint one counter
    series per stuck object, so reason ∈ _well_known_reasons() plus
    "other": anything outside the well-known vocabulary lands under
    reason="other"."""
    if reason not in _well_known_reasons():
        reason = "other"
    registry.inc(
        f"{_NAMESPACE}_unschedulable_task_reasons", {"reason": reason}, tasks
    )


_WELL_KNOWN_REASONS: frozenset = frozenset()


def _well_known_reasons() -> frozenset:
    """Bounded label vocabulary for the per-reason counter (built
    lazily — volcano_tpu.api must not import at metrics-module import
    time)."""
    global _WELL_KNOWN_REASONS
    if not _WELL_KNOWN_REASONS:
        from volcano_tpu.api import unschedule_info as ui

        _WELL_KNOWN_REASONS = frozenset(
            (
                ui.NODE_RESOURCE_FIT_FAILED,
                ui.NODE_POD_NUMBER_EXCEEDED,
                ui.NODE_SELECTOR_MISMATCH,
                ui.NODE_AFFINITY_MISMATCH,
                ui.NODE_TAINT_UNTOLERATED,
                ui.NODE_PORT_CONFLICT,
                ui.NODE_UNSCHEDULABLE,
                ui.NODE_NOT_READY,
                ui.POD_AFFINITY_MISMATCH,
                "node(s) had memory pressure",
                "node(s) had disk pressure",
                "node(s) had pid pressure",
                "pod has unbound immediate PersistentVolumeClaims",
            )
        )
    return _WELL_KNOWN_REASONS


def update_explain_duration(seconds: float) -> None:
    """volcano_explain_latency_milliseconds: cost of the on-device
    reason-count reduction (ops/explain.run_explain), from the
    duration of its ``vtpu/allocate.explain`` span."""
    registry.histogram(
        f"{_NAMESPACE}_explain_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def update_unschedule_task_count(job_name: str, count: int) -> None:
    """gang.go's per-job unready gauge.  job ∈ the bounded_label-capped
    vocabulary: the first _LABEL_CARDINALITY_CAP job names keep their
    own series, later ones collapse to job="other" (metric hygiene —
    operator input must not mint unbounded series)."""
    job_name = bounded_label("unschedule_task_count", "job", job_name)
    registry.set_gauge(f"{_NAMESPACE}_unschedule_task_count", {"job": job_name}, count)


def update_unschedule_job_count(count: int) -> None:
    registry.set_gauge(f"{_NAMESPACE}_unschedule_job_count", {}, count)


def register_job_retries(job_name: str) -> None:
    """job ∈ the bounded_label-capped vocabulary (see
    update_unschedule_task_count)."""
    job_name = bounded_label("job_retry_counts", "job", job_name)
    registry.inc(f"{_NAMESPACE}_job_retry_counts", {"job": job_name})


# ---- bus metrics (the out-of-process API-server boundary) ----
# Client side instruments every RemoteAPIServer call and the informer
# resync machinery; server side instruments the vtpu-apiserver daemon.
# volcano_bus_relists_total is the divergence canary: a relist means a
# watch stream could not resume and the informer cache was rebuilt.

def observe_bus_request(method: str, seconds: float, code: str) -> None:
    """code ∈ {ok, error, timeout, disconnected}."""
    # label-vocab: method — the protocol.OP_VERSIONS op registry plus
    # "ping", a static set
    registry.inc(f"{_NAMESPACE}_bus_requests_total",
                 {"method": method, "code": code})
    registry.histogram(
        f"{_NAMESPACE}_bus_request_latency_milliseconds", {"method": method}
    ).observe(seconds * 1e3)


def register_bus_reconnect() -> None:
    registry.inc(f"{_NAMESPACE}_bus_reconnects_total", {})


def register_bus_relist(kind: str) -> None:
    # label-vocab: kind — the protocol.KINDS decode registry, a static
    # set of K8sObject kinds
    registry.inc(f"{_NAMESPACE}_bus_relists_total", {"kind": kind})


def register_bus_watch_event(kind: str) -> None:
    # label-vocab: kind — the protocol.KINDS decode registry, a static
    # set of K8sObject kinds
    registry.inc(f"{_NAMESPACE}_bus_watch_events_total", {"kind": kind})


def update_bus_watch_lag(seconds: float) -> None:
    """Server-stamp → client-dispatch latency of a watch event or
    bookmark (the wall-clock watch lag operators alert on)."""
    registry.histogram(
        f"{_NAMESPACE}_bus_watch_lag_milliseconds", {}
    ).observe(max(seconds, 0.0) * 1e3)


#: frame-size buckets (bytes): watch entries are hundreds of bytes,
#: relist replies and batch frames reach megabytes
_FRAME_BYTE_BUCKETS = [64, 256, 1024, 4096, 16384, 65536, 262144,
                       1048576, 4194304]


def update_bus_codec_connections(codec: str, count: int) -> None:
    """volcano_bus_codec: live server-side connections per negotiated
    body codec (protocol v8 ``bus_hello``)."""
    # label-vocab: codec ∈ {json, binary}, a static set
    registry.set_gauge(f"{_NAMESPACE}_bus_codec", {"codec": codec}, count)


def observe_bus_frame_bytes(codec: str, nbytes: int) -> None:
    """volcano_bus_frame_bytes: serialized body size of one outbound
    server frame, by codec — the byte half of the codec win the
    serde-floor bench measures in time."""
    # label-vocab: codec ∈ {json, binary}, a static set
    registry.histogram(
        f"{_NAMESPACE}_bus_frame_bytes", {"codec": codec},
        buckets=_FRAME_BYTE_BUCKETS,
    ).observe(nbytes)


def register_bus_codec_fallback() -> None:
    """volcano_bus_codec_fallbacks_total: a client offered binary and
    the peer declined (pre-v8 server, msgpack-less build, or an
    explicit JSON answer) — the connection degraded to JSON.  A
    non-zero rate in a fleet that should be all-binary is version skew
    made visible."""
    registry.inc(f"{_NAMESPACE}_bus_codec_fallbacks_total", {})


# ---- replicated persistent bus (bus/wal.py + bus/replication.py) ----
# The durability plane's vital signs: fsync cost (the floor under every
# acked write), WAL growth between snapshots, replication lag, the
# replica's current role, and how often recovery actually ran.

def observe_wal_fsync(seconds: float) -> None:
    """volcano_wal_fsync_latency_milliseconds: one WAL fsync — the
    durability cost every acknowledged store transaction pays."""
    registry.histogram(
        f"{_NAMESPACE}_wal_fsync_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def update_wal_size(size_bytes: int) -> None:
    """volcano_wal_size_bytes: bytes in the live WAL segment (resets to
    0 at each snapshot rotation — sawtooth growth is healthy, an
    unbounded ramp means snapshots stopped)."""
    registry.set_gauge(f"{_NAMESPACE}_wal_size_bytes", {}, size_bytes)


def observe_repl_quorum_wait(seconds: float) -> None:
    """volcano_repl_quorum_wait_milliseconds: how long a leader-side
    write parked (outside the store lock) waiting for the follower
    majority — the replication half of every acked write's tail, next
    to the fsync half (`vtctl top`'s QUORUM column)."""
    registry.histogram(
        f"{_NAMESPACE}_repl_quorum_wait_milliseconds", {}
    ).observe(seconds * 1e3)


def update_repl_lag(entries: int) -> None:
    """volcano_repl_lag_entries: replication lag in log entries — on
    the leader, the slowest follower's deficit; on a follower, its own
    distance behind the leader's last shipped record."""
    registry.set_gauge(f"{_NAMESPACE}_repl_lag_entries", {}, entries)


def update_membership_epoch(epoch: int) -> None:
    """volcano_repl_membership_epoch: the replication group's
    membership-config version (bumped by every committed add/remove) —
    a divergence between replicas' exported values is a config change
    still propagating; a persistent divergence is the split the
    membership chaos drill exists to rule out."""
    registry.set_gauge(f"{_NAMESPACE}_repl_membership_epoch", {}, epoch)


def register_autoscale_decision(direction: str) -> None:
    """volcano_shard_autoscale_decisions_total{direction}: one count
    per shard-count change the autoscale controller committed to the
    shard map."""
    # label-vocab: direction ∈ {up, down}
    registry.inc(
        f"{_NAMESPACE}_shard_autoscale_decisions_total",
        {"direction": direction},
    )


#: bounded role vocabulary for the one-hot role gauge ("removed" is a
#: replica retired by a membership change, still alive for reads)
_REPL_ROLES = ("leader", "follower", "standalone", "init", "removed")


def update_repl_role(role: str) -> None:
    """volcano_repl_role{role}: one-hot role gauge (1 on the current
    role's series, 0 on the rest) so a promotion flip is a visible
    edge on both series."""
    for r in _REPL_ROLES:
        # label-vocab: role — the _REPL_ROLES enum above
        registry.set_gauge(
            f"{_NAMESPACE}_repl_role", {"role": r}, 1.0 if r == role else 0.0
        )
    # the identity `role` label follows the SAME transitions, both
    # directions — a deposed leader must not keep exporting series
    # tagged role="leader" next to the real leader's
    registry.refresh_identity_role(role)


def register_bus_recovery(kind: str) -> None:
    """volcano_bus_recoveries_total{kind}: one count per recovery
    source actually used at startup/resync — kind ∈ {snapshot,
    wal_tail}."""
    registry.inc(f"{_NAMESPACE}_bus_recoveries_total", {"kind": kind})


def observe_bus_server_request(op: str, seconds: float, code: str) -> None:
    """code ∈ {ok, error}."""
    # label-vocab: op — the protocol.OP_VERSIONS registry, a static set
    registry.inc(f"{_NAMESPACE}_bus_server_requests_total",
                 {"op": op, "code": code})
    registry.histogram(
        f"{_NAMESPACE}_bus_server_request_latency_milliseconds", {"op": op}
    ).observe(seconds * 1e3)


def update_bus_server_watchers(count: int) -> None:
    registry.set_gauge(f"{_NAMESPACE}_bus_server_watchers", {}, count)


# ---- fault plane + graceful degradation (volcano_tpu/faults) ----
# volcano_executor_fallbacks_total is the demotion audit: every time an
# executor path degrades to a lower rung (pallas→blocked, native→
# xla-scan, preempt-pallas→dense, device→host) one count lands here
# with the cause, so a silent permanent demotion is impossible.

def register_executor_fallback(from_: str, to: str, cause: str) -> None:
    """cause ∈ {error, circuit-open, deadline, corrupt-output}."""
    # label-vocab: from, to — the executor rung names (ops/dispatch.py
    # degradation ladder), a static set
    registry.inc(
        f"{_NAMESPACE}_executor_fallbacks_total",
        {"from": from_, "to": to, "cause": cause},
    )


def executor_fallbacks_total() -> float:
    """Every demotion counted so far, across all label sets."""
    name = f"{_NAMESPACE}_executor_fallbacks_total"
    with registry._lock:
        return sum(v for (n, _), v in registry._counters.items() if n == name)


def update_circuit_breaker_state(executor: str, value: float) -> None:
    """0 = closed, 0.5 = half-open (probing), 1 = open (tripped)."""
    # label-vocab: executor — the per-name breaker registry
    # (faults/breaker.py), a static set of executor/seam names
    registry.set_gauge(
        f"{_NAMESPACE}_circuit_breaker_open", {"executor": executor}, value
    )


def register_fault_injected(point: str) -> None:
    """One count per fault-plane firing — lets a chaos run's metrics be
    cross-checked against its trace journal."""
    # label-vocab: point — the parsed fault schedule's point names
    # (finitely many per process; chaos harnesses only, never prod)
    registry.inc(f"{_NAMESPACE}_faults_injected_total", {"point": point})


def update_resync_quarantined(count: int) -> None:
    """volcano_resync_quarantined_tasks: tasks whose resync exhausted
    its bounded retries and now sit quarantined awaiting fresh API
    truth (cache.SchedulerCache poison-task handling)."""
    registry.set_gauge(f"{_NAMESPACE}_resync_quarantined_tasks", {}, count)


# ---- pipelined commit plane (cache/commit_plane.py) ----

#: coalesce sizes are small powers of two up to the per-frame cap
_COALESCE_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                     4096, 8192]


def update_commit_queue_depth(depth: int) -> None:
    """volcano_commit_queue_depth: commit-plane items (binds / evicts /
    status writebacks) enqueued but not yet landed on the bus."""
    registry.set_gauge(f"{_NAMESPACE}_commit_queue_depth", {}, depth)


def observe_bind_coalesce(size: int) -> None:
    """volcano_bind_coalesce_size: how many binds one coalesced
    commit-plane frame carried — the multi-bind batching win is visible
    as mass in the high buckets."""
    registry.histogram(
        f"{_NAMESPACE}_bind_coalesce_size", {}, buckets=_COALESCE_BUCKETS
    ).observe(size)


def update_commit_overlap_ratio(ratio: float) -> None:
    """volcano_commit_overlap_ratio: per commit-barrier, the fraction of
    the plane's busy time that overlapped other host work instead of
    blocking the barrier — 1.0 means the whole commit landed behind the
    next cycle's pack+device phase, 0.0 means the barrier absorbed all
    of it (no better than synchronous)."""
    registry.set_gauge(f"{_NAMESPACE}_commit_overlap_ratio", {}, ratio)


def register_commit_failure(kind: str) -> None:
    """volcano_commit_failures_total{kind}: commit-plane items whose
    async effect failed (kind ∈ {bind, evict, status}); binds/evicts
    take the resync path, status writebacks retry next cycle."""
    registry.inc(f"{_NAMESPACE}_commit_failures_total", {"kind": kind})


# ---- event-driven micro-cycles (scheduler/scheduler.py) ----
# Under sustained churn the user-visible number is submit→bind latency,
# not batch cycle latency: the wake-on-event loop runs an incremental
# micro-cycle per coalesced watch notification, with periodic full
# cycles for fair-share/gang re-equilibration.  bench/loadgen.py reads
# these back to report the SLO percentiles and the micro-vs-full mix.

def register_micro_cycle(trigger: str) -> None:
    """volcano_micro_cycles_total{trigger}: one count per event-driven
    micro-cycle; trigger ∈ {task, node, group, gang, topology, mixed} —
    the coalesced watch-event category that woke the loop."""
    registry.inc(f"{_NAMESPACE}_micro_cycles_total", {"trigger": trigger})


def update_micro_cycle_duration(seconds: float) -> None:
    """volcano_micro_cycle_latency_milliseconds: wall-clock of one
    micro-cycle (wake → session closed) — the incremental-path twin of
    e2e_scheduling_latency, kept separate so full-cycle mass cannot
    hide a micro-path regression."""
    registry.histogram(
        f"{_NAMESPACE}_micro_cycle_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def observe_submit_to_bind(seconds: float) -> None:
    """volcano_submit_to_bind_latency_milliseconds: pod creation (store
    timestamp) → bind effect landed on the bus.  THE sustained-load SLO
    number (p99 < 100 ms at 10k jobs/sec is the ROADMAP target);
    recorded at the single bind-landing site shared by the synchronous
    and pipelined commit paths."""
    registry.histogram(
        f"{_NAMESPACE}_submit_to_bind_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def register_full_cycle_fallback(cause: str) -> None:
    """volcano_full_cycle_fallbacks_total{cause}: an event that wanted a
    micro-cycle ran (or forced) a full cycle instead.  cause ∈
    {gang-arrival, topology, registry-overflow, axis-change, node-set,
    pack-cold} — scheduler-level routing causes plus the pack-level
    causes PackCache.last_stats reports."""
    registry.inc(
        f"{_NAMESPACE}_full_cycle_fallbacks_total", {"cause": cause}
    )


# ---- incremental-session plane (volcano_tpu/incremental) ----
# The 1M-resident-job story in four series: how many jobs are resident
# vs actually schedulable (the micro-cycle working set), which scope
# each session opened at, and the shadow cross-check verdict stream
# that keeps the restricted path honest.


def update_resident_jobs(count: int) -> None:
    """volcano_resident_jobs: jobs resident in the scheduler cache
    (everything with a PodGroup, running or pending) — the O(resident)
    cost a full session pays and a restricted session does not."""
    registry.set_gauge(f"{_NAMESPACE}_resident_jobs", {}, count)


def update_schedulable_jobs(count: int) -> None:
    """volcano_schedulable_jobs: jobs with schedulable pending work
    (the share ledger's schedulable set) — the O(pending) working set a
    restricted session opens over."""
    registry.set_gauge(f"{_NAMESPACE}_schedulable_jobs", {}, count)


def register_session_scope(mode: str) -> None:
    """volcano_session_scope_total{mode}: one count per session opened,
    by scope."""
    # label-vocab: mode ∈ {full, restricted}, a static set
    registry.inc(f"{_NAMESPACE}_session_scope_total", {"mode": mode})


def register_share_ledger_drift_check(result: str) -> None:
    """volcano_share_ledger_drift_checks_total{result}: one count per
    shadow full-session cross-check of a restricted session.  Any
    divergence in the bind/evict outcome sets counts as
    result="divergence" (and raises in strict mode); a sustained ok
    stream is the production evidence the incremental ledger tracks
    swept truth."""
    # label-vocab: result ∈ {ok, divergence}, a static set
    registry.inc(
        f"{_NAMESPACE}_share_ledger_drift_checks_total", {"result": result}
    )


def observe_watch_batch(size: int) -> None:
    """volcano_bus_watch_batch_size: how many watch events one coalesced
    T_WATCH_BATCH frame carried (bus/server.py writer-thread
    coalescing) — loadgen churn multiplies watcher traffic, and this
    shows the fan-out amortization actually happening."""
    registry.histogram(
        f"{_NAMESPACE}_bus_watch_batch_size", {}, buckets=_COALESCE_BUCKETS
    ).observe(size)


# ---- sharded scheduler federation (volcano_tpu/federation) ----
# N scheduler processes each own a disjoint node shard via CAS leases;
# jobs that fail to place on their home shard spill over via optimistic
# CAS binds.  These four are the federation's vital signs: slice size,
# spillover pressure (the shard-hash-skew signal), ownership churn, and
# the lease plane's health.


def update_shard_nodes_owned(count: int) -> None:
    """volcano_shard_nodes_owned: nodes this scheduler currently owns
    through its shard leases (the slice the cache/pack planes cover)."""
    registry.set_gauge(f"{_NAMESPACE}_shard_nodes_owned", {}, count)


def register_spillover_bind(result: str) -> None:
    """volcano_spillover_binds_total{result}: cross-shard optimistic
    CAS bind outcomes.  result ∈ {bound, conflict, exhausted, no-fit,
    lost-race, error} — conflicts are the Omega model working as
    intended; a high no-fit/exhausted rate means the cluster (not just
    the home shard) is full or the shard hash is skewed."""
    registry.inc(
        f"{_NAMESPACE}_spillover_binds_total", {"result": result}
    )


def register_shard_rebalance(cause: str) -> None:
    """volcano_shard_rebalances_total{cause}: shard ownership moved.
    cause ∈ {expiry (absorbed a dead member's slice), join (claimed a
    free slice), release (shed a slice for a joining member)}."""
    registry.inc(
        f"{_NAMESPACE}_shard_rebalances_total", {"cause": cause}
    )


def observe_shard_lease_renew(seconds: float) -> None:
    """volcano_shard_lease_renew_latency_milliseconds: read-modify-CAS
    round trip of one successful shard-map renew tick — creeping toward
    the lease duration is the early warning before ownership flaps."""
    registry.histogram(
        f"{_NAMESPACE}_shard_lease_renew_latency_milliseconds", {}
    ).observe(seconds * 1e3)


def register_sketch_solicitation(result: str) -> None:
    """volcano_sketch_solicitations_total{result}: per-node outcomes of
    sketch-solicited foreign candidates (federation/sketches.py).
    result ∈ {verified (node truth read-back confirmed the sketch
    entry), stale (the sketch advertised a node the store says is gone
    or unschedulable — pruning signal, never a correctness event)}."""
    # label-vocab: result ∈ {verified, stale}, a static set
    registry.inc(
        f"{_NAMESPACE}_sketch_solicitations_total", {"result": result}
    )


def register_gang_assembly(result: str) -> None:
    """volcano_gang_assemblies_total{result}: cross-shard gang assembly
    outcomes (federation/broker.py).  result ∈ {committed (one
    txn_commit bound the gang whole), conflict (a claim went stale —
    assembly discarded whole, retried with backoff; the Omega model at
    gang granularity), aborted (transport/unsupported — incl. the
    pre-v6 old-peer refusal mode), infeasible (no full-gang placement
    exists in the ledger's view — the honest Pending outcome)}."""
    registry.inc(
        f"{_NAMESPACE}_gang_assemblies_total", {"result": result}
    )


def observe_txn_commit(seconds: float) -> None:
    """volcano_txn_commit_latency_milliseconds: the atomic multi-object
    transaction's round trip (VBUS v6) as the gang broker sees it —
    precondition sweep + N binds + one WAL fsync + quorum ack, over
    whichever backend the member holds."""
    registry.histogram(
        f"{_NAMESPACE}_txn_commit_latency_milliseconds", {}
    ).observe(seconds * 1e3)


# ---- flight recorder telemetry channel (volcano_tpu/obs) ----
# The channel's one invariant is drop-not-block, so the drop counter
# IS the health signal: a non-zero rate under steady load means the
# ring is undersized or the bus is rejecting segments.


def register_telemetry_dropped(reason: str, count: int = 1) -> None:
    """volcano_telemetry_dropped_total{reason}: spans the telemetry
    channel dropped instead of blocking a cycle.  reason ∈ {ring-full,
    export-error}."""
    registry.inc(
        f"{_NAMESPACE}_telemetry_dropped_total", {"reason": reason}, count
    )


def observe_telemetry_batch(size: int) -> None:
    """volcano_telemetry_batch_size: spans per exported segment batch
    (the channel's achieved batching; mass at 1 means the flush
    interval is outrunning emission)."""
    registry.histogram(
        f"{_NAMESPACE}_telemetry_batch_size", {}, buckets=_COALESCE_BUCKETS
    ).observe(size)


# ---- tail-based retention + SLO watchdog + incidents (obs/tail,slo,
# incident).  Same drop-not-block stance: these counters are the only
# way a squeezed pending pool or a boost-window capture is visible.


def register_telemetry_tail_eviction(reason: str) -> None:
    """volcano_telemetry_tail_evictions_total{reason}: pending-pool
    traces that could not wait for their completion-time decision and
    fell back to the head coin.  reason ∈ {pool-full, timeout}."""
    registry.inc(
        f"{_NAMESPACE}_telemetry_tail_evictions_total", {"reason": reason}
    )


def register_telemetry_tail_decision(result: str) -> None:
    """volcano_telemetry_tail_decisions_total{result}: completion-time
    keep/drop decisions (anomaly keeps, settled coins, peer-resolved).
    result ∈ {keep, drop}."""
    registry.inc(
        f"{_NAMESPACE}_telemetry_tail_decisions_total", {"result": result}
    )


def update_slo_burn(slo: str, window: str, value: float) -> None:
    """volcano_slo_burn{slo,window}: the burn-rate watchdog's current
    consumption ratio per declared SLO and evaluation window (>= 1.0
    in BOTH windows = breach).  window ∈ {fast, slow}."""
    # label-vocab: slo — the declared SLO names (obs/slo.py
    # DEFAULT_SLOS, a static per-process set)
    registry.set_gauge(
        f"{_NAMESPACE}_slo_burn", {"slo": slo, "window": window}, value
    )


def register_incident_captured(trigger: str) -> None:
    """volcano_incidents_captured_total{trigger}: incident bundles this
    daemon wrote."""
    # label-vocab: trigger — the declared SLO names plus
    # {manual, watchdog}; routed through bounded_label at the manager
    # so an operator-shaped reason cannot mint unbounded series
    registry.inc(
        f"{_NAMESPACE}_incidents_captured_total",
        {"trigger": bounded_label(
            f"{_NAMESPACE}_incidents_captured_total", "trigger", trigger
        )},
    )


def update_capture_boost(active: float) -> None:
    """volcano_capture_boost_active: 1 while this daemon's exporter is
    inside a cluster capture-boost window (sample rate forced to 1.0),
    else 0."""
    registry.set_gauge(f"{_NAMESPACE}_capture_boost_active", {}, active)


# ---- TPU-build additions: per-kernel phase timings ----

def update_kernel_duration(phase: str, seconds: float) -> None:
    """phase ∈ {pack, compile, transfer, execute} for the device session
    kernel, from the duration of the phase's span (volcano_tpu/trace),
    which feeds the cycle recorder and the flight recorder itself."""
    registry.histogram(
        f"{_NAMESPACE}_tpu_kernel_latency_milliseconds", {"phase": phase}
    ).observe(seconds * 1e3)


# ---- where jax-allocate's APPLY sends each task ----
# The device resolves a task in APPLY when its proposal validates, when
# the bulk commit places it, or when the reason counts prove it fits no
# node; every other task takes the host predicate sweep over all nodes,
# the O(tasks × nodes) path.  These say how many fell off the device
# path in a slow cycle and how much host work that cost.

#: the APPLY paths, in the order the per-task chooser tries them
APPLY_PATHS = ("fast_apply", "proposal", "explained", "host_sweep")


def register_allocate_apply(paths: Dict[str, int], rejected: int) -> None:
    """volcano_allocate_apply_tasks_total{path}: tasks jax-allocate's
    APPLY resolved by each path, path ∈ {fast_apply, proposal,
    explained, host_sweep}; volcano_allocate_proposals_rejected_total:
    kernel proposals that failed host validation and fell through to
    the explanation or the sweep."""
    for path, n in paths.items():
        if n:
            registry.inc(
                f"{_NAMESPACE}_allocate_apply_tasks_total", {"path": path}, n
            )
    if rejected:
        registry.inc(f"{_NAMESPACE}_allocate_proposals_rejected_total", {}, rejected)


def register_explain_tail(tasks: int) -> None:
    """volcano_allocate_explain_tail_tasks_total: pending tasks ORDER did
    not list that APPLY packed and explained on the device in one pass
    (the tail explanation of actions/jax_allocate) instead of sweeping
    each over every node on the host."""
    registry.inc(f"{_NAMESPACE}_allocate_explain_tail_tasks_total", {}, tasks)


def register_spread_rows(device: int, host: int) -> None:
    """volcano_allocate_spread_rows_total{path}: pending tasks with
    topology spread constraints that jax-allocate packed, by where their
    constraints are decided, path ∈ {device, host}: ``device`` when the
    kernel encodes every hard constraint of the row exactly, ``host``
    when the row keeps host validation or host scoring (ScheduleAnyway,
    minDomains, matchLabelKeys, an empty selector, class overflow)."""
    for path, n in (("device", device), ("host", host)):
        if n:
            registry.inc(
                f"{_NAMESPACE}_allocate_spread_rows_total", {"path": path}, n
            )


def register_gang_outcome(outcome: str, path: str, n: int = 1) -> None:
    """volcano_allocate_gang_outcomes_total{outcome, path}: jobs whose
    gang decision allocate made — commit or discard at the end of the
    job's first episode — outcome ∈ {committed, discarded}, by where it
    was made, path ∈ {device, host}: ``device`` when jax-allocate
    applied the kernel's decision in bulk, ``host`` when the Statement
    loop's gang_end_job made it."""
    if n:
        registry.inc(
            f"{_NAMESPACE}_allocate_gang_outcomes_total",
            {"outcome": outcome, "path": path}, n,
        )


def register_order_cut(rows: int) -> None:
    """volcano_allocate_order_cut_rows_total{reason}: rows of ORDER
    jax-allocate handed to the host chooser because a gang the kernel
    did not commit left a share ORDER's simulation assumed placed;
    reason ∈ {queue_share}: shares decide between queues."""
    if rows:
        registry.inc(
            f"{_NAMESPACE}_allocate_order_cut_rows_total", {"reason": "queue_share"}, rows
        )


def register_gang_rollback(tasks: int) -> None:
    """volcano_allocate_gang_rollback_tasks_total: rows the allocate
    kernel placed and then undid when their gang was discarded."""
    if tasks:
        registry.inc(f"{_NAMESPACE}_allocate_gang_rollback_tasks_total", {}, tasks)


def register_affinity_rows(device: int, host: int) -> None:
    """volcano_allocate_affinity_rows_total{path}: pending tasks under a
    required inter-pod term — one of their own, or a placed or pending
    pod's anti-affinity term they match — that jax-allocate packed, by
    where the terms are decided, path ∈ {device, host}: ``device`` when
    the kernel encodes every such term of the row as an anti-affinity
    class, ``host`` when the row keeps host validation or host scoring
    (required pod affinity, a namespace selector, label keys, class
    overflow, preferred terms)."""
    for path, n in (("device", device), ("host", host)):
        if n:
            registry.inc(
                f"{_NAMESPACE}_allocate_affinity_rows_total", {"path": path}, n
            )


def register_host_sweep(nodes: int) -> None:
    """volcano_allocate_host_sweep_nodes_total: nodes whose predicates
    one host sweep (actions/allocate.host_node_chooser) evaluated."""
    registry.inc(f"{_NAMESPACE}_allocate_host_sweep_nodes_total", {}, nodes)


def gc_pause_histograms() -> List[_Histogram]:
    """volcano_gc_pause_seconds{generation}, generation 0, 1 and 2: the
    histograms the gc callback (utils/gcutil.py) adds each collection's
    pause to.  The callback may fire while this thread holds the registry
    lock, so they are observed without it."""
    return [
        registry.unlocked_histogram(
            f"{_NAMESPACE}_gc_pause_seconds", {"generation": str(g)},
            _GC_PAUSE_BUCKETS_S,
        )
        for g in range(3)
    ]


def register_gc_quiesce(reason: str, frozen: int) -> None:
    """volcano_gc_quiesce_total{reason}: quiesces the collector policy
    (utils/gcutil.py) ran at a cycle boundary, reason ∈ {first, growth};
    volcano_gc_frozen_objects: the objects the last one froze."""
    registry.inc(f"{_NAMESPACE}_gc_quiesce_total", {"reason": reason})
    registry.set_gauge(f"{_NAMESPACE}_gc_frozen_objects", {}, frozen)
