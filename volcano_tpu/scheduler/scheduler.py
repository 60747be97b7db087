"""Scheduler loop — load conf, open session, run actions, close session.

Reference: pkg/scheduler/scheduler.go (a fixed-period wait.Until loop).

This build adds an opt-in event-driven mode (``micro_cycles=True`` /
``vtpu-scheduler --micro-cycles``): the watch stream is the sole bus
(PAPER.md layer map), and the cache's event handlers already classify
every event, so instead of a freshly-submitted pod waiting out the next
full fixed-period cycle, the loop sleeps on a condition variable and
wakes when the cache reports schedulable change.  A debounce window
coalesces event storms into one **micro-cycle**; periodic **full
cycles** (every ``period``) keep running for fair-share/gang
re-equilibration, and events whose class makes incremental treatment
pointless (gang arrival — the members land as a storm right behind the
PodGroup — or a node-set change, which wholesale-invalidates the packed
planes) route straight to an immediate full cycle, counted in
``volcano_full_cycle_fallbacks_total{cause}``.

Soundness: a micro-cycle runs the SAME session machinery over the same
full snapshot as a full cycle — micro vs. full is a *physical* split
(what woke the loop, and how much the warm packer rebuilds:
ops/pack_cache.py packs only fresh task rows against the persistent
device-resident node planes), never a semantic one.  Bindings are
therefore bit-identical to a full cycle over the same store state by
construction, and tests/test_micro_cycle.py pins it end-to-end through
``trace.replay.verify``.

Either mode, the inter-cycle sleep is a condition wait: shutdown (and,
in event mode, event arrival) no longer waits out ``--schedule-period``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional

from volcano_tpu import actions as _actions  # noqa: F401 — registers actions
from volcano_tpu import plugins as _plugins  # noqa: F401 — registers plugin builders
from volcano_tpu import trace
from volcano_tpu.cache.interface import Cache
from volcano_tpu.conf import (
    default_scheduler_conf,
    load_scheduler_conf,
    SchedulerConf,
)
from volcano_tpu.framework import close_session, get_action, open_session
from volcano_tpu.framework.interface import Action
from volcano_tpu.incremental import subgraph
from volcano_tpu.metrics import metrics
from volcano_tpu.utils import gcutil
from volcano_tpu.utils.logging import get_logger

log = get_logger(__name__)

DEFAULT_SCHEDULE_PERIOD = 1.0  # options.go:28


class Scheduler:
    """scheduler.go:45-106."""

    #: event categories that route to an immediate full cycle instead of
    #: a micro-cycle, with the fallback-counter cause they record
    _FULL_CAUSES = {"gang": "gang-arrival", "topology": "topology"}

    def __init__(
        self,
        cache: Cache,
        scheduler_conf_path: str = "",
        period: float = DEFAULT_SCHEDULE_PERIOD,
        cycle_deadline_ms: Optional[float] = None,
        micro_cycles: bool = False,
        micro_debounce_ms: float = 5.0,
        restricted_sessions: bool = False,
        shadow_every: int = 16,
        shadow_strict: bool = False,
    ):
        self.cache = cache
        #: cycle watchdog (--cycle-deadline-ms): arms a process-global
        #: wall-clock budget; the device phase (ops/executor) runs under
        #: the remaining budget and an overrun completes the cycle on
        #: the host path.  None leaves the global watchdog untouched
        #: (so auxiliary Scheduler instances can't disarm a configured
        #: daemon's deadline).
        if cycle_deadline_ms is not None:
            from volcano_tpu.faults import watchdog

            watchdog.configure_deadline(cycle_deadline_ms)
        self.scheduler_conf_path = scheduler_conf_path
        self.period = period
        self._stopped = False
        #: monotonically increasing cycle sequence — the cross-process
        #: correlation id when no trace recorder assigns one
        self._cycle_seq = -1

        # ---- event-driven micro-cycles ----
        self.micro_cycles = micro_cycles
        self.micro_debounce_s = max(micro_debounce_ms, 0.0) / 1e3
        #: wake condition the inter-cycle sleep parks on; cache change
        #: listeners (and stop()) notify it
        self._wake = threading.Condition()
        #: category → events seen since the last cycle consumed them
        self._pending_triggers: Dict[str, int] = {}  # guarded-by: self._wake
        #: fallback cause pending a full cycle (gang arrival / topology
        #: change), or None
        self._full_cause: Optional[str] = None  # guarded-by: self._wake
        self._listener_attached = False
        #: post-cycle hook, invoked after every run_once outside the
        #: session (the federation spillover pass hangs here — work that
        #: must see the cycle's outcome but never run concurrently with
        #: a session).  Exceptions are logged, never kill the loop.
        self.post_cycle: Optional[Callable[[], None]] = None
        # ---- restricted-subgraph sessions (incremental/subgraph.py) ----
        #: opt-in: micro-cycles whose conf is entirely within
        #: RESTRICTABLE_ACTIONS open over only the jobs with schedulable
        #: work plus the share ledger's seed — O(pending) instead of
        #: O(resident).  Periodic full cycles are untouched.
        self.restricted_sessions = restricted_sessions
        #: shadow cross-check sampling: every Nth restricted cycle also
        #: runs a store-inert FULL session over the same snapshot and
        #: fails on ANY binding divergence.  1 = every restricted cycle
        #: (the test setting), 0 = never.
        self.shadow_every = shadow_every
        #: strict mode raises ShadowDivergence instead of only counting
        #: it in volcano_share_ledger_drift_checks_total{result}
        self.shadow_strict = shadow_strict
        self._restricted_since_shadow = 0
        #: observability for tests and bench/loadgen.py
        self.micro_cycles_run = 0
        self.full_cycles_run = 0
        self.restricted_cycles_run = 0
        self.shadow_divergences = 0
        #: cumulative wall time spent opening sessions (snapshot +
        #: plugin on_session_open; sampled shadow cross-checks excluded)
        #: and the count behind the mean — loadgen --resident-sweep
        #: gates the per-session open cost on these
        self.session_open_seconds = 0.0
        self.sessions_opened = 0
        #: the restricted-only slice of the above: periodic full cycles
        #: stay O(resident) by design, so the O(pending) claim is gated
        #: on the micro-cycle (restricted) open cost alone.  Sampled
        #: shadow-audit cycles pay an O(resident) shadow snapshot and
        #: are excluded too — hence the separate cycle count.
        self.restricted_open_seconds = 0.0
        self.restricted_open_cycles = 0
        #: per-cycle samples behind the sweep's MEDIAN gate (a single
        #: GC/contention stall in a short CI run should not read as an
        #: O(resident) regression); bounded so resident campaigns don't
        #: grow it without limit
        self.restricted_open_samples: List[float] = []
        self.shadow_checks_run = 0
        #: conf hot-reload cache: (mtime_ns, size) of the last parse
        self._conf_key = None
        self._conf_cached: Optional[SchedulerConf] = None
        self._default_conf: Optional[SchedulerConf] = None
        if micro_cycles:
            self.attach_cache_events()
        gcutil.install_gc_hook()

    # ---- event wake plumbing ----

    def attach_cache_events(self) -> None:
        """Register this scheduler as the cache's change listener
        (idempotent).  Caches without the listener surface (bare test
        fakes) simply leave the loop purely periodic."""
        if self._listener_attached:
            return
        add = getattr(self.cache, "add_change_listener", None)
        if add is None:
            return
        add(self.notify_event)
        self._listener_attached = True

    def notify_event(self, category: str) -> None:
        """Cache change listener: record the trigger and wake the loop.
        Runs on whatever thread delivered the watch event — must stay
        cheap and lock only the wake condition."""
        with self._wake:
            cause = self._FULL_CAUSES.get(category)
            if cause is not None and self._full_cause is None:
                self._full_cause = cause
            self._pending_triggers[category] = (
                self._pending_triggers.get(category, 0) + 1
            )
            self._wake.notify_all()

    def _drain_triggers(self) -> Dict[str, int]:
        """Capture-and-clear the pending trigger set.  Called at cycle
        START, so events landing while the cycle runs re-arm the wake
        instead of being silently consumed by a snapshot that predates
        them."""
        with self._wake:
            pending, self._pending_triggers = self._pending_triggers, {}
            return pending

    def _take_full_cause(self) -> Optional[str]:
        with self._wake:
            cause, self._full_cause = self._full_cause, None
            return cause

    def _full_due(self) -> bool:
        with self._wake:
            return self._full_cause is not None

    def _wait_wake(self, timeout: float, for_events: bool) -> bool:
        """Park until ``timeout`` elapses — or, with ``for_events``,
        until a trigger arrives — always waking immediately on stop().
        Returns True when an event (or pending full cause) is waiting."""
        deadline = time.monotonic() + timeout
        with self._wake:
            while not self._stopped:
                if for_events and (
                    self._pending_triggers or self._full_cause is not None
                ):
                    return True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._wake.wait(remaining)
        with self._wake:
            return bool(self._pending_triggers) or self._full_cause is not None

    @staticmethod
    def _trigger_label(pending: Dict[str, int]) -> str:
        """Metric label for a coalesced wake: the single category, or
        ``mixed`` when the debounce window batched several kinds."""
        cats = [c for c in pending if c not in ("gang", "topology")] or list(
            pending
        )
        return cats[0] if len(cats) == 1 else "mixed"

    def _load_conf(self) -> SchedulerConf:
        """Hot-reload every cycle (scheduler.go:77,89-106) — but parse
        only when the file actually changed: the YAML parse costs ~7 ms,
        a third of a whole steady-state micro-cycle, and the mtime stat
        preserves the hot-reload semantics exactly."""
        if self.scheduler_conf_path and os.path.exists(self.scheduler_conf_path):
            try:
                st = os.stat(self.scheduler_conf_path)
                key = (st.st_mtime_ns, st.st_size)
                if self._conf_key == key and self._conf_cached is not None:
                    return self._conf_cached
                with open(self.scheduler_conf_path) as f:
                    conf = load_scheduler_conf(f.read())
                self._conf_key, self._conf_cached = key, conf
                return conf
            except Exception as e:  # noqa: BLE001 — fall back to defaults
                log.error("Failed to load scheduler conf: %s", e)
                self._conf_key = self._conf_cached = None
        if self._default_conf is None:
            self._default_conf = default_scheduler_conf()
        return self._default_conf

    def _resolve_actions(self, conf: SchedulerConf) -> List[Action]:
        out = []
        for name in conf.actions:
            action = get_action(name)
            if action is None:
                log.error("Failed to find action %s", name)
                continue
            out.append(action)
        return out

    def run_once(self, trigger: str = "full") -> None:
        """scheduler.go:71-87.  ``trigger`` is "full" for periodic/forced
        full cycles, else the coalesced watch-event category that woke an
        event-driven micro-cycle (the ``volcano_micro_cycles_total``
        label).  The SESSION is identical either way — micro vs. full
        only governs wake accounting and how much the warm packer
        rebuilds."""
        from volcano_tpu.faults import watchdog

        micro = trigger != "full"
        # consumed by jax-allocate to attribute pack-level cold fallbacks
        # (registry overflow etc.) during a micro-triggered cycle; plain
        # attribute, single-threaded cycle-loop discipline
        self.cache.in_micro_cycle = micro

        watchdog.begin_cycle()  # stamp the cycle-deadline budget
        rec = trace.get_recorder()
        cid = rec.begin_cycle()
        # cycle correlation id: the recorder's cycle id when tracing,
        # else a local sequence — attached to VBUS request frames
        # (bus/remote.py) so bus/controller-side records can be joined
        # back to the scheduling cycle that caused them
        self._cycle_seq += 1
        cycle_no = cid if cid >= 0 else self._cycle_seq
        trace.set_current_cycle(cycle_no)
        # the cycle's span, on the profiler's clock; in the flight
        # recorder (volcano_tpu/obs) a process-scope span that per-pod
        # bind/commit spans parent to, and the ambient context every
        # VBUS request this cycle issues propagates (bus/remote.py).
        # Entered manually so the try/finally journaling structure
        # stays untouched.
        cycle_span = trace.span(
            "vtpu/cycle", "scheduler",
            label=f"cycle:{trigger if micro else 'full'}", cycle=cycle_no,
        )
        cycle_span.__enter__()
        ssn = None
        rec_cache = None
        shadow_outcome = None
        try:
            conf = self._load_conf()
            actions = self._resolve_actions(conf)

            restricted = (
                micro
                and self.restricted_sessions
                and subgraph.conf_is_restrictable(conf.actions)
                and getattr(self.cache, "share_ledger", None) is not None
            )
            if restricted:
                shadow = self.shadow_every > 0 and (
                    self._restricted_since_shadow + 1 >= self.shadow_every
                )
                # one atomic snapshot feeds BOTH the restricted session
                # and (when sampled) its shadow full-session cross-check
                # — the restricted job set is computed inside the cache
                # mutex, so churn between two snapshots can never read
                # as a false divergence
                t_open = time.perf_counter()
                snap = self.cache.snapshot(
                    scope="shadow" if shadow else "restricted"
                )
                open_s = time.perf_counter() - t_open
                if shadow:
                    self._restricted_since_shadow = 0
                    shadow_outcome = subgraph.run_shadow_session(
                        self.cache, snap, conf.tiers,
                        conf.configurations, actions,
                    )
                else:
                    self._restricted_since_shadow += 1
                rec_cache = subgraph.RecordingCache(self.cache)
                ssn = open_session(
                    rec_cache, conf.tiers, conf.configurations,
                    snapshot=snap, job_uids=snap.restricted_uids,
                )
                # the sampled shadow run between snapshot and open is
                # soundness auditing, not steady-state open cost
                open_s += ssn.open_seconds
                if not shadow:
                    self.restricted_open_seconds += open_s
                    self.restricted_open_cycles += 1
                    if len(self.restricted_open_samples) < 65536:
                        self.restricted_open_samples.append(open_s)
                self.restricted_cycles_run += 1
                metrics.register_session_scope("restricted")
            else:
                ssn = open_session(
                    self.cache, conf.tiers, conf.configurations
                )
                open_s = ssn.open_seconds
                metrics.register_session_scope("full")
            self.session_open_seconds += open_s
            self.sessions_opened += 1
            for action in actions:
                name = action.name()
                with trace.span(
                    f"vtpu/action.{name}", "action", label=f"action:{name}"
                ) as sp:
                    action.execute(ssn)
                metrics.update_action_duration(name, sp.seconds)
            if shadow_outcome is not None:
                self.shadow_checks_run += 1
                shadow_binds, shadow_evicts = shadow_outcome
                diffs = subgraph.compare_outcomes(
                    rec_cache.binds, rec_cache.evicts,
                    shadow_binds, shadow_evicts,
                )
                if diffs is None:
                    metrics.register_share_ledger_drift_check("ok")
                else:
                    self.shadow_divergences += 1
                    metrics.register_share_ledger_drift_check("divergence")
                    log.error(
                        "restricted session diverged from shadow full "
                        "session (%d diffs): %s",
                        len(diffs), "; ".join(diffs),
                    )
                    if self.shadow_strict:
                        # raised inside the try so close_session still
                        # runs for the (real) restricted session
                        raise subgraph.ShadowDivergence(diffs)
        except BaseException:
            # an action that raised may have written a clone it never
            # marked touched: a stale generation hands nothing back, so
            # the next snapshot clones every object
            if ssn is not None:
                ssn.clone_gen = 0
            raise
        finally:
            try:
                # ssn is None when open_session itself crashed (a plugin
                # on_session_open is the likeliest site) — that cycle's
                # spans still get journaled below
                if ssn is not None:
                    close_session(ssn)
            finally:
                # close the cycle BEFORE the quiesce: the collection
                # pause is maintenance, not scheduling latency — folding
                # it in would spike the cycle that quiesces
                cycle_span.__exit__(None, None, None)
                elapsed = cycle_span.seconds
                # the collector policy: freeze the long-lived cache graph
                # out of full collections.  In a finally so persistently
                # failing cycles (BaseDaemon retries them) still thaw and
                # collect previously frozen dead objects
                gcutil.after_cycle(
                    getattr(self.cache, "resident_objects", lambda: 0)
                )
                # journal flush sits outside the e2e latency stamp for
                # the same reason the gc quiesce does (maintenance I/O),
                # but in the innermost finally: a cycle that crashes in
                # session open, an action, OR session close is exactly
                # the one the forensics journal must not drop
                rec.end_cycle(duration_s=elapsed)
                self.cache.in_micro_cycle = False
        metrics.update_e2e_duration(elapsed)
        counts = getattr(self.cache, "ledger_counts", None)
        if counts is not None:
            resident, schedulable = counts()
            metrics.update_resident_jobs(resident)
            metrics.update_schedulable_jobs(schedulable)
        if micro:
            self.micro_cycles_run += 1
            metrics.register_micro_cycle(trigger)
            metrics.update_micro_cycle_duration(elapsed)
        else:
            self.full_cycles_run += 1
        if self.post_cycle is not None:
            try:
                self.post_cycle()
            except Exception as e:  # noqa: BLE001 — a hook failure must
                # not take the scheduling loop down with it
                log.error("post-cycle hook failed: %s", e)

    def run_cycle_window(self, max_cycles: Optional[int] = None) -> int:
        """One full-cycle period of the event-driven loop: a full cycle
        now (counting the fallback cause when an event class forced it),
        then debounced micro-cycles on watch-event arrival until the
        next full cycle is due.  Returns the number of cycles run —
        the daemon's ``_work`` body and :meth:`run`'s micro mode share
        this single copy."""
        window_start = time.monotonic()
        cause = self._take_full_cause()
        if cause is not None:
            metrics.register_full_cycle_fallback(cause)
        self._drain_triggers()  # the full cycle serves everything pending
        self.run_once()
        ran = 1
        deadline = window_start + self.period
        while not self._stopped and (max_cycles is None or ran < max_cycles):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            if not self._wait_wake(remaining, for_events=True):
                break  # period elapsed quietly — window ends
            if self._full_due():
                break  # gang/topology event: next window's full cycle
            if self.micro_debounce_s > 0:
                # debounce: let the rest of the storm land, then one
                # micro-cycle serves the whole coalesced batch
                self._wait_wake(self.micro_debounce_s, for_events=False)
                if self._stopped:
                    break
                if self._full_due():
                    break
            pending = self._drain_triggers()
            if not pending:
                continue
            if "task" not in pending and not self._has_pending_work():
                # capacity-freed / object churn woke us but nothing is
                # pending — a session would bind nothing.  The next
                # event (or the periodic full cycle) re-checks.
                continue
            self.run_once(trigger=self._trigger_label(pending))
            ran += 1
        return ran

    def _has_pending_work(self) -> bool:
        check = getattr(self.cache, "has_schedulable_pending", None)
        return True if check is None else bool(check())

    def run(self, cycles: Optional[int] = None) -> None:
        """scheduler.go:63-69 — wait.Until(runOnce, period); in micro
        mode, the event-driven window loop instead."""
        self.cache.run()
        self.cache.wait_for_cache_sync()
        if self.micro_cycles:
            self.attach_cache_events()
        n = 0
        while not self._stopped:
            if self.micro_cycles:
                n += self.run_cycle_window(
                    max_cycles=None if cycles is None else cycles - n
                )
                if cycles is not None and n >= cycles:
                    break
                continue
            cycle_start = time.monotonic()
            self.run_once()
            n += 1
            if cycles is not None and n >= cycles:
                break
            # interruptible: shutdown no longer waits out the period
            self._wait_wake(
                self.period - (time.monotonic() - cycle_start),
                for_events=False,
            )

    def stop(self) -> None:
        self._stopped = True
        with self._wake:
            self._wake.notify_all()
