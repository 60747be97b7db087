"""Test env: force JAX onto CPU with 8 virtual devices so multi-chip
sharding paths are exercised without TPU hardware, and turn JAX's
persistent compile cache off.  The chip's own path is checked by
``chip_smoke.py`` (run through the chip tool) and, without a chip, by the
compiles in tests/test_tpu_compile.py.

Suite-wide guards live here too:

* **Thread-leak guard** — every test asserts it left no new
  *non-daemon* threads behind (a small named allowlist excepted).  An
  abandoned bind worker or watchdog thread fails the test that leaked
  it, loudly and with the thread names, instead of wedging the exit of
  some unrelated later test.
* **Fd/socket-leak guard** — the thread guard's twin, one layer down:
  a ``/proc/self/fd`` snapshot diff asserts no new sockets or
  real-file descriptors (bus clients, WAL handles) survive a test,
  with a target-pattern allowlist for interpreter/test-infra plumbing.
  Disarmed under ``VTPU_RACE`` (the race detector pins tracked
  instances alive, so their fds outlive tests by design).
* **Lock-order verifier** (opt-in, ``VTPU_LOCK_ORDER=1``) — wraps every
  lock volcano_tpu creates in the instrumented proxy from
  ``volcano_tpu.analysis.lock_order``, records the cross-thread
  acquisition graph, fails the leaking test on any ABBA inversion, and
  fails the session if the final graph has a cycle.  CI runs the chaos
  and commit-plane suites under it; ``VTPU_LOCK_ORDER_REPORT=<path>``
  additionally dumps the acquisition graph as JSON.
* **Happens-before race detector** (opt-in, ``VTPU_RACE=1``) — the
  enforcement layer over the ``# guarded-by:`` declarations: installs
  before any volcano_tpu import (lock factories + thread/queue/event
  patches + tracking descriptors on every declared attribute), fails
  the test that recorded a fresh race and the session on any race;
  ``VTPU_RACE_REPORT=<path>`` dumps the full report.  CI runs the
  chaos, commit-plane, federation and bus-HA suites under it.
"""

import json
import os
import threading
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# no persistent compile cache in the suite, daemons it spawns included
# (volcano_tpu/utils/compile_cache.py)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# must precede any volcano_tpu import so every lock construction runs
# through the patched factories
_LOCK_ORDER = os.environ.get("VTPU_LOCK_ORDER") == "1"
if _LOCK_ORDER:
    from volcano_tpu.analysis import lock_order

    lock_order.install()

# the happens-before race detector rides the same proxies (it installs
# them itself when the lock-order verifier is off) and additionally
# wraps every `# guarded-by:`-declared attribute in the tree — so the
# install AND the class instrumentation must both precede the system
# under test's imports/instance construction
_RACE = os.environ.get("VTPU_RACE") == "1"
if _RACE:
    from volcano_tpu.analysis import race

    race.install()
    _RACE_INSTRUMENTATION = race.instrument_package()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---- thread-leak guard ----

#: non-daemon threads these names (prefixes) are allowed to outlive a
#: test: pytest/session infrastructure only.  Project threads are all
#: daemon=True by convention — anything non-daemon left running is a
#: shutdown bug (the exact class this guard exists for: abandoned
#: watchdog / bind-worker threads used to wedge interpreter exit).
_LEAK_ALLOWLIST = (
    "MainThread",
    "pytest_timeout",      # pytest-timeout watcher, when installed
    "ThreadPoolExecutor",  # joined at interpreter exit by concurrent.futures
)
_LEAK_GRACE_S = 2.0


def _leaked_nondaemon(before):
    return [
        t for t in threading.enumerate()
        if t not in before
        and t.is_alive()
        and not t.daemon
        and not t.name.startswith(_LEAK_ALLOWLIST)
    ]


@pytest.fixture(autouse=True)
def _identity_label_guard():
    """Daemons started inside a test stamp process-global identity
    labels (metrics.set_identity) that would re-label every series a
    LATER test renders — clear just the identity (never the counters,
    which tests manage themselves) so cross-test isolation matches the
    pre-identity-label world."""
    yield
    from volcano_tpu.metrics import metrics as _metrics

    _metrics.registry.set_identity()


@pytest.fixture(autouse=True)
def _thread_leak_guard():
    before = set(threading.enumerate())
    yield
    leaked = _leaked_nondaemon(before)
    if leaked:
        # teardown finalizers may still be joining — give them a moment
        deadline = time.monotonic() + _LEAK_GRACE_S
        while leaked and time.monotonic() < deadline:
            time.sleep(0.05)
            leaked = _leaked_nondaemon(before)
    assert not leaked, (
        "test leaked non-daemon thread(s): "
        + ", ".join(sorted(t.name for t in leaked))
        + " — stop/join them in the test (or daemonize them if they are "
        "genuinely fire-and-forget)"
    )


# ---- fd/socket-leak guard (the thread guard's twin) ----

#: fd targets these substrings match may survive a test: interpreter /
#: test-infra machinery only.  Project sockets and files (bus
#: connections, WAL handles, journals) must be closed by the test that
#: opened them — an unclosed WAL handle or bus socket is the shutdown
#: bug class the thread guard catches, one layer down.
_FD_ALLOWLIST = (
    "/dev/",            # urandom, null, tty — interpreter plumbing
    "/proc/",
    "/sys/",
    "pipe:",            # pytest capture + subprocess plumbing
    "anon_inode:",      # epoll/eventfd (asyncio, JAX runtime)
    "/memfd",
    "(deleted)",        # unlinked tempfiles (pytest capsys machinery)
    "/usr/",            # stdlib/site-packages handles (zipimport etc.)
    ".local/lib",       # pip --user site-packages, same class
)


def _fd_table():
    """fd → readlink target, or None where /proc is unavailable (the
    guard silently disarms off-Linux)."""
    try:
        entries = os.listdir("/proc/self/fd")
    except OSError:
        return None
    table = {}
    for e in entries:
        try:
            table[int(e)] = os.readlink(f"/proc/self/fd/{e}")
        except (OSError, ValueError):
            continue  # closed between listdir and readlink
    return table


def _leaked_fds(before):
    now = _fd_table()
    if now is None:
        return []
    return sorted(
        (fd, target) for fd, target in now.items()
        if before.get(fd) != target
        and (target.startswith("socket:") or target.startswith("/"))
        and not any(pat in target for pat in _FD_ALLOWLIST)
    )


@pytest.fixture(autouse=True)
def _fd_leak_guard():
    if _RACE:
        # the race detector pins every tracked instance alive (shadow
        # state is keyed by id(); releasing an object would let a
        # recycled id inherit dead epochs), so sockets those instances
        # hold outlive their tests by design — the leak signal is
        # meaningless under VTPU_RACE.  The plain tier-1 job keeps the
        # guard armed.
        yield
        return
    before = _fd_table()
    if before is None:
        yield
        return
    yield
    leaked = _leaked_fds(before)
    if leaked:
        # a client abandoned inside an exception traceback sits in a
        # reference cycle — its socket closes only when the cycle
        # collector runs, so force that before calling it a leak.
        # INSIDE the grace loop: a daemon thread exiting during the
        # wait can drop the cycle's last external reference, so one
        # up-front collect would miss it
        import gc

        # daemon teardown may still be closing — same grace as threads
        deadline = time.monotonic() + _LEAK_GRACE_S
        while leaked and time.monotonic() < deadline:
            gc.collect()
            leaked = _leaked_fds(before)
            if leaked:
                time.sleep(0.05)
    assert not leaked, (
        "test leaked file descriptor(s): "
        + ", ".join(f"fd {fd} -> {t}" for fd, t in leaked)
        + " — close them in the test (bus clients, WAL stores and "
        "exporters all have close()/stop())"
    )


# ---- lock-order verifier + race detector wiring ----

if _LOCK_ORDER:

    @pytest.fixture(autouse=True)
    def _lock_order_guard():
        """Fail the test that CLOSED a lock-order cycle — per-test
        attribution beats one opaque session-end failure."""
        n_before = len(lock_order.violations())
        yield
        fresh = lock_order.violations()[n_before:]
        assert not fresh, (
            "lock-order inversion(s) recorded during this test:\n"
            + "\n".join(v.render() for v in fresh)
        )


if _RACE:

    @pytest.fixture(autouse=True)
    def _race_guard():
        """Fail the test whose schedule exposed a data race — the
        lock-order guard's per-test attribution, for the HB engine."""
        n_before = len(race.races())
        yield
        fresh = race.races()[n_before:]
        assert not fresh, (
            "happens-before race(s) recorded during this test:\n"
            + "\n".join(r.render() for r in fresh)
        )


if _LOCK_ORDER or _RACE:

    def pytest_sessionfinish(session, exitstatus):
        failed = False
        if _LOCK_ORDER:
            report = lock_order.report()
            path = os.environ.get("VTPU_LOCK_ORDER_REPORT")
            if path:
                with open(path, "w", encoding="utf-8") as f:
                    json.dump(report, f, indent=2)
                    f.write("\n")
            failed = failed or bool(report["violations"])
        if _RACE:
            path = os.environ.get("VTPU_RACE_REPORT")
            if path:
                race.dump_report(
                    path, extra={"instrumentation": _RACE_INSTRUMENTATION}
                )
            failed = failed or bool(race.races())
        if failed:
            session.exitstatus = 3

    def pytest_terminal_summary(terminalreporter):
        if _LOCK_ORDER:
            report = lock_order.report()
            terminalreporter.write_line(
                f"lock-order verifier: {report['locks']} instrumented "
                f"locks, {len(report['edges'])} acquisition edges, "
                f"{len(report['violations'])} violation(s)"
            )
            for v in report["violations"]:
                terminalreporter.write_line(v)
        if _RACE:
            rep = race.report()
            terminalreporter.write_line(
                f"race detector: {rep['accesses']} tracked accesses over "
                f"{rep['tracked_vars']} guarded variables "
                f"({_RACE_INSTRUMENTATION['instrumented_attrs']} "
                f"instrumented attrs, "
                f"{len(_RACE_INSTRUMENTATION['waived'])} waived), "
                f"{len(rep['races'])} race(s)"
            )
            for r in race.races():
                terminalreporter.write_line(r.render())
