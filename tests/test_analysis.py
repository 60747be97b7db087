"""Static-analysis suite coverage: every pass gets positive (finding
expected) and negative (clean) fixture snippets, the baseline
suppression machinery round-trips, the serde-drift pass catches
registry drift, and the runtime lock-order verifier detects a contrived
ABBA interleave while staying quiet on consistent ordering.

The last class pins the whole-tree contract the CI `analysis` job
enforces: `python -m volcano_tpu.analysis` over this repo exits 0 —
which also pins every genuine violation this PR fixed (unlocked
guarded-attribute accesses in trace/recorder, bus/remote,
client/apiserver, cache/cache; the serde
round-trip registry) against regression: reverting any fix re-raises
its finding and fails the suite.
"""

from __future__ import annotations

import io
import json
import threading

import pytest

from volcano_tpu.analysis import determinism, jit_safety, lock_discipline
from volcano_tpu.analysis import lock_order, serde_drift
from volcano_tpu.analysis.__main__ import find_root, main as analysis_main
from volcano_tpu.analysis.core import Baseline, Finding, SourceFile


def _src(text: str, rel: str = "volcano_tpu/fixture.py") -> SourceFile:
    return SourceFile("<fixture>", rel, text)


def _codes(findings):
    return [f.code for f in findings]


# ---- lock discipline ----


class TestLockDiscipline:
    def test_unlocked_write_and_read_flagged(self):
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []  # guarded-by: self._lock\n"
            "    def bad_write(self):\n"
            "        self._items.append(1)\n"
            "    def bad_read(self):\n"
            "        return len(self._items)\n"
            "    def good(self):\n"
            "        with self._lock:\n"
            "            self._items.clear()\n"
        ))
        assert _codes(findings) == ["LCK001", "LCK001"]
        assert {f.symbol for f in findings} == {
            "C.bad_write:_items", "C.bad_read:_items",
        }

    def test_locked_access_and_init_are_clean(self):
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []  # guarded-by: self._lock\n"
            "        self._items.append(0)\n"  # construction is exempt
            "    def good(self):\n"
            "        with self._lock:\n"
            "            self._items.append(1)\n"
            "            return list(self._items)\n"
        ))
        assert findings == []

    def test_requires_lock_helper_trusted(self):
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: self._lock\n"
            "    def _bump(self):\n"
            "        # requires-lock: self._lock\n"
            "        self._n += 1\n"
            "    def caller(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n"
        ))
        assert findings == []

    def test_closure_resets_held_scope(self):
        # the with-scope does NOT extend into a nested def: the closure
        # runs later, when the lock has long been released
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: self._lock\n"
            "    def make(self):\n"
            "        with self._lock:\n"
            "            def cb():\n"
            "                return self._n\n"
            "            return cb\n"
        ))
        assert _codes(findings) == ["LCK001"]
        assert findings[0].symbol == "C.make.cb:_n"

    def test_unlocked_ok_waiver(self):
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._flag = False  # guarded-by: self._lock\n"
            "    def peek(self):\n"
            "        return self._flag  # unlocked-ok: benign flag read\n"
            "    def set(self):\n"
            "        with self._lock:\n"
            "            self._flag = True\n"
        ))
        assert findings == []

    def test_module_global_guard_and_global_stmt_write(self):
        findings = lock_discipline.check_file(_src(
            "import threading\n"
            "_lock = threading.Lock()\n"
            "_state = {}  # guarded-by: _lock\n"
            "def bad_write(v):\n"
            "    global _state\n"
            "    _state = v\n"
            "def good(v):\n"
            "    with _lock:\n"
            "        _state[1] = v\n"
            "def shadow():\n"
            "    _state = {}\n"  # local binding — not the global
            "    return _state\n"
        ))
        assert _codes(findings) == ["LCK001"]
        assert findings[0].symbol == "bad_write:_state"

    def test_stale_annotation_dead_lock_flagged(self):
        findings = lock_discipline.check_file(_src(
            "class C:\n"
            "    def __init__(self):\n"
            "        self._n = 0  # guarded-by: self._never_taken\n"
        ))
        assert _codes(findings) == ["LCK002"]


# ---- determinism ----


class TestDeterminism:
    def test_wall_clock_and_global_rng_flagged(self):
        findings = determinism.check_file(_src(
            "import random\n"
            "import time\n"
            "def decide():\n"
            "    if random.random() < 0.5:\n"
            "        return time.time()\n"
        ))
        assert sorted(_codes(findings)) == ["DET001", "DET002"]

    def test_seeded_rng_and_monotonic_are_clean(self):
        findings = determinism.check_file(_src(
            "import random\n"
            "import time\n"
            "import numpy as np\n"
            "def decide(seed):\n"
            "    rng = random.Random(seed)\n"
            "    st = np.random.RandomState(seed)\n"
            "    t0 = time.monotonic()\n"
            "    return rng.random() + st.rand() + time.perf_counter() - t0\n"
        ))
        assert findings == []

    def test_set_iteration_order_escape(self):
        findings = determinism.check_file(_src(
            "def leak(xs):\n"
            "    out = []\n"
            "    for x in set(xs):\n"
            "        out.append(x)\n"
            "    return out + list({1, 2})\n"
        ))
        assert _codes(findings) == ["DET003", "DET003"]

    def test_sorted_set_is_the_blessed_fix(self):
        findings = determinism.check_file(_src(
            "def ok(xs):\n"
            "    return [x for x in sorted(set(xs))]\n"
        ))
        assert findings == []

    def test_det_marker_waives(self):
        findings = determinism.check_file(_src(
            "import time\n"
            "def stamp():\n"
            "    return time.time()  # det: journal timestamp\n"
        ))
        assert findings == []

    def test_uuid_entropy_flagged(self):
        findings = determinism.check_file(_src(
            "import uuid\n"
            "def ident():\n"
            "    return uuid.uuid4().hex\n"
        ))
        assert _codes(findings) == ["DET004"]


# ---- jit safety ----


class TestJitSafety:
    def test_item_and_concretize_inside_jit(self):
        findings = jit_safety.check_file(_src(
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    v = x.sum().item()\n"
            "    return float(x[0]) + v\n"
        ))
        assert sorted(_codes(findings)) == ["JIT001", "JIT002"]

    def test_tracer_branch_flagged_static_allowed(self):
        findings = jit_safety.check_file(_src(
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, static_argnames=('k',))\n"
            "def f(x, k):\n"
            "    if k > 2:\n"          # static — allowed
            "        return x * 2\n"
            "    if x.shape[0] > 4:\n"  # shape is static — allowed
            "        return x\n"
            "    if x > 0:\n"           # tracer value — flagged
            "        return x + 1\n"
            "    return x\n"
        ))
        assert _codes(findings) == ["JIT003"]
        assert findings[0].symbol == "f:x"

    def test_outside_jit_is_not_flagged(self):
        findings = jit_safety.check_file(_src(
            "def host(x):\n"
            "    return float(x[0].item())\n"
        ))
        assert findings == []

    def test_jit_wrapped_local_def_checked(self):
        findings = jit_safety.check_file(_src(
            "import jax\n"
            "def factory():\n"
            "    def inner(x):\n"
            "        return int(x.sum())\n"
            "    return jax.jit(inner)\n"
        ))
        assert _codes(findings) == ["JIT002"]

    def test_donated_buffer_reuse_flagged(self):
        findings = jit_safety.check_file(_src(
            "import jax\n"
            "def scatter(buf, rows, vals):\n"
            "    return buf.at[rows].set(vals)\n"
            "g = jax.jit(scatter, donate_argnums=(0,))\n"
            "def use(buf, rows, vals):\n"
            "    out = g(buf, rows, vals)\n"
            "    return out + buf\n"  # buf was donated — invalid
        ))
        assert _codes(findings) == ["JIT004"]
        assert findings[0].symbol == "use:buf"

    def test_donated_rebind_is_clean(self):
        findings = jit_safety.check_file(_src(
            "import jax\n"
            "def scatter(buf, rows, vals):\n"
            "    return buf.at[rows].set(vals)\n"
            "g = jax.jit(scatter, donate_argnums=(0,))\n"
            "def use(buf, rows, vals):\n"
            "    buf = g(buf, rows, vals)\n"  # rebound — fresh buffer
            "    return buf\n"
        ))
        assert findings == []


# ---- serde drift ----


class TestSerdeDrift:
    def test_real_tree_is_drift_free(self):
        assert serde_drift.run(find_root()) == []

    def test_unregistered_kind_missing_exemplar(self, monkeypatch):
        from volcano_tpu.bus import protocol

        monkeypatch.setitem(protocol.KINDS, "Phantom", object)
        findings = serde_drift.run(find_root())
        assert [f.code for f in findings] == ["SRD001"]
        assert findings[0].symbol == "Phantom"

    def test_server_op_without_version_registration(self, monkeypatch):
        from volcano_tpu.bus import protocol

        trimmed = dict(protocol.OP_VERSIONS)
        del trimmed["commit_batch"]
        monkeypatch.setattr(protocol, "OP_VERSIONS", trimmed)
        findings = serde_drift.run(find_root())
        assert [f.code for f in findings] == ["SRD002"]
        assert findings[0].symbol == "commit_batch"

    def test_post_v1_op_declared_but_unhandled_is_drift(self, monkeypatch):
        from volcano_tpu.bus import protocol

        grown = dict(protocol.OP_VERSIONS)
        # a fictional future op — registered but dispatched nowhere
        # (watch_batch, the old fixture name here, became a REAL v3 op)
        grown["evict_batch"] = 4
        monkeypatch.setattr(protocol, "OP_VERSIONS", grown)
        findings = serde_drift.run(find_root())
        # the phantom op draws BOTH halves of the discipline: nobody
        # dispatches it (SRD004) and the README ladder never names it
        # (SRD005)
        assert sorted(f.code for f in findings) == ["SRD004", "SRD005"]
        assert {f.symbol for f in findings} == {"evict_batch"}


# ---- baseline machinery ----


class TestBaseline:
    def _finding(self, symbol="C.bad:_x"):
        return Finding("lock", "LCK001", "volcano_tpu/m.py", 7, symbol, "msg")

    def test_round_trip_suppresses_by_key_not_line(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        Baseline.write(path, [self._finding()])
        data = json.load(open(path))
        # reasons are mandatory: the writer emits a TODO the author edits
        assert data["suppressions"][0]["reason"].startswith("TODO")
        data["suppressions"][0]["reason"] = "known benign"
        json.dump(data, open(path, "w"))
        bl = Baseline.load(path)
        moved = Finding("lock", "LCK001", "volcano_tpu/m.py", 99,
                        "C.bad:_x", "msg")  # line drifted — still matches
        unsup, sup, stale = bl.split([moved])
        assert unsup == [] and sup == [moved] and stale == []

    def test_stale_entry_reported(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        Baseline.write(path, [self._finding()])
        data = json.load(open(path))
        data["suppressions"][0]["reason"] = "obsolete"
        json.dump(data, open(path, "w"))
        unsup, sup, stale = Baseline.load(path).split([])
        assert stale and stale[0]["symbol"] == "C.bad:_x"

    def test_missing_reason_rejected(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        json.dump({"suppressions": [{
            "pass": "lock", "code": "LCK001", "file": "f.py",
            "symbol": "s", "reason": "",
        }]}, open(path, "w"))
        with pytest.raises(ValueError, match="reason"):
            Baseline.load(path)


# ---- runtime lock-order verifier ----


class TestLockOrder:
    def _graph(self):
        return lock_order._Graph()

    def _run_in_thread(self, fn):
        t = threading.Thread(target=fn)
        t.start()
        t.join(5)
        assert not t.is_alive()

    def test_abba_interleave_detected(self):
        g = self._graph()
        g.register(1, "a.py:10")
        g.register(2, "b.py:20")
        self._run_in_thread(lambda: (
            g.acquired(1), g.acquired(2), g.released(2), g.released(1),
        ))
        assert g.violations == []  # one order alone is fine
        self._run_in_thread(lambda: (
            g.acquired(2), g.acquired(1), g.released(1), g.released(2),
        ))
        assert len(g.violations) == 1
        rendered = g.violations[0].render()
        assert "a.py:10" in rendered and "b.py:20" in rendered

    def test_consistent_order_stays_acyclic(self):
        g = self._graph()
        for lid in (1, 2, 3):
            g.register(lid, f"l{lid}.py:1")
        for _ in range(3):
            self._run_in_thread(lambda: (
                g.acquired(1), g.acquired(2), g.acquired(3),
                g.released(3), g.released(2), g.released(1),
            ))
        assert g.violations == []
        assert g.report()["violations"] == []

    def test_rlock_reentry_is_not_an_edge(self):
        g = self._graph()
        g.register(1, "a.py:1")
        self._run_in_thread(lambda: (
            g.acquired(1), g.acquired(1), g.released(1), g.released(1),
        ))
        assert g.edges == {} and g.violations == []

    def test_transitive_cycle_detected(self):
        g = self._graph()
        for lid in (1, 2, 3):
            g.register(lid, f"l{lid}.py:1")
        self._run_in_thread(lambda: (
            g.acquired(1), g.acquired(2), g.released(2), g.released(1),
        ))
        self._run_in_thread(lambda: (
            g.acquired(2), g.acquired(3), g.released(3), g.released(2),
        ))
        assert g.violations == []
        self._run_in_thread(lambda: (
            g.acquired(3), g.acquired(1), g.released(1), g.released(3),
        ))
        assert len(g.violations) == 1  # 1→2→3→1

    def test_instrumented_lock_supports_condition_wait(self):
        """The _release_save/_acquire_restore forwarding keeps
        Condition.wait working over an instrumented RLock, and the
        held-stack stays balanced across the wait."""
        g = self._graph()
        old = lock_order._graph
        lock_order._graph = g
        try:
            inner = threading.RLock()
            lk = lock_order._InstrumentedLock(inner, "fixture.py:1")
            cv = threading.Condition(lk)
            fired = []

            def waiter():
                with cv:
                    got = cv.wait(timeout=5)
                    fired.append(got)
                assert g.held() == []

            t = threading.Thread(target=waiter)
            t.start()
            import time as _t

            deadline = _t.monotonic() + 5
            while not cv._waiters and _t.monotonic() < deadline:
                _t.sleep(0.01)  # until the waiter parks in wait()
            with cv:
                cv.notify_all()
            t.join(5)
            assert fired == [True]
            assert g.held() == []  # this thread's stack balanced too
        finally:
            lock_order._graph = old


# ---- the whole-tree gate (pins every fixed violation) ----


class TestRepoTree:
    def test_analysis_suite_is_green_on_this_tree(self):
        out = io.StringIO()
        rc = analysis_main([], out=out)
        assert rc == 0, f"analysis found regressions:\n{out.getvalue()}"

    def test_partial_run_ignores_other_passes_baseline(self):
        out = io.StringIO()
        rc = analysis_main(["--pass", "det"], out=out)
        assert rc == 0, out.getvalue()

    def test_report_artifact_shape(self, tmp_path):
        report = tmp_path / "findings.json"
        rc = analysis_main(["--report", str(report)], out=io.StringIO())
        assert rc == 0
        data = json.loads(report.read_text())
        assert set(data) == {"findings", "suppressed",
                             "stale_baseline_entries"}
        assert data["findings"] == []
        # the one reasoned suppression (faults/watchdog fast-path read)
        assert [s["symbol"] for s in data["suppressed"]] == [
            "begin_cycle:_deadline_s"
        ]


# ---- happens-before race detector (ISSUE 13) ----


class TestRaceDetector:
    """Drive a private Detector engine directly — the global install is
    exercised by the CI suites under VTPU_RACE=1; these pin the vector-
    clock semantics themselves."""

    def _det(self):
        from volcano_tpu.analysis import race

        return race.Detector(restrict_to_pkg=False)

    def _run_in_thread(self, fn):
        t = threading.Thread(target=fn)
        t.start()
        t.join()

    def test_planted_unlocked_write_is_a_race(self):
        import sys

        det = self._det()
        obj = object()

        def writer():
            det.record(obj, "fixture.C.x", True, sys._getframe())

        det.record(obj, "fixture.C.x", True, sys._getframe())
        self._run_in_thread(writer)
        kinds = {r.kind for r in det.reports}
        assert kinds == {"write-write"}, [r.render() for r in det.reports]

    def test_lock_ordered_accesses_stay_clean(self):
        import sys

        det = self._det()
        obj = object()
        lock_id = 7001

        def locked(is_write):
            det.recv(lock_id)  # acquire
            det.record(obj, "fixture.C.x", is_write, sys._getframe())
            det.send(lock_id)  # release

        locked(True)
        self._run_in_thread(lambda: locked(True))
        self._run_in_thread(lambda: locked(False))
        assert det.reports == [], [r.render() for r in det.reports]

    def test_read_write_race_detected_and_read_clear(self):
        import sys

        det = self._det()
        obj = object()
        lock_id = 7002

        det.record(obj, "fixture.C.y", False, sys._getframe())

        def racing_write():
            det.record(obj, "fixture.C.y", True, sys._getframe())
            det.send(lock_id)  # release: publish for the next thread

        self._run_in_thread(racing_write)
        assert [r.kind for r in det.reports] == ["read-write"]

        # FastTrack read-clear: the racing write RESET the read set and
        # became the variable's write epoch.  A third thread's write
        # ordered after it (lock edge) has NO happens-before path to
        # the main thread's stale read — an engine that kept the read
        # set would re-report that read here.  Exactly one report, and
        # the two write sites differ so site-key dedup cannot mask a
        # cascade.
        def ordered_write():
            det.recv(lock_id)  # acquire: join the racing write's clock
            det.record(obj, "fixture.C.y", True, sys._getframe())

        self._run_in_thread(ordered_write)
        assert [r.kind for r in det.reports] == ["read-write"], (
            [r.render() for r in det.reports]
        )

    def test_scan_guarded_finds_declarations_and_waivers(self):
        from volcano_tpu.analysis import race

        decls = race.scan_guarded(find_root())
        symbols = {d.symbol for d in decls}
        # the first real race this detector caught, now lock-published
        assert "volcano_tpu.faults.plane:FaultPlane._points" in symbols
        assert "volcano_tpu.bus.replication:" \
               "ReplicationCoordinator._records" in symbols
        # every declaration names its lock
        assert all(d.lock for d in decls)

    def test_fault_plane_publication_race_fixed_and_pinned(self):
        """The first real race the HB detector caught on this tree:
        ``FaultPlane.__init__`` populated ``_points`` without the lock
        ``should()`` readers take, and ``get_plane()``'s fast path
        publishes the instance without synchronization.  Run the real
        instrumentation in a subprocess (the install patches process
        globals): the FIXED constructor is clean, and a racy twin that
        reverts the fix is flagged — the revert cannot land silently."""
        import subprocess
        import sys

        code = (
            "import sys, time\n"
            "from volcano_tpu.analysis import race\n"
            "race.install(restrict_to_pkg=False)\n"
            "# restrict off: the racy twin's constructor lives in this\n"
            "# script, not under volcano_tpu/\n"
            "import threading\n"
            "from volcano_tpu.faults import plane as plane_mod\n"
            "spec = plane_mod.parse_faults('seed=1;x.y=0.5')\n"
            "def publish_to_preexisting_reader(cls):\n"
            "    # the get_plane() shape: a reader thread ALIVE BEFORE\n"
            "    # construction picks the instance up through an\n"
            "    # unsynchronized global — only the lock inside the\n"
            "    # constructor can order the _points write before the\n"
            "    # reader's locked access\n"
            "    holder = {}\n"
            "    def reader():\n"
            "        while 'p' not in holder:\n"
            "            time.sleep(0.001)\n"
            "        holder['p'].should('x.y')\n"
            "    t = threading.Thread(target=reader)\n"
            "    t.start()\n"
            "    holder['p'] = cls(spec)\n"
            "    t.join()\n"
            "race.instrument_class(\n"
            "    race.get_detector(), plane_mod.FaultPlane, ['_points'],\n"
            "    'volcano_tpu.faults.plane.FaultPlane')\n"
            "publish_to_preexisting_reader(plane_mod.FaultPlane)\n"
            "fixed_clean = not race.report()['races']\n"
            "class RacyPlane(plane_mod.FaultPlane):\n"
            "    def __init__(self, spec):\n"
            "        self.spec = spec\n"
            "        self._lock = threading.Lock()\n"
            "        self._points = {}  # the pre-fix unlocked publication\n"
            "        for point in spec.rules:\n"
            "            self._points[point] = plane_mod._PointState(\n"
            "                __import__('random').Random(1))\n"
            "race.instrument_class(\n"
            "    race.get_detector(), RacyPlane, ['_points'],\n"
            "    'volcano_tpu.faults.plane.RacyPlane')\n"
            "publish_to_preexisting_reader(RacyPlane)\n"
            "racy_flagged = any(\n"
            "    'RacyPlane._points' in r['symbol']\n"
            "    for r in race.report()['races'])\n"
            "sys.exit(0 if (fixed_clean and racy_flagged) else\n"
            "         (1 if not fixed_clean else 2))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert proc.returncode == 0, (
            f"rc={proc.returncode} (1=fixed ctor raced, 2=racy twin "
            f"missed)\n{proc.stdout}\n{proc.stderr}"
        )

    def test_instrumented_class_attribute_round_trips(self):
        from volcano_tpu.analysis import race

        det = self._det()

        class Fixture:
            def __init__(self):
                self.val = 1

        n = race.instrument_class(det, Fixture, ["val"], "fixture.Fixture")
        assert n == 1
        f = Fixture()
        f.val = 5
        assert f.val == 5
        assert hasattr(f, "val")
        del f.val
        assert not hasattr(f, "val")
        assert det.n_accesses >= 3


# ---- deterministic interleaving explorer (ISSUE 13) ----


class TestExplorer:
    def test_schedule_systematic_prefixes_are_distinct(self):
        from volcano_tpu.analysis.explore import Schedule

        seen = set()
        for sid in range(16):
            s = Schedule(sid, systematic_below=16)
            digits = []
            while True:
                digits.append(s.choose(2))
                if s._forced is None:
                    break  # systematic prefix exhausted; random tail
            seen.add(tuple(digits))
        # the mixed-radix digits reconstruct the sid: every systematic
        # seed walks a distinct node of the decision tree
        assert len(seen) == 16

    def test_clean_protocols_hold_across_schedules(self):
        from volcano_tpu.analysis import explore

        results = explore.explore(
            ["election", "lease", "gang"], schedules=40
        )
        for name, r in results.items():
            assert r["violations"] == [], (name, r["violations"])
        assert sum(r["schedules"] for r in results.values()) == 120

    def test_planted_stale_election_is_caught(self):
        from volcano_tpu.analysis import explore

        r = explore.explore(
            ["election"], schedules=100, plant="stale-election"
        )["election"]
        assert r["violations"], "stale-election plant went undetected"
        v = r["violations"][0]
        assert "leader" in v["invariant"] or "acked" in v["invariant"]

    def test_planted_partial_commit_is_caught(self):
        from volcano_tpu.analysis import explore

        r = explore.explore(
            ["gang"], schedules=100, plant="partial-commit"
        )["gang"]
        assert r["violations"], "partial-commit plant went undetected"
        assert "partial gang" in r["violations"][0]["invariant"]

    def test_planted_lease_steal_is_caught(self):
        from volcano_tpu.analysis import explore

        r = explore.explore(
            ["lease"], schedules=60, plant="lease-steal"
        )["lease"]
        assert r["violations"], "lease-steal plant went undetected"
        assert "doubly owned" in r["violations"][0]["invariant"]

    def test_violating_schedule_replays_from_its_seed(self):
        from volcano_tpu.analysis import explore

        r = explore.explore(
            ["gang"], schedules=100, plant="partial-commit"
        )["gang"]
        v = r["violations"][0]
        replays = [
            explore.run_schedule(
                explore.GangMachine(), v["sid"], plant="partial-commit"
            )[0]
            for _ in range(2)
        ]
        for rv in replays:
            assert rv is not None
            assert rv.trace == v["trace"]      # bit-identical schedule
            assert rv.step == v["step"]
        # the same seed WITHOUT the plant holds the invariant
        clean, _steps = explore.run_schedule(explore.GangMachine(), v["sid"])
        assert clean is None

    def test_lease_machine_restores_patched_module_state(self):
        import time as real_time

        from volcano_tpu.analysis import explore
        from volcano_tpu.federation import leases

        explore.explore(["lease"], schedules=3, plant="lease-steal")
        assert leases.time is real_time
        # _expired is back to the real staticmethod semantics
        assert leases.ShardLeaseManager._expired(
            {"renewTime": 0.0, "leaseDurationSeconds": 1e12},
            real_time.time(),
        ) is False

    def test_vtctl_explore_quick_meets_the_schedule_floor(self):
        from volcano_tpu.cli.vtctl import main as vtctl_main

        out = io.StringIO()
        rc = vtctl_main(
            ["explore", "--quick", "--max-steps", "30"], out=out
        )
        text = out.getvalue()
        assert rc == 0, text
        total = int(text.rsplit("explore: ", 1)[1].split()[0])
        assert total >= 200  # the acceptance floor

    def test_explore_report_artifact_shape(self, tmp_path):
        from volcano_tpu.analysis.explore import main as explore_main

        report = tmp_path / "explore.json"
        rc = explore_main(
            ["--machine", "gang", "--schedules", "10",
             "--report", str(report)],
            out=io.StringIO(),
        )
        assert rc == 0
        data = json.loads(report.read_text())
        assert set(data) == {"gang"}
        assert data["gang"]["schedules"] == 10
        assert data["gang"]["violations"] == []


# ---- SRD005: README version-ladder doc drift ----


class TestVersionLadderDrift:
    def _ops(self):
        return {"create": 1, "commit_batch": 2, "txn_commit": 6}

    def test_stale_declared_version_flagged(self):
        readme = (
            "The wire protocol is at **VBUS version 3**: `create`, "
            "`commit_batch`, `txn_commit`.\n\n## Next\n"
        )
        findings = serde_drift._check_ladder(readme, self._ops())
        assert [f.code for f in findings] == ["SRD005"]
        assert "version 3" in findings[0].message
        assert "v6" in findings[0].message

    def test_unmentioned_op_flagged(self):
        readme = (
            "The wire protocol is at **VBUS version 6**: `create` and "
            "`commit_batch`.\n\n## Next\n"
        )
        findings = serde_drift._check_ladder(readme, self._ops())
        assert [f.symbol for f in findings] == ["txn_commit"]

    def test_mention_outside_the_ladder_section_does_not_count(self):
        readme = (
            "`txn_commit` is great.\n\n"
            "The wire protocol is at **VBUS version 6**: `create` and "
            "`commit_batch`.\n\n## Next\n"
        )
        findings = serde_drift._check_ladder(readme, self._ops())
        assert [f.symbol for f in findings] == ["txn_commit"]

    def test_missing_ladder_paragraph_flagged(self):
        findings = serde_drift._check_ladder("# hi\n", self._ops())
        assert [f.symbol for f in findings] == ["version-ladder"]

    def test_complete_ladder_is_clean(self):
        readme = (
            "The wire protocol is at **VBUS version 6**: `create`, "
            "`commit_batch` and `txn_commit`.\n\n## Next\n"
        )
        assert serde_drift._check_ladder(readme, self._ops()) == []

    def test_fenced_comment_does_not_end_the_section(self):
        # a `# comment` inside a ```bash example is not a heading: ops
        # named after the code block still count as in-section, and the
        # section still ends at the next REAL heading
        readme = (
            "The wire protocol is at **VBUS version 6**: `create` and "
            "`commit_batch`.\n\n"
            "```bash\n# a shell comment, not a heading\nvtctl bus "
            "status\n```\n\n"
            "`txn_commit` rides v6.\n\n## Next\n\n`unrelated` here.\n"
        )
        assert serde_drift._check_ladder(readme, self._ops()) == []
        ops = dict(self._ops(), unrelated=6)
        findings = serde_drift._check_ladder(readme, ops)
        assert [f.symbol for f in findings] == ["unrelated"]


# ---- conftest fd/socket-leak guard ----


class TestFdLeakGuard:
    def test_leaked_socket_is_flagged_and_close_clears_it(self):
        import socket

        from tests.conftest import _fd_table, _leaked_fds

        before = _fd_table()
        if before is None:
            pytest.skip("no /proc/self/fd on this platform")
        s = socket.socket()
        try:
            leaked = _leaked_fds(before)
            assert any(t.startswith("socket:") for _fd, t in leaked), leaked
        finally:
            s.close()
        assert _leaked_fds(before) == []

    def test_leaked_file_is_flagged(self, tmp_path):
        from tests.conftest import _fd_table, _leaked_fds

        before = _fd_table()
        if before is None:
            pytest.skip("no /proc/self/fd on this platform")
        f = open(tmp_path / "wal.log", "w")
        try:
            leaked = _leaked_fds(before)
            assert any(t.endswith("wal.log") for _fd, t in leaked), leaked
        finally:
            f.close()
        assert _leaked_fds(before) == []
