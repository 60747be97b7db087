"""The comparison catches a broken timed path: each fault that a cell
can have, planted underneath a whole run on the CPU, turns ``correct``
false.  (No cell spans chips, so the fault of a missing exchange between
chips does not arise.)"""

import time

import pytest

from test_rehearsal import SMALL, small_run


def half_left_out(traffic):
    """Half of what the program delivers is dropped."""
    rec = traffic.recorder
    for kind in ("binds", "evicts"):
        class Half(list):
            def append(self, item, _n=[0]):
                _n[0] += 1
                if _n[0] % 2:
                    super().append(item)
        setattr(rec, kind, Half())


def altered(traffic):
    """One answer altered where it is delivered: a placement lands on
    another node."""
    rec = traffic.recorder

    class Alter(list):
        def append(self, item, _n=[0]):
            _n[0] += 1
            if _n[0] % 50 == 3:
                uid, node, t, c = item
                item = (uid, "n%05d" % ((int(node[1:]) + 1) % 100), t, c)
            super().append(item)

    rec.binds = Alter()
    rec.evicts = Alter()


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_unchanged_state_turns_correct_false(cell, monkeypatch):
    """A cycle that returns the state unchanged: run_once does nothing."""
    from volcano_tpu.scheduler.scheduler import Scheduler

    monkeypatch.setattr(Scheduler, "run_once", lambda self, trigger="full": time.sleep(0.01))
    out = small_run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [half_left_out, altered])
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_fault_turns_correct_false(cell, fault):
    out = small_run(cell, hook=fault)
    assert not out["correct"], out["checks"]
