"""The cell's control — its reference with a guarantee of the
configuration broken, put in the program's place after the window —
turns the run's own ``correct`` false, at a small size, on three seeds
(``benchmark/controls.py`` runs the same on the chip at the cell's own
size), while the program's own outcome of the same run passes."""

import pytest

from controls import control_hook
from test_rehearsal import small_run

SEEDS = (11, 2**31 + 5, 987654321012)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_turns_correct_false(seed):
    record = {}
    out = small_run("preempt-full-cycle", seed=seed, hook=control_hook(record))
    assert not out["correct"], out["checks"]
    assert out["checks"]["evicts_mismatch"]["value"] > 0
    assert all(c["value"] <= c["limit"] for c in record["program"].values()), record
