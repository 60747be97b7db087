#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the cell's
reference, computed with one guarantee of its configuration broken
(``reference/<name>.py`` ``control=True``), put in the program's place,
must come out as not correct.

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 [--seconds 10]

One process runs the cell once per seed, on the chip, at its own size:
set-up, the window, then the check.  After the window the control's
outcome of each cycle takes the place of the outcome the program
delivered, and the run's own check (``Traffic.check``) and its own
``correct`` decide it.  Prints one JSON line per seed: ``correct``,
each compared number beside its limit for the control, and the same for
the program's own outcome.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def control_hook(record: dict):
    """A ``core.run`` hook: the check reads the program's outcome into
    ``record["program"]``, then returns the check of the control's
    outcome in its place."""

    def hook(traffic):
        orig = traffic.check

        def check():
            record["program"] = orig()
            name = traffic.cell.generator.node_name
            got = traffic.cell.reference.expected_full_cycle(traffic.world, control=True)
            outcome = {k: {u: name(n) for u, n in got[k].items()} for k in got}
            traffic.outcomes = [dict(outcome) for _ in traffic.outcomes]
            return orig()

        traffic.check = check

    return hook


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10)
    args = p.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness import core

    for seed in (int(s) for s in args.seeds.split(",")):
        record = {}
        res = core.run(args.workload, seed, args.seconds, False, time.perf_counter(),
                       hook=control_hook(record))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "correct": res["correct"],
            "control": res["checks"], "program": record["program"],
            "attempted": res["attempted"], "failed": res["failed"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
