#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches the chip.  The last line of
stdout is the result object; each number compared with the reference is
printed beside its limit as the last lines of stderr and under
``checks`` in that object.  Without a TPU, with fewer chips than the
cell asks for, or outside a checkout of volcano-tpu it prints no result
and exits 2.
"""

import os
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness.core import main

    sys.exit(main(t_start=T_START))
