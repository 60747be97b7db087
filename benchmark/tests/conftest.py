"""The benchmark's own tests: on the CPU, at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.dirname(BENCH), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
