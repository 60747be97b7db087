"""The benchmark's own code: what every cell shares.  Files for one
configuration, traffic mix or metric live beside this package and are
found by name (``benchmark/run.py``)."""
