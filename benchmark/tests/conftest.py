"""Tests of the benchmark's own code.  Run by hand from the repo's root:

    python -m pytest benchmark/tests -q

They are outside ``tests/`` and leave the tier-1 count alone.  They run on
the CPU; four virtual devices, set before JAX starts."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
