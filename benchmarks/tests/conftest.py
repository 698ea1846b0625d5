"""Tests of the benchmark's own yardstick. Run from the repo's root:

    env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest benchmarks/tests -q

They are not part of tier-1 (which collects ``tests/`` only)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
